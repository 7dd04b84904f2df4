"""Reduce a JAX profiler trace to what the per-layer metrics read.

A trace (``<dir>/plugins/profile/<time>/<host>.xplane.pb``) holds a host
plane, whose threads carry the benchmark's ``bench.*`` spans
(``jax.profiler.TraceAnnotation``), and one plane per device, whose
"XLA Ops" line holds every operation that ran on it and whose
"XLA Modules" line holds every program execution.  Both are read on the
profiler's one clock.  From them:

- busy time: the union of the device's op intervals (averaged over the
  devices when there are several, and also kept per device), and the
  traced window: from the start of the first ``bench.batch`` span to the
  end of the last;
- device time per XLA module name, summed over the devices and per device;
- device idle gaps, each named by the innermost ``bench.*`` span that
  was open on the host at the gap's midpoint ("no span" when none was).

    python bench/trace_reduce.py <trace dir or .xplane.pb>   # prints it
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

SPAN_PREFIX = "bench."
BATCH_SPAN = "bench.batch"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = "/device:"
TOP = 10


def union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(np.r_[new[1:], True])
    return np.stack([starts, ends[last]], axis=1)


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` that the disjoint intervals cover."""
    if merged.size == 0 or hi <= lo:
        return 0.0
    s = np.clip(merged[:, 0], lo, hi)
    e = np.clip(merged[:, 1], lo, hi)
    return float(np.sum(e - s))


@dataclasses.dataclass
class Reduced:
    """One trace, reduced.  Times in ns on the profiler's clock."""

    spans: list            # (name, start, end) of every bench.* span
    batches: np.ndarray    # (B, 2) bench.batch spans, in order
    busy: list             # per device: merged (n, 2) op intervals
    modules: dict          # module name -> total device ns (all devices)
    module_runs: dict      # module name -> executions (all devices)
    ops: dict              # op name -> total device ns (all devices)
    device_modules: list = dataclasses.field(default_factory=list)
    #                        per device of ``busy``: module name -> ns

    @property
    def window(self) -> tuple[float, float]:
        if self.batches.size == 0:
            return 0.0, 0.0
        return float(self.batches[0, 0]), float(self.batches[-1, 1])

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def busy_ns(self, lo: float, hi: float) -> float:
        """Device-busy ns inside [lo, hi), averaged over the devices."""
        if not self.busy:
            return 0.0
        return float(np.mean([covered(b, lo, hi) for b in self.busy]))

    @property
    def busy_s(self) -> float:
        return self.busy_ns(*self.window) * 1e-9

    def busy_s_by_device(self) -> list[float]:
        """Device-busy seconds inside the window, per device (``busy_s``
        is their mean): the slowest chip, or the spread between chips."""
        lo, hi = self.window
        return [covered(b, lo, hi) * 1e-9 for b in self.busy]

    def module_ns(self, pred) -> float:
        """Device ns of the modules whose name satisfies ``pred``."""
        return float(sum(t for n, t in self.modules.items() if pred(n)))

    def module_ns_by_device(self, pred) -> list[float]:
        """``module_ns(pred)`` per device (the same devices as ``busy``)."""
        return [float(sum(t for n, t in mods.items() if pred(n)))
                for mods in self.device_modules]

    def gaps(self) -> list[tuple[str, float]]:
        """Idle gaps of the first device inside the window, as (name of the
        innermost span open at the gap's midpoint, ns)."""
        lo, hi = self.window
        b = self.busy[0] if self.busy else np.zeros((0, 2))
        b = b[(b[:, 1] > lo) & (b[:, 0] < hi)]
        edges = np.concatenate([[lo], np.clip(b.ravel(), lo, hi), [hi]])
        gs, ge = edges[0::2], edges[1::2]
        keep = ge > gs
        gs, ge = gs[keep], ge[keep]
        # the other bench.* spans nest inside one batch span each
        inner: list[list] = [[] for _ in range(len(self.batches))]
        for name, s, e in self.spans:
            if name != BATCH_SPAN:
                i = int(np.searchsorted(self.batches[:, 0], s, "right")) - 1
                if i >= 0:
                    inner[i].append((name, s, e))
        out = []
        for s, e in zip(gs, ge):
            mid = 0.5 * (s + e)
            i = int(np.searchsorted(self.batches[:, 0], mid, "right")) - 1
            name = "no span"
            if i >= 0 and self.batches[i, 1] > mid:
                name = BATCH_SPAN
                for n, a, z in inner[i]:
                    if a <= mid < z:
                        name = n
            out.append((name, float(e - s)))
        return out

    def breakdown(self) -> dict:
        """The device ops that took most time, and idle time by the span
        that was open on the host, in seconds."""
        idle: dict[str, float] = {}
        for name, ns in self.gaps():
            idle[name] = idle.get(name, 0.0) + ns
        top_ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, t * 1e-9] for n, t in top_ops],
                "idle_gaps": [[n, t * 1e-9] for n, t in top_idle]}


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_xplane(path: Path) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans, busy, device_modules = [], [], []
    modules: dict[str, float] = {}
    runs: dict[str, int] = {}
    ops: dict[str, float] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            iv, mods = [], {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        iv.append((ev.start_ns, ev.end_ns))
                        ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        modules[ev.name] = (modules.get(ev.name, 0.0)
                                            + ev.duration_ns)
                        mods[ev.name] = mods.get(ev.name, 0.0) + ev.duration_ns
                        runs[ev.name] = runs.get(ev.name, 0) + 1
            # a device plane with no ops (the runtime's own) is no chip
            if iv:
                busy.append(union(np.asarray(iv, np.float64)))
                device_modules.append(mods)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    batches = np.asarray(sorted((s, e) for n, s, e in spans
                                if n == BATCH_SPAN), np.float64).reshape(-1, 2)
    return Reduced(spans, batches, busy, modules, runs, ops, device_modules)


def reduce_trace(path) -> Reduced:
    """Reduce a trace directory's newest trace, or one ``.xplane.pb``."""
    path = Path(path)
    return reduce_xplane(path if path.is_file() else find_xplane(path))


def summary(red: Reduced) -> dict:
    return {"batches": int(len(red.batches)), "window_s": red.window_s,
            "busy_s": red.busy_s, "busy_s_by_device": red.busy_s_by_device(),
            "modules_s": {
                k: v * 1e-9 for k, v in sorted(red.modules.items())},
            "module_runs": dict(sorted(red.module_runs.items())),
            "breakdown": red.breakdown()}


if __name__ == "__main__":
    print(json.dumps(summary(reduce_trace(sys.argv[1])), indent=1))
