"""The program's own spans and per-call counters in a traced run.

Besides the benchmark's ``bench.*`` spans, the host plane of a traced
run's xplane (``harness.TRACE_DIR``) holds one ``index.<method>`` event
per ``Index`` call (``repro.obs.trace``), with the call's sequence number
as the integer stat ``seq``.  The program keeps each call's counters in
a ring on the device (``repro.obs.calls``); read in the same process
after the run, its rows join to the events by ``seq``.

A program without these spans or counters (one older than them) gives
no events and no rows: every function here returns None then, and so
does each reader built on it.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

PREFIX = "index."

_CACHE: dict = {}


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns on the profiler's clock
    end: float
    seq: int | None


def index_events(path: Path) -> list[Event]:
    """Every ``index.*`` host event of one ``.xplane.pb``, in time order
    (cached by path and modification time)."""
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _CACHE:
        from jax.profiler import ProfileData

        out = []
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        seq = dict(ev.stats).get("seq")
                        out.append(Event(ev.name, ev.start_ns, ev.end_ns,
                                         None if seq is None else int(seq)))
        _CACHE.clear()
        _CACHE[key] = sorted(out, key=lambda e: e.start)
    return _CACHE[key]


def window_events(view, trace_dir=None) -> list[Event] | None:
    """The ``index.*`` events inside the traced window (first
    ``bench.batch`` start to last end); None when there are none."""
    import harness
    from trace_reduce import find_xplane

    try:
        path = find_xplane(trace_dir or harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    lo, hi = view.trace.window
    evs = [e for e in index_events(path) if lo <= e.start and e.end <= hi]
    return evs or None


def window_rows(view, name: str, trace_dir=None) -> np.ndarray | None:
    """The ring rows of the window's ``name`` calls (``index.update``),
    in call order.  None when the program keeps no ring, when there are
    no such calls, or when the ring no longer holds every one of them
    (it was overwritten since): a partial sum would mislead."""
    evs = window_events(view, trace_dir)
    if evs is None:
        return None
    seqs = [e.seq for e in evs if e.name == name]
    if not seqs or None in seqs:
        return None
    try:
        from repro.obs import calls
    except ImportError:
        return None
    rows = calls(seqs)
    if not np.array_equal(rows["seq"], np.asarray(seqs)):
        return None
    return rows


def self_ns(events: list[Event]) -> float:
    """Time covered by the events, nested or overlapping ones once."""
    from trace_reduce import union

    m = union(np.asarray([(e.start, e.end) for e in events], np.float64))
    return float(np.sum(m[:, 1] - m[:, 0]))


def span_total_s(name: str) -> float | None:
    """The program's host total of span ``name`` over the process."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    totals = getattr(trace, "totals", None)
    row = totals().get(name) if totals is not None else None
    return None if row is None else float(row["total_s"])
