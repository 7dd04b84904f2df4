"""One run of one cell: build, warm up, measure a closed-loop window, check
every answer against the plain reference, and report.

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the cell's configuration and mix, the
configuration lives in ``bench/configs/<config>.json``, the mix in
``bench/mixes/<traffic>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  A new cell, mix or metric is a new file.

The window is one client with ``in_flight`` batches in flight (the mix
file's; one unless it says otherwise): a batch is submitted as soon as the
oldest one's results are on the host.  A batch's time covers its transfer
to the device, the ``Index`` calls, the wait for the device and the fetch
of its results.  Reads see the snapshot before their batch; a batch's
inserts are applied after its reads and scans, and every later batch must
see them: the device runs the batches in the order they were sent.

The index is a map from key to record id (``payload_bits`` in the
configuration's ``index``): reads return the record id of each key found,
scans the keys and record ids in order.  Map mode packs key and payload
into int64, so such a configuration needs ``JAX_ENABLE_X64`` set before
JAX is imported (``needs_x64``).
"""

from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 5.0      # a traced run's window: long enough for tens of
#                          batches of the slowest cell, short enough that
#                          the trace stays a few MB
CHECK_IN_FLIGHT = 8      # probe calls of the read-back dispatched ahead of
#                          the one fetched

if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from reference import SortedMap  # noqa: E402
from traffic import Traffic, make_traffic  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# the cell, by name
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list     # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((BENCH / "mixes" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", ())]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer)


def needs_x64(config: dict) -> bool:
    """Map mode packs key and payload into int64."""
    return int(config["index"].get("payload_bits", 0)) > 0


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------


class IndexSystem:
    """The program's ``Index`` behind the calls the window makes.  Each
    call returns device arrays; ``fetch`` brings them to the host."""

    def __init__(self, config: dict, keys: np.ndarray, ids: np.ndarray):
        import jax
        from repro.api import OP_INSERT, OP_SEARCH, OpBatch, make_index

        self._jax, self._OpBatch = jax, OpBatch
        self._op_insert, self._op_search = OP_INSERT, OP_SEARCH
        # every key of the configuration's ``index`` but the backend goes
        # to the backend's config as it stands in the file
        ix = dict(config["index"])
        self.ix = make_index(ix.pop("backend"), initial=keys, payloads=ids,
                             **ix)
        self._kinds: dict[int, np.ndarray] = {}

    def impls(self, scan_width: int) -> dict:
        """Which walk and scan implementation this arena runs.  A sharded
        backend's state stacks its shards' arenas on a leading axis: it
        reports its shards, the devices of their mesh, its read dispatch
        (``fused``: one walk per device over its shards' arenas; ``vmap``:
        one per shard), and the ΔNodes, walk and scan of one shard."""
        from repro.api.index import cfg_attr
        from repro.kernels import ops

        ix, h = self.ix, cfg_attr(self.ix.cfg, "height")
        if not ix.capability.sharded:
            value, child = ix.state.value, ix.state.child
            out = {"engine": ix.engine, "arena_dnodes": int(value.shape[0])}
        else:
            from jax import ShapeDtypeStruct
            from repro.distributed.router import forest_mesh

            n, trees = ix.cfg.num_shards, ix.state.trees
            # one shard's arena by its shape: a slice would copy it on the
            # device, and raise the peak
            value, child = (ShapeDtypeStruct(a.shape[1:], a.dtype)
                            for a in (trees.value, trees.child))
            out = {"backend": ix.backend, "engine": ix.engine,
                   "num_shards": n,
                   "devices": [d.id for d in forest_mesh(n).devices.flat],
                   "read_dispatch": ("fused" if ix.capability.fused_forest
                                     else "vmap"),
                   "dnodes_per_shard": int(value.shape[0])}
        return out | {"walk": ops.walk_impl(value, child, height=h),
                      "scan": ops.scan_impl(value, child, height=h,
                                            max_out=max(scan_width, 1))}

    def read(self, keys):
        """(found, payload, hops) of each key."""
        return self.ix.lookup(self._jax.numpy.asarray(keys))

    def scan(self, starts, width: int):
        """(keys, payloads, counts) of the ``width`` smallest keys
        ``>= start``, per start."""
        # successor_k is exclusive: start - 1 gives the keys >= start
        keys, pays, n, _, _ = self.ix.successor_k(
            self._jax.numpy.asarray(starts - 1), width)
        return keys, pays, n

    def _batch(self, keys, payloads, kind: int):
        """An OpBatch of one op kind, built from host arrays so that it
        costs transfers and no device ops."""
        n = keys.shape[0]
        if (n, kind) not in self._kinds:
            self._kinds[n, kind] = np.full(n, kind, np.int32)
        return self._OpBatch.mixed(self._kinds[n, kind], keys, payloads)

    def insert(self, keys, ids):
        self.ix, res = self.ix.insert_delete(
            self._batch(keys, ids, self._op_insert))
        return res

    def warm_insert(self, n: int):
        """Run the insert program on ``n`` no-op rows (OP_SEARCH rows are
        no-ops), compiling it without changing the map."""
        ones = np.ones(n, np.int32)
        self.ix, res = self.ix.insert_delete(
            self._batch(ones, ones, self._op_search))
        return res

    def fetch(self, out):
        return self._jax.device_get(out)

    def size(self) -> int:
        return self.ix.size()

    def alloc_failed(self) -> bool:
        return self.ix.alloc_failed()


# --------------------------------------------------------------------------
# warm-up, window, check
# --------------------------------------------------------------------------


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def submit(system, traffic: Traffic, b: int) -> dict:
    """Dispatch batch ``b``: {"read": (found, payload, hops), "scan":
    (keys, payloads, counts), "insert": results}, device arrays that are
    still being computed."""
    bt = traffic.batch(b)
    out = {}
    if bt.read is not None:
        with _span("bench.search"):
            out["read"] = system.read(bt.read)
    if bt.scan_start is not None:
        with _span("bench.successor_k"):
            out["scan"] = system.scan(bt.scan_start, traffic.scan_width)
    if bt.insert is not None:
        with _span("bench.insert_delete"):
            out["insert"] = system.insert(bt.insert, bt.insert_id)
    return out


def collect(system, traffic: Traffic, b: int, out: dict) -> dict:
    """Wait for batch ``b``'s results and bring them to the host, numpy.
    Scan counts are cut to each op's drawn length."""
    with _span("bench.fetch"):
        got = system.fetch(out)
    if "scan" in got:
        rows, pays, n = got["scan"]
        got["scan"] = (rows, pays, np.minimum(n, traffic.batch(b).scan_len))
    return got


def warm_up(system, traffic: Traffic) -> None:
    """Run every shape the window and the check use, twice, leaving the
    map as it was: reads and scans on the pool's first batch, the insert
    program on no-op rows."""
    for _ in range(2):
        bt = traffic.batch(0)
        out = {}
        if bt.read is not None:
            out["read"] = system.read(bt.read)
        if bt.scan_start is not None:
            out["scan"] = system.scan(bt.scan_start, traffic.scan_width)
        if bt.insert is not None:
            out["insert"] = system.warm_insert(bt.insert.shape[0])
        system.fetch(out)
    system.size()
    system.alloc_failed()


@dataclasses.dataclass
class Window:
    results: list        # per batch, what `collect` returned
    latency_s: np.ndarray
    seconds: float
    compiles: int        # compilations seen inside the window


def run_window(system, traffic: Traffic, seconds: float) -> Window:
    """Closed loop for ``seconds`` with ``traffic.in_flight`` batches in
    flight: a batch is submitted as soon as the oldest one's results are
    on the host.  When the time is up nothing more is sent, every batch
    sent is waited for, and the clock is read after that wait: all of
    that work counts, over all of that time.  Each loop step (a submit,
    and the fetch of the oldest batch once ``in_flight`` are out, or one
    fetch of the final wait) is one ``bench.batch`` span."""
    import jax

    compiles = []

    def on_event(event, duration, **_):
        if "backend_compile" in event:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    results, lat = [], []
    pending = collections.deque()    # (batch, submitted at, device arrays)
    last = traffic.max_batches

    def retire() -> float:
        b, t0, out = pending.popleft()
        results.append(collect(system, traffic, b, out))
        t = time.perf_counter()
        lat.append(t - t0)
        return t

    try:
        start = now = time.perf_counter()
        b = 0
        while now - start < seconds and (last is None or b < last):
            with _span("bench.batch"):
                pending.append((b, time.perf_counter(),
                                submit(system, traffic, b)))
                b += 1
                now = (retire() if len(pending) >= traffic.in_flight
                       else time.perf_counter())
        while pending:
            with _span("bench.batch"):
                now = retire()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    if last is not None and b == last:
        log(f"window: closed early, the drawn inserts ran out after {b} "
            "batches; raise insert_batches in the mix file")
    return Window(results, np.asarray(lat), now - start, len(compiles))


def scan_mismatch(got, want, width: int) -> np.ndarray:
    """Per scan op: does (keys, payloads, count) differ from the
    reference's?  Rows are compared up to the count the system gave."""
    rows, pays, n = got
    exp_rows, exp_pays, exp_n = want
    valid = np.arange(width)[None, :] < n[:, None]
    return ((n != exp_n)
            | np.any(np.where(valid, rows, 0) != exp_rows, axis=1)
            | np.any(np.where(valid, pays, 0) != exp_pays, axis=1))


def read_mismatch(got, want) -> tuple[np.ndarray, np.ndarray]:
    """Per read: (found flag differs, payload of a found key differs)."""
    found, pay = got[0], got[1]
    exp_found, exp_pay = want
    return found != exp_found, exp_found & found & (pay != exp_pay)


def absent_keys(reference: SortedMap, key_hi: int) -> np.ndarray:
    """Keys next to the live ones that the map does not hold: each key
    plus one, where that is absent and inside the domain."""
    q = reference.keys.astype(np.int64) + 1
    q = q[q < key_hi].astype(np.int32)
    return q[~reference.contains(q)]


def _probe(system, call, chunks, compare) -> int:
    """Sum ``compare(q, m, got)`` over the probe batches ``(q, m)`` of
    ``chunks``, ``got`` being ``call(q)`` fetched to the host.  Up to
    ``CHECK_IN_FLIGHT`` calls are dispatched before the oldest one is
    fetched, so the device walks the next probes while the host compares."""
    bad = 0
    pending = collections.deque()
    for q, m in chunks:
        pending.append((q, m, call(q)))
        if len(pending) == CHECK_IN_FLIGHT:
            oldest, n, out = pending.popleft()
            bad += compare(oldest, n, system.fetch(out))
    for oldest, n, out in pending:
        bad += compare(oldest, n, system.fetch(out))
    return bad


def _chunks(probes: np.ndarray, lanes: int):
    """``probes`` in batches of ``lanes``, the last padded with repeats:
    (batch, how many of it are probes)."""
    for i in range(0, probes.size, lanes):
        yield (np.resize(probes[i:i + lanes], lanes),
               min(lanes, probes.size - i))


def live_mismatch(system, reference: SortedMap, traffic: Traffic,
                  key_lo: int, key_hi: int) -> dict:
    """Read the whole live map back through the system and count what
    differs from the reference, in batches of the window's shape
    (``n_scan`` starts, ``n_read`` lanes).  With scans: overlapping
    full-width scans that tile the reference (each starts at the last key
    of the one before, the first at ``key_lo``), so a missing, an extra
    or a misplaced key or record id shows in some row or count.  Reads
    only: every reference key must be found with its record id, a key
    next to each (``absent_keys``) must not be found, and ``size`` must
    match."""
    keys = reference.keys
    if traffic.n_scan:
        w, s = traffic.scan_width, traffic.n_scan
        step = w - 1
        starts = np.concatenate([[key_lo], keys[step::step]]).astype(np.int32)

        def scan_bad(q, m, got):
            want = reference.scan(q, np.full(s, w, np.int32), w)
            return int(np.count_nonzero(scan_mismatch(got, want, w)[:m]))

        return {"live_keys": _probe(system, lambda q: system.scan(q, w),
                                    _chunks(starts, s), scan_bad)}

    def read_bad(q, m, got):
        bad_f, bad_p = read_mismatch(got, reference.lookup(q))
        return int(np.count_nonzero((bad_f | bad_p)[:m]))

    r = traffic.n_read
    out = {"live_keys": abs(system.size() - len(reference)),
           "absent_found": 0}
    for probes, kind in ((keys, "live_keys"),
                         (absent_keys(reference, key_hi), "absent_found")):
        out[kind] += _probe(system, system.read, _chunks(probes, r), read_bad)
    return out


def check(window: Window, traffic: Traffic, reference: SortedMap,
          system, key_domain: tuple[int, int]) -> tuple[dict, dict]:
    """Compare every answer of the window with the reference, replaying
    the window's batches in order, then the live map read back.

    Returns the two numbers compared, each with the limit 0 (an exact
    comparison): ``wrong_answers``, the window's ops whose answer differs
    (a read's found flag or record id, a scan's keys, record ids or
    count, an insert's result), and ``wrong_live_keys``, the probes by
    which the map read back after the window differs (a live key missing
    or with another record id, an absent key found, a wrong ``size``),
    plus one where the index reports an exhausted arena
    (``alloc_failed()``), which the guarantees leave no room for.  The
    second dict splits them by kind, for the reader of a failed run."""
    w = traffic.scan_width
    kinds = {}
    if traffic.n_read:
        kinds["read_found"] = kinds["read_payload"] = 0
    if traffic.n_scan:
        kinds["scan"] = 0
    if traffic.n_insert:
        kinds["insert_result"] = 0
    wrong = 0
    read_cache = {}   # the map is static without inserts: reuse answers
    for b, got in enumerate(window.results):
        bt = traffic.batch(b)
        if bt.read is not None:
            p = b % traffic.pool_batches
            exp = read_cache.get(p)
            if exp is None:
                exp = reference.lookup(bt.read)
                if not traffic.n_insert:
                    read_cache[p] = exp
            bad_f, bad_p = read_mismatch(got["read"], exp)
            kinds["read_found"] += int(np.count_nonzero(bad_f))
            kinds["read_payload"] += int(np.count_nonzero(bad_p))
            wrong += int(np.count_nonzero(bad_f | bad_p))
        if bt.scan_start is not None:
            bad = int(np.count_nonzero(scan_mismatch(
                got["scan"], reference.scan(bt.scan_start, bt.scan_len, w),
                w)))
            kinds["scan"] += bad
            wrong += bad
        if bt.insert is not None:
            exp = reference.insert(bt.insert, bt.insert_id)
            bad = int(np.count_nonzero(got["insert"] != exp))
            kinds["insert_result"] += bad
            wrong += bad
    live = live_mismatch(system, reference, traffic, *key_domain)
    kinds.update(live)
    kinds["alloc_failed"] = int(bool(system.alloc_failed()))
    nums = {"wrong_answers": wrong,
            "wrong_live_keys": sum(live.values()) + kinds["alloc_failed"]}
    return nums, kinds


# --------------------------------------------------------------------------
# per-layer metrics
# --------------------------------------------------------------------------


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader may read about one traced run."""

    cell: Cell
    traffic: Traffic
    window: Window
    trace: object        # trace_reduce.Reduced
    peaks: dict          # bench/peaks.json entry of this device
    hops: int | None     # sum of per-read ΔNode hops in the window


def metric_base(name: str) -> str:
    """A metric split by groups of cells (``host_ms_per_batch.reads``)
    is one quantity: its reader and its arithmetic go by the part before
    the first dot."""
    return name.split(".", 1)[0]


def load_reader(metric: str):
    """``bench/metrics/<metric>.py``, else the reader of its base name."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{metric_base(metric)}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def per_layer_metrics(view: RunView) -> dict:
    out = {}
    for m in view.cell.per_layer:
        v = load_reader(m["name"])(view)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


def device_memory(devices) -> dict:
    """The memory entries of a result's ``device``: each of the cell's
    chips' ``peak_bytes_in_use`` after the window (0 where the backend
    keeps none), and the fullest chip's."""
    by_device = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in devices]
    return {"memory_peak_bytes": max(by_device),
            "memory_peak_bytes_by_device": by_device}


def end_to_end_metrics(cell: Cell, window: Window, attempted: int,
                       peak: int, live_keys: int, setup_s: float) -> dict:
    """The cell's end-to-end metrics of one untraced run; ``peak`` is
    the sum of the cell's chips' peaks, so that ``peak_bytes_per_key``
    counts every chip that holds the map."""
    values = {"ops_per_s": attempted / window.seconds,
              "peak_bytes_per_key": peak / live_keys,
              "setup_s": setup_s}
    out = {}
    for m in cell.end_to_end:
        name = base = metric_base(m["name"])
        if name.startswith("op_p") and name.endswith("_ms"):
            # every op of a batch has the batch's latency, so the
            # percentile over ops is the one over equally weighted
            # batches, taken without interpolation
            q = float(name[len("op_p"):-len("_ms")])
            values[name] = 1e3 * float(np.percentile(
                window.latency_s, q, method="inverted_cdf"))
        out[m["name"]] = {"value": values[base], "unit": m["unit"]}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, system_factory=IndexSystem) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    if needs_x64(cell.config) and not jax.config.jax_enable_x64:
        raise SystemExit("bench: map mode needs JAX_ENABLE_X64=1 set before "
                         "JAX is imported")
    devs = jax.devices()[:cell.chips]
    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if trace else None
    setup = {"init_s": time.perf_counter() - t_process}
    t = time.perf_counter()
    traffic = make_traffic(cell.config, cell.mix, seed)
    setup["traffic_s"] = time.perf_counter() - t
    t = time.perf_counter()
    system = system_factory(cell.config, traffic.loaded, traffic.loaded_ids)
    jax.block_until_ready(getattr(system, "ix", None))
    setup["build_s"] = time.perf_counter() - t
    impl = None
    if hasattr(system, "impls"):
        impl = system.impls(traffic.scan_width)
        print("implementations: " + json.dumps(impl), flush=True)
    t = time.perf_counter()
    warm_up(system, traffic)
    setup["warm_up_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_process
    log(f"setup: {setup_s!r} s {json.dumps(setup)}")

    if trace:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        window = run_window(system, traffic, min(seconds, TRACE_SECONDS))
        jax.profiler.stop_trace()
    else:
        window = run_window(system, traffic, seconds)
    memory = device_memory(devs)
    n_batches = len(window.results)
    lat = 1e3 * window.latency_s
    tenths = [round(float(x.mean()), 3) for x in np.array_split(lat, 10)
              if x.size]
    log(f"window: {n_batches} batches in {window.seconds!r} s, "
        f"{window.compiles} compiles inside it; batch ms min "
        f"{float(lat.min())!r} median {float(np.median(lat))!r} max "
        f"{float(lat.max())!r}, mean by tenth of the window {tenths}")

    t = time.perf_counter()
    reference = SortedMap(traffic.loaded, traffic.loaded_ids)
    key_domain = tuple(int(x) for x in cell.config["key_domain"])
    nums, kinds = check(window, traffic, reference, system, key_domain)
    check_s = time.perf_counter() - t
    log(f"check: {check_s!r} s, mismatches by kind {json.dumps(kinds)}")
    attempted = n_batches * traffic.batch_ops

    result = {"correct": all(v == 0 for v in nums.values()),
              "attempted": attempted, "failed": nums["wrong_answers"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())} | memory
    if trace:
        from trace_reduce import reduce_trace

        red = reduce_trace(TRACE_DIR)
        hops = (sum(int(g["read"][2].sum()) for g in window.results)
                if traffic.n_read else None)
        result["metrics"] = per_layer_metrics(
            RunView(cell, traffic, window, red, peaks, hops))
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["device"] = device
        result["breakdown"] = red.breakdown()
    else:
        result["metrics"] = end_to_end_metrics(
            cell, window, attempted,
            sum(memory["memory_peak_bytes_by_device"]), len(reference),
            setup_s)
        result["device"] = device
    result["implementations"] = impl
    result["setup_split"] = setup
    result["check_s"] = check_s
    result["batches"] = n_batches
    result["compiles_in_window"] = window.compiles
    result["mismatches_by_kind"] = kinds
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in nums.items()}
    return result


def report(result: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout."""
    for k, v in result["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
