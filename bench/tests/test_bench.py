"""Tests of the benchmark harness, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They drive a run as ``bench/run.py`` does, without the look for a chip:
clean runs must read ``correct: true``; runs with a fault planted under
the timed path (``bench/faults.py``) and runs of the control
(``bench/control.py``) must read ``correct: false``.  The trace reduction
is checked on synthetic intervals and on a small trace recorded on a TPU
v5e (``bench/testdata``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
os.environ.setdefault("REPRO_PALLAS_INTERPRET", "0")
os.environ.setdefault("JAX_ENABLE_X64", "1")   # map mode, before JAX
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import control  # noqa: E402
import faults  # noqa: E402
import harness  # noqa: E402
import rehearse  # noqa: E402
import trace_reduce as TRD  # noqa: E402
import traffic as TF  # noqa: E402
from reference import SortedMap  # noqa: E402

CELLS = ["ycsb_c.4m", "ycsb_e.4m"]
SECONDS = 0.5
SEED = 2**31 + 12345          # past 32 signed bits, as the driver's are


def tiny_run(name, seed=SEED, factory=harness.IndexSystem):
    cell = rehearse.tiny(harness.load_cell(name))
    return harness.run(cell, seed, SECONDS, False, time.perf_counter(),
                       system_factory=factory)


# ---- the reference --------------------------------------------------------


def test_reference_matches_brute_force():
    rng = np.random.default_rng(0)
    keys = rng.choice(np.arange(1, 500), 200, replace=False)
    live = {int(k): 7 * i for i, k in enumerate(keys)}
    ref = SortedMap(keys, list(live.values()))
    q = rng.integers(0, 520, 300)
    found, pay = ref.lookup(q)
    assert np.array_equal(found, [int(x) in live for x in q])
    assert np.array_equal(pay, [live.get(int(x), 0) for x in q])
    starts = rng.integers(1, 520, 50)
    lens = rng.integers(1, 9, 50)
    rows, pays, n = ref.scan(starts, lens, 8)
    for s, ln, row, prow, c in zip(starts, lens, rows, pays, n):
        want = sorted(k for k in live if k >= s)[:ln]
        assert c == len(want) and list(row[:c]) == want
        assert list(prow[:c]) == [live[k] for k in want]
        assert not row[c:].any() and not prow[c:].any()
    new = np.array([3, 3, 1000, int(keys[0]), 999], np.int32)
    res = ref.insert(new, [11, 12, 13, 14, 15])
    exp = [3 not in live, False, True, False, True]
    assert list(res) == exp
    if exp[0]:
        live[3] = 11
    # keys[0], already present, keeps its record id
    live |= {1000: 13, 999: 15}
    assert list(ref.keys) == sorted(live)
    assert list(ref.payloads) == [live[k] for k in sorted(live)]


def test_absent_keys_are_absent():
    ref = SortedMap([1, 2, 5, 9], [0, 1, 2, 3])
    assert harness.absent_keys(ref, 10).tolist() == [3, 6]


# ---- traffic ----------------------------------------------------------------


def test_fnvhash64_is_ycsb_fnv1a():
    def one(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= v & 0xFF
            h = (h * 1099511628211) % 2**64
            v >>= 8
        return abs(h - 2**64 if h >= 2**63 else h)

    vals = np.array([0, 1, 255, 2**40 + 7, 2**62], np.uint64)
    assert [int(x) for x in TF.fnvhash64(vals)] == [one(int(v)) for v in vals]


def test_traffic_is_drawn_from_the_seed():
    cell = rehearse.tiny(harness.load_cell("ycsb_e.4m"))
    a = TF.make_traffic(cell.config, cell.mix, SEED)
    b = TF.make_traffic(cell.config, cell.mix, SEED)
    c = TF.make_traffic(cell.config, cell.mix, SEED + 1)
    assert np.array_equal(a.scan_starts, b.scan_starts)
    assert np.array_equal(a.scan_lens, b.scan_lens)
    assert not np.array_equal(a.scan_starts, c.scan_starts)
    # records and inserts are YCSB's hashed ids, alike under every seed
    assert np.array_equal(a.loaded, c.loaded)
    assert np.array_equal(a.inserts, c.inserts)
    # the record ids: loaded records 0..N-1, inserts N, N+1, ... in order
    assert a.loaded_ids.tolist() == list(range(a.loaded.size))
    b1 = a.batch(1)
    assert b1.insert_id[0] == a.loaded.size + a.n_insert
    assert np.array_equal(b1.insert, a.inserts[1])
    allkeys = np.concatenate([a.loaded, a.inserts.ravel()])
    assert np.unique(allkeys).size == allkeys.size
    lo, hi = cell.config["key_domain"]
    assert allkeys.min() >= lo and allkeys.max() < hi
    assert np.isin(a.scan_starts, a.loaded).all()
    assert a.scan_lens.min() >= 1 and a.scan_lens.max() <= a.scan_width
    # every seed gets the same shapes
    assert a.scan_starts.shape == c.scan_starts.shape
    assert a.inserts.shape == c.inserts.shape


def test_zipfian_is_skewed_like_ycsb():
    rng = np.random.default_rng(1)
    items = TF.zipfian(rng.random(200_000))
    # item 0 takes 1/zeta(n, 0.99) of the draws, item 1 0.5**0.99 of that
    p0 = np.mean(items == 0)
    assert abs(p0 - 1 / TF.ZIPF_ZETAN) < 0.004
    assert abs(np.mean(items == 1) / p0 - 0.5 ** 0.99) < 0.05
    recs = TF.scrambled_zipfian(rng, 1000, 100_000)
    assert recs.min() >= 0 and recs.max() < 1000


# ---- runs -------------------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_clean_run_is_correct(name):
    res = tiny_run(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert list(res["checks"]) == ["wrong_answers", "wrong_live_keys"]
    assert list(res)[-1] == "checks"
    assert res["implementations"]["engine"] == "lockstep"


# faults a cell can have: no inserts in C, no point reads in E
FAULT_CASES = [(c, f) for c in CELLS
               for f in faults.FAULTS
               if not (f == "state_unchanged" and c.startswith("ycsb_c"))
               and not (f == "all_found" and c.startswith("ycsb_e"))]


@pytest.mark.parametrize("name,fault", FAULT_CASES)
def test_planted_fault_reads_incorrect(name, fault):
    wrap = faults.FAULTS[fault]
    res = tiny_run(name, factory=lambda cfg, keys, ids: wrap(
        harness.IndexSystem(cfg, keys, ids)))
    assert not res["correct"], res["checks"]
    if fault == "all_found":
        # the window reads present keys only: the absent probes catch it
        assert res["mismatches_by_kind"]["absent_found"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_incorrect(name):
    cell = rehearse.tiny(harness.load_cell(name))
    ops = sum(int(cell.mix.get(k, 0)) for k in ("read", "scan", "insert"))
    res = harness.run(cell, SEED, SECONDS, False, time.perf_counter(),
                      system_factory=control.factory(ops))
    assert not res["correct"], res["checks"]


# the 1-chip cells build and report as they did before the harness took
# any backend: make_index's keyword arguments with their types, and the
# printed implementations, at rehearsal size
PINNED_INDEX_CALL = {"height": 7, "max_dnodes": rehearse.TINY_DNODES,
                     "payload_bits": 32, "engine": "auto"}
PINNED_IMPLS = ('{"engine": "lockstep", "arena_dnodes": 2048, '
                '"walk": "ref_delta_walk_fused", '
                '"scan": "ref_delta_scan_fused"}')


@pytest.mark.parametrize("name", CELLS)
def test_one_chip_cells_build_and_report_as_before(name, monkeypatch):
    import repro.api

    calls = []
    real = repro.api.make_index

    def recording(backend, **kw):
        calls.append((backend, {k: v for k, v in kw.items()
                                if k not in ("initial", "payloads")}))
        return real(backend, **kw)

    monkeypatch.setattr(repro.api, "make_index", recording)
    cell = rehearse.tiny(harness.load_cell(name))
    tr = TF.make_traffic(cell.config, cell.mix, SEED)
    system = harness.IndexSystem(cell.config, tr.loaded, tr.loaded_ids)
    [(backend, kw)] = calls
    assert backend == "deltatree"
    assert kw == PINNED_INDEX_CALL
    assert all(type(kw[k]) is type(v) for k, v in PINNED_INDEX_CALL.items())
    assert json.dumps(system.impls(tr.scan_width)) == PINNED_IMPLS


class _StubDevice:
    def __init__(self, peak):
        self.peak = peak

    def memory_stats(self):
        return None if self.peak is None else {"peak_bytes_in_use": self.peak}


def test_device_memory_is_read_on_every_chip_of_the_cell(monkeypatch):
    """``peak_bytes_per_key`` divides the sum over the cell's chips by the
    live keys; ``memory_peak_bytes`` is the fullest chip's."""
    stubs = [_StubDevice(p) for p in (100, 700, None, 200)]
    mem = harness.device_memory(stubs)
    assert mem == {"memory_peak_bytes": 700,
                   "memory_peak_bytes_by_device": [100, 700, 0, 200]}
    read_on = []

    def stubbed(devices):
        read_on.append(list(devices))
        return mem

    monkeypatch.setattr(harness, "device_memory", stubbed)
    cell = rehearse.tiny(harness.load_cell("ycsb_c.4m"))
    res = harness.run(cell, SEED, SECONDS, False, time.perf_counter())
    import jax

    assert read_on == [jax.devices()[:cell.chips]]
    assert res["device"]["memory_peak_bytes"] == 700
    assert res["device"]["memory_peak_bytes_by_device"] == [100, 700, 0, 200]
    # C inserts nothing: the live keys are the loaded records
    assert res["metrics"]["peak_bytes_per_key"]["value"] == \
        1000 / cell.config["recordcount"]


def test_rehearsal_gives_the_cpu_a_device_per_chip():
    one = {"workloads": [{"chips": 1}, {"chips": 1}]}
    four = {"workloads": [{"chips": 1}, {"chips": 4}]}
    flag = "--xla_force_host_platform_device_count"
    assert rehearse.with_host_devices("", one) == ""
    assert rehearse.with_host_devices("--xla_foo", one) == "--xla_foo"
    assert rehearse.with_host_devices("", four) == f"{flag}=4"
    assert rehearse.with_host_devices("--xla_foo", four) == \
        f"--xla_foo {flag}=4"
    # a count already set stands
    assert rehearse.with_host_devices(f"{flag}=8", four) == f"{flag}=8"


class _Prober:
    """A read-only system that counts its probe calls in flight."""

    def __init__(self, reference):
        self.ref, self.out, self.most = reference, 0, 0

    def read(self, keys):
        self.out += 1
        self.most = max(self.most, self.out)
        found, pay = self.ref.lookup(keys)
        return found, pay, np.zeros(keys.size, np.int32)

    def scan(self, starts, width):
        self.out += 1
        self.most = max(self.most, self.out)
        return self.ref.scan(starts, np.full(starts.size, width, np.int32),
                             width)

    def fetch(self, out):
        self.out -= 1
        return out

    def size(self):
        return len(self.ref)


@pytest.mark.parametrize("name", CELLS)
def test_read_back_keeps_probe_calls_in_flight(name):
    """The read-back dispatches up to CHECK_IN_FLIGHT probe calls of the
    window's shape before it fetches the oldest, fetches every one, and
    counts a planted difference once."""
    cell = rehearse.tiny(harness.load_cell(name))
    # few scans a batch, so that the tiles of the map take many batches
    mix = dict(cell.mix, scan=16) if cell.mix["scan"] else cell.mix
    tr = TF.make_traffic(cell.config, mix, SEED)
    ref = SortedMap(tr.loaded, tr.loaded_ids)
    lo, hi = cell.config["key_domain"]
    prober = _Prober(SortedMap(tr.loaded, tr.loaded_ids))
    assert set(harness.live_mismatch(prober, ref, tr, lo, hi).values()) == {0}
    assert prober.most == harness.CHECK_IN_FLIGHT and prober.out == 0
    # the system's copy loses its last key
    prober = _Prober(SortedMap(tr.loaded, tr.loaded_ids))
    prober.ref.keys, prober.ref.payloads = (prober.ref.keys[:-1],
                                            prober.ref.payloads[:-1])
    got = harness.live_mismatch(prober, ref, tr, lo, hi)
    want = {"live_keys": 2, "absent_found": 0} if tr.n_read else \
        {"live_keys": 1}
    assert got == want


# ---- a sharded forest on 4 devices -----------------------------------------


@pytest.fixture(scope="module")
def forest_runs():
    """Tiny C and E runs of a 4-shard forest (``bench/forest_probe.py``),
    clean and with one answer altered, in a process whose CPU has 4
    devices: the count is set before JAX starts."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, str(BENCH / "forest_probe.py"), "--tiny",
         "--seed", str(SEED), "--seconds", str(SECONDS),
         "--fault", "answer_altered"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    runs = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]
    return {(r["workload"], r["fault"]): r for r in runs}


@pytest.mark.parametrize("name", CELLS)
def test_forest_on_four_devices_runs_correct(forest_runs, name):
    res = forest_runs[f"{name}.forest4", None]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["implementations"] == {
        "backend": "forest", "engine": "lockstep", "num_shards": 4,
        "devices": [0, 1, 2, 3], "read_dispatch": "fused",
        "dnodes_per_shard": rehearse.TINY_DNODES,
        "walk": "ref_delta_walk_fused", "scan": "ref_delta_scan_fused"}
    assert res["device"]["count"] == 4
    assert len(res["device"]["memory_peak_bytes_by_device"]) == 4


@pytest.mark.parametrize("name", CELLS)
def test_forest_answer_altered_reads_incorrect(forest_runs, name):
    res = forest_runs[f"{name}.forest4", "answer_altered"]
    assert not res["correct"], res["checks"]
    assert res["failed"] == 1


def test_benchmark_cells_and_metrics_are_found_by_name():
    for name in CELLS:
        cell = harness.load_cell(name)
        e2e = {m["name"] for m in cell.end_to_end}
        group, tail = (("reads", "op_p95_ms") if name.startswith("ycsb_c")
                       else ("scans", "op_p90_ms"))
        assert e2e == {f"ops_per_s.{group}", tail, "peak_bytes_per_key",
                       "setup_s"}
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]))
            assert m["moves"] in e2e
    kinds = {m["name"] for m in harness.load_cell("ycsb_c.4m").per_layer}
    assert "walk_roofline" in kinds and "scan_device_ms" not in kinds
    assert "host_ms_per_batch.reads" in kinds


class _Recorder:
    """A system whose calls only log their order; answers are batch ids."""

    def __init__(self):
        self.log = []

    def scan(self, starts, width):
        self.log.append(("scan", int(starts[0])))
        return starts, starts, np.full(starts.size, width, np.int32)

    def insert(self, keys, ids):
        self.log.append(("insert", int(ids[0])))
        return np.ones(keys.size, bool)

    def fetch(self, out):
        self.log.append(("fetch", int(out["scan"][0][0])))
        return out


@pytest.mark.parametrize("in_flight", [1, 3])
def test_window_keeps_batches_in_flight_and_waits_for_all(in_flight):
    cell = rehearse.tiny(harness.load_cell("ycsb_e.4m"))
    tr = TF.make_traffic(cell.config, dict(cell.mix, in_flight=in_flight),
                         SEED)
    tr.scan_starts = np.arange(tr.scan_starts.shape[0])[:, None] + np.zeros(
        tr.scan_starts.shape, np.int64)          # batch b scans from b
    sys_ = _Recorder()
    win = harness.run_window(sys_, tr, 0.05)
    n = len(win.results)
    assert n == win.latency_s.size and n >= in_flight
    fetched = [b for op, b in sys_.log if op == "fetch"]
    assert fetched == [b % tr.pool_batches for b in range(n)]
    # a batch's fetch comes after the next in_flight - 1 batches were sent
    # (in the window's steady part), and nothing is sent after the last
    # batch's time is up
    sent = [i for i, (op, _) in enumerate(sys_.log) if op == "scan"]
    got = [i for i, (op, _) in enumerate(sys_.log) if op == "fetch"]
    assert len(sent) == n
    for b in range(n - in_flight + 1):
        assert got[b] > sent[b + in_flight - 1]
    assert all(got[b] > sent[-1] for b in range(n - in_flight + 1, n))


def test_end_to_end_metrics_of_split_names():
    cell = harness.load_cell("ycsb_e.4m")
    win = harness.Window(results=[], latency_s=np.array([0.5, 0.4, 0.6]),
                         seconds=2.0, compiles=0)
    out = harness.end_to_end_metrics(cell, win, 12288, 1000, 10, 3.5)
    assert out["ops_per_s.scans"] == {"value": 6144.0, "unit": "ops/s"}
    assert out["op_p90_ms"]["value"] == 600.0
    assert out["peak_bytes_per_key"]["value"] == 100.0
    assert list(out) == [m["name"] for m in cell.end_to_end]


# ---- trace reduction --------------------------------------------------------


def test_union_and_coverage_on_known_intervals():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [12, 13]], float)
    assert TRD.union(iv).tolist() == [[0, 3], [5, 9], [12, 13]]
    merged = TRD.union(iv)
    assert TRD.covered(merged, 2, 12) == 1 + 4
    red = TRD.Reduced(
        spans=[("bench.batch", 0, 10), ("bench.search", 0, 1),
               ("bench.fetch", 1, 10), ("bench.batch", 10, 14)],
        batches=np.array([[0, 10], [10, 14]], float), busy=[merged],
        modules={"jit_search_jit": 8.0}, module_runs={"jit_search_jit": 2},
        ops={"fusion": 8.0})
    assert red.window == (0, 14)
    assert red.busy_ns(0, 14) == 3 + 4 + 1
    gaps = red.gaps()
    assert sum(g for _, g in gaps) == 14 - 8
    # [3, 5) inside the fetch; [9, 12) has its midpoint in the second
    # batch, which opened no other span; [13, 14) likewise
    assert gaps == [("bench.fetch", 2.0), ("bench.batch", 3.0),
                    ("bench.batch", 1.0)]


def test_device_time_by_device():
    """Per device: busy seconds in the window (their mean is ``busy_s``)
    and module time under ``module_ns``'s name filter (their sum is
    ``module_ns``)."""
    red = TRD.Reduced(
        spans=[("bench.batch", 0, 10)], batches=np.array([[0, 10]], float),
        busy=[TRD.union(np.array([[0, 4], [6, 8]], float)),
              TRD.union(np.array([[2, 14]], float))],
        modules={"jit_lookup_jit": 9.0, "jit_record": 1.0},
        module_runs={"jit_lookup_jit": 2, "jit_record": 2}, ops={},
        device_modules=[{"jit_lookup_jit": 5.0, "jit_record": 0.5},
                        {"jit_lookup_jit": 4.0, "jit_record": 0.5}])
    assert red.busy_s_by_device() == [pytest.approx(6e-9),
                                      pytest.approx(8e-9)]
    assert red.busy_s == pytest.approx(7e-9)
    walk = lambda name: "lookup_jit" in name  # noqa: E731
    assert red.module_ns_by_device(walk) == [5.0, 4.0]
    assert red.module_ns(walk) == 9.0


RECORDED = BENCH / "testdata" / "ycsb_c.1m.xplane.pb"


def test_reduction_of_a_recorded_chip_trace():
    """A 0.03 s window of point reads on a 1M-key set-mode arena that the
    Pallas walk held in VMEM, on a TPU v5e: 9 batches, one search program
    each.  The numbers the chip run printed for it are
    pinned, and the idle gaps add up to the window less the busy time."""
    red = TRD.reduce_trace(RECORDED)
    assert len(red.batches) == 9
    assert list(red.module_runs.values()) == [9]
    assert "search_jit" in next(iter(red.modules))
    assert red.busy_s == pytest.approx(0.012446801, rel=1e-9)
    assert red.window_s == pytest.approx(0.0301558, rel=1e-9)
    lo, hi = red.window
    idle = sum(g for _, g in red.gaps())
    assert idle == pytest.approx(hi - lo - red.busy_ns(lo, hi), rel=1e-9)
    bd = red.breakdown()
    assert [n for n, _ in bd["idle_gaps"]] == ["bench.fetch", "bench.search"]
    assert bd["idle_gaps"][0][1] == pytest.approx(0.017061475, rel=1e-9)
    assert 0 < len(bd["device_ops"]) <= TRD.TOP
    # one chip: its plane is the one device (the trace's other device
    # plane, the runtime's own, holds no ops)
    assert red.busy_s_by_device() == [pytest.approx(red.busy_s, rel=1e-12)]
    every = lambda name: True  # noqa: E731
    assert red.module_ns_by_device(every) == [red.module_ns(every)]
