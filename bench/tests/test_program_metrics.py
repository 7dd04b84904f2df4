"""Tests of the readers of the program's own spans and per-call counters
(``bench/program_trace.py``), on a trace captured on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

A tiny traced run of each cell is driven as ``bench/run.py --trace 1``
drives one, with the trace in a temporary directory: its ``index.*``
events must join to the program's ring rows and the readers must read
what the rows and spans say.  A trace of a program without those spans
(the recorded chip trace of ``bench/testdata``), or a ring that no
longer holds the window's rows, must read None.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
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

import harness  # noqa: E402
import program_trace as PT  # noqa: E402
import rehearse  # noqa: E402
import trace_reduce as TRD  # noqa: E402

SEED = 2**31 + 4242
PEAKS = {"hbm_bytes_per_s": 8.19e11}


def _built_s() -> float:
    from repro.obs import trace

    return trace.totals().get("index.build", {}).get("total_s", 0.0)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One tiny traced run per cell: (result, RunView, trace dir, the
    run's own ``index.build`` seconds)."""
    out = {}
    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "peaks_for", lambda kind: PEAKS)
    views = []
    mp.setattr(harness, "per_layer_metrics",
               _keep(views, harness.per_layer_metrics))
    try:
        for name in ("ycsb_c.4m", "ycsb_e.4m"):
            d = tmp_path_factory.mktemp(name)
            mp.setattr(harness, "TRACE_DIR", d)
            cell = rehearse.tiny(harness.load_cell(name))
            before = _built_s()
            res = harness.run(cell, SEED, 0.5, True, time.perf_counter())
            out[name] = (res, views[-1], d, _built_s() - before)
    finally:
        mp.undo()
    return out


def _keep(views, fn):
    def wrapped(view):
        views.append(view)
        return fn(view)
    return wrapped


def _reader(name):
    return harness.load_reader(name)


def test_window_events_join_the_ring(traced, monkeypatch):
    from repro.obs import calls

    for name, (res, view, d, _) in traced.items():
        assert res["correct"], res["checks"]
        monkeypatch.setattr(harness, "TRACE_DIR", d)
        evs = PT.window_events(view)
        kinds = {"ycsb_c.4m": {"index.lookup"},
                 "ycsb_e.4m": {"index.successor_k", "index.update"}}[name]
        assert {e.name for e in evs} == kinds
        # one call of each kind per batch of the window
        n = len(view.window.results)
        for k in kinds:
            assert sum(e.name == k for e in evs) == n
        rows = calls([e.seq for e in evs])
        assert rows["seq"].tolist() == [e.seq for e in evs]


def test_readers_read_the_rows_and_spans(traced, monkeypatch):
    from repro.obs import trace

    res, view, d, built = traced["ycsb_e.4m"]
    monkeypatch.setattr(harness, "TRACE_DIR", d)
    m = res["metrics"]
    for key in ("scan_visits_per_key", "build_s"):
        assert key in m, m
    # E's scan spans hold the runtime's back-pressure, not host work
    assert "dispatch_ms_per_batch.scans" not in m
    rows = PT.window_rows(view, "index.successor_k")
    want = rows["hops_sum"].sum() / rows["emitted"].sum()
    assert m["scan_visits_per_key"]["value"] == pytest.approx(want)
    # every emitted key costs at least one ΔNode visit
    assert m["scan_visits_per_key"]["value"] >= 1
    n = len(view.window.results)
    # the reading is the process's total; one process builds once in a
    # benchmark run, several times here
    assert m["build_s"]["value"] == trace.totals()["index.build"]["total_s"]
    c_res, _, _, c_built = traced["ycsb_c.4m"]
    for r, b in ((res, built), (c_res, c_built)):
        assert 0 < b <= r["setup_split"]["build_s"]
    # the CPU trace has no device modules: the round time reads from a
    # stand-in reduction with 2 ms of successor_k_jit per batch
    red = dataclasses.replace(view.trace, modules={
        "jit_successor_k_jit": 2e6 * n, "jit_update_batch": 1e6})
    v = dataclasses.replace(view, trace=red)
    got = _reader("scan_us_per_round")(v)
    assert got == pytest.approx(2e3 * n / rows["hops_max"].sum())
    c_res, c_view, c_dir, _ = traced["ycsb_c.4m"]
    monkeypatch.setattr(harness, "TRACE_DIR", c_dir)
    evs = PT.window_events(c_view)
    assert c_res["metrics"]["dispatch_ms_per_batch.reads"]["value"] == \
        pytest.approx(sum(e.end - e.start for e in evs) * 1e-6
                      / len(c_view.window.results))
    assert "scan_visits_per_key" not in c_res["metrics"]


def test_overwritten_window_rows_read_none(traced, monkeypatch):
    from repro.obs import ring

    res, view, d, _ = traced["ycsb_e.4m"]
    monkeypatch.setattr(harness, "TRACE_DIR", d)
    assert _reader("scan_visits_per_key")(view) is not None
    for _ in range(ring.ROWS):
        ring.record("search", ring.next_seq(), 1)
    assert PT.window_rows(view, "index.successor_k") is None
    assert _reader("scan_visits_per_key")(view) is None
    assert _reader("scan_us_per_round")(view) is None
    # the spans need no ring
    assert _reader("dispatch_ms_per_batch")(view) is not None


def test_a_program_without_spans_or_counters_reads_none(tmp_path,
                                                        monkeypatch):
    """The recorded chip trace comes from a program that had no index.*
    spans: every new reader returns None, and so do the ring and totals
    readers when the program lacks them."""
    rec = BENCH / "testdata" / "ycsb_c.1m.xplane.pb"
    dst = tmp_path / "plugins" / "profile" / "t"
    dst.mkdir(parents=True)
    shutil.copy(rec, dst / "h.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    red = TRD.reduce_trace(rec)
    cell = harness.load_cell("ycsb_e.4m")
    win = harness.Window(results=[{}] * len(red.batches),
                         latency_s=np.ones(len(red.batches)), seconds=1.0,
                         compiles=0)
    view = harness.RunView(cell, None, win, red, PEAKS, None)
    for name in ("dispatch_ms_per_batch", "scan_visits_per_key",
                 "scan_us_per_round"):
        assert _reader(name)(view) is None, name
    import repro.obs
    import repro.obs.trace

    monkeypatch.delattr(repro.obs, "calls")
    monkeypatch.delattr(repro.obs.trace, "totals")
    assert _reader("build_s")(view) is None
    assert PT.window_rows(view, "index.successor_k") is None
