"""Tests of the forest cell's readers (``forest_walk_device_ms``,
``forest_walk_roofline``, ``walk_lanes_per_op``,
``shard_lane_max_share``) on a tiny traced rehearsal of
``ycsb_c.forest4``, on the CPU with 4 devices.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The run happens in a subprocess whose CPU has 4 devices (the count is
set before JAX starts), and the readers run there too, since the ring
they read lives in that process.  The CPU trace holds no device modules,
so the device-time readers read a stand-in reduction with a
``jit_forest_lookup`` module on each device.  Each reader must read None
where what it reads is absent, as on a program older than it: a ring
without the router columns (a stubbed ``repro.obs.calls``), a trace
whose forest read program has another name.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SEED = 2**31 + 16016

_SCRIPT = """
import dataclasses, json, sys, tempfile, time
from pathlib import Path
import numpy as np
sys.path[:0] = [%(src)r, %(bench)r]
import harness, rehearse
import repro.obs

PEAKS = {"hbm_bytes_per_s": 8.19e11}
harness.peaks_for = lambda kind: PEAKS
harness.TRACE_DIR = Path(tempfile.mkdtemp())
views = []
real = harness.per_layer_metrics
harness.per_layer_metrics = lambda view: views.append(view) or real(view)
cell = rehearse.tiny(harness.load_cell("ycsb_c.forest4"))
res = harness.run(cell, %(seed)d, 0.5, True, time.perf_counter())
(view,) = views
read = {m: harness.load_reader(m) for m in (
    "forest_walk_device_ms", "forest_walk_roofline", "walk_lanes_per_op",
    "shard_lane_max_share")}
n = len(view.window.results)
out = {"correct": res["correct"], "impl": res["implementations"],
       "metrics": {k: v["value"] for k, v in res["metrics"].items()},
       "batches": n, "hops": view.hops,
       "height": cell.config["index"]["height"]}

# stand-in device modules: chip i spends (i + 1) ms a batch in the read
per_dev = [{"jit_forest_lookup": 1e6 * (i + 1) * n, "jit__record": 5e3}
           for i in range(4)]
stand_in = dataclasses.replace(
    view, trace=dataclasses.replace(view.trace, device_modules=per_dev))
out["stand_in"] = {m: read[m](stand_in) for m in read}
older = [{"jit__lookup_core": 1e6 * n} for _ in range(4)]
renamed = dataclasses.replace(
    view, trace=dataclasses.replace(view.trace, device_modules=older))
out["renamed"] = {m: read[m](renamed) for m in read}

# a ring of the program before the router columns
calls = repro.obs.calls
def old_calls(seqs):
    rows = calls(seqs)
    keep = [c for c in rows.dtype.names
            if c not in ("lanes_dev_max", "lanes_walked")]
    return rows[keep]
repro.obs.calls = old_calls
out["old_ring"] = {m: read[m](stand_in) for m in read}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def forest_trace():
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="1",
               REPRO_PALLAS_INTERPRET="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _SCRIPT % {"src": str(ROOT / "src"), "bench": str(BENCH),
                      "seed": SEED}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_forest_rehearsal_is_correct_on_four_devices(forest_trace):
    assert forest_trace["correct"]
    impl = forest_trace["impl"]
    assert impl["backend"] == "forest" and impl["num_shards"] == 4
    assert impl["devices"] == [0, 1, 2, 3]
    assert impl["read_dispatch"] == "fused"


def test_router_readers_read_the_ring(forest_trace):
    m = forest_trace["metrics"]
    # (D, K) lanes on D = 4 devices: 4 lanes walked per read
    assert m["walk_lanes_per_op"] == 4.0
    # the hot device holds at least an even quarter, at most every lane
    assert 25.0 <= m["shard_lane_max_share"] <= 100.0
    # the CPU trace has no device modules: no device time to read
    assert "forest_walk_device_ms" not in m
    assert "forest_walk_roofline" not in m


def test_device_readers_read_the_slowest_chip_and_the_sum(forest_trace):
    got = forest_trace["stand_in"]
    # the slowest of 1, 2, 3 and 4 ms a batch
    assert got["forest_walk_device_ms"] == pytest.approx(4.0)
    n, h = forest_trace["batches"], forest_trace["height"]
    need = forest_trace["hops"] * (2 ** h - 1) * 8
    assert need > 0
    want = 100.0 * need / ((1 + 2 + 3 + 4) * 1e-3 * n * 8.19e11)
    assert got["forest_walk_roofline"] == pytest.approx(want)
    assert got["walk_lanes_per_op"] == 4.0


def test_readers_read_none_without_what_they_read(forest_trace):
    renamed = forest_trace["renamed"]
    assert renamed["forest_walk_device_ms"] is None
    assert renamed["forest_walk_roofline"] is None
    old = forest_trace["old_ring"]
    assert old["walk_lanes_per_op"] is None
    assert old["shard_lane_max_share"] is None
    # the device readers need no ring
    assert old["forest_walk_device_ms"] == pytest.approx(4.0)
