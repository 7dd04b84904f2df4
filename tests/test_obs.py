"""repro.obs — counter pytrees, spans and per-call counters (DESIGN.md §9).

The two contracts everything else leans on:

- ``collect_stats=False`` is *free*: the dispatched read lowers to HLO
  byte-identical to the bare engine-hook composition (the pre-obs graph).
- ``collect_stats=True`` stats are engine-invariant: derived from the
  (found, hops) columns the conformance suite already pins bit-identical,
  so the hop histogram must match bit for bit across scalar/lockstep and
  across the forest's fused/vmap dispatches.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import make_index
from repro.core import deltatree as DT
from repro.core import engine as E
from repro.core import layout
from repro.core.deltatree import TreeConfig
from repro.distributed import forest as D
from repro.distributed.forest import ForestConfig
from repro.obs import trace
from repro.obs.stats import (
    HOP_BINS,
    LATENCY_RESERVOIR,
    MaintenanceStats,
    ReadStats,
    RouterStats,
    SearchStats,
    ServeStats,
)

KEYS = np.arange(10, 400, 7, dtype=np.int64)
CFG = TreeConfig(height=4, max_dnodes=256, buf_cap=8, collect_stats=True)


def _queries():
    """Hits, misses, and born-resolved ROUTE_LEFT sentinel lanes."""
    return jnp.asarray(
        list(KEYS[:6]) + [5, 11, 401, layout.ROUTE_LEFT, layout.ROUTE_LEFT],
        jnp.int32)


# --------------------------------------------------------------- pytrees ---


def test_stats_jit_roundtrip():
    s = SearchStats.of(jnp.asarray([0, 1, 2, 2], jnp.int32),
                       jnp.zeros(4, bool), jnp.zeros(4, bool))
    r = RouterStats.of(jnp.asarray([3, 1], jnp.int32), 0)
    v = ServeStats.zero()

    s2 = jax.jit(lambda x: x.merge(x))(s)
    assert int(s2.queries) == 8 and int(s2.rounds) == 2
    r2 = jax.jit(lambda x: x.merge(x))(r)
    assert np.asarray(r2.lanes).tolist() == [6, 2]
    v2 = jax.jit(lambda x: x.record(1e-3, pending=3, flushed=True))(v)
    assert int(v2.steps) == 1 and int(v2.pending_hwm) == 3
    # ReadStats with router=None flattens to nothing on that leaf
    rs = ReadStats(search=s)
    rs2 = jax.jit(lambda x: x)(rs)
    assert rs2.router is None and int(rs2.search.queries) == 4


def test_reduce_semantics_max_rounds_sum_work():
    """reduce over stacked (S,) legs: rounds-like max, work-like sum."""
    a = SearchStats.of(jnp.asarray([1, 1], jnp.int32),
                       jnp.zeros(2, bool), jnp.zeros(2, bool))
    b = SearchStats.of(jnp.asarray([3, 2], jnp.int32),
                       jnp.zeros(2, bool), jnp.ones(2, bool))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), a, b)
    red = SearchStats.reduce(stacked)
    assert int(red.rounds) == 3 and int(red.hops_max) == 3   # critical path
    assert int(red.queries) == 4 and int(red.hops_sum) == 7  # work sums
    assert int(red.buffer_hits) == 2
    assert np.asarray(red.hops_hist).sum() == 4

    ma = MaintenanceStats(rounds=jnp.int32(2), rebuilds=jnp.int32(1),
                          expands=jnp.int32(0), merges=jnp.int32(3),
                          pending=jnp.int32(4))
    mb = MaintenanceStats(rounds=jnp.int32(5), rebuilds=jnp.int32(2),
                          expands=jnp.int32(1), merges=jnp.int32(0),
                          pending=jnp.int32(1))
    mred = MaintenanceStats.reduce(
        jax.tree.map(lambda *xs: jnp.stack(xs), ma, mb))
    assert int(mred.rounds) == 5          # max: shards run concurrently
    assert int(mred.rebuilds) == 3 and int(mred.pending) == 5  # sums


def test_serve_stats_ring_and_percentiles():
    s = ServeStats.zero()
    n = LATENCY_RESERVOIR + 40   # wrap the ring
    for i in range(n):
        s = s.record((i + 1) * 1e-6, pending=i % 7, flushed=(i % 10 == 0))
    assert int(s.steps) == n
    lat = s.valid_latencies()
    assert lat.size == LATENCY_RESERVOIR
    p = s.percentiles()
    assert 0 < p["p50_us"] <= p["p99_us"]
    d = s.asdict()
    assert d["flushes"] == (n + 9) // 10 and d["pending_hwm"] == 6


def test_maintenance_stats_rehomed():
    import repro.maintenance
    import repro.maintenance.stats
    import repro.obs.stats

    assert repro.maintenance.MaintenanceStats is MaintenanceStats
    assert repro.maintenance.stats.MaintenanceStats is \
        repro.obs.stats.MaintenanceStats


# ------------------------------------------------------ engine dispatch ---


@pytest.mark.parametrize("engine", ["scalar", "lockstep"])
def test_tree_read_stats(engine):
    import dataclasses

    cfg = dataclasses.replace(CFG, engine=engine)
    t = DT.bulk_build(cfg, KEYS)
    q = _queries()
    found, hops, stats = DT.search_jit(cfg, t, q)
    assert isinstance(stats, ReadStats) and stats.router is None
    s = stats.search
    assert int(s.queries) == q.shape[0]
    assert int(s.pad_lanes) == 2               # the two sentinel lanes
    assert int(s.hops_sum) == int(jnp.sum(hops))
    assert int(s.rounds) == int(jnp.max(hops)) == int(s.hops_max)
    ref_hist = np.bincount(np.clip(np.asarray(hops), 0, HOP_BINS - 1),
                           minlength=HOP_BINS)
    assert np.array_equal(np.asarray(s.hops_hist), ref_hist)
    # occupancy[r] = lanes active entering round r
    occ = np.asarray(s.occupancy)
    hnp = np.asarray(hops)
    assert all(occ[r] == int((hnp > r).sum()) for r in range(occ.size))


def test_hop_histogram_parity_across_engines():
    import dataclasses

    q = _queries()
    outs = {}
    for engine in ("scalar", "lockstep"):
        cfg = dataclasses.replace(CFG, engine=engine)
        t = DT.bulk_build(cfg, KEYS)
        outs[engine] = DT.search_jit(cfg, t, q)
    fs, hs, ss = outs["scalar"]
    fl, hl, sl = outs["lockstep"]
    assert np.array_equal(np.asarray(fs), np.asarray(fl))
    assert np.array_equal(np.asarray(hs), np.asarray(hl))
    for a, b in zip(jax.tree.leaves(ss), jax.tree.leaves(sl)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_buffer_hits_under_deferred_maintenance():
    import dataclasses

    cfg = dataclasses.replace(CFG, maintenance="deferred")
    t = DT.bulk_build(cfg, KEYS)
    # dense run between existing keys: overflows a leaf, so deferred
    # maintenance parks the spill in overflow buffers (I5' state)
    fresh = jnp.asarray([k for k in range(11, 30) if k not in set(KEYS)],
                        jnp.int32)
    t, res, _ = DT.update_batch(
        cfg, t, jnp.full(fresh.shape, DT.OP_INSERT, jnp.int32), fresh)
    assert bool(np.asarray(res).all())
    assert int(jnp.sum(t.bcount)) > 0   # deferred: items sit in buffers
    q = jnp.concatenate([fresh, jnp.asarray(KEYS[:4], jnp.int32)])
    found, hops, stats = DT.search_jit(cfg, t, q)
    assert bool(np.asarray(found).all())
    member = np.asarray(DT.buffered_member(cfg, t, q))
    expected = int((np.asarray(found) & member).sum())
    assert expected > 0                 # the leg is non-trivial
    assert int(stats.search.buffer_hits) == expected


def test_collect_stats_false_hlo_identical():
    """The static gate's whole contract: the disabled dispatch lowers
    byte-identically to the bare engine-hook composition (= the pre-obs
    read path), and the enabled one doesn't."""
    cfg = TreeConfig(height=4, max_dnodes=64, buf_cap=8)
    t = DT.bulk_build(cfg, KEYS[:20])
    q = jnp.asarray(KEYS[:8], jnp.int32)

    def dispatched(t, q):
        return E.search(cfg, t, q)

    def bare(t, q):
        found, _, hops = E.get_engine(cfg.engine).lookup(cfg, t, q)
        return found, hops

    def norm(txt):
        return re.sub(r"jit_\w+", "jit_fn", txt)

    lo_d = norm(jax.jit(dispatched).lower(t, q).as_text())
    lo_b = norm(jax.jit(bare).lower(t, q).as_text())
    assert lo_d == lo_b

    import dataclasses

    cfg_on = dataclasses.replace(cfg, collect_stats=True)
    lo_on = norm(jax.jit(lambda t, q: E.search(cfg_on, t, q))
                 .lower(t, q).as_text())
    assert lo_on != lo_b


def test_index_handle_collect_stats():
    ix = make_index("deltatree", initial=KEYS, height=4, max_dnodes=256,
                    buf_cap=8, collect_stats=True)
    assert ix.collect_stats
    found, hops, stats = ix.search(_queries())
    assert int(stats.search.queries) == int(_queries().shape[0])
    off = make_index("deltatree", initial=KEYS, height=4, max_dnodes=256,
                    buf_cap=8)
    assert not off.collect_stats
    assert len(off.search(_queries())) == 2
    assert not make_index("sorted_array", initial=KEYS).collect_stats


# ---------------------------------------------------------------- forest ---


def _fcfg(engine="scalar", fused=True):
    import dataclasses

    return ForestConfig(
        num_shards=4,
        tree=dataclasses.replace(CFG, engine=engine),
        fused=fused)


def test_forest_read_stats_router_leg():
    fcfg = _fcfg()
    f = D.bulk_build(fcfg, KEYS)
    q = _queries()
    found, hops, stats = D.search_batch(fcfg, f, q)
    r = stats.router
    assert r is not None
    assert int(np.asarray(r.lanes).sum()) == int(q.shape[0])
    assert int(r.batches) == 1
    assert r.skew() >= 1.0
    # ROUTE_LEFT inputs are already at the clamp target -> clamped counts
    # only keys the router *rewrote*; probe one true out-of-domain key
    _, _, st2 = D.search_batch(fcfg, f, jnp.asarray([-5, 7], jnp.int32))
    assert int(st2.router.clamped) == 1


@pytest.mark.parametrize("engine", ["scalar", "lockstep"])
def test_forest_stats_dispatch_parity(engine):
    """fused and vmap dispatches must produce bit-identical ReadStats."""
    q = _queries()
    outs = []
    for fused in (True, False):
        fcfg = _fcfg(engine, fused)
        f = D.bulk_build(fcfg, KEYS)
        outs.append(D.search_batch(fcfg, f, q))
    (fa, ha, sa), (fb, hb, sb) = outs
    assert np.array_equal(np.asarray(fa), np.asarray(fb))
    assert np.array_equal(np.asarray(ha), np.asarray(hb))
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_forest_load_counters_accumulate_and_survive_flush():
    import dataclasses

    fcfg = dataclasses.replace(
        _fcfg(), tree=dataclasses.replace(CFG, maintenance="deferred"))
    f = D.bulk_build(fcfg, KEYS)
    assert D.shard_load(f) == {"reads": [0] * 4, "updates": [0] * 4}
    q = jnp.asarray(KEYS[:12], jnp.int32)
    f = D.record_reads(fcfg, f, q)
    f = D.record_reads(fcfg, f, q)
    load = D.shard_load(f)
    assert sum(load["reads"]) == 24 and sum(load["updates"]) == 0
    kinds = jnp.asarray([DT.OP_INSERT, DT.OP_SEARCH, DT.OP_INSERT,
                         DT.OP_DELETE], jnp.int32)
    keys = jnp.asarray([13, 17, 20, int(KEYS[3])], jnp.int32)
    f, _, _ = D.update_batch(fcfg, f, kinds, keys)
    load = D.shard_load(f)
    assert sum(load["updates"]) == 3      # OP_SEARCH rows don't count
    f, _ = D.flush(fcfg, f)
    assert D.shard_load(f) == load        # flush preserves the counters


def test_forest_stats_8dev_shard_map():
    """Stats survive a real multi-device shard_map dispatch: lanes sum to
    K and the fused/vmap parity holds under 8 fake devices."""
    from tests._subproc import run_py

    out = run_py("""
import dataclasses, numpy as np, jax, jax.numpy as jnp
from repro.core.deltatree import TreeConfig
from repro.distributed import forest as D
from repro.distributed.forest import ForestConfig
keys = np.arange(10, 400, 7, dtype=np.int64)
cfg = TreeConfig(height=4, max_dnodes=256, buf_cap=8, collect_stats=True,
                 engine="lockstep")
q = jnp.asarray(list(keys[:6]) + [5, 11, 401], jnp.int32)
outs = []
for fused in (True, False):
    fcfg = ForestConfig(num_shards=8, tree=cfg, fused=fused)
    f = D.bulk_build(fcfg, keys)
    outs.append(D.search_batch(fcfg, f, q))
(fa, ha, sa), (fb, hb, sb) = outs
assert np.array_equal(np.asarray(ha), np.asarray(hb))
for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
    assert np.array_equal(np.asarray(a), np.asarray(b))
print("lanes", int(np.asarray(sa.router.lanes).sum()), "of", q.shape[0])
""", devices=8)
    assert "lanes 9 of 9" in out


# ----------------------------------------------------------------- trace ---


def test_trace_capture_smoke(tmp_path):
    try:
        out = trace.trace_run(
            lambda x: jnp.sum(x * 2), jnp.arange(8), logdir=str(tmp_path))
    except Exception as e:                      # pragma: no cover
        pytest.skip(f"profiler unavailable here: {e}")
    assert int(out) == 56
    assert any(tmp_path.rglob("*"))             # something was dumped


# --------------------------------------------- program spans and counters ---

# one concrete call each of lookup, successor_k and update under a CPU
# profiler capture; every call's index.<method> event must carry a seq
# whose ring row equals, bit for bit, what the call itself returned
_JOIN = """
import tempfile
import numpy as np, jax, jax.numpy as jnp
from jax.profiler import ProfileData
from repro.api import OpBatch, make_index
from repro.obs import calls, ring, trace

keys = np.arange(10, 4000, 7, dtype=np.int64)
kw = dict(height=4, max_dnodes=512, payload_bits=32)
if backend == "forest":
    kw["num_shards"] = 1
ix = make_index(backend, initial=keys, payloads=keys * 3, **kw)
q = jnp.asarray(np.arange(5, 4100, 9), jnp.int32)    # hits and misses
new = jnp.asarray(np.arange(4001, 4400, 2), jnp.int32)


def free_top(state):
    ft = getattr(state, "free_top", None)
    return int(jnp.sum(state.trees.free_top if ft is None else ft))


want = []
logdir = tempfile.mkdtemp()
with trace.capture(logdir):
    found, pay, hops = ix.lookup(q)
    w = dict(lanes=q.shape[0], hops_sum=int(hops.sum()),
             hops_max=int(hops.max()))
    if backend == "forest":
        # one shard on one device: every key there, one row of lanes
        w.update(lanes_dev_max=q.shape[0], lanes_walked=q.shape[0])
    want.append(("index.lookup", w))
    _, _, n, hops, more = ix.successor_k(q[:64], 8)
    want.append(("index.successor_k", dict(
        lanes=64, hops_sum=int(hops.sum()), hops_max=int(hops.max()),
        emitted=int(n.sum()), truncated=int(more.sum()))))
    ix, res, st = ix.update(OpBatch.inserts(new, new * 5))
    w = {f: int(getattr(st, f)) for f in st._fields}
    w.update(lanes=new.shape[0], free_top=free_top(ix.state))
    want.append(("index.update", w))
assert want[2][1]["expands"] + want[2][1]["rebuilds"] > 0, want

(path,) = __import__("glob").glob(logdir + "/plugins/profile/*/*.xplane.pb")
evs = sorted((ev.start_ns, ev.name, dict(ev.stats)["seq"])
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("index."))
assert [e[1] for e in evs] == [w[0] for w in want], evs
rows = calls([e[2] for e in evs])
for (_, name, seq), row, (_, exp) in zip(evs, rows, want):
    full = dict.fromkeys(ring.COLUMNS, 0)
    full.update(exp, seq=seq, kind=ring.KINDS.index(name[len("index."):]))
    assert {c: int(row[c]) for c in ring.COLUMNS} == full, (name, row, full)
print("JOINED", len(evs))
"""


@pytest.mark.parametrize("backend", ["deltatree", "forest"])
def test_index_calls_join_their_ring_rows_bit_exact(backend):
    """Map mode (x64 subprocess), a one-shard forest as well: each
    Index call yields one index.<method> event whose seq joins to a ring
    row equal to the call's own hops / n / more / MaintenanceStats /
    free_top."""
    from tests._subproc import run_py

    out = run_py(f"backend = {backend!r}\n" + _JOIN, x64=True)
    assert "JOINED 3" in out


def test_ring_wraps_and_keeps_the_last_rows():
    from repro.obs import calls, ring

    extra = 37
    seqs = []
    for i in range(ring.ROWS + extra):
        seq = ring.next_seq()
        ring.record("search", seq, 3,
                    hops=jnp.asarray([i % 5, 1, 2], jnp.int32))
        seqs.append(seq)
    rows = calls(seqs)
    assert (rows["seq"][:extra] == -1).all()        # overwritten
    held = rows[extra:]
    assert held["seq"].tolist() == seqs[extra:]
    i = np.arange(extra, ring.ROWS + extra)
    assert held["hops_sum"].tolist() == (i % 5 + 3).tolist()
    assert held["hops_max"].tolist() == np.maximum(i % 5, 2).tolist()
    assert (held["lanes"] == 3).all()
    assert ring.ROWS >= 4096
    assert ring.ROWS * len(ring.COLUMNS) * 4 <= 256 * 1024


def test_ring_totals_are_the_sums_of_the_rows():
    from repro.api import OpBatch
    from repro.obs import calls, ring

    ix = make_index("deltatree", initial=KEYS, height=4, max_dnodes=256,
                    buf_cap=8, maintenance="deferred")
    before = ring.totals()
    seqs = []
    q = _queries()
    for i in range(3):
        ix.search(q)
        seqs.append(ring.last_seq())
        ix.successor_k(q, 4)
        seqs.append(ring.last_seq())
        ix, _, _ = ix.update(OpBatch.inserts(
            jnp.asarray(np.arange(401 + 40 * i, 441 + 40 * i, 3), jnp.int32)))
        seqs.append(ring.last_seq())
    ix, _ = ix.flush()
    seqs.append(ring.last_seq())
    after = ring.totals()
    rows = calls(seqs)
    assert (rows["seq"] == seqs).all()
    for k, kind in enumerate(ring.KINDS):
        mine = rows[rows["kind"] == k]
        got = {c: after.get(f"{kind}_{c}", 0) - before.get(f"{kind}_{c}", 0)
               for c in ("calls",) + ring.SUMMED}
        want = {c: int(mine[c].sum()) for c in ring.SUMMED}
        want["calls"] = len(mine)
        assert got == want, kind
    assert after["update_calls"] - before.get("update_calls", 0) == 3
    assert after["flush_rounds"] > before.get("flush_rounds", 0)


def test_ring_reads_race_records_from_another_thread():
    """Readers and a recording thread interleave: every record donates
    the ring and the totals, and no read may touch a donated buffer."""
    import threading

    from repro.obs import calls, ring

    n = 300
    seqs, done = [], threading.Event()

    def writer():
        for _ in range(n):
            seq = ring.next_seq()
            ring.record("search", seq, 2,
                        hops=jnp.asarray([1, 2], jnp.int32))
            seqs.append(seq)
        done.set()

    t = threading.Thread(target=writer)
    t.start()
    reads = 0
    while not done.is_set() or reads == 0:
        if seqs:
            s = seqs[-1]       # recorded before it was appended
            assert calls([s])[0]["seq"] == s
        assert ring.totals().get("search_calls", 0) >= 0
        reads += 1
    t.join()
    rows = calls(seqs)
    assert rows["seq"].tolist() == seqs
    assert (rows["hops_sum"] == 3).all() and (rows["hops_max"] == 2).all()


def test_programs_lower_alike_with_the_profiler_on_and_off(tmp_path):
    """lookup_jit, successor_k_jit and update_batch (map mode, x64
    subprocess) lower to identical text with a profiler session active
    and without one; Index calls under jit add nothing to the program."""
    from tests._subproc import run_py

    out = run_py(f"""
import numpy as np, jax, jax.numpy as jnp
from repro.api import make_index
from repro.core import deltatree as DT
from repro.obs import trace

keys = np.arange(10, 4000, 7, dtype=np.int64)
ix = make_index("deltatree", initial=keys, payloads=keys * 3, height=4,
                max_dnodes=512, payload_bits=32)
cfg, t = ix.cfg, ix.state
q = jnp.asarray(np.arange(5, 4100, 9), jnp.int32)
kinds = jnp.ones(64, jnp.int32)

def lowered():
    return [DT.lookup_jit.lower(cfg, t, q).as_text(),
            DT.successor_k_jit.lower(cfg, t, q, 8).as_text(),
            DT.update_batch.lower(cfg, t, kinds, q[:64], q[:64]).as_text()]

off = lowered()
with trace.capture({str(tmp_path)!r}):
    on = lowered()
assert on == off
via_index = jax.jit(lambda ix, q: ix.lookup(q)).lower(ix, q).as_text()
bare = jax.jit(lambda t, q: DT.lookup_jit(cfg, t, q)).lower(t, q).as_text()
assert via_index == bare
print("SAME HLO", sum(map(len, off)))
""", x64=True)
    assert "SAME HLO" in out


def test_span_under_jit_tracing_records_nothing():
    from repro.obs import ring

    ix = make_index("deltatree", initial=KEYS, height=4, max_dnodes=256)
    q = _queries()
    name = "obs.test.traced"
    before = ring.next_seq()

    @jax.jit
    def f(x):
        with trace.span(name, lanes=1):
            found, hops = ix.search(x)
        return hops

    f(q).block_until_ready()
    f(q + 1).block_until_ready()
    assert ring.next_seq() == before + 1          # no Index call numbered
    assert name not in trace.totals()
    with trace.span(name, lanes=1):
        pass
    assert trace.totals()[name]["count"] == 1


def test_span_totals_count_time_and_max_across_threads():
    import threading
    import time

    name = "obs.test.threads"
    n, t = 300, 6

    def work():
        for _ in range(n):
            with trace.span(name):
                pass

    threads = [threading.Thread(target=work) for _ in range(t)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    with trace.span(name):
        time.sleep(0.02)
    tot = trace.totals()[name]
    assert tot["count"] == n * t + 1
    assert tot["max_s"] >= 0.02
    assert tot["max_s"] <= tot["total_s"]


def test_make_index_times_the_build_and_its_parts():
    before = trace.totals()
    make_index("deltatree", initial=KEYS, height=4, max_dnodes=256)
    after = trace.totals()
    parts = ("index.build.sort", "index.build.layout", "index.build.upload")
    for name in ("index.build",) + parts:
        assert (after[name]["count"]
                - before.get(name, {"count": 0})["count"]) == 1, name
    spent = {k: after[k]["total_s"] - before.get(k, {"total_s": 0.0})[
        "total_s"] for k in ("index.build",) + parts}
    assert 0 < sum(spent[p] for p in parts) <= spent["index.build"]


def test_export_snapshot_program_totals():
    from repro.obs import export

    ix = make_index("deltatree", initial=KEYS, height=4, max_dnodes=256)
    ix.search(_queries())
    snap = export.snapshot(**export.program_totals(),
                           pager={"searches": 1})
    assert snap["index"]["search_calls"] >= 1
    assert snap["index"]["search_lanes"] >= _queries().shape[0]
    assert snap["spans"]["index_search_count"] >= 1
    assert snap["pager"] == {"searches": 1}
    prom = export.to_prometheus(snap)
    assert "# TYPE repro_index_search_calls gauge" in prom
    assert "repro_spans_index_build_total_s" in prom
    assert "index" not in export.snapshot(pager={"searches": 1})


# ---------------------------------------------------------------- export ---


def test_export_snapshot_prometheus_json():
    from repro.obs import export

    s = SearchStats.of(jnp.asarray([0, 1, 2, 2], jnp.int32),
                       jnp.zeros(4, bool), jnp.zeros(4, bool))
    snap = export.snapshot(search=s, pager={"searches": 7, "hops": 3.5},
                           router=None)
    assert "router" not in snap                  # None groups dropped
    assert snap["search"]["queries"] == 4
    assert snap["pager"]["searches"] == 7
    # everything is plain python (json-serializable), lists included
    doc = json.loads(export.to_json(snap))
    assert doc["search"]["hops_hist"][0] == 1    # the zero-hop lane

    prom = export.to_prometheus(snap)
    assert "# TYPE repro_search_queries gauge" in prom
    assert "repro_search_queries 4" in prom
    assert 'repro_search_hops_hist{index="0"} 1' in prom
    assert "repro_pager_hops 3.5" in prom
    assert export.to_prometheus({}) == ""


def test_export_transfer_stats_group():
    """TransferStats round-trips through snapshot/prometheus with its
    per-block-size series."""
    from repro.core import deltatree as DT
    from repro.obs import export, transfers as OTR

    cfg = TreeConfig(height=4, max_dnodes=256, buf_cap=8,
                     collect_stats=True, collect_transfers=True)
    t = DT.bulk_build(cfg, KEYS)
    ts = OTR.measure(cfg, t, _queries())
    snap = export.snapshot(transfers=ts)
    d = snap["transfers"]
    assert d["queries"] == 11 and d["pad_lanes"] == 2
    prom = export.to_prometheus(snap)
    for b in OTR.TRANSFER_BLOCK_SIZES:
        assert f"repro_transfers_blocks_b{b} " in prom
    json.loads(export.to_json(snap))             # serializable end to end


def test_serve_stats_probe_accounting():
    s = ServeStats.zero()
    s = s.record_probe(12, 9)
    s = s.record(1e-3, pending=2, flushed=False)  # steps don't disturb it
    s = s.record_probe(4, 0)
    assert int(s.probe_queries) == 16 and int(s.probe_hits) == 9
    assert int(s.steps) == 1
    d = s.asdict()
    assert d["probe_queries"] == 16 and d["probe_hits"] == 9
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), s, s)
    red = ServeStats.reduce(stacked)
    assert int(red.probe_queries) == 32 and int(red.probe_hits) == 18
    assert int(red.steps) == 2
