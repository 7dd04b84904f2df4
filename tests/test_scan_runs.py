"""Leaf-run scans (DESIGN.md §15) on the compiled XLA mirror.

Off the TPU the lockstep engine runs the Pallas scan kernel in interpret
mode; ``REPRO_PALLAS_INTERPRET=0`` sends it to the XLA mirror
``ref.ref_delta_scan_fused``, which every map-mode or HBM-sized arena
runs on the chip.  Each case holds the mirror to the scalar engine bit
for bit (keys, payloads, ``n``, ``hops``, ``more``) and to the oracle, on
trees built to hold what a run meets: tombstones and all-dead runs,
markers beside key-leaves, runs that cross ΔNode boundaries, a buffer
that fills exactly at a run's end, ``hi`` inside a run, deferred
maintenance's buffered items, and the forest's fused multi-root scan.
Set mode runs in process, map mode under x64 in a subprocess.  The last
test pins the mechanism: a lane reads O(depth) rows per leaf ΔNode it
crosses, not two root walks per key.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import OpBatch, make_index
from repro.core import engine as E
from repro.core import layout
from repro.core.layout import EMPTY
from repro.kernels import ops as OPS
from tests._subproc import run_py

KEY_HI = 3000
# arenas sized apart from other test files' so that no jitted scan traced
# in interpret mode by an earlier test is found in the cache
KW = dict(height=4, max_dnodes=333, buf_cap=8)


def _runs(cfg, t):
    """The arena's key-leaves in global in-order: (packed value, dead,
    run id) each, a run being key-leaves of one ΔNode with no marker
    between them — what one landing of the scan emits from."""
    tab = layout.inorder_tables(cfg.height)
    value, mark = np.asarray(t.value), np.asarray(t.mark)
    child = np.asarray(t.child)
    big = int(np.asarray(cfg.route_left))
    out, run = [], [0]

    def visit(dn):
        run[0] += 1
        row = value[dn][tab["storage"]]
        dead = mark[dn][tab["storage"]]
        for r, v in enumerate(row):
            if v == EMPTY or v == big:
                continue
            if tab["bottom"][r] and child[dn, r // 2] >= 0:
                visit(child[dn, r // 2])
                run[0] += 1
            elif tab["bottom"][r] or row[tab["left"][r]] == EMPTY:
                out.append((int(v), bool(dead[r]), run[0]))

    visit(int(t.root))
    return out


def _has_marker_beside_leaf(cfg, t):
    tab = layout.inorder_tables(cfg.height)
    value, child = np.asarray(t.value), np.asarray(t.child)
    alive = np.asarray(t.alive)
    for dn in np.flatnonzero(alive):
        row = value[dn][tab["storage"]]
        occ = row != EMPTY
        internal = ~tab["bottom"] & occ[tab["left"]]
        marker = tab["bottom"] & occ & (child[dn][np.arange(row.size) // 2]
                                        >= 0)
        if marker.any() and (occ & ~internal & ~marker).any():
            return True
    return False


def _index(initial, **kw):
    return make_index("deltatree", initial=initial, engine="scalar",
                      **{**KW, **kw})


def _case(name):
    """(index, starts, his, max_out) for one case; ``starts`` exclusive,
    ``his`` inclusive."""
    rng = np.random.default_rng(["tombstones", "dead_runs", "markers",
                                 "exact_fill", "hi_inside",
                                 "cross"].index(name) + 60)
    initial = np.unique(rng.integers(1, KEY_HI, 900).astype(np.int32))
    ix = _index(initial)
    if name in ("tombstones", "dead_runs"):
        if name == "tombstones":
            dels = rng.choice(initial, size=initial.size // 2, replace=False)
        else:        # a whole band dead: runs and ΔNodes with no live key
            dels = initial[(initial > 800) & (initial < 1900)]
        ix, _ = ix.insert_delete(OpBatch.mixed(
            np.full(dels.size, 2, np.int32), dels.astype(np.int32)))
    if name == "markers":
        # a dense cluster grows leaves into child ΔNodes beside key-leaves
        ins = np.arange(1201, 1401, dtype=np.int32)
        ix, _ = ix.insert_delete(OpBatch.mixed(
            np.full(ins.size, 1, np.int32), ins))
        assert _has_marker_beside_leaf(ix.spec.cfg, ix.state)
    runs = _runs(ix.spec.cfg, ix.state)
    keys = np.asarray([v for v, _, _ in runs])          # set mode: v = key
    ids = np.asarray([r for _, _, r in runs])
    live = np.asarray([not d for _, d, _ in runs])
    if name == "exact_fill":
        # start just before a run, max_out its live count: the buffer
        # fills exactly at the run's end and must look past it for `more`
        firsts = np.flatnonzero(np.r_[True, ids[1:] != ids[:-1]])
        firsts = firsts[firsts > 0]
        starts = (keys[firsts] - 1).astype(np.int32)
        counts = np.asarray([np.count_nonzero(live[(ids == ids[f])])
                             for f in firsts])
        max_out = int(np.bincount(counts).argmax())
        pick = counts == max_out
        assert pick.sum() >= 4, counts
        starts = starts[pick][:24]
        his = np.full(starts.shape, KEY_HI + 5, np.int32)
    elif name == "hi_inside":
        # hi between two keys of one run
        same = np.flatnonzero(ids[1:] == ids[:-1])
        sel = rng.choice(same, size=24, replace=False)
        his = keys[sel].astype(np.int32)
        starts = np.maximum(his - rng.integers(1, 400, 24), 0).astype(
            np.int32)
        max_out = 40
    else:
        starts = rng.integers(0, KEY_HI, 32).astype(np.int32)
        width = 1200 if name == "cross" else 400
        his = (starts + rng.integers(1, width, 32)).astype(np.int32)
        max_out = {"cross": 96, "tombstones": 12}.get(name, 20)
    return ix, starts, his, max_out


def _oracle(ix, starts, his, max_out):
    live = np.asarray([k for k, _ in ix.live_items()])
    for i, (s, h) in enumerate(zip(starts, his)):
        band = live[(live > s) & (live <= h)]
        yield i, band[:max_out], band.size > max_out


def _check_parity_and_oracle(ix, scans, starts, his, max_out):
    a, b = scans
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    cfg = ix.spec.cfg
    out, n, _, more = (np.asarray(x) for x in a)
    for i, exp, exp_more in _oracle(ix, starts, his, max_out):
        assert int(n[i]) == exp.size, (i, int(n[i]), exp)
        np.testing.assert_array_equal(
            np.asarray(cfg.key_of(jnp.asarray(out[i, :exp.size]))), exp)
        assert bool(more[i]) == exp_more, i


@pytest.mark.parametrize("case", ["tombstones", "dead_runs", "markers",
                                  "exact_fill", "hi_inside", "cross"])
def test_mirror_scan_matches_scalar_and_oracle(case, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    ix, starts, his, max_out = _case(case)
    cfg, t = ix.spec.cfg, ix.state
    assert OPS.scan_impl(t.value, t.child, height=cfg.height,
                         max_out=max_out) == "ref_delta_scan_fused"
    scans = [E.get_engine(e).scan_batch(
        dataclasses.replace(cfg, engine=e), t, jnp.asarray(starts),
        jnp.asarray(his), max_out) for e in ("lockstep", "scalar")]
    _check_parity_and_oracle(ix, scans, starts, his, max_out)


def test_mirror_scan_deferred_merges_buffered_items(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    rng = np.random.default_rng(70)
    initial = np.unique(rng.integers(1, KEY_HI, 500).astype(np.int32))
    ix = _index(initial, maintenance="deferred")
    ins = rng.integers(1, KEY_HI, 200).astype(np.int32)
    ix, _, stats = ix.update(OpBatch.mixed(np.full(200, 1, np.int32), ins))
    assert int(stats.pending) > 0
    cfg, t = ix.spec.cfg, ix.state
    starts = rng.integers(0, KEY_HI, 32).astype(np.int32)
    his = (starts + rng.integers(1, 600, 32)).astype(np.int32)
    scans = [E.scan(dataclasses.replace(cfg, engine=e), t,
                    jnp.asarray(starts), jnp.asarray(his), max_out=24)
             for e in ("lockstep", "scalar")]
    _check_parity_and_oracle(ix, scans, starts, his, 24)


def test_mirror_forest_fused_scan(monkeypatch):
    """The fused multi-root scan on the mirror: every (lane, shard) pair
    restarts at its own shard's root; rows, hops and flags equal the
    scalar forest's and the oracle's."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    rng = np.random.default_rng(71)
    initial = np.unique(rng.integers(1, KEY_HI, 700).astype(np.int32))
    kw = dict(num_shards=3, height=4, max_dnodes=331, buf_cap=8,
              key_max=KEY_HI)
    ix_l = make_index("forest", initial=initial, engine="lockstep", **kw)
    ix_s = make_index("forest", initial=initial, engine="scalar", **kw)
    assert ix_l.capability.fused_forest
    dels = rng.choice(initial, size=200, replace=False).astype(np.int32)
    batch = OpBatch.mixed(np.full(dels.size, 2, np.int32), dels)
    ix_l, _ = ix_l.insert_delete(batch)
    ix_s, _ = ix_s.insert_delete(batch)
    starts = rng.integers(0, KEY_HI, 16).astype(np.int32)
    his = (starts + rng.integers(1, 1500, 16)).astype(np.int32)
    outs = [ix.spec.backend.scan(ix.spec.cfg, ix.state, jnp.asarray(starts),
                                 jnp.asarray(his), 30)
            for ix in (ix_l, ix_s)]
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    keys, _, n, _, more = (np.asarray(x) for x in outs[0])
    for i, exp, exp_more in _oracle(ix_l, starts, his, 30):
        assert int(n[i]) == exp.size
        np.testing.assert_array_equal(keys[i, :exp.size], exp)
        assert bool(more[i]) == exp_more


@pytest.mark.parametrize("case", ["tombstones", "markers"])
def test_mirror_scan_map_mode_x64(case):
    """Map mode (int64 packed rows, the chip's path) under x64: the
    mirror's successor_k and range rows, payloads included, equal the
    scalar engine's and the oracle's."""
    out = run_py(f"""
import dataclasses, os
import numpy as np, jax.numpy as jnp
os.environ["REPRO_PALLAS_INTERPRET"] = "0"
from repro.api import OpBatch, make_index
from repro.core import engine as E
from repro.kernels import ops as OPS
rng = np.random.default_rng(72)
keys = np.unique(rng.integers(1, 1 << 20, 1500)).astype(np.int32)
ids = rng.integers(0, 1 << 30, keys.size).astype(np.int32)
ix = make_index("deltatree", initial=keys, payloads=ids, height=4,
                max_dnodes=700, buf_cap=8, payload_bits=32, engine="scalar")
live = dict(zip(keys.tolist(), ids.tolist()))
if {case!r} == "tombstones":
    dels = rng.choice(keys, size=700, replace=False).astype(np.int32)
    ix, _ = ix.insert_delete(OpBatch.mixed(np.full(700, 2, np.int32), dels))
    for k in dels.tolist():
        live.pop(k)
else:
    ins = np.arange(5001, 5301, dtype=np.int32)
    iid = (ins * 7).astype(np.int32)
    ix, _ = ix.insert_delete(OpBatch.mixed(np.full(300, 1, np.int32), ins,
                                           iid))
    for k, v in zip(ins.tolist(), iid.tolist()):
        live.setdefault(k, v)
cfg, t = ix.spec.cfg, ix.state
assert OPS.scan_impl(t.value, t.child, height=4,
                     max_out=50) == "ref_delta_scan_fused"
sk = np.asarray(sorted(live))
sv = np.asarray([live[k] for k in sk])
starts = np.r_[rng.integers(0, 1 << 20, 30), [4990, 5100]].astype(np.int32)
his = (starts + rng.integers(1, 1 << 16, 32)).astype(np.int32)
scans = [E.get_engine(e).scan_batch(dataclasses.replace(cfg, engine=e), t,
                                    jnp.asarray(starts), jnp.asarray(his), 50)
         for e in ("lockstep", "scalar")]
for a, b in zip(*scans):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
out, n, hops, more = (np.asarray(x) for x in scans[0])
for i in range(starts.size):
    sel = (sk > starts[i]) & (sk <= his[i])
    exp_k, exp_v = sk[sel][:50], sv[sel][:50]
    assert int(n[i]) == exp_k.size, i
    np.testing.assert_array_equal(out[i, :n[i]] >> 32, exp_k)
    np.testing.assert_array_equal(out[i, :n[i]] & 0xffffffff, exp_v)
    assert bool(more[i]) == (sel.sum() > 50), i
print("MAP MIRROR OK", int(n.sum()), int(hops.max()))
""", x64=True)
    assert "MAP MIRROR OK" in out


def _depth_and_fewest(ix):
    t = ix.state
    alive = np.asarray(t.alive)
    parent, nchild = np.asarray(t.parent), np.asarray(t.nchild)
    nlive = np.asarray(t.nlive)
    leaves = np.flatnonzero(alive & (nchild == 0))
    depth = 0
    for dn in leaves:
        d, x = 1, dn
        while parent[x] >= 0:
            d, x = d + 1, parent[x]
        depth = max(depth, d)
    return depth, int(nlive[leaves].min()), leaves.size


@pytest.mark.parametrize("engine", ["scalar", "lockstep"])
def test_scan_reads_rows_per_leaf_dnode_not_walks_per_key(engine,
                                                          monkeypatch):
    """On a bulk-built tree with at least 16 leaf ΔNodes, successor_k
    (k = 100) keeps every lane within depth × (⌈k / fewest keys in a leaf
    ΔNode⌉ + 2) rows, where a walk from the root per key costs depth × k
    or more."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    rng = np.random.default_rng(73)
    initial = np.unique(rng.integers(1, 1 << 24, 6000).astype(np.int32))
    ix = make_index("deltatree", initial=initial, engine=engine, height=5,
                    max_dnodes=1100, buf_cap=8)
    depth, fewest, n_leaves = _depth_and_fewest(ix)
    assert n_leaves >= 16 and fewest > 0 and depth >= 2
    q = rng.choice(initial[:-200], size=64).astype(np.int32)
    keys, _, n, hops, _ = ix.successor_k(jnp.asarray(q), 100)
    assert (np.asarray(n) == 100).all()
    bound = depth * (-(-100 // fewest) + 2)
    assert int(np.asarray(hops).max()) <= bound, (np.asarray(hops).max(),
                                                 bound)
    assert bound < depth * 100 // 4
