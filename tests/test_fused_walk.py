"""Fused single-launch walk driver: bit-parity vs the per-round driver
and the scalar engine, sentinel/multi-root contracts, the derived round
cap, and compiled-mode (REPRO_PALLAS_INTERPRET=0) subprocess legs
including ``engine="auto"`` resolution."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import TreeConfig, bulk_build, search_jit, update_batch
from repro.kernels.ops import (
    delta_walk, delta_walk_fused, walk_round_cap,
)
from repro.kernels.veb_search import walk_big

from _subproc import run_py


def _churned_tree(h, m, nvals, seed, n_updates=128):
    rng = np.random.default_rng(seed)
    cfg = TreeConfig(height=h, max_dnodes=m, buf_cap=16)
    vals = np.unique(rng.integers(1, 100_000, size=nvals).astype(np.int32))
    t = bulk_build(cfg, vals)
    kinds = rng.choice([1, 2], size=n_updates).astype(np.int32)
    keys = rng.integers(1, 100_000, size=n_updates).astype(np.int32)
    t, _, _ = update_batch(cfg, t, jnp.asarray(kinds), jnp.asarray(keys))
    q = rng.integers(1, 100_000, size=500).astype(np.int32)
    return cfg, t, jnp.asarray(q)


@pytest.mark.parametrize("h,m,nvals", [
    (3, 8192, 1200), (4, 4096, 2000), (7, 2048, 3000),
])
def test_fused_walk_bit_parity(h, m, nvals):
    """The fused driver is bit-identical to the per-round driver on every
    output — hops included — and hops match the scalar engine's transfer
    statistic, on a churned tree (marks, buffers, expansions, merges)."""
    cfg, t, q = _churned_tree(h, m, nvals, seed=h)
    fused = delta_walk_fused(t.value, t.child, t.root, q, height=h,
                             q_tile=128)
    per_round = delta_walk(t.value, t.child, t.root, q, height=h,
                           q_tile=128, fused=False)
    names = ("leaf_val", "leaf_b", "final_dn", "hops", "cand")
    for name, a, b in zip(names, fused, per_round):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    _, chops = search_jit(cfg, t, q)
    np.testing.assert_array_equal(np.asarray(fused[3]), np.asarray(chops))


def test_fused_kernel_vs_ref_mirror_direct():
    """`veb_walk_fused` (Pallas, interpret) vs `ref_delta_walk_fused`
    (the XLA-compiled mirror it falls back to): same 5-tuple, same bits,
    on a padded arena with per-query roots."""
    from repro.kernels.ref import ref_delta_walk_fused
    from repro.kernels.veb_search import pad_arena, veb_walk_fused

    h = 5
    cfg, t, q = _churned_tree(h, 2048, 3000, seed=11)
    k = 384  # q_tile multiple: the raw kernel takes pre-padded batches
    q = q[:k]
    value_p, child_p = pad_arena(t.value, t.child)
    roots = jnp.broadcast_to(jnp.asarray(t.root, jnp.int32), (k,))
    cap = walk_round_cap(h, int(t.value.shape[0]))
    kern = veb_walk_fused(value_p, child_p, roots, q, height=h,
                          q_tile=128, max_rounds=cap, interpret=True)
    ref = ref_delta_walk_fused(t.value, t.child, roots, q, height=h,
                               max_rounds=cap)
    for i, (a, b) in enumerate(zip(kern, ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"out{i}")


def test_fused_sentinel_lanes_born_resolved():
    """Real lanes carrying the reserved ROUTE_LEFT key (`walk_big`) are
    born resolved under the fused driver — 0 hops, miss leaf, no successor
    candidate — exactly like the per-round driver (the forest router's
    dense-lane padding depends on this)."""
    cfg, t, q = _churned_tree(4, 512, 800, seed=3, n_updates=32)
    big = walk_big(jnp.int32)
    qs = jnp.concatenate([q[:45], jnp.full((3,), big, jnp.int32)])
    for fused in (True, False):
        lv, lb, dn, hops, cand = delta_walk(
            t.value, t.child, t.root, qs, height=4, q_tile=16, fused=fused)
        assert (np.asarray(hops)[-3:] == 0).all()
        assert (np.asarray(lv)[-3:] == 0).all()
        assert (np.asarray(cand)[-3:] == big).all()


def test_fused_multi_root_seeding():
    """(K,) per-query roots over a `fuse_arenas` view: the fused driver
    matches per-arena fused walks bit for bit (the fused-forest frontier's
    seeding contract)."""
    from repro.core import deltatree as DT
    from repro.kernels.veb_search import fuse_arenas

    rng = np.random.default_rng(9)
    tcfg = TreeConfig(height=4, max_dnodes=128, buf_cap=8)
    vals_a = np.unique(rng.integers(1, 500, 120).astype(np.int32))
    vals_b = np.unique(rng.integers(500, 999, 120).astype(np.int32))
    ta, tb = DT.bulk_build(tcfg, vals_a), DT.bulk_build(tcfg, vals_b)
    qa = rng.integers(1, 500, 40).astype(np.int32)
    qb = rng.integers(500, 999, 40).astype(np.int32)
    fv, fc, froots = fuse_arenas(jnp.stack([ta.value, tb.value]),
                                 jnp.stack([ta.child, tb.child]),
                                 jnp.stack([ta.root, tb.root]))
    lid = jnp.asarray([0] * 40 + [1] * 40, jnp.int32)
    q = jnp.asarray(np.concatenate([qa, qb]))
    fused = delta_walk_fused(fv, fc, froots[lid], q, height=4, q_tile=16)
    ra = delta_walk_fused(ta.value, ta.child, ta.root, jnp.asarray(qa),
                          height=4, q_tile=16)
    rb = delta_walk_fused(tb.value, tb.child, tb.root, jnp.asarray(qb),
                          height=4, q_tile=16)
    m = int(ta.value.shape[0])
    for i, (a, b) in enumerate(zip(ra, rb)):
        one = np.concatenate([np.asarray(a), np.asarray(b)])
        if i == 2:  # final_dn: arena-local ids shift by the shard base
            one = np.concatenate([np.asarray(a), np.asarray(b) + m])
        np.testing.assert_array_equal(np.asarray(fused[i]), one)


def test_round_cap_derived_and_never_hit():
    """`max_rounds=None` derives the cap from arena geometry; the cap
    strictly clears the deepest observed walk (a truncated walk would
    return wrong leaves silently), and matches an effectively-unbounded
    walk bit for bit."""
    for h, m in ((3, 8192), (4, 4096), (7, 2048)):
        cfg, t, q = _churned_tree(h, m, 2000, seed=h + 20)
        cap = walk_round_cap(h, m)
        derived = delta_walk(t.value, t.child, t.root, q, height=h,
                             q_tile=128)  # max_rounds=None -> cap
        unbounded = delta_walk(t.value, t.child, t.root, q, height=h,
                               q_tile=128, max_rounds=256)
        for a, b in zip(derived, unbounded):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(np.asarray(derived[3]).max()) < cap


def test_tree_config_walk_round_cap_property():
    cfg = TreeConfig(height=7, max_dnodes=2048)
    assert cfg.walk_round_cap == walk_round_cap(7, 2048)
    assert TreeConfig(height=7, max_dnodes=2048,
                      walk_rounds=33).walk_round_cap == 33


def test_resolve_engine_auto_table():
    from repro.core.engine import resolve_engine

    # compiled mode: the committed bench table says lockstep wins reads
    assert resolve_engine("auto", "deltatree", compiled=True) == "lockstep"
    assert resolve_engine("auto", "forest", compiled=True) == "lockstep"
    # interpret mode / unknown backends: scalar (never pay the Pallas
    # interpreter tax by default)
    assert resolve_engine("auto", "deltatree", compiled=False) == "scalar"
    assert resolve_engine("auto", "sorted_array", compiled=True) == "scalar"
    # non-auto names pass through untouched
    assert resolve_engine("lockstep", "deltatree", compiled=False) == "lockstep"


def test_make_index_auto_engine_interpret():
    """In this (interpret-mode) process, engine="auto" resolves to scalar
    — and the row-level engine stamp records the resolved name, never the
    sentinel."""
    from repro.api import make_index

    ix = make_index("deltatree", initial=np.asarray([5, 9, 42], np.int32),
                    engine="auto", height=3, max_dnodes=64)
    assert ix.engine == "scalar"
    found = ix.search(jnp.asarray([5, 7], jnp.int32))[0]
    np.testing.assert_array_equal(np.asarray(found), [True, False])


def test_fused_walk_map_mode_int64_subprocess():
    """Map-mode (int64 packed rows) fused walk parity — x64 subprocess:
    fused vs per-round vs the legacy search contract on packed queries."""
    out = run_py("""
import numpy as np, jax.numpy as jnp
from repro.core import TreeConfig, bulk_build
from repro.kernels.ops import delta_walk
from repro.kernels.ref import ref_delta_search

cfg = TreeConfig(height=4, max_dnodes=1024, buf_cap=8, payload_bits=16)
rng = np.random.default_rng(2)
vals = np.unique(rng.integers(1, 60_000, 1500).astype(np.int32))
pay = rng.integers(0, 2**16, vals.size).astype(np.int32)
t = bulk_build(cfg, jnp.asarray(vals), jnp.asarray(pay))
assert t.value.dtype == jnp.int64
q = cfg.qpack(jnp.asarray(rng.integers(1, 60_000, 300).astype(np.int32)))
fused = delta_walk(t.value, t.child, t.root, q, height=4, q_tile=64)
per_round = delta_walk(t.value, t.child, t.root, q, height=4, q_tile=64,
                       fused=False)
for i, (a, b) in enumerate(zip(fused, per_round)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(i))
rlv, rlb, rdn = ref_delta_search(t.value, t.child, t.root, q, height=4)
np.testing.assert_array_equal(np.asarray(fused[0]), np.asarray(rlv))
np.testing.assert_array_equal(np.asarray(fused[2]), np.asarray(rdn))
print("MAP64_OK")
""", x64=True)
    assert "MAP64_OK" in out


def test_compiled_mode_subprocess_parity_and_auto_engine():
    """REPRO_PALLAS_INTERPRET=0 leg: the compiled fused walk (the XLA
    mirror on CPU) matches the interpret-mode Pallas kernel bit for bit,
    walks run under the derived round cap, and engine="auto" resolves to
    lockstep — the committed compiled-mode table winner."""
    out = run_py("""
import os
os.environ["REPRO_PALLAS_INTERPRET"] = "0"
import numpy as np, jax.numpy as jnp
from repro.core import TreeConfig, bulk_build, search_jit, update_batch
from repro.kernels.ops import default_interpret, delta_walk
assert default_interpret() is False

rng = np.random.default_rng(13)
cfg = TreeConfig(height=5, max_dnodes=2048, buf_cap=16)
vals = np.unique(rng.integers(1, 80_000, 2500).astype(np.int32))
t = bulk_build(cfg, vals)
kinds = rng.choice([1, 2], size=96).astype(np.int32)
keys = rng.integers(1, 80_000, size=96).astype(np.int32)
t, _, _ = update_batch(cfg, t, jnp.asarray(kinds), jnp.asarray(keys))
q = jnp.asarray(rng.integers(1, 80_000, 400).astype(np.int32))
compiled = delta_walk(t.value, t.child, t.root, q, height=5)
interp = delta_walk(t.value, t.child, t.root, q, height=5, interpret=True)
for i, (a, b) in enumerate(zip(compiled, interp)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(i))
_, chops = search_jit(cfg, t, q)
np.testing.assert_array_equal(np.asarray(compiled[3]), np.asarray(chops))

from repro.api import make_index
ix = make_index("deltatree", initial=vals, engine="auto", height=5,
                max_dnodes=2048)
assert ix.engine == "lockstep", ix.engine
found = ix.search(jnp.asarray([int(vals[0]), 0x7ead]))[0]
assert bool(np.asarray(found)[0])
print("COMPILED_OK")
""")
    assert "COMPILED_OK" in out
