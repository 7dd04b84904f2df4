"""Measured memory-transfer accounting (DESIGN.md §14).

The loop this module closes: the analytical ideal-cache model
(`core.baselines.count_block_transfers` over the host replay in
`core.transfers`) and the *measured* device-side `TransferStats` replay
(`obs.transfers`) must agree **exactly** on a quiescent tree — same
distinct-block counts per search for every block size — and the
measured statistic must be bit-identical across engines (scalar /
lockstep) and dispatches (fused / vmap forest), because it is derived
in the dispatch layer from the same gather indices every engine pins.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deltatree as DT
from repro.core import layout
from repro.core.deltatree import TreeConfig
from repro.distributed import forest as D
from repro.distributed.forest import ForestConfig
from repro.obs import transfers as OTR
from repro.obs.stats import ReadStats, TransferStats
from repro.obs.transfers import TRANSFER_BLOCK_SIZES

from _subproc import run_py

KEYS = np.arange(10, 400, 7, dtype=np.int64)
CFG = TreeConfig(height=4, max_dnodes=256, buf_cap=8,
                 collect_stats=True, collect_transfers=True)


def _queries():
    """Hits, misses, and born-resolved ROUTE_LEFT sentinel lanes."""
    return jnp.asarray(
        list(KEYS[:6]) + [5, 11, 401, layout.ROUTE_LEFT, layout.ROUTE_LEFT],
        jnp.int32)


# ------------------------------------------------- measured == model ---


@pytest.mark.parametrize("height,n", [(4, 300), (5, 900), (7, 2500)])
def test_measured_equals_model_exactly(height, n):
    """On a quiescent (bulk-built) tree the measured distinct-block
    transfers per search equal `count_block_transfers` exactly — ratio
    1.0, not approximately — for every supported block size."""
    rng = np.random.default_rng(height)
    keys = np.unique(rng.integers(1, 50_000, size=n).astype(np.int64))
    cfg = TreeConfig(height=height, max_dnodes=4096, buf_cap=8,
                     collect_stats=True, collect_transfers=True)
    t = DT.bulk_build(cfg, keys)
    q = rng.integers(1, 50_000, size=256).astype(np.int64)  # hits + misses
    cm = OTR.compare_model(cfg, t, jnp.asarray(q, jnp.int32))
    for b in TRANSFER_BLOCK_SIZES:
        assert cm[b]["measured"] == pytest.approx(cm[b]["model"], abs=0), \
            (b, cm[b])
        assert cm[b]["ratio"] == 1.0


def test_transfer_stats_field_consistency():
    t = DT.bulk_build(CFG, KEYS)
    q = _queries()
    ts = OTR.measure(CFG, t, q)
    assert isinstance(ts, TransferStats)
    k = int(q.shape[0])
    assert int(ts.queries) == k and int(ts.batches) == 1
    assert int(ts.pad_lanes) == 2            # the two ROUTE_LEFT lanes
    assert int(ts.buffer_probes) == k - 2    # one probe per real query
    # every real query terminates in exactly one leaf touch
    assert int(ts.leaf_touches) == k - 2
    assert int(ts.router_touches) > 0
    assert int(ts.dnode_visits) >= k - 2     # >= one ΔNode per real query
    # block totals are monotone in block size (coarser blocks, fewer)
    blocks = np.asarray(ts.blocks)
    assert blocks.shape == (len(TRANSFER_BLOCK_SIZES),)
    assert all(blocks[i] >= blocks[i + 1] for i in range(blocks.size - 1))
    d = ts.asdict()
    for b in TRANSFER_BLOCK_SIZES:
        assert d[f"blocks_b{b}"] == int(blocks[TRANSFER_BLOCK_SIZES.index(b)])
        assert d[f"blocks_b{b}_mean"] > 0


def test_pad_lanes_contribute_zero():
    """A batch of only ROUTE_LEFT sentinels touches nothing."""
    t = DT.bulk_build(CFG, KEYS)
    q = jnp.full(8, layout.ROUTE_LEFT, jnp.int32)
    ts = OTR.measure(CFG, t, q)
    assert int(ts.pad_lanes) == 8 and int(ts.buffer_probes) == 0
    assert int(ts.dnode_visits) == 0
    assert int(ts.router_touches) == 0 and int(ts.leaf_touches) == 0
    assert np.asarray(ts.blocks).sum() == 0


def test_transfer_stats_merge_reduce():
    t = DT.bulk_build(CFG, KEYS)
    a = OTR.measure(CFG, t, _queries())
    m = jax.jit(lambda x: x.merge(x))(a)
    assert int(m.queries) == 2 * int(a.queries)
    assert int(m.batches) == 2
    assert np.array_equal(np.asarray(m.blocks), 2 * np.asarray(a.blocks))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), a, a)
    r = TransferStats.reduce(stacked)
    for la, lm in zip(jax.tree.leaves(r), jax.tree.leaves(m)):
        assert np.array_equal(np.asarray(la), np.asarray(lm))


# ----------------------------------------------- engine / dispatch parity ---


def test_transfer_stats_engine_parity():
    """scalar and lockstep reads return bit-identical TransferStats —
    the stat is derived in the dispatch layer, not per engine."""
    q = _queries()
    outs = {}
    for engine in ("scalar", "lockstep"):
        cfg = dataclasses.replace(CFG, engine=engine)
        t = DT.bulk_build(cfg, KEYS)
        outs[engine] = DT.search_jit(cfg, t, q)[2]
    sa, sl = outs["scalar"], outs["lockstep"]
    assert isinstance(sa, ReadStats)
    assert sa.transfers is not None and sl.transfers is not None
    for a, b in zip(jax.tree.leaves(sa.transfers),
                    jax.tree.leaves(sl.transfers)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("engine", ["scalar", "lockstep"])
def test_transfer_stats_forest_dispatch_parity(engine):
    """fused and vmap forest dispatches produce bit-identical
    TransferStats (replay runs in shard-local address space on the
    stacked arenas, fed the same shard ids by both paths)."""
    q = _queries()
    outs = []
    for fused in (True, False):
        fcfg = ForestConfig(num_shards=4,
                            tree=dataclasses.replace(CFG, engine=engine),
                            fused=fused)
        f = D.bulk_build(fcfg, KEYS)
        outs.append(D.search_batch(fcfg, f, q)[2])
    sa, sb = outs
    assert sa.transfers is not None and sb.transfers is not None
    assert int(sa.transfers.pad_lanes) == 2
    assert int(sa.transfers.buffer_probes) == int(q.shape[0]) - 2
    for a, b in zip(jax.tree.leaves(sa.transfers),
                    jax.tree.leaves(sb.transfers)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------- model fit ---


def test_fit_log_b_r2():
    """Height sweep of measured transfers fits c*log_B(N) + d with
    R^2 >= 0.98 — the paper's O(log_B N) transfer bound, observed."""
    fit = OTR.fit_log_b()
    assert fit["r2"] >= 0.98, fit
    assert fit["c"] > 0
    assert len(fit["points"]) == 11
    # measured mean transfers grow monotonically with N overall
    first, last = fit["points"][0][1], fit["points"][-1][1]
    assert last > first


# ------------------------------------------------------- static gate ---


def test_collect_transfers_gate_hlo():
    """collect_transfers is a sub-gate of collect_stats: with
    collect_stats=False it changes nothing (byte-identical HLO to the
    bare composition), and with collect_stats=True it adds the replay
    (different HLO from stats-only)."""
    import re

    from repro.core import engine as E

    base = TreeConfig(height=4, max_dnodes=64, buf_cap=8)
    t = DT.bulk_build(base, KEYS[:20])
    q = jnp.asarray(KEYS[:8], jnp.int32)

    def norm(txt):
        return re.sub(r"jit_\w+", "jit_fn", txt)

    def lower(cfg):
        return norm(jax.jit(lambda t, q: E.search(cfg, t, q))
                    .lower(t, q).as_text())

    def bare(t, q):
        found, _, hops = E.get_engine(base.engine).lookup(base, t, q)
        return found, hops

    lo_b = norm(jax.jit(bare).lower(t, q).as_text())
    off = dataclasses.replace(base, collect_transfers=True)
    assert lower(off) == lo_b        # dead sub-gate: still the bare graph
    stats_only = dataclasses.replace(base, collect_stats=True)
    both = dataclasses.replace(stats_only, collect_transfers=True)
    assert lower(both) != lower(stats_only)   # replay actually lowers


def test_compiled_fused_hlo_identity_subprocess():
    """Compiled-mode leg (REPRO_PALLAS_INTERPRET=0): around the fused
    single-launch walk, collect_stats=False still lowers byte-identical
    HLO to the bare engine-hook composition."""
    out = run_py("""
import os
os.environ["REPRO_PALLAS_INTERPRET"] = "0"
import re
import numpy as np, jax, jax.numpy as jnp
from repro.core import deltatree as DT
from repro.core import engine as E
from repro.core.deltatree import TreeConfig
from repro.kernels.ops import default_interpret
assert default_interpret() is False

cfg = TreeConfig(height=4, max_dnodes=64, buf_cap=8, engine="lockstep")
keys = np.arange(10, 150, 7, dtype=np.int64)
t = DT.bulk_build(cfg, keys)
q = jnp.asarray(keys[:8], jnp.int32)

def dispatched(t, q):
    return E.search(cfg, t, q)

def bare(t, q):
    found, _, hops = E.get_engine(cfg.engine).lookup(cfg, t, q)
    return found, hops

def norm(txt):
    return re.sub(r"jit_\\w+", "jit_fn", txt)

lo_d = norm(jax.jit(dispatched).lower(t, q).as_text())
lo_b = norm(jax.jit(bare).lower(t, q).as_text())
assert lo_d == lo_b, "stats-off dispatch is not free around the fused walk"

import dataclasses
on = dataclasses.replace(cfg, collect_stats=True, collect_transfers=True)
lo_on = norm(jax.jit(lambda t, q: E.search(on, t, q)).lower(t, q).as_text())
assert lo_on != lo_b
print("FUSED_HLO_IDENTITY_OK")
""")
    assert "FUSED_HLO_IDENTITY_OK" in out


# ------------------------------------------------------------ plumbing ---


def test_index_handle_collect_transfers():
    from repro.api import make_index

    ix = make_index("deltatree", initial=KEYS, height=4, max_dnodes=256,
                    buf_cap=8, collect_stats=True, collect_transfers=True)
    found, hops, stats = ix.search(_queries())
    ts = stats.transfers
    assert ts is not None and int(ts.pad_lanes) == 2
    # stats-only index: transfers leg absent, search stats still there
    ix2 = make_index("deltatree", initial=KEYS, height=4, max_dnodes=256,
                     buf_cap=8, collect_stats=True)
    _, _, st2 = ix2.search(_queries())
    assert st2.transfers is None and int(st2.search.queries) == 11
