"""repro.maintenance — policy-driven scheduler semantics.

Covers: every policy × randomized op traces vs the oracle (searches and
successors must stay correct over items still pending in overflow buffers
— the policy-conditional I5'), flush restoring I5 (bit-for-bit vs an
eager-built tree when no op was force-blocked), the budgeted repair cap,
MaintenanceStats telemetry + the legacy ``rounds`` deprecation shim,
``make_index(maintenance=)`` validation, and the configurable lockstep
q_tile (TreeConfig / REPRO_PALLAS_QTILE).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    OpBatch,
    make_index,
    supported_maintenance,
)
from repro.core import deltatree as DT
from repro.core.oracle import SetOracle
from repro.maintenance import MaintenanceStats, parse_policy
from tests.test_deltatree import check_invariants

POLICIES = ("eager", "deferred", "budgeted:2")
KEY_HI = 300

BUILD_KW = {
    "deltatree": dict(height=4, max_dnodes=512, buf_cap=8),
    "forest": dict(num_shards=3, height=4, max_dnodes=512, buf_cap=8,
                   key_max=KEY_HI),
}


def _check_reads(ix, oracle, rng):
    keys = rng.integers(1, KEY_HI + 5, size=24).astype(np.int32)
    f, _ = ix.search(jnp.asarray(keys))
    np.testing.assert_array_equal(np.asarray(f), oracle.snapshot_search(keys))
    live = oracle.keys()
    fs, sc = ix.successor(jnp.asarray(keys))
    idx = np.searchsorted(live, keys, side="right")
    ef = idx < live.size
    np.testing.assert_array_equal(np.asarray(fs), ef)
    if live.size:
        np.testing.assert_array_equal(np.asarray(sc)[ef], live[idx[ef]])


@pytest.mark.parametrize("backend", ["deltatree", "forest"])
@pytest.mark.parametrize("policy", POLICIES)
def test_policy_trace_matches_oracle(backend, policy):
    """Interleaved update + search/successor agree with the oracle under
    every policy — including reads over keys still pending in overflow
    buffers — and flush drains to I5 without changing the live set."""
    rng = np.random.default_rng(31)
    initial = np.unique(rng.integers(1, KEY_HI, 80).astype(np.int32))
    ix = make_index(backend, initial=initial, maintenance=policy,
                    **BUILD_KW[backend])
    assert ix.maintenance == policy
    oracle = SetOracle(initial)
    saw_pending = False
    for _ in range(8):
        _check_reads(ix, oracle, rng)
        kinds = rng.integers(0, 3, size=24).astype(np.int32)
        keys = rng.integers(1, KEY_HI, size=24).astype(np.int32)
        ix, res, stats = ix.update(OpBatch.mixed(kinds, keys))
        np.testing.assert_array_equal(
            np.asarray(res), oracle.apply_updates(kinds, keys))
        assert isinstance(stats, MaintenanceStats)
        saw_pending |= int(stats.pending) > 0
        if policy == "eager":
            assert int(stats.pending) == 0  # I5
        assert ix.size() == len(oracle.s)
        assert [k for k, _ in ix.live_items()] == sorted(oracle.s)
    if policy != "eager":
        assert saw_pending, "trace never exercised carried buffers"
    ix, fstats = ix.flush()
    assert int(fstats.pending) == 0
    assert [k for k, _ in ix.live_items()] == sorted(oracle.s)
    _check_reads(ix, oracle, rng)


def test_deferred_buffered_live_deleted_reads():
    """Explicit read legs over a deferred tree with non-empty buffers:
    buffered keys are found, deleted keys are not, untouched live keys
    stay found — through BOTH engines, bit for bit (hops included)."""
    cfg_s = DT.TreeConfig(height=4, max_dnodes=512, buf_cap=8,
                          maintenance="deferred")
    cfg_l = DT.TreeConfig(height=4, max_dnodes=512, buf_cap=8,
                          maintenance="deferred", engine="lockstep")
    rng = np.random.default_rng(33)
    initial = np.unique(rng.integers(1, KEY_HI, 90).astype(np.int32))
    t = DT.bulk_build(cfg_s, initial)
    oracle = SetOracle(initial)
    for _ in range(6):
        kinds = rng.integers(1, 3, size=24).astype(np.int32)
        keys = rng.integers(1, KEY_HI, size=24).astype(np.int32)
        t, res, stats = DT.update_batch(cfg_s, t, jnp.asarray(kinds),
                                        jnp.asarray(keys))
        oracle.apply_updates(kinds, keys)
    assert int(stats.pending) > 0, "trace must leave buffered items"
    check_invariants(cfg_s, t, require_empty_buffers=False)

    buffered = {int(cfg_s.key_of(v)) for row in np.asarray(t.buf)
                for v in row if v != 0}
    assert buffered and buffered <= oracle.s, "buffered keys must be live"
    live_not_buf = sorted(oracle.s - buffered)[:10]
    deleted = sorted(set(range(1, KEY_HI)) - oracle.s)[:10]
    q = np.asarray(sorted(buffered) + live_not_buf + deleted, np.int32)
    exp = np.asarray([k in oracle.s for k in q])

    f_s, h_s = DT.search_jit(cfg_s, t, jnp.asarray(q))
    f_l, h_l = DT.search_jit(cfg_l, t, jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(f_s), exp)
    np.testing.assert_array_equal(np.asarray(f_s), np.asarray(f_l))
    np.testing.assert_array_equal(np.asarray(h_s), np.asarray(h_l))

    # successor must see buffered keys too (the buffered-floor fold)
    live = oracle.keys()
    probes = np.asarray([k - 1 for k in sorted(buffered)], np.int32)
    for cfg in (cfg_s, cfg_l):
        fs, sc = DT.successor_jit(cfg, t, jnp.asarray(probes))
        idx = np.searchsorted(live, probes, side="right")
        ef = idx < live.size
        np.testing.assert_array_equal(np.asarray(fs), ef)
        np.testing.assert_array_equal(np.asarray(sc)[ef], live[idx[ef]])

    # flush drains to I5; live set unchanged
    t, fstats = DT.flush(cfg_s, t)
    assert int(fstats.pending) == 0
    check_invariants(cfg_s, t)
    assert (DT.live_keys(cfg_s, t) == live).all()


@pytest.mark.skipif(not jax.config.jax_enable_x64,
                    reason="map mode packs int64 values; needs JAX_ENABLE_X64")
def test_deferred_map_mode_buffered_payloads():
    """Map-mode deferred leg: payloads of buffered (pending) items are
    returned by lookup, and both engines agree bit for bit."""
    bits = 6
    cfg_s = DT.TreeConfig(height=4, max_dnodes=512, buf_cap=8,
                          payload_bits=bits, maintenance="deferred")
    cfg_l = DT.TreeConfig(height=4, max_dnodes=512, buf_cap=8,
                          payload_bits=bits, maintenance="deferred",
                          engine="lockstep")
    rng = np.random.default_rng(34)
    initial = np.unique(rng.integers(1, KEY_HI, 70).astype(np.int32))
    pays = rng.integers(0, 2**bits, size=initial.size).astype(np.int32)
    t = DT.bulk_build(cfg_s, initial, pays)
    expect = dict(zip(initial.tolist(), pays.tolist()))
    for _ in range(5):
        kinds = rng.integers(1, 3, size=20).astype(np.int32)
        keys = rng.integers(1, KEY_HI, size=20).astype(np.int32)
        vals = rng.integers(0, 2**bits, size=20).astype(np.int32)
        t, res, stats = DT.update_batch(cfg_s, t, jnp.asarray(kinds),
                                        jnp.asarray(keys), jnp.asarray(vals))
        for kk, ky, pp, rr in zip(kinds, keys, vals, np.asarray(res)):
            if kk == 1 and rr:
                expect[int(ky)] = int(pp)
            elif kk == 2 and rr:
                expect.pop(int(ky), None)
    assert int(stats.pending) > 0
    q = np.asarray(sorted(expect), np.int32)
    f_s, p_s, h_s = DT.lookup_jit(cfg_s, t, jnp.asarray(q))
    f_l, p_l, h_l = DT.lookup_jit(cfg_l, t, jnp.asarray(q))
    assert bool(np.asarray(f_s).all())
    np.testing.assert_array_equal(
        np.asarray(p_s), np.asarray([expect[int(k)] for k in q]))
    np.testing.assert_array_equal(np.asarray(f_s), np.asarray(f_l))
    np.testing.assert_array_equal(np.asarray(p_s), np.asarray(p_l))
    np.testing.assert_array_equal(np.asarray(h_s), np.asarray(h_l))


def test_deferred_flush_bit_identical_to_eager():
    """deferred batch + flush(budget=min(K,64)) reproduces the EAGER tree
    bit for bit when no op was force-blocked (large buffers): same arrays,
    not just the same live set."""
    kw = dict(height=4, max_dnodes=512, buf_cap=64)  # roomy: no forcing
    cfg_e = DT.TreeConfig(**kw)
    cfg_d = DT.TreeConfig(**kw, maintenance="deferred")
    rng = np.random.default_rng(35)
    initial = np.unique(rng.integers(1, KEY_HI, 60).astype(np.int32))
    t_e = DT.bulk_build(cfg_e, initial)
    t_d = DT.bulk_build(cfg_d, initial)
    for step in range(4):
        kinds = rng.integers(1, 3, size=24).astype(np.int32)
        keys = rng.integers(1, KEY_HI, size=24).astype(np.int32)
        t_e, res_e, st_e = DT.update_batch(cfg_e, t_e, jnp.asarray(kinds),
                                           jnp.asarray(keys))
        t_d, res_d, st_d = DT.update_batch(cfg_d, t_d, jnp.asarray(kinds),
                                           jnp.asarray(keys))
        np.testing.assert_array_equal(np.asarray(res_e), np.asarray(res_d))
        assert int(st_d.rounds) == 1, "deferred should take one round here"
        t_d, _ = DT.flush(cfg_d, t_d, min(24, 64))
        for name, a, b in zip(DT.DeltaTree._fields, t_e, t_d):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"{name} @ step {step}")
    check_invariants(cfg_d, t_d)


def test_budgeted_respects_repair_budget():
    """With roomy buffers (no forced repairs) a budgeted:1 policy does at
    most one Rebalance/Expand/Merge per batch and carries the rest."""
    cfg = DT.TreeConfig(height=4, max_dnodes=512, buf_cap=64,
                        maintenance="budgeted:1")
    rng = np.random.default_rng(36)
    t = DT.empty(cfg)
    oracle = SetOracle()
    carried = False
    for _ in range(10):
        kinds = np.ones(24, np.int32)  # insert-heavy: plenty of flags
        keys = rng.integers(1, KEY_HI, size=24).astype(np.int32)
        t, res, stats = DT.update_batch(cfg, t, jnp.asarray(kinds),
                                        jnp.asarray(keys))
        np.testing.assert_array_equal(
            np.asarray(res), oracle.apply_updates(kinds, keys))
        repairs = int(stats.rebuilds) + int(stats.merges)
        assert repairs <= 1, stats.asdict()
        carried |= int(stats.pending) > 0
        assert (DT.live_keys(cfg, t) == oracle.keys()).all()
    assert carried, "budget never left work pending"
    t, _ = DT.flush(cfg, t)
    check_invariants(cfg, t)


def test_stats_shim_and_fields():
    """MaintenanceStats still unpacks like the old 3-tuple and coerces to
    the legacy round count via int() with a DeprecationWarning."""
    cfg = DT.TreeConfig(height=4, max_dnodes=128, buf_cap=8)
    t = DT.empty(cfg)
    t, res, rounds = DT.update_batch(
        cfg, t, jnp.asarray([1, 1], np.int32), jnp.asarray([5, 9], np.int32))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = int(rounds)
    assert legacy == int(rounds.rounds)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    d = rounds.asdict()
    assert set(d) == {"rounds", "rebuilds", "expands", "merges",
                      "pending", "reclaimed"}
    zero = MaintenanceStats.zero()
    assert int(zero.rounds) == 0


def test_make_index_maintenance_validation():
    for bad in ("warp", "budgeted", "budgeted:0", "budgeted:x", 7):
        with pytest.raises(ValueError):
            make_index("deltatree", maintenance=bad,
                       **BUILD_KW["deltatree"])
    with pytest.raises(ValueError, match="maintenance"):
        make_index("sorted_array", maintenance="deferred", cap=64)
    # eager is universal; baselines flush as a no-op returning stats=None
    ix = make_index("sorted_array", maintenance="eager", cap=64)
    assert ix.maintenance == "eager"
    assert not ix.capability.deferred_maintenance
    ix2, st = ix.flush()
    assert st is None and ix2.spec is ix.spec
    # policies smuggled via a prebuilt cfg= fail at construction
    with pytest.raises(ValueError, match="maintenance"):
        make_index("deltatree",
                   cfg=DT.TreeConfig(height=4, max_dnodes=64,
                                     maintenance="lazyy"))
    assert supported_maintenance("deltatree") == (
        "eager", "deferred", "budgeted")
    assert supported_maintenance("static_veb") == ("eager",)
    assert parse_policy("budgeted:4").budget == 4
    assert str(parse_policy("budgeted:4")) == "budgeted:4"


def test_forest_stats_aggregation():
    """Forest updates aggregate per-shard stats (pending sums across
    shards) and forest flush drains every shard."""
    rng = np.random.default_rng(37)
    initial = np.unique(rng.integers(1, KEY_HI, 100).astype(np.int32))
    ix = make_index("forest", initial=initial, maintenance="deferred",
                    **BUILD_KW["forest"])
    oracle = SetOracle(initial)
    for _ in range(4):
        kinds = rng.integers(1, 3, size=32).astype(np.int32)
        keys = rng.integers(1, KEY_HI, size=32).astype(np.int32)
        ix, res, stats = ix.update(OpBatch.mixed(kinds, keys))
        np.testing.assert_array_equal(
            np.asarray(res), oracle.apply_updates(kinds, keys))
    assert int(stats.pending) > 0
    total_buf = int(np.asarray(ix.state.trees.bcount).sum())
    assert total_buf == int(stats.pending)
    ix, fstats = ix.flush()
    assert int(fstats.pending) == 0
    assert int(np.asarray(ix.state.trees.bcount).sum()) == 0
    assert [k for k, _ in ix.live_items()] == sorted(oracle.s)


# --------------------------------------------------------------------------
# q_tile configuration (lockstep kernel tile)
# --------------------------------------------------------------------------


def test_q_tile_config_and_env(monkeypatch):
    from repro.kernels import ops as OPS

    assert OPS.default_q_tile() == 256
    monkeypatch.setenv("REPRO_PALLAS_QTILE", "128")
    assert OPS.default_q_tile() == 128
    monkeypatch.setenv("REPRO_PALLAS_QTILE", "100")
    with pytest.raises(ValueError, match="multiple of 128"):
        OPS.default_q_tile()  # the process-wide knob is lane-aligned
    monkeypatch.delenv("REPRO_PALLAS_QTILE")
    # explicit per-call tiles stay lenient (tests use 16/64 in interpret
    # mode) but must still be positive
    assert OPS._resolve_q_tile(64) == 64
    with pytest.raises(ValueError, match="positive"):
        OPS._resolve_q_tile(-4)
    cfg_bad = DT.TreeConfig(height=4, max_dnodes=64, engine="lockstep",
                            q_tile=-4)
    with pytest.raises(ValueError, match="positive"):
        DT.search_batch(cfg_bad, DT.empty(cfg_bad),
                        jnp.asarray([5], jnp.int32))

    # a TreeConfig q_tile override produces identical results
    rng = np.random.default_rng(38)
    initial = np.unique(rng.integers(1, KEY_HI, 80).astype(np.int32))
    q = jnp.asarray(rng.integers(1, KEY_HI, 64).astype(np.int32))
    cfg128 = DT.TreeConfig(height=4, max_dnodes=256, engine="lockstep",
                           q_tile=128)
    cfg_def = DT.TreeConfig(height=4, max_dnodes=256, engine="lockstep")
    t = DT.bulk_build(cfg128, initial)
    f_a, h_a = DT.search_jit(cfg128, t, q)
    f_b, h_b = DT.search_jit(cfg_def, t, q)
    np.testing.assert_array_equal(np.asarray(f_a), np.asarray(f_b))
    np.testing.assert_array_equal(np.asarray(h_a), np.asarray(h_b))
    ix = make_index("deltatree", initial=initial, engine="lockstep",
                    height=4, max_dnodes=256, q_tile=128)
    assert OPS._resolve_q_tile(ix.cfg.q_tile or None, 4) == 128


# the hypothesis property legs live in tests/test_maintenance_property.py
# (importorskip on hypothesis must not skip this whole module)
