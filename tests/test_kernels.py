"""Per-kernel interpret-mode validation vs the pure-jnp oracles (ref.py):
shape/dtype sweeps per the assignment."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import TreeConfig, bulk_build, search_jit, update_batch
from repro.kernels.delta_paged_attention import paged_decode_attention
from repro.kernels.ops import default_interpret, delta_contains, delta_search, delta_walk
from repro.kernels.ref import ref_delta_search, ref_paged_decode_attention


@pytest.mark.parametrize("h,m,nvals,qt", [
    (3, 8192, 1200, 64), (4, 4096, 2000, 128), (5, 2048, 3000, 128),
    (7, 2048, 3000, 256),
])
def test_veb_search_kernel_vs_ref(h, m, nvals, qt):
    rng = np.random.default_rng(h)
    cfg = TreeConfig(height=h, max_dnodes=m, buf_cap=16)
    vals = np.unique(rng.integers(1, 100_000, size=nvals).astype(np.int32))
    t = bulk_build(cfg, vals)
    # churn: marks, buffers, expansions, merges
    kinds = rng.choice([1, 2], size=64).astype(np.int32)
    keys = rng.integers(1, 100_000, size=64).astype(np.int32)
    t, _, _ = update_batch(cfg, t, jnp.asarray(kinds), jnp.asarray(keys))
    q = rng.integers(1, 100_000, size=500).astype(np.int32)
    lv, lb, dn = delta_search(t.value, t.child, t.root, jnp.asarray(q),
                              height=h, q_tile=qt)
    rlv, rlb, rdn = ref_delta_search(t.value, t.child, t.root, jnp.asarray(q),
                                     height=h)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(rlv))
    np.testing.assert_array_equal(np.asarray(lb), np.asarray(rlb))
    np.testing.assert_array_equal(np.asarray(dn), np.asarray(rdn))
    found = delta_contains(t.value, t.mark, t.child, t.buf, t.root,
                           jnp.asarray(q), height=h, q_tile=qt)
    cfound, chops = search_jit(cfg, t, jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(found), np.asarray(cfound))
    # full-walk contract: per-query hop counts equal the scalar engine's
    # transfer statistic (rounds active == ΔNodes visited)
    _, _, _, hops, _ = delta_walk(t.value, t.child, t.root, jnp.asarray(q),
                                  height=h, q_tile=qt)
    np.testing.assert_array_equal(np.asarray(hops), np.asarray(chops))


def test_delta_walk_pad_sentinel_no_alias():
    """Query batches not divisible by q_tile pad with a provably-missing
    sentinel and pre-resolved lanes: results must be identical whatever
    the padding width, and a query equal to the old pad value (EMPTY-
    adjacent key 1) must still resolve correctly."""
    rng = np.random.default_rng(7)
    cfg = TreeConfig(height=4, max_dnodes=512, buf_cap=8)
    vals = np.unique(
        np.concatenate([[1], rng.integers(1, 5000, 800)]).astype(np.int32))
    t = bulk_build(cfg, vals)
    q = np.concatenate([[1, 2], rng.integers(1, 5000, 41)]).astype(np.int32)
    outs = [delta_walk(t.value, t.child, t.root, jnp.asarray(q),
                       height=4, q_tile=qt) for qt in (16, 64, 256)]
    for other in outs[1:]:
        for a, b in zip(outs[0], other):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    lv = np.asarray(outs[0][0])
    assert lv[0] == 1  # key 1 (== EMPTY + 1) found despite padded lanes


def test_ref_walk_rows_matches_kernel():
    """The compiled jnp mirror (the int64-on-TPU production fallback) must
    match the Pallas kernel's one-round contract exactly, cand included."""
    from repro.kernels.ref import ref_veb_walk_rows
    from repro.kernels.veb_search import pad_arena, veb_walk_rows

    rng = np.random.default_rng(3)
    cfg = TreeConfig(height=5, max_dnodes=2048, buf_cap=16)
    vals = np.unique(rng.integers(1, 50_000, 2500).astype(np.int32))
    t = bulk_build(cfg, vals)
    n_alive = int(np.asarray(t.alive).sum())
    q = jnp.asarray(rng.integers(1, 50_000, 256).astype(np.int32))
    vp, cp = pad_arena(t.value, t.child)
    dns = jnp.asarray(rng.integers(0, n_alive, 256).astype(np.int32))
    rows, childrows = vp[dns], cp[dns]
    out_k = veb_walk_rows(rows, childrows, q, height=5, q_tile=256,
                          interpret=True)
    out_r = ref_veb_walk_rows(rows, childrows, q, height=5)
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_default_interpret_env_override(monkeypatch):
    """REPRO_PALLAS_INTERPRET overrides the backend auto-detection."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert default_interpret() is True
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    import jax

    assert default_interpret() is (jax.default_backend() != "tpu")


@pytest.mark.parametrize("b,qh,kvh,d,ps,maxp", [
    (2, 4, 2, 64, 8, 4),
    (3, 8, 1, 128, 16, 3),
    (1, 2, 2, 32, 4, 6),
    (4, 8, 8, 64, 8, 2),   # MHA (G=1)
])
@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-5), (jnp.bfloat16, 0.12)])
def test_paged_attention_kernel_vs_ref(b, qh, kvh, d, ps, maxp, dtype, tol):
    rng = np.random.default_rng(b * 100 + qh)
    npages = b * maxp + 3
    q = rng.standard_normal((b, qh, d)).astype(np.float32)
    kp = rng.standard_normal((npages, kvh, ps, d)).astype(np.float32)
    vp = rng.standard_normal((npages, kvh, ps, d)).astype(np.float32)
    lens = rng.integers(1, maxp * ps + 1, size=b).astype(np.int32)
    bt = np.full((b, maxp), -1, np.int32)
    perm = rng.permutation(npages)
    c = 0
    for i in range(b):
        for j in range(-(-int(lens[i]) // ps)):
            bt[i, j] = perm[c]
            c += 1
    ref = ref_paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(lens))
    out = paged_decode_attention(
        jnp.asarray(q, dtype), jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
        jnp.asarray(bt), jnp.asarray(lens))
    err = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32)).max()
    assert err < tol, (b, qh, kvh, d, ps, maxp, dtype, err)


def test_paged_attention_ignores_garbage_pages():
    """Pages not referenced by a sequence's block table must not leak in."""
    rng = np.random.default_rng(0)
    b, qh, kvh, d, ps, maxp = 2, 4, 2, 32, 8, 3
    npages = 10
    q = rng.standard_normal((b, qh, d)).astype(np.float32)
    kp = rng.standard_normal((npages, kvh, ps, d)).astype(np.float32)
    vp = rng.standard_normal((npages, kvh, ps, d)).astype(np.float32)
    lens = np.asarray([9, 17], np.int32)
    bt = np.asarray([[4, 5, -1], [6, 7, 8]], np.int32)
    out1 = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                  jnp.asarray(vp), jnp.asarray(bt),
                                  jnp.asarray(lens))
    kp2 = kp.copy()
    vp2 = vp.copy()
    for g in (0, 1, 2, 3, 9):  # unreferenced pages scrambled
        kp2[g] = 1e3
        vp2[g] = -1e3
    out2 = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp2),
                                  jnp.asarray(vp2), jnp.asarray(bt),
                                  jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)
