"""Split-K sharded decode attention (flash-decoding style) via shard_map.

The decode KV cache is sharded along *sequence* on the "model" axis
(parallel/shardings.py).  Instead of letting the SPMD partitioner all-gather
the cache for the softmax, each shard computes a partial (max, sum, out)
over its local KV slice and the shards combine with two tiny psums — wire
traffic O(B·H·D) instead of O(B·S·KVH·D).  Used as a §Perf optimization for
the decode cells and unit-tested against `decode_attention` on host devices.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _local_partial(q, k, v, length, s0):
    """Partial attention over a local KV slice starting at position s0."""
    b, _, h, d = q.shape
    s_loc, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qf = q.reshape(b, kvh, g, d).astype(jnp.float32)
    sc = jnp.einsum("bhgd,bshd->bhgs", qf, k.astype(jnp.float32)) / np.sqrt(d)
    pos = s0 + jnp.arange(s_loc)
    sc = jnp.where((pos[None, :] < length[:, None])[:, None, None], sc, NEG_INF)
    m = jnp.max(sc, axis=-1)                     # (B,KVH,G)
    p = jnp.exp(sc - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return m, l, o


def split_k_decode_attention(mesh, q, k_cache, v_cache, length,
                             axis: str = "model"):
    """q: (B,1,H,D) replicated over `axis`; caches: (B,S,KVH,D) sharded on S
    over `axis`; length: (B,). Returns (B,1,H,D)."""
    n = mesh.shape[axis]
    s = k_cache.shape[1]
    s_loc = s // n

    def local(q, k, v, length):
        i = jax.lax.axis_index(axis)
        m, l, o = _local_partial(q, k, v, length, i * s_loc)
        # rescaled combine: M = global max; sum l', o' with alpha factors
        mm = jax.lax.pmax(m, axis)
        alpha = jnp.exp(m - mm)
        ll = jax.lax.psum(l * alpha, axis)
        oo = jax.lax.psum(o * alpha[..., None], axis)
        out = oo / jnp.maximum(ll, 1e-30)[..., None]
        b, kvh, g, d = out.shape
        return out.reshape(b, 1, kvh * g, d).astype(q.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None), P()),
        out_specs=P(),
        check_vma=False,
    )(q, k_cache, v_cache, length)
