"""Pallas TPU kernel: ΔTree-paged decode attention (serving hot path).

The ΔTree serving index (serving/pager.py) resolves (seq, logical_block) →
physical page; this kernel consumes the resolved block table and DMAs *only
the pages a sequence owns* — the paper's locality thesis applied to the KV
cache: the transfer unit (one KV page) is sized to the VMEM block, and the
indirection is a scalar-prefetched pointer, exactly like a ΔNode hop.

Grid (B, KVH, MAXP): one (batch row, kv head, page) per step, accumulating
online softmax in VMEM scratch (flash-decoding style).  The block table and
sequence lengths ride in scalar-prefetch memory so the K/V `BlockSpec
index_map` can pick the physical page per grid step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Typed constants: a Python float (or an index map's Python 0) traces as a
# 64-bit value under JAX_ENABLE_X64 — which the serving pager runs under —
# and Mosaic refuses it.
NEG_INF = np.float32(-1e30)
_TINY = np.float32(1e-30)
_Z = np.int32(0)


def _kernel(maxp: int, page_size: int, scale: float,
            # scalar prefetch
            bt_ref, len_ref,
            # inputs
            q_ref, k_ref, v_ref,
            # outputs
            o_ref,
            # scratch
            m_ref, l_ref, acc_ref):
    b = pl.program_id(0)
    p = pl.program_id(2)
    seq_len = len_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p * page_size < seq_len)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)         # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)         # (PS, D)
        v = v_ref[0, 0].astype(jnp.float32)         # (PS, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                    # (G, PS)
        tok = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tok < seq_len, s, NEG_INF)
        m_old = m_ref[:, 0]                          # (G,)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
        alpha = jnp.exp(m_old - m_new)               # (G,)
        pr = jnp.exp(s - m_new[:, None])             # (G, PS)
        l_new = alpha * l_ref[:, 0] + jnp.sum(pr, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(p == maxp - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[:, 0], _TINY)[:, None]).astype(
            o_ref.dtype
        )


def paged_decode_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                           block_tables: jax.Array, seq_lens: jax.Array,
                           *, interpret: bool | None = None) -> jax.Array:
    """ΔTree-paged GQA decode attention.

    q:            (B, QH, D)
    k/v_pages:    (NP, KVH, PS, D) — head-major pages, so one (PS, D)
                  page of one kv head is a tiled block
    block_tables: (B, MAXP) int32 (-1 = unused; clamped for DMA, masked in
                  compute via seq_lens)
    seq_lens:     (B,) int32
    Returns (B, QH, D) in q.dtype.

    ``interpret=None`` auto-resolves at call time like the search kernels
    (`ops.default_interpret`): compiled on TPU, interpret elsewhere —
    serving decode steps stop silently paying the interpreter tax on TPU.
    """
    from repro.kernels.ops import _resolve_interpret

    return _paged_decode_attention(q, k_pages, v_pages, block_tables,
                                   seq_lens,
                                   interpret=_resolve_interpret(interpret))


@functools.partial(
    jax.jit, static_argnames=("interpret",)
)
def _paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens,
                            *, interpret: bool):
    b, qh, d = q.shape
    np_, kvh, ps, _ = k_pages.shape
    maxp = block_tables.shape[1]
    g = qh // kvh
    assert g * kvh == qh
    scale = np.float32(1.0 / (d**0.5))

    bt_flat = jnp.maximum(block_tables, 0).reshape(-1)
    q4 = q.reshape(b, kvh, g, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, pi, bt, sl: (bi, hi, _Z, _Z)),
            pl.BlockSpec(
                (1, 1, ps, d),
                lambda bi, hi, pi, bt, sl: (bt[bi * maxp + pi], hi, _Z, _Z),
            ),
            pl.BlockSpec(
                (1, 1, ps, d),
                lambda bi, hi, pi, bt, sl: (bt[bi * maxp + pi], hi, _Z, _Z),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda bi, hi, pi, bt, sl: (bi, hi, _Z, _Z)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, maxp, ps, scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(bt_flat, seq_lens, q4, k_pages, v_pages)
    return out.reshape(b, qh, d)
