"""Model-side decode machinery shared by every serve loop (DESIGN.md §10).

Extracted from the legacy lockstep engine so the scheduler and the
compat engine drive the exact same compute: per-layer parameter
unstacking, dense prefill with K/V scatter into allocated pages, and the
single paged decode step (per layer: scatter the new token's K/V into
each sequence's tail page slot, then run the Pallas paged
decode-attention kernel over the block table).

Everything here is pure over its inputs — no pager, no queue, no index.
The scheduler owns *which* lanes decode; this module owns *how* a lane's
tokens turn into logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.delta_paged_attention import paged_decode_attention
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.layers.attention import attn_out, qkv_proj
from repro.models.layers.basic import (
    embed_apply,
    logits_apply,
    mlp_apply,
    rmsnorm_apply,
)
from repro.models.layers.moe import moe_apply


def layer_params(cfg: ModelConfig, params):
    """Unstack scan-stacked params into a per-layer list."""
    n_pro, period, reps = T._layout(cfg)
    out = list(params["prologue"])
    for r in range(reps):
        for j in range(period):
            out.append(jax.tree.map(lambda x: x[r], params["slots"][j]))
    return out


def prefill_to_pages(cfg: ModelConfig, params, page_size: int,
                     k_pages, v_pages, prompt, pages):
    """Dense prefill of one prompt, K/V scattered into ``pages``.

    Returns (k_pages, v_pages, seq_len, first_token) — the first decoded
    token is the argmax over the prompt's last logit, exactly the legacy
    engine's submit-time behavior."""
    toks = jnp.asarray(prompt)[None]
    s = toks.shape[1]
    caches = T.init_caches(cfg, 1, -(-s // page_size) * page_size)
    logits, caches = T.prefill(params, cfg, toks, caches)
    # flatten slot caches to per-layer order
    n_pro, period, reps = T._layout(cfg)
    layer_caches = list(caches["prologue"])
    for r in range(reps):
        for j in range(period):
            layer_caches.append(
                jax.tree.map(lambda x: x[r], caches["slots"][j]))
    for li, c in enumerate(layer_caches):
        k = c["k"][0]  # (Smax, KVH, HD)
        v = c["v"][0]
        for bi, page in enumerate(pages):
            sl = slice(bi * page_size, (bi + 1) * page_size)
            k_pages = k_pages.at[li, page].set(k[sl].swapaxes(0, 1))
            v_pages = v_pages.at[li, page].set(v[sl].swapaxes(0, 1))
    return k_pages, v_pages, s, int(jnp.argmax(logits[0, -1]))


def paged_decode_step(params, cfg: ModelConfig, layer_params, tokens,
                      k_pages, v_pages, block_tables, lengths, page_size):
    """One decode step over paged caches: per layer, scatter the new token's
    K/V into each sequence's tail page slot, then run the Pallas paged
    decode-attention kernel over the block table."""
    x = embed_apply(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    positions = lengths[:, None].astype(jnp.int32)
    b = tokens.shape[0]
    rows = jnp.arange(b)
    tail_page = block_tables[rows, lengths // page_size]
    tail_off = lengths % page_size
    for li, lp in enumerate(layer_params):
        kinds = (cfg.layer_kind(li), cfg.ffn_kind(li))
        h = rmsnorm_apply(lp["norm1"], x, cfg.norm_eps)
        q, k, v = qkv_proj(lp["mixer"], cfg, h, positions)
        k_pages = k_pages.at[li, tail_page, :, tail_off].set(
            k[:, 0].astype(k_pages.dtype))
        v_pages = v_pages.at[li, tail_page, :, tail_off].set(
            v[:, 0].astype(v_pages.dtype))
        o = paged_decode_attention(
            q[:, 0], k_pages[li], v_pages[li], block_tables, lengths + 1)
        x = x + attn_out(lp["mixer"], o[:, None])
        if "ffn" in lp:
            h2 = rmsnorm_apply(lp["norm2"], x, cfg.norm_eps)
            if kinds[1] == "moe":
                x = x + moe_apply(lp["ffn"], cfg, h2)
            else:
                x = x + mlp_apply(lp["ffn"], h2)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = logits_apply(params["embed"], x, cfg.logits_softcap)
    return logits, k_pages, v_pages
