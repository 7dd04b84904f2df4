"""Decoder-only LM: scan-over-layers with heterogeneous pattern periods.

The layer stack is grouped into `period = cfg.pattern_period` slots (dense:
1; jamba: 8 — 7 SSD + 1 attn with alternating MoE).  Params for slot j are
stacked over the `reps = L // period` repetitions and applied with
`lax.scan`, keeping the HLO compact enough to compile 80-layer models.
`cfg.remat` wraps each scan body in jax.checkpoint (policy: nothing
saveable — §Perf iterates on this).

Three entry points per the assignment's shapes:
  forward_train   (train_4k)      tokens -> logits
  prefill         (prefill_32k)   tokens -> (logits_last, caches)
  decode_step     (decode_32k / long_500k)  token + caches -> (logits, caches)

VLM family: `vision_embeds` (precomputed patch embeddings — frontend stub)
are concatenated in front of the token embeddings.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models.config import ModelConfig
from repro.models.scan_utils import scan_or_unroll
from repro.models.layers.basic import (
    embed_apply,
    init_embedding,
    init_rmsnorm,
    logits_apply,
    rmsnorm_apply,
)
from repro.parallel.ax import constrain


def _remat_policy(cfg: ModelConfig):
    """Checkpoint policy: 'nothing' = min memory / max recompute;
    'dots' = save matmul outputs (no backward recompute of the big GEMMs)
    — §Perf trade-off knob."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


# ----------------------------------------------------------------- params ---


def _layout(cfg: ModelConfig):
    """(n_prologue, period, reps): prologue layers (e.g. DeepSeek's leading
    dense-FFN layer) are applied unscanned; the rest scan over the pattern."""
    period = cfg.pattern_period
    n_pro = cfg.dense_layers
    assert (cfg.num_layers - n_pro) % period == 0, (cfg.num_layers, n_pro, period)
    return n_pro, period, (cfg.num_layers - n_pro) // period


def init_params(key, cfg: ModelConfig):
    cfg.validate()
    n_pro, period, reps = _layout(cfg)
    k_embed, k_final, *k_layers = jax.random.split(key, 2 + cfg.num_layers)

    prologue = [B.init_block(k_layers[i], cfg, i) for i in range(n_pro)]
    slots = []
    for j in range(period):
        per_rep = [
            B.init_block(k_layers[n_pro + r * period + j], cfg,
                         n_pro + r * period + j)
            for r in range(reps)
        ]
        slots.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep))

    return {
        "embed": init_embedding(
            k_embed, cfg.vocab_size, cfg.d_model, jnp.dtype(cfg.param_dtype),
            tie=cfg.tie_embeddings,
        ),
        "prologue": prologue,
        "slots": slots,
        "final_norm": init_rmsnorm(cfg.d_model, jnp.dtype(cfg.param_dtype)),
    }


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def _slot_kinds(cfg: ModelConfig):
    n_pro, period, reps = _layout(cfg)
    kinds = [B.block_kinds(cfg, n_pro + j) for j in range(period)]
    for j in range(period):  # pattern must be uniform across reps
        for r in range(1, reps):
            assert B.block_kinds(cfg, n_pro + r * period + j) == kinds[j]
    return kinds


# ---------------------------------------------------------------- forward ---


def _embed_inputs(params, cfg: ModelConfig, tokens, vision_embeds=None):
    x = embed_apply(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        assert vision_embeds is not None
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return constrain(x, "batch", "seq", "embed"), positions


def forward_train(params, cfg: ModelConfig, tokens, vision_embeds=None):
    """tokens: (B, S_text) -> logits (B, S_total, V)."""
    x, positions = _embed_inputs(params, cfg, tokens, vision_embeds)
    kinds = _slot_kinds(cfg)

    for i, lp in enumerate(params["prologue"]):
        x = B.block_train(lp, cfg, B.block_kinds(cfg, i), x, positions)

    period = cfg.pattern_period

    def body(x, slot_params):
        for j, kp in enumerate(slot_params):
            blk = lambda kp, x, j=j: B.block_train(kp, cfg, kinds[j], x,
                                                   positions)
            if cfg.remat and period > 1:
                # nested per-layer remat: bounds the backward live set to
                # ONE layer of a multi-layer pattern (jamba's 8-layer
                # super-block otherwise keeps 7 SSD layers' intermediates
                # alive; §Perf iteration)
                blk = jax.checkpoint(blk, policy=_remat_policy(cfg))
            x = blk(kp, x)
        return x, None

    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    x, _ = scan_or_unroll(body, x, params["slots"], cfg.unroll)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    return logits_apply(params["embed"], x, cfg.logits_softcap)


def xent(logits, labels):
    """Cross entropy friendly to vocab-sharded logits: logsumexp (partial
    reduce + tiny all-reduce) and a one-hot contraction instead of a gather
    across vocab shards.  The one-hot rides in bf16 (0/1 exact) — halves the
    largest loss-side tensor's bytes (§Perf iteration)."""
    logits = constrain(logits, "batch", "seq", "vocab")
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.bfloat16)
    lab = jnp.einsum("bsv,bsv->bs", logits.astype(jnp.bfloat16), onehot,
                     preferred_element_type=jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    return ((lse - lab) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(params, cfg: ModelConfig, batch):
    """Next-token cross entropy. batch: {tokens, labels[, vision_embeds]}."""
    logits = forward_train(
        params, cfg, batch["tokens"], batch.get("vision_embeds")
    )
    labels = batch["labels"]
    if cfg.family == "vlm":  # vision prefix carries no labels
        logits = logits[:, -labels.shape[1]:]
    return xent(logits, labels)


# ------------------------------------------------------------------ cache ---


def init_caches(cfg: ModelConfig, batch: int, max_len: int):
    """{'prologue': [...], 'slots': [stacked (reps, ...) per slot]}."""
    n_pro, period, reps = _layout(cfg)
    kinds = _slot_kinds(cfg)
    dtype = jnp.dtype(cfg.dtype)

    def stack(tree):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (reps,) + x.shape), tree)

    return {
        "prologue": [
            B.init_block_cache(cfg, B.block_kinds(cfg, i), batch, max_len, dtype)
            for i in range(n_pro)
        ],
        "slots": [
            stack(B.init_block_cache(cfg, kinds[j], batch, max_len, dtype))
            for j in range(period)
        ],
    }


def prefill(params, cfg: ModelConfig, tokens, caches, vision_embeds=None):
    """Fill caches[...][:, :S]; returns (last-position logits, caches)."""
    x, positions = _embed_inputs(params, cfg, tokens, vision_embeds)
    kinds = _slot_kinds(cfg)

    pro_caches = []
    for i, (lp, kc) in enumerate(zip(params["prologue"], caches["prologue"])):
        x, nc = B.block_prefill(lp, cfg, B.block_kinds(cfg, i), x, positions, kc)
        pro_caches.append(nc)

    def body(x, slot):
        slot_params, slot_cache = slot
        new_caches = []
        for j, (kp, kc) in enumerate(zip(slot_params, slot_cache)):
            x, nc = B.block_prefill(kp, cfg, kinds[j], x, positions, kc)
            new_caches.append(nc)
        return x, new_caches

    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    x, new_caches = scan_or_unroll(body, x, (params["slots"], caches["slots"]), cfg.unroll)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = logits_apply(params["embed"], x[:, -1:], cfg.logits_softcap)
    return logits, {"prologue": pro_caches, "slots": new_caches}


def decode_step(params, cfg: ModelConfig, token, caches, length):
    """token: (B,1) int32; length: (B,) cached tokens. -> (logits, caches)."""
    x = embed_apply(params["embed"], token).astype(jnp.dtype(cfg.dtype))
    positions = length[:, None].astype(jnp.int32)
    kinds = _slot_kinds(cfg)

    pro_caches = []
    for i, (lp, kc) in enumerate(zip(params["prologue"], caches["prologue"])):
        x, nc = B.block_decode(lp, cfg, B.block_kinds(cfg, i), x, positions, kc,
                               length)
        pro_caches.append(nc)

    def body(x, slot):
        slot_params, slot_cache = slot
        new_caches = []
        for j, (kp, kc) in enumerate(zip(slot_params, slot_cache)):
            x, nc = B.block_decode(kp, cfg, kinds[j], x, positions, kc, length)
            new_caches.append(nc)
        return x, new_caches

    x, new_caches = scan_or_unroll(body, x, (params["slots"], caches["slots"]), cfg.unroll)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = logits_apply(params["embed"], x, cfg.logits_softcap)
    return logits, {"prologue": pro_caches, "slots": new_caches}
