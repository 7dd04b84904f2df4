"""Model facade + assignment shapes + input_specs (ShapeDtypeStruct inputs).

`api(cfg)` returns a uniform interface regardless of family:
    init_params(key) / loss_fn(params, batch) / prefill(params, ...) /
    decode_step(params, ...) / init_caches(batch, max_len)

`input_specs(cfg, shape_name)` returns ShapeDtypeStruct stand-ins for every
input of the step that shape lowers (train_step / prefill_step /
serve_step), with no device allocation.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models.config import ModelConfig

# assignment shape table: name -> (seq_len, global_batch, step kind)
SHAPES = {
    "train_4k": (4096, 256, "train"),
    "prefill_32k": (32768, 32, "prefill"),
    "decode_32k": (32768, 128, "decode"),
    "long_500k": (524288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape_name: str) -> tuple[bool, str]:
    """Per-assignment skips: long_500k needs sub-quadratic attention."""
    if shape_name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return False, "long_500k skipped: pure full-attention arch (per assignment)"
    return True, ""


def api(cfg: ModelConfig) -> SimpleNamespace:
    mod = encdec if cfg.family == "audio" else transformer
    return SimpleNamespace(
        init_params=lambda key: mod.init_params(key, cfg),
        loss_fn=lambda params, batch: mod.loss_fn(params, cfg, batch),
        forward_train=lambda params, **kw: mod.forward_train(params, cfg, **kw),
        prefill=lambda params, *a, **kw: mod.prefill(params, cfg, *a, **kw),
        decode_step=lambda params, *a, **kw: mod.decode_step(params, cfg, *a, **kw),
        init_caches=lambda batch, max_len: mod.init_caches(cfg, batch, max_len),
        module=mod,
    )


def input_specs(cfg: ModelConfig, shape_name: str, batch_override: int | None = None):
    """ShapeDtypeStructs for the step the shape lowers. Returns
    (step_kind, specs_dict)."""
    seq, gbatch, kind = SHAPES[shape_name]
    b = batch_override or gbatch
    i32 = jnp.int32
    act = jnp.dtype(cfg.dtype)
    S = jax.ShapeDtypeStruct

    if kind == "train":
        if cfg.family == "vlm":
            st = seq - cfg.vision_tokens
            specs = {
                "tokens": S((b, st), i32),
                "labels": S((b, st), i32),
                "vision_embeds": S((b, cfg.vision_tokens, cfg.d_model), act),
            }
        elif cfg.family == "audio":
            specs = {
                "tokens": S((b, seq), i32),
                "labels": S((b, seq), i32),
                "frames": S((b, cfg.encoder_seq, cfg.d_model), act),
            }
        else:
            specs = {"tokens": S((b, seq), i32), "labels": S((b, seq), i32)}
        return "train", specs

    mod = encdec if cfg.family == "audio" else transformer
    cache_spec = jax.eval_shape(lambda: mod.init_caches(cfg, b, seq))

    if kind == "prefill":
        if cfg.family == "vlm":
            specs = {
                "tokens": S((b, seq - cfg.vision_tokens), i32),
                "vision_embeds": S((b, cfg.vision_tokens, cfg.d_model), act),
                "caches": cache_spec,
            }
        elif cfg.family == "audio":
            specs = {
                "tokens": S((b, seq), i32),
                "frames": S((b, cfg.encoder_seq, cfg.d_model), act),
                "caches": cache_spec,
            }
        else:
            specs = {"tokens": S((b, seq), i32), "caches": cache_spec}
        return "prefill", specs

    # decode: one new token against a seq-long cache
    specs = {
        "token": S((b, 1), i32),
        "length": S((b,), i32),
        "caches": cache_spec,
    }
    return "decode", specs
