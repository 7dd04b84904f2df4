"""Beyond-paper serving benchmark: Index-paged decode vs dense-cache decode
(per step wall time at smoke scale on CPU) + pager hot-path stats.

``--backend`` picks the pager's Index backend (``deltatree`` single arena
or ``forest`` sharded) through the same factory path the engine uses.

Run under JAX_ENABLE_X64=1 (map-mode packed values); benchmarks.run turns
it on for its whole process.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import DEFAULT_SEED, add_common_args, emit


def run(steps: int = 10, seed: int = DEFAULT_SEED,
        backend: str | None = None, engine: str | None = None):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models.registry import api
    from repro.serving import PagerConfig, ServeEngine, ShardedPagerConfig

    backend = backend or "deltatree"
    if backend not in ("deltatree", "forest"):
        # the pager needs a map-mode index; only the tree backends pack
        # payloads — note and skip instead of failing the whole sweep
        return {"bench": "serve_paged", "backend": backend,
                "skipped": "pager needs a map-mode (payload) backend"}
    cfg = get_smoke_config("granite_8b")
    m = api(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    pager_kw = dict(num_pages=256, page_size=8, max_seqs=32, max_blocks=128,
                    tree_height=5, engine=engine or "scalar")
    if backend == "forest":
        pc = ShardedPagerConfig(num_shards=4, **pager_kw)
    else:
        assert backend == "deltatree", f"no pager mapping for {backend!r}"
        pc = PagerConfig(**pager_kw)
    eng = ServeEngine(cfg, params, pc, max_batch=8)
    assert eng.pager.index.backend == backend
    for n in (12, 20, 7, 30, 16, 9, 24, 11):
        eng.submit(rng.integers(1, cfg.vocab_size, size=n).astype(np.int32),
                   max_new=steps + 2)
    eng.step()  # warm
    t0 = time.perf_counter()
    for _ in range(steps):
        eng.step()
    dt = (time.perf_counter() - t0) / steps

    # dense baseline: batch-8 decode_step
    caches = m.init_caches(8, 64)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (8, 40)), jnp.int32)
    _, caches = m.prefill(params, toks, caches)
    ln = jnp.full((8,), 40, jnp.int32)
    tok = toks[:, -1:]
    lg, caches = m.decode_step(params, tok, caches, ln)  # warm
    t0 = time.perf_counter()
    for _ in range(steps):
        lg, caches = m.decode_step(params, tok, caches, ln)
    jax.block_until_ready(lg)
    dense = (time.perf_counter() - t0) / steps
    s = eng.pager.stats
    obs = eng.obs.asdict()  # ServeStats: latency reservoir + flush log
    return {"bench": "serve_paged", "backend": backend,
            "engine": eng.pager.index.engine, "seed": seed,
            "paged_step_us": round(dt * 1e6), "dense_step_us": round(dense * 1e6),
            "p50_us": obs["p50_us"], "p99_us": obs["p99_us"],
            "decode_steps": obs["steps"], "flushes": obs["flushes"],
            "pending_hwm": obs["pending_hwm"],
            "pager_searches": s["searches"], "pager_inserts": s["inserts"],
            "pager_deletes": s["deletes"],
            "hops_per_search": round(s["hops"] / max(s["searches"], 1), 2)}


def main(quick=True, seed=DEFAULT_SEED, backend=None, engine=None,
         smoke=False):
    return emit(run(steps=2 if smoke else (5 if quick else 20), seed=seed,
                    backend=backend, engine=engine))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    add_common_args(ap)
    args = ap.parse_args()
    main(quick=not args.full, seed=args.seed, backend=args.backend,
         engine=args.engine, smoke=args.smoke)
