"""Sustained mixed-arrival serve trace: continuous batching vs lockstep.

Replays one ``repro.serve.trace.synth_trace`` plan (same seed → same
arrivals everywhere) through four rows:

  serve_trace/lockstep      — the legacy loop (prefill at submit, rigid
                              lockstep decode, maintenance inline on the
                              decode path at the high-water mark);
  serve_trace/sched         — the continuous-batching scheduler on the
                              identical arrivals-only trace;
  serve_trace/sched_churn   — + mid-flight cancels and zipfian probe
                              traffic (op combining earns its keep);
  serve_trace/sched_churn_forest — churn over the sharded forest pager,
                              where the hoisted fused view serves
                              consecutive decode steps from cache.

Every scheduler row reports p50/p99 step latency, queue-depth high-water,
admission waits, combined ops, fused-view cache hits and worker drains —
and asserts the acceptance invariant that the decode path ran ZERO
inline structural maintenance (the worker owns every drain).

Run under JAX_ENABLE_X64=1 (packed map-mode values); benchmarks.run
turns it on for its whole process.
"""

from __future__ import annotations

import argparse

from benchmarks.common import DEFAULT_SEED, add_common_args, emit


def _model():
    import jax
    from repro.configs import get_smoke_config
    from repro.models.registry import api

    cfg = get_smoke_config("granite_8b")
    m = api(cfg)
    return cfg, m.init_params(jax.random.PRNGKey(0))


def _pager_cfg(backend: str, engine: str | None):
    from repro.serving import PagerConfig, ShardedPagerConfig

    kw = dict(num_pages=1024, page_size=4, max_seqs=256, max_blocks=64,
              tree_height=5, maintenance="deferred", maint_high_water=8)
    if backend == "forest":
        # the fused frontier (and so the hoisted view) needs the
        # lockstep engine unless the sweep pinned one explicitly
        return ShardedPagerConfig(num_shards=4,
                                  engine=engine or "lockstep", **kw)
    return PagerConfig(engine=engine or "scalar", **kw)


def _base_row(tag: str, eng, seed: int) -> dict:
    obs = eng.obs.asdict()
    s = eng.pager.stats
    return {"bench": f"serve_trace/{tag}",
            "backend": eng.pager.index.backend,
            "engine": eng.pager.index.engine,
            "maintenance": "deferred", "seed": seed,
            "p50_us": obs["p50_us"], "p99_us": obs["p99_us"],
            "decode_steps": obs["steps"], "pending_hwm": obs["pending_hwm"],
            "inline_maint": s["inline_maint"],
            "pager_searches": s["searches"],
            "hops_per_search": round(s["hops"] / max(s["searches"], 1), 2)}


def _run_lockstep(cfg, params, pc, plans, max_batch: int, seed: int) -> dict:
    from repro.serving.engine import LockstepServeEngine

    eng = LockstepServeEngine(cfg, params, pc, max_batch=max_batch)
    for plan in plans:
        for prompt, max_new in plan.arrivals:
            eng.submit(prompt, max_new=max_new)
        eng.step()
    for _ in range(500):                       # drain the long tail
        if not eng.step():
            break
    row = _base_row("lockstep", eng, seed)
    row.update(submitted=eng._next_id,
               finished=sum(r.done for r in eng.active.values()),
               inline_flushes=eng.obs.asdict()["flushes"])
    return row


def _run_sched(tag: str, cfg, params, pc, plans, max_live: int,
               seed: int) -> dict:
    from repro.distributed import forest as F
    from repro.serve import SchedulerConfig, ServeScheduler

    F.reset_fused_view_cache()
    sch = ServeScheduler(cfg, params, pc, SchedulerConfig(max_live=max_live))
    summary = sch.run_trace(plans)
    obs = sch.obs.asdict()
    w = sch.worker.stats()
    row = _base_row(tag, sch, seed)
    # acceptance: all structural maintenance ran on the worker path
    assert row["inline_maint"] == 0, row
    row.update(submitted=summary["submitted"],
               finished=summary["finished"], rejected=summary["rejected"],
               queue_hwm=obs["queue_hwm"], admitted=obs["admitted"],
               admit_wait=obs["admit_wait"], combined=obs["combined"],
               view_hits=obs["view_hits"], view_builds=obs["view_builds"],
               probe_queries=obs["probe_queries"],
               probe_hits=obs["probe_hits"],
               worker_drains=w["drains"], worker_rounds=w["rounds"])
    return row


def run(steps: int, seed: int = DEFAULT_SEED, backend: str | None = None,
        engine: str | None = None) -> list[dict]:
    from repro.serve import synth_trace

    if backend not in (None, "deltatree", "forest"):
        return [{"bench": "serve_trace", "backend": backend,
                 "skipped": "pager needs a map-mode (payload) backend"}]
    cfg, params = _model()
    calm = synth_trace(steps, seed=seed, prompt_lens=(3, 17),
                       max_new=(4, 12), vocab=cfg.vocab_size)
    churn = synth_trace(steps, seed=seed + 1, prompt_lens=(3, 17),
                        max_new=(4, 12), cancel_p=0.25,
                        probes_per_step=16, vocab=cfg.vocab_size)
    rows = []
    if backend in (None, "deltatree"):
        rows.append(_run_lockstep(cfg, params,
                                  _pager_cfg("deltatree", engine), calm,
                                  max_batch=6, seed=seed))
        rows.append(_run_sched("sched", cfg, params,
                               _pager_cfg("deltatree", engine), calm,
                               max_live=6, seed=seed))
        rows.append(_run_sched("sched_churn", cfg, params,
                               _pager_cfg("deltatree", engine), churn,
                               max_live=6, seed=seed))
    if backend in (None, "forest"):
        rows.append(_run_sched("sched_churn_forest", cfg, params,
                               _pager_cfg("forest", engine), churn,
                               max_live=6, seed=seed))
    return rows


def main(quick=True, seed=DEFAULT_SEED, backend=None, engine=None,
         smoke=False):
    steps = 5 if smoke else (14 if quick else 40)
    return [emit(r) for r in run(steps, seed=seed, backend=backend,
                                 engine=engine)]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    add_common_args(ap)
    args = ap.parse_args()
    main(quick=not args.full, seed=args.seed, backend=args.backend,
         engine=args.engine, smoke=args.smoke)
