# One function per paper table/figure. Every benchmark runs its structures
# through the `repro.api.make_index` factory and prints one JSON row per
# result line (each row records `seed` + `backend` for reproducibility).
# Default is the quick profile (CPU-friendly); --full is the paper-scale
# sweep; --smoke runs everything at tiny sizes (CI bitrot guard);
# --backend narrows every benchmark to one registered backend; --seed
# reseeds every RNG.  All rows from one invocation are additionally
# consolidated into BENCH_<timestamp>.json at the repo root — every row
# stamped with its suite, backend, engine and maintenance policy — so the
# perf trajectory stays recorded across PRs.
#
# Every suite runs in this one process, under JAX_ENABLE_X64 (the serve
# suites' map-mode pager needs it): a chip belongs to one process, so no
# suite may run in a child once this process has touched JAX.
from __future__ import annotations

import argparse
import json
import os
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _consolidate(rows: list, args: dict) -> str:
    """Write BENCH_<timestamp>.json at the repo root: run metadata plus
    every row stamped with suite/backend/engine/maintenance.  Smoke runs
    get the gitignored ``BENCH_SMOKE_`` prefix — their numbers are
    meaningless and must not pollute the committed perf trajectory.

    The top-level ``meta`` block is this process's execution stamp
    (`benchmarks.common.exec_meta`); per-row stamps still win."""
    from benchmarks.common import exec_meta

    stamped = []
    for row in rows:
        r = dict(row)
        r.setdefault("suite", r.get("bench", "unknown"))
        r.setdefault("backend", None)
        r.setdefault("engine", None)
        r.setdefault("maintenance", None if r.get("skipped") else "eager")
        stamped.append(r)
    ts = time.strftime("%Y%m%d_%H%M%S")
    prefix = "BENCH_SMOKE_" if args.get("smoke") else "BENCH_"
    path = os.path.join(REPO_ROOT, f"{prefix}{ts}.json")
    with open(path, "w") as f:
        json.dump({"timestamp": ts, "args": args, "meta": exec_meta(),
                   "rows": stamped}, f, indent=1)
    print(f"# consolidated {len(stamped)} rows -> {path}", flush=True)
    return path


def main() -> None:
    from benchmarks.common import add_common_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--compiled", action="store_true",
                    help="force compiled kernels (REPRO_PALLAS_INTERPRET=0 "
                         "for this process): "
                         "Pallas lowered on TPU, the XLA-compiled fused "
                         "mirrors elsewhere — no interpreter tax. Rows "
                         "stamp meta interpret=false; run_compiled.sh is "
                         "the full launch harness around this flag")
    ap.add_argument("--only", default=None,
                    help="fig11|fig12|table1|ub_sweep|serve|serve_trace"
                         "|forest|engines|maint")
    ap.add_argument("--maintenance", default=None,
                    help="maint suite: run only this policy")
    ap.add_argument("--trace-dir", default=None,
                    help="capture an xprof trace of the whole run into "
                         "this logdir (repro.obs.trace.capture: the "
                         "xplane, with the program's host spans beside "
                         "the device ops)")
    add_common_args(ap)
    args, _ = ap.parse_known_args()
    if args.compiled:
        # before any kernel-mode resolution or exec_meta stamp
        os.environ["REPRO_PALLAS_INTERPRET"] = "0"
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_enable_x64", True)
    enable_compile_cache()
    quick = not args.full
    seed, backend, engine = args.seed, args.backend, args.engine
    smoke = args.smoke

    from benchmarks import engine_compare, fig11_small_tree, fig12_big_tree
    from benchmarks import forest_scale, maint_sweep, scan_sweep
    from benchmarks import serve_paged, serve_trace, table1_transfers
    from benchmarks import ub_sweep

    todo = args.only.split(",") if args.only else [
        "table1", "ub_sweep", "fig11", "fig12", "serve", "serve_trace",
        "forest", "engines", "maint", "scan"]
    rows: list = []

    def add(suite, got):
        if not got:
            return
        if isinstance(got, dict):
            got = [got]
        for r in got:
            r = dict(r)
            r["suite"] = suite
            rows.append(r)

    if args.trace_dir:
        from repro.obs import trace as OT

        cm = OT.capture(args.trace_dir)
    else:
        import contextlib

        cm = contextlib.nullcontext()

    common = dict(quick=quick, seed=seed, backend=backend, engine=engine,
                  smoke=smoke)
    with cm:
        if "table1" in todo:
            add("table1", table1_transfers.main(**common))
        if "ub_sweep" in todo:
            add("ub_sweep", ub_sweep.main(**common))
        if "fig11" in todo:
            add("fig11", fig11_small_tree.main(**common))
        if "fig12" in todo:
            add("fig12", fig12_big_tree.main(**common))
        if "serve" in todo:
            add("serve", serve_paged.main(**common))
        if "serve_trace" in todo:
            add("serve_trace", serve_trace.main(**common))
        if "forest" in todo:
            add("forest", forest_scale.main(quick=quick, seed=seed,
                                            engine=engine, smoke=smoke))
        if "engines" in todo:
            add("engines", engine_compare.main(quick=quick, seed=seed,
                                               backend=backend, smoke=smoke))
        if "maint" in todo:
            add("maint", maint_sweep.main(quick=quick, seed=seed,
                                          backend=backend, engine=engine,
                                          maintenance=args.maintenance,
                                          smoke=smoke))
        if "scan" in todo:
            add("scan", scan_sweep.main(quick=quick, seed=seed,
                                        backend=backend, engine=engine,
                                        smoke=smoke))
    _consolidate(rows, dict(full=args.full, smoke=smoke, seed=seed,
                            backend=backend, engine=engine,
                            only=args.only, compiled=args.compiled))


if __name__ == '__main__':
    main()
