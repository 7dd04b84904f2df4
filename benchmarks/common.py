"""Shared benchmark utilities: timed op-mix runner over the Index API.

Maps the paper's experiment protocol (§5) to the batched-SPMD world:
- concurrency = batch width of one SPMD step (the paper's thread count),
- update rate u%: each batch mixes u% insert/delete (50/50) with (100-u)%
  searches; searches run vectorized on the snapshot (wait-free), updates
  apply in batch order,
- performance = ops/second over `total_ops` with the jit warm.

Every structure runs through the same ``make_index`` factory — a benchmark
names a backend string plus a SearchEngine name, never a concrete
implementation.  All RNGs derive from one ``--seed`` flag
(``add_common_args``), and every emitted JSON row records ``seed`` +
``backend`` + ``engine`` so perf rows are reproducible.  ``--engine``
narrows the read path (``scalar`` reference walk vs ``lockstep`` Pallas
vEB walk); backends that don't support the requested engine are skipped
with an explicit row rather than silently falling back.
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import OpBatch, make_index, supported_engines

DEFAULT_SEED = 0

# Backends whose update kernel rebuilds per op (O(cap) sequential work):
# compact each step's update rows into one fixed UPDATE_CHUNK-wide
# sub-batch (padded with OP_SEARCH no-ops, so shapes stay static).
CHUNKED_BACKENDS = {"sorted_array", "pointer_bst", "static_veb"}
UPDATE_CHUNK = 64

# Backends whose configs carry the static ``collect_stats`` knob:
# run_index turns it on by default so every perf row carries its hop /
# round / router telemetry (repro.obs) alongside the timing.
STATS_BACKENDS = {"deltatree", "forest"}


@functools.lru_cache(maxsize=1)
def exec_meta() -> dict:
    """Execution-mode stamp merged into every emitted row: numbers from a
    CPU-interpret run and a TPU-compiled run must never be comparable
    silently.  Cached per process."""
    from repro.kernels.ops import default_interpret

    return {
        "device_kind": jax.devices()[0].device_kind,
        "interpret": bool(default_interpret()),
        "x64": bool(jax.config.jax_enable_x64),
        "jax_version": jax.__version__,
    }


def add_common_args(ap) -> None:
    """--seed / --backend / --engine / --smoke flags shared by every
    benchmark CLI."""
    ap.add_argument("--seed", type=int, default=DEFAULT_SEED,
                    help="root seed for every RNG (recorded in JSON rows)")
    ap.add_argument("--backend", default=None,
                    help="run only this registered Index backend "
                         "(default: the benchmark's historical set)")
    ap.add_argument("--engine", default=None,
                    help="read-path SearchEngine (scalar|lockstep; default "
                         "scalar). Recorded in every JSON row; backends "
                         "without the engine are skipped explicitly")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: exercise every code path in seconds "
                         "(CI bitrot guard), numbers meaningless")


def resolved_q_tile(ix) -> int:
    """The lockstep kernel tile this Index would run with (cfg override,
    else the env/autotune/default chain) — recorded in benchmark JSON
    rows."""
    from repro.api.index import cfg_attr
    from repro.kernels.ops import default_q_tile

    qt = cfg_attr(ix.cfg, "q_tile")
    if qt:
        return int(qt)
    return default_q_tile(cfg_attr(ix.cfg, "height"),
                          cfg_attr(ix.cfg, "payload_bits") or 0)


def engine_supported(backend: str, engine: str | None) -> bool:
    """True when ``backend`` can run its reads under ``engine``
    (``"auto"`` is checked against what it would resolve to)."""
    if engine is None:
        return True
    if engine == "auto":
        from repro.core.engine import resolve_engine

        engine = resolve_engine(engine, backend)
    return engine in supported_engines(backend)


def dispatch_of(ix) -> str | None:
    """How this Index's sharded reads dispatch: "fused" (one cross-shard
    frontier per device), "vmap" (dense per-shard lanes), or None for
    single-arena backends — recorded in benchmark JSON rows."""
    if not ix.capability.sharded:
        return None
    return "fused" if ix.capability.fused_forest else "vmap"


def emit(row: dict) -> dict:
    """One machine-parsable JSON row per result line, stamped with the
    process's execution mode (`exec_meta`; row keys win on collision)."""
    row = {**exec_meta(), **row}
    print(json.dumps(row), flush=True)
    return row


def mixed_kinds(rng, k: int, update_pct: float) -> np.ndarray:
    u = rng.random(k) < (update_pct / 100.0)
    ins = rng.random(k) < 0.5
    kinds = np.where(u, np.where(ins, 1, 2), 0).astype(np.int32)
    return kinds


def backend_kwargs(backend: str, n_keys: int, *, key_max: int,
                   total_ops: int = 0, height: int = 7,
                   num_shards: int = 4) -> dict:
    """make_index config for a benchmark-scale instance of ``backend``.

    Sizing accounts for workload growth: up to total_ops/2 inserts can land
    on fresh keys, so arenas/capacities are provisioned for n + total/2.
    """
    n_eff = n_keys + total_ops // 2
    if backend == "deltatree":
        return dict(height=height, buf_cap=32, max_rounds=256,
                    max_dnodes=max(256, int(6 * n_eff / 2 ** (height - 1))))
    if backend == "forest":
        per_shard = max(64, int(8 * n_eff / num_shards / 2 ** (height - 1)))
        return dict(num_shards=num_shards, key_max=key_max, height=height,
                    buf_cap=32, max_rounds=256, max_dnodes=per_shard)
    if backend in ("sorted_array", "pointer_bst"):
        return dict(cap=2 * n_keys + total_ops + 16)
    return {}


def _chunk_updates(kinds: np.ndarray, keys: np.ndarray,
                   idx: np.ndarray) -> OpBatch:
    """Compact the update rows at ``idx`` into a fixed-width OpBatch (padded
    with OP_SEARCH rows, which insert_delete treats as no-ops)."""
    ck = np.zeros(UPDATE_CHUNK, np.int32)
    cv = np.zeros(UPDATE_CHUNK, np.int32)
    ck[: idx.size] = kinds[idx]
    cv[: idx.size] = keys[idx]
    return OpBatch.mixed(ck, cv)


def run_index(backend: str, initial: np.ndarray, key_hi: int,
              update_pct: float, batch: int, total_ops: int,
              seed: int = DEFAULT_SEED, engine: str | None = None,
              maintenance: str | None = None, flush_every: int = 0,
              **make_kw) -> dict:
    """Timed mixed workload against one backend through the Index handle.

    ``engine`` selects the read-path SearchEngine, ``maintenance`` the
    scheduler policy (both validated by ``make_index``; None = backend
    defaults).  ``flush_every`` > 0 drains deferred/budgeted maintenance
    every N steps *inside the timed loop* (the serving amortization
    pattern), so non-eager rows pay their structural work honestly.

    Warmup (compile) runs fully off the steady-state clock — blocked to
    completion and reported separately as ``compile_seconds`` — so
    ``ops_per_s`` is a pure steady-state number.  Stats-capable backends
    (`STATS_BACKENDS`) collect ``repro.obs`` read telemetry by default
    (merged device-side across the counted loop; one host sync at the
    end), giving every perf row its hop / round / router columns."""
    from repro.obs import trace as OT

    if backend in STATS_BACKENDS:
        make_kw.setdefault("collect_stats", True)
    ix = make_index(backend, initial=initial, engine=engine,
                    maintenance=maintenance, **make_kw)
    collect = bool(getattr(ix, "collect_stats", False))
    rng = np.random.default_rng(seed)
    chunked = backend in CHUNKED_BACKENDS
    any_update = update_pct > 0
    # walk_launches: kernel launches per search dispatch under the
    # lockstep engine — 1 for the fused single-launch driver, the step's
    # frontier round count for the per-round driver (one veb_walk_rows
    # launch per round; the round count is device data, accumulated
    # alongside the stats merge so the loop still never syncs the host).
    from repro.api.index import cfg_attr

    lockstep = ix.engine == "lockstep"
    fused_walk = lockstep and bool(cfg_attr(ix.cfg, "walk_fused", True))

    def one_step(ix, count=False):
        nonlocal n_search, n_update, sacc, racc, wl_acc
        kinds = mixed_kinds(rng, batch, update_pct)
        keys = rng.integers(1, key_hi, size=batch).astype(np.int32)
        # fixed shapes: searches on the whole batch (wait-free snapshot);
        # updates ride a whole fixed-shape batch too, with OP_SEARCH rows
        # as no-ops — avoids per-step recompiles from dynamic sub-batches
        res = ix.search(jnp.asarray(keys))
        found = res[0]
        if collect and count:
            # device-side accumulation (merge): no host sync mid-loop
            rs = res[-1]
            sacc = rs.search if sacc is None else sacc.merge(rs.search)
            if rs.router is not None:
                racc = rs.router if racc is None else racc.merge(rs.router)
            if lockstep:
                step_launches = (jnp.int32(1) if fused_walk
                                 else rs.search.rounds)
                wl_acc = (step_launches if wl_acc is None
                          else wl_acc + step_launches)
        n_upd_step = 0
        if any_update:
            uidx = np.flatnonzero(kinds != 0)
            if chunked:
                uidx = uidx[:UPDATE_CHUNK]
                ub = _chunk_updates(kinds, keys, uidx)
            else:
                ub = OpBatch.mixed(kinds, keys)
            ix, _ = ix.insert_delete(ub)
            n_upd_step = int(uidx.size)
        if count:  # host-side only — never syncs the device mid-loop
            n_search += int((kinds == 0).sum())
            n_update += n_upd_step
        return ix, found

    n_search = n_update = 0
    sacc = racc = wl_acc = None
    # warmup compile — two iterations: a sharded backend's first update
    # output carries mesh shardings the host-built input didn't, so the
    # second call retraces once; after that the jit cache is steady.
    # Blocked and timed separately (``compile_seconds``) so no async
    # warmup work leaks into the steady-state clock.
    tc = time.perf_counter()
    # host spans: the warmup and steady-state loops, as rows of the
    # --trace-dir xplane
    with OT.span(f"bench.{backend}.compile"):
        for _ in range(2):
            ix, found = one_step(ix)
        if flush_every:  # warm the flush compile too, off the clock
            ix, _ = ix.flush()
        jax.block_until_ready(
            [x for x in jax.tree.leaves(ix.state)
             if hasattr(x, "block_until_ready")])
        found.block_until_ready()
    compile_seconds = time.perf_counter() - tc
    n_search = n_update = 0

    steps = max(total_ops // batch, 1)
    t0 = time.perf_counter()
    with OT.span(f"bench.{backend}.steady"):
        for step in range(steps):
            ix, found = one_step(ix, count=True)
            if flush_every and (step + 1) % flush_every == 0:
                ix, _ = ix.flush()
        if flush_every:
            # drain the trailing window on the clock — otherwise short
            # sweeps (steps < flush_every) would time non-eager policies
            # with zero structural work and flatter them vs eager
            ix, _ = ix.flush()
        jax.block_until_ready(
            [x for x in jax.tree.leaves(ix.state)
             if hasattr(x, "block_until_ready")])
        found.block_until_ready()
    dt = time.perf_counter() - t0
    row = {"backend": backend, "engine": ix.engine,
           "dispatch": dispatch_of(ix),
           "walk": (("fused" if fused_walk else "per-round")
                    if lockstep else None),
           "maintenance": ix.maintenance, "q_tile": resolved_q_tile(ix),
           "flush_every": flush_every,
           "seed": seed, "update_pct": update_pct, "batch": batch,
           "ops_per_s": round((n_search + n_update) / dt, 1),
           "seconds": round(dt, 4),
           "compile_seconds": round(compile_seconds, 4),
           "n_search": n_search, "n_update": n_update}
    if sacc is not None:  # the one host sync, after the clock stopped
        sd = sacc.asdict()
        row.update(hops_mean=sd["hops_mean"], hops_max=sd["hops_max"],
                   rounds=sd["rounds"], buffer_hits=sd["buffer_hits"],
                   hops_hist=sd["hops_hist"])
    if wl_acc is not None:
        row["walk_launches"] = round(float(wl_acc) / steps, 2)
    if racc is not None:
        rd = racc.asdict()
        row.update(shard_lanes=rd["lanes"], shard_skew=rd["skew"],
                   clamped=rd["clamped"])
    return row
