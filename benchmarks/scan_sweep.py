"""Range-scan throughput sweep: ordered bulk reads across backends.

The tentpole read-path claim (DESIGN.md §15): a locality-aware tree
should serve ordered range scans at array-like throughput while staying
updatable.  This sweep times the batched ``scan`` hook — ``K`` lanes per
dispatch, each emitting up to ``max_items`` (key, payload) pairs in key
order — for ``deltatree`` vs the ``sorted_array`` baseline (and
``forest`` via ``--backend``), across two range densities:

- ``sparse``: the window holds ~max_items/4 live keys — the scan is
  dominated by the successor walks between far-apart keys,
- ``dense``: the window holds ~4*max_items live keys — the emit cursor
  saturates and the row is truncated (``more``), the best case for the
  frontier's locality.

Each JSON row records ``density`` / ``max_items`` / ``scans_per_s`` /
``items_per_s`` plus the hop telemetry, and lockstep rows pin
``walk_launches = 1.0``: the lockstep scan driver is a single
``delta_scan`` launch per dispatch, the scan-path analogue of the
fused walk's single-launch guarantee.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    DEFAULT_SEED, add_common_args, backend_kwargs, dispatch_of, emit,
    engine_supported, resolved_q_tile,
)
from repro.api import make_index

KEY_MAX = 2_000_000
DEFAULT_BACKENDS = ("deltatree", "sorted_array")
# expected live keys inside one scanned window, as a multiple of
# max_items: sparse underfills the emit buffer, dense saturates it
DENSITY_FILL = {"sparse": 0.25, "dense": 4.0}


def _scan_row(backend: str, ix, vals: np.ndarray, density: str,
              max_items: int, batch: int, total_scans: int,
              seed: int) -> dict:
    """Time ``total_scans`` scans in ``batch``-lane dispatches against a
    pre-built index, all windows sized for ``density``."""
    rng = np.random.default_rng(seed + max_items)
    span_per_key = KEY_MAX / vals.size
    width = max(1, int(span_per_key * DENSITY_FILL[density] * max_items))

    spec = ix.spec
    scan = spec.backend.scan

    def one_step(count=False):
        nonlocal n_scans, emitted, truncated, hops_sum
        lo = rng.integers(1, max(2, KEY_MAX - width), size=batch)
        starts = jnp.asarray(lo - 1, jnp.int32)          # exclusive start
        his = jnp.asarray(np.minimum(lo + width, KEY_MAX), jnp.int32)
        keys, pays, n, hops, more = scan(spec.cfg, ix.state, starts, his,
                                         max_items)
        if count:  # host-side tallies only; device sync happens once below
            n_scans += batch
            se, st, sh = jnp.sum(n), jnp.sum(more), jnp.sum(hops)
            emitted = se if emitted is None else emitted + se
            truncated = st if truncated is None else truncated + st
            hops_sum = sh if hops_sum is None else hops_sum + sh
        return keys

    n_scans = 0
    emitted = truncated = hops_sum = None
    tc = time.perf_counter()
    for _ in range(2):                                   # warm the jit cache
        keys = one_step()
    jax.block_until_ready(keys)
    compile_seconds = time.perf_counter() - tc

    steps = max(total_scans // batch, 1)
    t0 = time.perf_counter()
    for _ in range(steps):
        keys = one_step(count=True)
    jax.block_until_ready(keys)
    dt = time.perf_counter() - t0
    emitted = int(emitted)
    row = {"bench": "scan_sweep", "backend": backend, "engine": ix.engine,
           "dispatch": dispatch_of(ix), "maintenance": ix.maintenance,
           "seed": seed, "density": density, "width": width,
           "max_items": max_items, "batch": batch, "n_scans": n_scans,
           "scans_per_s": round(n_scans / dt, 1),
           "items_per_s": round(emitted / dt, 1),
           "emitted_mean": round(emitted / n_scans, 2),
           "truncated_frac": round(int(truncated) / n_scans, 3),
           "hops_mean": round(int(hops_sum) / n_scans, 2),
           "seconds": round(dt, 4),
           "compile_seconds": round(compile_seconds, 4)}
    if ix.engine == "lockstep":
        # single-launch guarantee: the lockstep scan frontier is ONE
        # delta_scan dispatch per batch (engine._lockstep_scan), same
        # contract the compiled smoke asserts on
        row["walk_launches"] = 1.0
        row["q_tile"] = resolved_q_tile(ix)
    return row


def run(initial_size: int, total_scans: int, batch: int, k_list,
        seed: int = DEFAULT_SEED, backend: str | None = None,
        engine: str | None = None):
    rng = np.random.default_rng(seed)
    vals = np.unique(rng.integers(1, KEY_MAX, size=initial_size)
                     .astype(np.int32))
    rows = []
    names = (backend,) if backend else DEFAULT_BACKENDS
    for name in names:
        kw = backend_kwargs(name, vals.size, key_max=KEY_MAX)
        engines: tuple = (None,)
        if name in ("deltatree", "forest"):
            engines = (engine,) if engine else ("scalar", "lockstep")
        for eng in engines:
            if not engine_supported(name, eng):
                rows.append(emit({"bench": "scan_sweep", "backend": name,
                                  "skipped": f"no {eng} engine"}))
                continue
            ix = make_index(name, initial=vals, engine=eng, **kw)
            if not ix.capability.range_scan:
                rows.append(emit({"bench": "scan_sweep", "backend": name,
                                  "skipped": "no range_scan capability"}))
                continue
            for density in ("sparse", "dense"):
                for k in k_list:
                    rows.append(emit(_scan_row(
                        name, ix, vals, density, k, batch, total_scans,
                        seed)))
    return rows


def main(quick=True, seed=DEFAULT_SEED, backend=None, engine=None,
         smoke=False):
    if smoke:
        return run(initial_size=2_000, total_scans=128, batch=64,
                   k_list=(8,), seed=seed, backend=backend, engine=engine)
    if quick:
        return run(initial_size=50_000, total_scans=2_048, batch=256,
                   k_list=(16, 64), seed=seed, backend=backend,
                   engine=engine)
    return run(initial_size=200_000, total_scans=8_192, batch=512,
               k_list=(16, 128), seed=seed, backend=backend, engine=engine)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    add_common_args(ap)
    args = ap.parse_args()
    main(quick=not args.full, seed=args.seed, backend=args.backend,
         engine=args.engine, smoke=args.smoke)
