"""lax.scan with an unroll escape hatch.

cost_analysis() counts a while-loop body ONCE regardless of trip count, so
shallow depth probes set cfg.unroll=True to get exact FLOP/byte counts;
production configs keep lax.scan for compact HLO."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def scan_or_unroll(body, init, xs, unroll: bool):
    if not unroll:
        return jax.lax.scan(body, init, xs)
    reps = jax.tree.leaves(xs)[0].shape[0]
    carry, ys = init, []
    for r in range(reps):
        xr = jax.tree.map(lambda a: a[r], xs)
        carry, y = body(carry, xr)
        ys.append(y)
    if ys and any(l is not None for l in jax.tree.leaves(ys[0])):
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys
