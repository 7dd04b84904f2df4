"""Batched cross-shard routing for the DeltaForest (DESIGN.md §4, §8).

A mixed query/update batch arrives in *linearization order*.  The dense
dispatch (updates; reads under engines without a fused entry point)

  1. assigns every op its owner shard with one ``searchsorted`` against the
     (S-1,) boundary array,
  2. bucket-sorts the batch by shard with a single stable argsort (stability
     preserves batch order *within* each shard, which is exactly what the
     per-shard linearization needs — ops on the same key always land in the
     same shard, so batch-order semantics are preserved end to end),
  3. computes segment offsets of the sorted shard ids (a second
     searchsorted) and scatters each op into a dense (S, K) per-shard lane,
     padded with no-op rows (OP_SEARCH / the born-resolved ROUTE_LEFT
     sentinel key),
  4. dispatches the per-shard kernels under ``shard_map`` over the
     "shards" mesh (leftover shards-per-device vmapped inside the body),
  5. inverse-permutes the (S, K) per-shard results back to batch order.

``fused_dispatch`` (DESIGN.md §8) is the read path's alternative when the
engine provides a fused cross-shard frontier: no per-*shard* lanes at all
— on one device the batch passes through in batch order; on D devices it
bucket-sorts by owner device ((D, K) lanes) and each device fuses its
co-resident shards into one base-offset arena walk.

Everything on the hot path is shape-static and jittable: no Python loop
touches an op, and the only per-shard state a device reads is its own arena
slice — the forest's realization of the paper's "maintenance stays local".
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import layout
from repro.obs import trace as TR
from repro.parallel import make_forest_mesh


class Routing(NamedTuple):
    """Static-shape routing plan for one batch (all (K,) int32)."""

    sid: jax.Array         # owner bucket per op, batch order
    order: jax.Array       # stable permutation sorting ops by bucket
    sid_sorted: jax.Array  # sid[order]
    local: jax.Array       # lane within the owner bucket's dense row


def shard_ids(splits: jax.Array, keys: jax.Array) -> jax.Array:
    """Owner shard per key: one searchsorted against the boundaries.

    The *boundaries* widen to the key dtype, never the reverse — an int64
    probe beyond the int32 range (x64 callers) must not wrap before it is
    routed, or it lands on a bogus shard.  Splits always fit int32, so
    widening them is lossless."""
    return jnp.searchsorted(
        splits.astype(keys.dtype), keys, side="right"
    ).astype(jnp.int32)


def route(splits: jax.Array, keys: jax.Array) -> Routing:
    """Build the bucket-sort plan: searchsorted + segment offsets."""
    return route_by(shard_ids(splits, keys), splits.shape[0] + 1)


def route_by(ids: jax.Array, num_buckets: int) -> Routing:
    """Bucket-sort plan over precomputed bucket ids (stable argsort ⇒
    batch order is preserved *within* each bucket — the per-bucket
    linearization).  ``route`` is this over owner shards; the fused
    dispatch uses it over owner *devices*."""
    k = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    ids_sorted = ids[order]
    # offsets[s] = first sorted index owned by bucket s (segment offsets)
    offsets = jnp.searchsorted(
        ids_sorted, jnp.arange(num_buckets, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    local = jnp.arange(k, dtype=jnp.int32) - offsets[ids_sorted]
    return Routing(ids, order, ids_sorted, local)


def lane_counts(ids: jax.Array, num_buckets: int) -> jax.Array:
    """Per-bucket lane counts of one routed batch ((num_buckets,) int32)
    — the router leg of ``ReadStats`` and the forest's per-shard load
    counters share this one scatter-add."""
    return jnp.zeros((num_buckets,), jnp.int32).at[ids].add(1)


def scatter_dense(r: Routing, num_shards: int, x: jax.Array, fill) -> jax.Array:
    """Batch-order (K,) -> dense per-shard (S, K), padded with ``fill``."""
    k = x.shape[0]
    dense = jnp.full((num_shards, k), fill, x.dtype)
    return dense.at[r.sid_sorted, r.local].set(x[r.order])


def gather_batch(r: Routing, dense: jax.Array) -> jax.Array:
    """Inverse permute dense per-shard (S, K, ...) results to batch order."""
    k = r.order.shape[0]
    picked = dense[r.sid_sorted, r.local]
    out = jnp.zeros((k,) + dense.shape[2:], dense.dtype)
    return out.at[r.order].set(picked)


@functools.lru_cache(maxsize=None)
def _forest_mesh_cached(num_shards: int, ndev: int):
    del ndev  # cache key only — make_forest_mesh reads the live device set
    return make_forest_mesh(num_shards)


def forest_mesh(num_shards: int):
    """The "shards" mesh for ``num_shards``, cached per (num_shards,
    device_count) — a change in visible devices within one process
    (fake-device tests, late backend selection) gets a fresh mesh instead
    of a stale cached one."""
    return _forest_mesh_cached(num_shards, jax.device_count())


def dispatch(num_shards: int, fn, trees, *dense_args, sequential=False):
    """Run ``fn(tree, *args)`` once per shard under shard_map.

    ``trees`` is the stacked (S, ...) arena pytree; every ``dense_args``
    leaf carries a leading S axis.  The mesh splits the S axis across
    devices; shards co-resident on one device run under vmap (reads) or
    ``lax.map`` (``sequential=True`` — the update path: vmapping
    `update_batch_impl` would lower its lax.cond/switch branches to
    execute-all-branches selects, a ~100x slowdown, whereas lax.map keeps
    them real XLA conditionals; cross-*device* shards still run in
    parallel under the shard_map).  Outputs may be any pytree whose
    leaves carry the leading S axis.
    """
    mesh = forest_mesh(num_shards)

    def body(trees_loc, *args_loc):
        if sequential:
            return jax.lax.map(lambda a: fn(*a), (trees_loc,) + args_loc)
        return jax.vmap(fn)(trees_loc, *args_loc)

    nargs = 1 + len(dense_args)
    with TR.annotate("router.dispatch"):
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("shards"),) * nargs,
            out_specs=P("shards"),
            check_vma=False,
        )(trees, *dense_args)


def build_fused_view(num_shards: int, make_view, trees):
    """Precompute the fused base-offset view ``fused_dispatch`` would
    otherwise rebuild per call (the engine's ``ForestBatch.make_view``
    hook, run under the same mesh layout the dispatch uses).

    On a 1-device mesh this is ``make_view(trees)`` verbatim; on D
    devices each device fuses its co-resident shards and the per-device
    views stack to a leading (D,) axis (mirroring the dispatch body's
    ``x[None]`` wrap), so ``fused_dispatch(view=...)`` can split the same
    axis back out through shard_map.  The result is pure data derived
    from ``trees`` — the forest layer caches it keyed on the update
    epoch and hands it back to read calls until the arena changes."""
    mesh = forest_mesh(num_shards)
    d = mesh.devices.size
    if d == 1:
        with TR.annotate("router.fuse_view"):
            return make_view(trees)

    def body(trees_loc):
        return jax.tree.map(lambda x: x[None], make_view(trees_loc))

    with TR.annotate("router.fuse_view"):
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("shards"),),
            out_specs=P("shards"),
            check_vma=False,
        )(trees)


def fused_dispatch(num_shards: int, fn, trees, sid, keys, view=None):
    """Fused-frontier dispatch: one ``fn`` call per *device*, each over
    the base-offset fusion of its co-resident shards (DESIGN.md §8).

    ``fn(trees_loc, lid[K'], keys[K'], view_loc)`` sees the device-local
    stacked (S_loc, ...) arenas, the per-lane local shard index, its
    lanes' keys, and the device-local slice of ``view`` (None when no
    precomputed view was passed — the hook builds it inline), and returns
    ``(lane_outs, shard_outs)`` — pytrees whose leaves carry a leading
    lane axis (K',) resp. per-local-shard axis (S_loc,); ``shard_outs``
    may be None.  ``view`` must come from ``build_fused_view`` over the
    *same* trees (1-device: passed through as-is; D devices: leading (D,)
    axis split across the mesh alongside the arenas).

    On a 1-device mesh the whole batch passes through in batch order —
    no permutation, no dense scatter (the fused path's claim that routing
    needs only ``sid``).  On D devices the batch bucket-sorts by owner
    *device* (stable, so per-device batch order is preserved) into (D, K)
    dense lanes — D×K lanes instead of the vmap dispatch's S×K — padded
    with the born-resolved ROUTE_LEFT sentinel key (pad lanes terminate
    in round 0 and are never gathered).

    Returns (routing | None, lane_outs, shard_outs): lane outputs stay in
    the device-dense layout — map them through ``gather_fused`` with the
    returned routing; shard outputs concatenate to a leading (S,) axis in
    shard order.
    """
    mesh = forest_mesh(num_shards)
    d = mesh.devices.size
    if d == 1:
        with TR.annotate("router.fused"):
            lane, per_shard = fn(trees, sid, keys, view)
        return None, lane, per_shard
    sloc = num_shards // d
    r = route_by(sid // jnp.int32(sloc), d)
    dlid = scatter_dense(r, d, sid % jnp.int32(sloc), jnp.int32(0))
    # ``keys`` may be a pytree of per-lane columns (the scan path sends
    # (starts, his) pairs); every leaf scatters identically, and the pad
    # fill is the born-resolved sentinel either way
    dkeys = jax.tree.map(
        lambda x: scatter_dense(r, d, x, jnp.int32(layout.ROUTE_LEFT)), keys)

    def body(trees_loc, lid_loc, keys_loc, *view_arg):
        # each device's view slice arrives with a leading length-1 device
        # axis (the build's x[None] wrap) — peel it before the hook
        view_loc = (jax.tree.map(lambda x: x[0], view_arg[0])
                    if view_arg else None)
        lane, per_shard = fn(trees_loc, lid_loc[0],
                             jax.tree.map(lambda x: x[0], keys_loc), view_loc)
        # lane leaves regain a leading device axis so shard_map stacks
        # them to (D, K); per-shard leaves concatenate to (S,) directly
        return jax.tree.map(lambda x: x[None], lane), per_shard

    extra = () if view is None else (view,)
    with TR.annotate("router.fused"):
        lane, per_shard = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("shards"),) * (3 + len(extra)),
            out_specs=P("shards"),
            check_vma=False,
        )(trees, dlid, dkeys, *extra)
    return r, lane, per_shard


def gather_fused(r: Routing | None, lane_outs):
    """Batch-order view of ``fused_dispatch`` lane outputs: the identity
    when no permutation happened (1-device mesh), else the device-dense
    inverse permutation."""
    if r is None:
        return lane_outs
    return jax.tree.map(lambda x: gather_batch(r, x), lane_outs)
