"""DeltaForest — S independent ΔTree arenas partitioned by key range.

The forest is the scale-out layer over `repro.core` (DESIGN.md §4): each
shard is a full ΔTree arena owning a contiguous key range, stacked into one
pytree with a leading (S,) axis and driven through ``jax.shard_map`` over
the "shards" mesh (`repro.launch.mesh.make_forest_mesh`).  The shards are
laid out in host memory and placed from there, each device receiving
only its own shards' rows.  The public API
is a drop-in superset of `repro.core`:

    ForestConfig, Forest, empty, bulk_build,
    search_batch, lookup_batch, update_batch, successor_jit,
    live_keys, live_items

Semantics are *identical* to a single tree: the router's stable bucket
sort preserves batch order within each shard, and ops on one key always
route to the same shard, so per-shard batch-order application is a valid
linearization of the whole batch.  Searches stay wait-free (pre-step
snapshot per shard).  Maintenance (Rebalance / Expand / Merge) runs
entirely shard-local — the paper's locality argument is what makes the
partition free of cross-shard traffic outside the router's permutation.

Reads take one of two dispatches (DESIGN.md §8): the dense per-shard
vmap (always for updates; for reads when the engine has no fused entry
point or ``ForestConfig.fused`` is off) or the *fused* cross-shard
frontier — co-resident shard arenas concatenated into one base-offset
view, every query seeded at its owner shard's root, one ``delta_walk``
kernel launch per frontier round for the whole routed batch.  Both are
bit-identical (found/payload/succ and per-query hops); the fused path is
what makes ``engine="lockstep"`` pay one frontier instead of S.

Cross-shard coordination exists in exactly one read-only place: a
successor query whose owner shard has no key above it falls through to the
first later non-empty shard's minimum.  The per-shard minima are computed
inside the same dispatch (one extra wait-free successor probe per shard)
and combined with a suffix-min outside the shard_map — no second hop.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import (
    DeltaTree,
    TreeConfig,
    layout,
)
from repro.core import deltatree as DT
from repro.core import engine as E
from repro.distributed import router as R
from repro.distributed import splits as SP
from repro.maintenance import MaintenanceStats
from repro.obs import trace as OT

OP_SEARCH, OP_INSERT, OP_DELETE = DT.OP_SEARCH, DT.OP_INSERT, DT.OP_DELETE

_NO_SUCC = jnp.int32(2**31 - 1)  # suffix-min identity for absent shard minima


@dataclasses.dataclass(frozen=True)
class ForestConfig:
    """Static forest parameters (hashable; closed over by jitted fns).

    num_shards: S — number of independent ΔTree arenas.
    tree:       per-shard TreeConfig (arena size is *per shard*; its
                ``engine`` field picks the SearchEngine every shard's
                reads run under the shard_map dispatch).
    key_min/max: key domain used for fallback equi-width boundaries.
    """

    num_shards: int = 4
    tree: TreeConfig = TreeConfig()
    key_min: int = layout.KEY_MIN
    key_max: int = layout.KEY_MAX
    fused: bool = True      # use the engine's fused cross-shard frontier
    #                         (when it provides one); False pins reads to
    #                         the dense per-shard vmap dispatch — the
    #                         reference path the fused-conformance suite
    #                         and benchmarks compare against


class Forest(NamedTuple):
    """Stacked-arena pytree: every DeltaTree leaf gains a leading (S,) axis;
    ``splits`` is the (S-1,) boundary array the router searchsorts.

    ``reads``/``updates`` are cumulative per-shard (S,) op counters (the
    obs subsystem's skew view — `shard_load`).  Updates auto-count inside
    `update_batch`; reads are pure, so read batches only accumulate when
    the caller opts in via the `record_reads` state transition.

    ``epoch`` is the arena-mutation counter: advanced by every
    `update_batch`/`flush` (the only transitions that touch arena
    contents), preserved by pure-counter transitions (`record_reads`).
    It keys the host-side fused-view cache — a read on an unchanged
    epoch reuses the cached `fuse_arenas` base-offset view instead of
    rebuilding it per call."""

    trees: DeltaTree
    splits: jax.Array
    reads: jax.Array      # (S,) int32 — ops recorded via `record_reads`
    updates: jax.Array    # (S,) int32 — non-search rows seen by `update_batch`
    epoch: jax.Array      # () int32 — arena-mutation counter (view cache key)


def _new_forest(fcfg: ForestConfig, hosts: list[DeltaTree],
                splits) -> Forest:
    """A fresh forest laid out over the "shards" mesh its dispatch runs
    on, from its shards' host arenas (``deltatree.bulk_layout`` /
    ``empty_layout``): stacked with numpy and placed in one
    ``device_put``, so each device receives only its own shards' rows and
    no device ever holds the whole forest; the small leaves are
    replicated.  That is the layout `update_batch` returns, so neither
    the first read nor the second update reshards or recompiles.  The
    placement, waited for, is the ``index.build.place`` span."""
    mesh = R.forest_mesh(fcfg.num_shards)
    shard, rep = NamedSharding(mesh, P("shards")), NamedSharding(mesh, P())
    with OT.span("index.build.place"):
        trees = jax.device_put(
            jax.tree.map(lambda *xs: np.stack(xs), *hosts), shard)
        small = jax.device_put(
            (_as_splits(fcfg, splits), _zero_counters(fcfg),
             _zero_counters(fcfg), jnp.int32(0)), rep)
        f = Forest(trees, *small)
        jax.block_until_ready(f)
    return f


def shard_tree(forest: Forest, s: int) -> DeltaTree:
    """Host-side view of one shard's arena (tests / debug)."""
    return jax.tree.map(lambda x: x[s], forest.trees)


def _as_splits(fcfg: ForestConfig, splits) -> jax.Array:
    if splits is None:
        splits = SP.equiwidth_splits(fcfg.num_shards, fcfg.key_min,
                                     fcfg.key_max)
    splits = np.asarray(splits, np.int64)
    assert splits.shape == (fcfg.num_shards - 1,), splits.shape
    return jnp.asarray(splits.astype(np.int32))


# --------------------------------------------------------------------------
# construction
# --------------------------------------------------------------------------


def _zero_counters(fcfg: ForestConfig) -> jax.Array:
    return jnp.zeros((fcfg.num_shards,), jnp.int32)


def empty(fcfg: ForestConfig, splits=None) -> Forest:
    host = DT.empty_layout(fcfg.tree)
    return _new_forest(fcfg, [host] * fcfg.num_shards, splits)


def bulk_build(fcfg: ForestConfig, values: np.ndarray,
               payloads: np.ndarray | None = None, splits=None) -> Forest:
    """Build a forest from unique keys: each shard's arena is laid out on
    the host (``deltatree.bulk_layout``), then all are placed at once,
    one shard's rows per device (`_new_forest`).

    With no explicit ``splits`` the boundaries are equi-depth over
    ``values`` — every shard starts with |values|/S keys regardless of the
    key distribution (the interpolation-tree property)."""
    values = np.asarray(values, np.int64)
    order = np.argsort(values)
    values = values[order]
    if payloads is not None:
        payloads = np.asarray(payloads, np.int64)[order]
    if splits is None:
        splits = SP.equidepth_splits(values, fcfg.num_shards,
                                     fcfg.key_min, fcfg.key_max)
    splits = np.asarray(splits, np.int64)
    sid = SP.shard_of_np(splits, values)
    trees = []
    for s in range(fcfg.num_shards):
        mask = sid == s
        trees.append(DT.bulk_layout(
            fcfg.tree, values[mask],
            payloads[mask] if payloads is not None else None))
    return _new_forest(fcfg, trees, splits)


# --------------------------------------------------------------------------
# wait-free reads
# --------------------------------------------------------------------------

# dense pad-lane key: the reserved ROUTE_LEFT sentinel — provably matches
# no stored key, makes lockstep pad lanes born-resolved (round 0, no
# successor chase), and can never alias a real query the way the old
# ``fill=0`` did (0 is EMPTY-adjacent but a *legal* key's neighborhood;
# ROUTE_LEFT is outside the key domain entirely)
_PAD_KEY = jnp.int32(layout.ROUTE_LEFT)


def _route_keys(keys: jax.Array) -> jax.Array:
    """Clamp query keys to the int32 key domain *in the caller's dtype*,
    then cast: under x64 an int64 probe beyond the int32 range would
    otherwise wrap before ``searchsorted`` and route to (and walk in) the
    wrong shard.  Below-domain probes clamp to KEY_MIN-1 = 0 (never
    stored; successor = global minimum), above-domain probes to the
    reserved ROUTE_LEFT sentinel (never stored; no successor) — both
    exactly the semantics of the original out-of-range key."""
    keys = jnp.asarray(keys)
    return jnp.clip(keys, 0, layout.ROUTE_LEFT).astype(jnp.int32)


def _fused(fcfg: ForestConfig):
    """The engine's fused forest entry point when enabled, else None."""
    return E.forest_batch(fcfg.tree) if fcfg.fused else None


# ---- fused-view hoisting (ROADMAP fold-in; serve decode loops) -----------
#
# The fused dispatch's base-offset arena view (`ForestBatch.make_view` →
# `kernels.veb_search.fuse_arenas`) is pure data derived from the arenas:
# read-heavy loops over an unchanged forest were rebuilding it on every
# call.  The public read wrappers below look it up in a small host-side
# LRU keyed on ``(fcfg, epoch)`` — epoch advances on every arena mutation,
# and a paranoid identity check on the trees pytree catches two distinct
# forests that happen to share an epoch — then hand it to the jitted read
# core as a regular pytree argument.  Inside someone else's trace the
# epoch is a Tracer (unreadable host-side), so the wrapper passes
# ``view=None`` and the hooks build inline — exactly the old graph.

_VIEW_CACHE_CAP = 4  # distinct (fcfg, forest) streams kept warm at once
_VIEW_CACHE: collections.OrderedDict = collections.OrderedDict()
_VIEW_STATS = {"builds": 0, "hits": 0}


@functools.partial(jax.jit, static_argnums=0)
def _build_view(fcfg: ForestConfig, trees):
    fb = _fused(fcfg)
    return R.build_fused_view(fcfg.num_shards,
                              functools.partial(fb.make_view, fcfg.tree),
                              trees)


def _maybe_cached_view(fcfg: ForestConfig, f: Forest):
    """The cached fused view for ``f`` (building + caching on miss), or
    None when hoisting does not apply: fused dispatch off / engine has no
    ``make_view`` / we are inside a trace (epoch unreadable)."""
    fb = _fused(fcfg)
    if fb is None or fb.make_view is None:
        return None
    if isinstance(f.epoch, jax.core.Tracer):
        return None
    key = (fcfg, int(f.epoch))
    ent = _VIEW_CACHE.get(key)
    if ent is not None and ent[0] is f.trees:
        _VIEW_STATS["hits"] += 1
        _VIEW_CACHE.move_to_end(key)
        return ent[1]
    with OT.span("index.forest.view"):
        view = _build_view(fcfg, f.trees)
    _VIEW_STATS["builds"] += 1
    # one live view per fcfg: a rebuild means the arena moved on (update /
    # different forest), so the old epoch's view is dead weight — arena-
    # sized, worth dropping eagerly rather than waiting out the LRU
    for stale in [k for k in _VIEW_CACHE if k[0] == fcfg]:
        del _VIEW_CACHE[stale]
    _VIEW_CACHE[key] = (f.trees, view)
    while len(_VIEW_CACHE) > _VIEW_CACHE_CAP:
        _VIEW_CACHE.popitem(last=False)
    return view


def fused_view_cache_stats() -> dict:
    """Host-side cache counters (obs / regression tests): cumulative
    builds + hits since process start or the last reset, current size."""
    return {"builds": _VIEW_STATS["builds"], "hits": _VIEW_STATS["hits"],
            "size": len(_VIEW_CACHE)}


def reset_fused_view_cache() -> None:
    _VIEW_CACHE.clear()
    _VIEW_STATS["builds"] = 0
    _VIEW_STATS["hits"] = 0


def search_batch(fcfg: ForestConfig, f: Forest, keys: jax.Array):
    """Routed wait-free search. Returns (found[K], hops[K]) — plus a
    trailing `ReadStats` when ``fcfg.tree.collect_stats`` is on."""
    return _search_core(fcfg, f, keys, _maybe_cached_view(fcfg, f))


def lookup_batch(fcfg: ForestConfig, f: Forest, keys: jax.Array):
    """Routed map-mode lookup. Returns (found[K], payload[K], hops[K]) —
    plus a trailing `ReadStats` when ``fcfg.tree.collect_stats`` is on."""
    return forest_lookup(fcfg, f, keys, _maybe_cached_view(fcfg, f))


@functools.partial(jax.jit, static_argnums=0)
def _search_core(fcfg: ForestConfig, f: Forest, keys: jax.Array, view):
    out = _lookup(fcfg, f, keys, view)
    if E.collecting(fcfg.tree):
        found, _, hops, stats = out
        return found, hops, stats
    found, _, hops = out
    return found, hops


@functools.partial(jax.jit, static_argnums=0)
def forest_lookup(fcfg: ForestConfig, f: Forest, keys: jax.Array, view):
    """The map-mode read program.  Its name is the XLA module's
    (``jit_forest_lookup``) on every device of the mesh, which is how a
    device trace finds the forest's walk."""
    return _lookup(fcfg, f, keys, view)


def _forest_read_stats(fcfg: ForestConfig, f: Forest, raw, keys, sid,
                       found, hops):
    """Forest `ReadStats` from batch-order read columns (obs tentpole).

    Computed on the *gathered* batch-order (found, hops) so both dispatch
    paths (fused frontier / dense vmap) produce bit-identical stats —
    same structural argument as the single-tree dispatch layer.  The
    router leg adds per-shard lane counts plus how many caller keys the
    key-domain clamp (`_route_keys`) rewrote."""
    from repro.obs.stats import ReadStats, RouterStats, SearchStats

    pad = keys == _PAD_KEY
    member = jax.vmap(lambda t: DT.buffered_member(fcfg.tree, t, keys))(
        f.trees)  # (S, K) buffered membership; pick each lane's owner shard
    bhit = found & member[sid, jnp.arange(keys.shape[0])]
    clamped = jnp.sum((raw != keys.astype(raw.dtype)).astype(jnp.int32))
    transfers = None
    if E.collecting_transfers(fcfg.tree):
        from repro.obs import transfers as OTR

        # shard-local replay from (stacked arenas, owner sid, keys): both
        # dispatch paths hand this the same sid values (fused computes
        # shard_ids, vmap reuses the route's), so fused/vmap transfer
        # parity is structural like the search leg above
        transfers = OTR.measure_stacked(
            fcfg.tree, f.trees.value, f.trees.child, f.trees.root[sid],
            sid, keys)
    return ReadStats(
        search=SearchStats.of(hops, pad, bhit),
        router=RouterStats.of(R.lane_counts(sid, fcfg.num_shards), clamped),
        transfers=transfers,
    )


def _lookup(fcfg: ForestConfig, f: Forest, keys: jax.Array, view=None):
    raw = jnp.asarray(keys)
    keys = _route_keys(raw)
    fb = _fused(fcfg)
    if fb is not None:
        # fused frontier: batch order end to end, one kernel launch per
        # round across all co-resident shards (no (S, K) dense scatter)
        sid = R.shard_ids(f.splits, keys)

        def per_device(trees_loc, lid, ks, view_loc):
            return fb.lookup(fcfg.tree, trees_loc, lid, ks,
                             view=view_loc), None

        r, lane, _ = R.fused_dispatch(fcfg.num_shards, per_device,
                                      f.trees, sid, keys, view=view)
        found, pay, hops = R.gather_fused(r, lane)
    else:
        r = R.route(f.splits, keys)
        sid = r.sid
        dkeys = R.scatter_dense(r, fcfg.num_shards, keys, _PAD_KEY)

        def per_shard(t, ks):
            # bare engine hook (always 3-tuple): stats derive once below,
            # from batch-order columns, not per shard inside the dispatch
            return E.lookup_cols(fcfg.tree, t, ks)

        found, pay, hops = R.dispatch(fcfg.num_shards, per_shard, f.trees,
                                      dkeys)
        found, pay, hops = (R.gather_batch(r, found), R.gather_batch(r, pay),
                            R.gather_batch(r, hops))
    if not E.collecting(fcfg.tree):
        return found, pay, hops
    return found, pay, hops, _forest_read_stats(fcfg, f, raw, keys, sid,
                                                found, hops)


def _succ_combine(sid, f_owner, s_owner, has_min, mins):
    """Cross-shard successor combine: first non-empty shard strictly
    after each owner shard (suffix min over shard minima works because
    shards are key-ordered) — shared by both dispatch paths so the fused
    frontier cannot drift from the vmap reference."""
    masked = jnp.where(has_min, mins, _NO_SUCC)
    suffix = jax.lax.associative_scan(jnp.minimum, masked, reverse=True)
    after = jnp.concatenate([suffix[1:], jnp.full((1,), _NO_SUCC)])
    fallback = after[sid]
    out_found = f_owner | (fallback < _NO_SUCC)
    out_succ = jnp.where(f_owner, s_owner,
                         jnp.where(fallback < _NO_SUCC, fallback, 0))
    return out_found, out_succ


def successor_jit(fcfg: ForestConfig, f: Forest, keys: jax.Array):
    """Routed wait-free successor. Returns (found[K], succ[K]).

    Owner-shard miss falls through to the first later non-empty shard's
    minimum (computed in the same dispatch; combined with a suffix-min)."""
    return _successor_core(fcfg, f, keys, _maybe_cached_view(fcfg, f))


@functools.partial(jax.jit, static_argnums=0)
def _successor_core(fcfg: ForestConfig, f: Forest, keys: jax.Array, view):
    keys = _route_keys(keys)
    fb = _fused(fcfg)
    if fb is not None:
        sid = R.shard_ids(f.splits, keys)

        def per_device(trees_loc, lid, ks, view_loc):
            found, succ, has_min, mins = fb.successor(
                fcfg.tree, trees_loc, lid, ks, view=view_loc)
            return (found, succ), (has_min, mins)

        r, (found, succ), (has_min, mins) = R.fused_dispatch(
            fcfg.num_shards, per_device, f.trees, sid, keys, view=view)
        f_owner, s_owner = R.gather_fused(r, (found, succ))
        return _succ_combine(sid, f_owner, s_owner, has_min, mins)
    r = R.route(f.splits, keys)
    dkeys = R.scatter_dense(r, fcfg.num_shards, keys, _PAD_KEY)

    def per_shard(t, ks):
        # shard minimum = successor of (KEY_MIN - 1), riding the same
        # engine dispatch as one extra lane of the batch (lanes are
        # independent, so results are unchanged and the lockstep engine
        # pays no second walk)
        probe = jnp.concatenate(
            [ks, jnp.full((1,), layout.KEY_MIN - 1, jnp.int32)])
        found, succ = DT.successor_batch(fcfg.tree, t, probe)
        return found[:-1], succ[:-1], found[-1], succ[-1]

    found, succ, has_min, mins = R.dispatch(
        fcfg.num_shards, per_shard, f.trees, dkeys)
    f_owner = R.gather_batch(r, found)
    s_owner = R.gather_batch(r, succ)
    return _succ_combine(r.sid, f_owner, s_owner, has_min, mins)


# --------------------------------------------------------------------------
# ordered bulk reads (range scan / successor_k)
# --------------------------------------------------------------------------


def scan_batch(fcfg: ForestConfig, f: Forest, starts: jax.Array,
               his: jax.Array, *, max_items: int):
    """Routed wait-free range scan: per lane, up to ``max_items`` live
    items with ``start < key <= hi`` in *global* key order.

    Returns the engine `scan` contract — (out (K, max_items) packed
    ascending with sentinel padding, n (K,), hops (K,), more (K,) bool).
    Unlike point reads, a range can span shards, so every lane is scanned
    against every shard (one leaf-run scan lane per (lane, shard) pair —
    still ONE ``delta_scan`` dispatch under the fused frontier); shards
    partition the key space in split order, so the per-shard bands
    concatenate sorted and the first ``max_items`` of the union are the
    globally correct page even when an early shard's band truncated
    (everything after a truncated band belongs to the continuation).
    ``hops`` is the lane's total ΔNode visits across all shards."""
    return _scan_core(fcfg, f, starts, his, max_items,
                      _maybe_cached_view(fcfg, f))


def successor_k(fcfg: ForestConfig, f: Forest, keys: jax.Array, k: int):
    """Routed bulk successors: the ``k`` smallest live keys strictly
    greater than each query, forest-wide (same return contract as
    `scan_batch`; subsumes the point `successor_jit` fallthrough — the
    scan's shard bands are what the suffix-min combine approximates for
    k=1)."""
    keys = jnp.asarray(keys, jnp.int32)
    his = jnp.full(keys.shape, layout.KEY_MAX, jnp.int32)
    return _scan_core(fcfg, f, keys, his, k, _maybe_cached_view(fcfg, f))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _scan_core(fcfg: ForestConfig, f: Forest, starts: jax.Array,
               his: jax.Array, max_items: int, view):
    cfg = fcfg.tree
    starts = _route_keys(starts)
    his = _route_keys(his)
    s = fcfg.num_shards
    k = starts.shape[0]
    fb = _fused(fcfg)
    if fb is not None and fb.scan is not None:
        # (lane, shard) tiling, shard-major: tiled lane s*k + i scans
        # lane i's band inside shard s, seeded at that shard's fused
        # root; sid routes each tiled lane to its shard's device
        sid = jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)

        def per_device(trees_loc, lid, bounds, view_loc):
            st, hb = bounds
            return fb.scan(cfg, trees_loc, lid, st, hb, max_items,
                           view=view_loc), None

        r, lane, _ = R.fused_dispatch(
            s, per_device, f.trees, sid,
            (jnp.tile(starts, s), jnp.tile(his, s)), view=view)
        out, n, hops, more = R.gather_fused(r, lane)
        out = out.reshape(s, k, max_items)
        n, hops, more = (n.reshape(s, k), hops.reshape(s, k),
                         more.reshape(s, k))
    else:

        def per_shard(t):
            return E.scan(cfg, t, starts, his, max_out=max_items)

        out, n, hops, more = R.dispatch(s, per_shard, f.trees)
    # shard bands are key-disjoint and shard order == key order: the
    # sorted union's first max_items are exactly the bands in split
    # order, truncated where the page fills (sentinel padding sorts last)
    union = jnp.sort(out.transpose(1, 0, 2).reshape(k, s * max_items),
                     axis=1)[:, :max_items]
    total = jnp.sum(n, axis=0)
    return (union,
            jnp.minimum(jnp.int32(max_items), total),
            jnp.sum(hops, axis=0),
            jnp.any(more, axis=0) | (total > max_items))


# --------------------------------------------------------------------------
# batched updates
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def update_batch(fcfg: ForestConfig, f: Forest, kinds: jax.Array,
                 keys: jax.Array, payloads: jax.Array | None = None):
    """Routed batch-order updates; per-shard maintenance under the tree
    config's ``maintenance`` policy (shard-local, like all maintenance).

    Returns (forest, results[K] bool, MaintenanceStats) — stats aggregated
    over shards (``rounds`` = max, the critical path of the concurrent
    shards; work counters and ``pending`` sum) — identical contract to
    ``repro.core.update_batch``.

    Updates share the reads' key-domain boundary (`_route_keys`): an
    out-of-int32-domain key (x64 caller) is a no-op row with result
    False — it can never be stored, and silently wrapping it would
    insert a bogus key that the clamped reads could then never see."""
    kq = jnp.asarray(keys)
    in_domain = (kq >= layout.KEY_MIN) & (kq <= layout.KEY_MAX)
    kinds = jnp.where(in_domain, kinds.astype(jnp.int32),
                      jnp.int32(OP_SEARCH))
    keys = _route_keys(kq)
    k = keys.shape[0]
    if payloads is None:
        payloads = jnp.zeros((k,), jnp.int32)
    payloads = payloads.astype(jnp.int32)
    r = R.route(f.splits, keys)
    s = fcfg.num_shards
    dkinds = R.scatter_dense(r, s, kinds.astype(jnp.int32),
                             jnp.int32(OP_SEARCH))  # pads are no-ops
    dkeys = R.scatter_dense(r, s, keys, jnp.int32(0))
    dpays = R.scatter_dense(r, s, payloads, jnp.int32(0))

    def per_shard(t, kn, ks, ps):
        return DT.update_batch_impl(fcfg.tree, t, kn, ks, ps)

    trees, dres, stats = R.dispatch(s, per_shard, f.trees, dkinds, dkeys,
                                    dpays, sequential=True)
    # per-shard cumulative update counters: non-search rows post in-domain
    # masking (a clamped-out row never reaches a shard's update kernel)
    upd = jnp.zeros((s,), jnp.int32).at[r.sid].add(
        (kinds != OP_SEARCH).astype(jnp.int32))
    return (Forest(trees=trees, splits=f.splits,
                   reads=f.reads, updates=f.updates + upd,
                   epoch=f.epoch + 1),
            R.gather_batch(r, dres), MaintenanceStats.reduce(stats))


@functools.partial(jax.jit, static_argnums=(0, 2), donate_argnums=1)
def flush(fcfg: ForestConfig, f: Forest, budget: int = 64):
    """Drain pending maintenance on every shard (restores I5 forest-wide
    after ``deferred``/``budgeted`` batches).  Returns (forest, stats)."""

    def per_shard(t):
        return DT.flush_impl(fcfg.tree, t, budget)

    trees, stats = R.dispatch(fcfg.num_shards, per_shard, f.trees,
                              sequential=True)
    return (Forest(trees=trees, splits=f.splits,
                   reads=f.reads, updates=f.updates, epoch=f.epoch + 1),
            MaintenanceStats.reduce(stats))


# --------------------------------------------------------------------------
# per-shard load counters (obs)
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def record_reads(fcfg: ForestConfig, f: Forest, keys: jax.Array) -> Forest:
    """Fold one read batch into the cumulative per-shard ``reads``
    counters.  Reads themselves are pure (wait-free snapshots), so
    accumulation is an explicit state transition the serving/benchmark
    loop opts into — the read path never grows a hidden side effect."""
    sid = R.shard_ids(f.splits, _route_keys(keys))
    return f._replace(reads=f.reads + R.lane_counts(sid, fcfg.num_shards))


def routing(fcfg: ForestConfig, f: Forest) -> dict:
    """What a point read's counters (``repro.obs.ring``) need of its
    routing: the boundaries, the devices the shards spread over, and the
    lane rows the read dispatch walks — one per device under the fused
    frontier ((D, K) lanes), one per shard under the vmap ((S, K))."""
    d = R.forest_mesh(fcfg.num_shards).devices.size
    return {"splits": f.splits, "devices": d,
            "lane_rows": d if _fused(fcfg) is not None else fcfg.num_shards}


def shard_load(f: Forest) -> dict:
    """Host-side view of the cumulative per-shard op counters."""
    return {"reads": np.asarray(f.reads).tolist(),
            "updates": np.asarray(f.updates).tolist()}


# --------------------------------------------------------------------------
# host-side debug / verification (mirror repro.core)
# --------------------------------------------------------------------------


def live_items(fcfg: ForestConfig, f: Forest):
    """All live (key, payload) pairs, key-sorted (shard order == key order)."""
    out = []
    for s in range(fcfg.num_shards):
        out.extend(DT.live_items(fcfg.tree, shard_tree(f, s)))
    return out


def live_keys(fcfg: ForestConfig, f: Forest) -> np.ndarray:
    return np.asarray([k for k, _ in live_items(fcfg, f)], dtype=np.int64)


def alloc_failed(f: Forest) -> bool:
    """True if any shard's arena ever exhausted (sticky, like core)."""
    return bool(np.asarray(f.trees.alloc_fail).any())
