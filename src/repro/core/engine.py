"""SearchEngine layer — pluggable read path for the ΔTree (DESIGN.md §6).

Every wait-free read (search / lookup / contains / successor) on a
``DeltaTree`` goes through one of the registered engines; ``cfg.engine``
(a static ``TreeConfig`` field, threaded from ``make_index(..., engine=)``
down to the per-shard forest dispatch and the serving pager) picks which:

- ``"scalar"``  — the reference walk: ``vmap`` of a per-query
  ``lax.while_loop`` descent (`deltatree._descend`).  Correct everywhere,
  but the vmap scalarizes the ΔNode visit into per-level gathers — the
  paper's one-block-transfer-per-ΔNode discipline is lost.
- ``"lockstep"`` — frontier-synchronized rounds driving the Pallas vEB
  walk kernel (`kernels.ops.delta_walk`): each round gathers every active
  query's current ΔNode row with one contiguous DMA and descends it fully
  in VMEM, so a round *is* the paper's memory transfer and the round count
  is the O(log_B N) bound.  Pallas lowers compiled on TPU; elsewhere the
  kernel runs in interpret mode, and packed int64 rows outside interpret
  mode take the compiled jnp mirror (`kernels.ref.ref_veb_walk_rows`).

Both engines implement full paper SEARCHNODE semantics — packed
key/payload handling (``cfg.qpack``/``key_of``/``payload_of``), mark-bit
liveness, overflow-buffer membership + payload extraction — and both
report the identical per-query ``hops`` transfer statistic (scalar: ΔNode
boundary crossings counted by `_descend`; lockstep: rounds the query
stayed active).  The conformance suite asserts bit-for-bit equality.

An engine is a table of pure functions over ``(cfg, tree, keys)``; new
read paths (e.g. a fused update-aware walk) register with
``register_engine`` and become selectable everywhere by name.

An engine may additionally declare a ``forest_batch`` entry point
(``ForestBatch``): fused cross-shard reads over a base-offset view of
co-resident shard arenas — one multi-root ``delta_walk`` frontier for
the whole routed batch instead of a vmap over (S, K) dense lanes.  The
forest dispatch (`repro.distributed.forest`) selects it automatically
via ``TreeConfig.engine`` (DESIGN.md §8); the scalar engine declares
none and keeps the dense vmap dispatch as the reference.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import deltatree as DT
from repro.core import layout
from repro.core.layout import EMPTY


@dataclasses.dataclass(frozen=True)
class ForestBatch:
    """An engine's fused cross-shard forest entry point (DESIGN.md §8).

    Both hooks run over the *device-local* stacked arena pytree ``trees``
    (leading (S_loc,) axis — the shards co-resident on one device) fused
    into a single base-offset arena view, with every query seeded at its
    owner shard's root (``lid`` = per-query local shard index).  One
    kernel launch per frontier round serves all co-resident shards — no
    dense (S, K) scatter, no vmap over shards.

    lookup:    (cfg, trees, lid[K], keys[K], *, view=None)
               -> (found, payload, hops)
    successor: (cfg, trees, lid[K], keys[K], *, view=None)
               -> (found[K], succ[K], has_min[S_loc], mins[S_loc])
               — the per-shard minimum probes (successor of KEY_MIN-1,
               one per local shard) ride the same chase as S_loc extra
               lanes; the forest's cross-shard suffix-min combine
               consumes them.
    make_view: optional (cfg, trees) -> view — precompute the fused
               base-offset view the hooks would otherwise build inline.
               A caller holding an unchanged arena across many reads
               (the serve decode loop) builds it once and passes it back
               through the hooks' ``view=`` keyword; ``None`` (and a
               ``view=None`` call) mean build-per-call, the original
               semantics.  The view is pure data derived from ``trees``
               — passing a stale one is the caller's bug, which is why
               the forest layer keys its cache on the update epoch.

    Results must be bit-identical to the dense per-shard vmap dispatch
    (found/payload/succ and per-query hops) — the fused-conformance suite
    asserts it.
    """

    lookup: Callable[..., Any]
    successor: Callable[..., Any]
    make_view: Callable[..., Any] | None = None
    # scan: (cfg, trees, starts[S_loc], his[S_loc], max_out, *, view=None)
    #       -> (out[S_loc, max_out], n, hops, more) — one leaf-run scan lane
    #       per co-resident shard over the fused view (each lane scans its
    #       own arena band), per-shard I5' buffered merge included; None
    #       means the forest falls back to the dense per-shard dispatch
    scan: Callable[..., Any] | None = None


@dataclasses.dataclass(frozen=True)
class SearchEngine:
    """One registered read path: pure functions over (cfg, tree, keys).

    lookup:    (cfg, t, keys[K]) -> (found[K], payload[K], hops[K])
               — map-mode read; set mode returns payload 0/-1.  ``search``
               and ``contains`` are this minus the payload column.
    successor: (cfg, t, keys[K]) -> (found[K], succ[K])
    scan_batch: optional ordered bulk read — (cfg, t, starts[K], his[K],
               max_out, root=None) -> (out[K, max_out] packed, n[K],
               hops[K], more[K]) — up to ``max_out`` live *leaf* items per
               lane with start < key <= hi, key ascending; tree side only
               (the `scan` dispatch merges I5' buffered items).  None
               means the engine cannot serve range_scan/successor_k.
    forest_batch: optional fused cross-shard read entry point
               (``ForestBatch``); None means the forest falls back to the
               dense per-shard vmap dispatch for this engine.
    """

    name: str
    lookup: Callable[..., Any]
    successor: Callable[..., Any]
    scan_batch: Callable[..., Any] | None = None
    forest_batch: ForestBatch | None = None


_ENGINES: dict[str, SearchEngine] = {}


def register_engine(engine: SearchEngine, *, overwrite: bool = False
                    ) -> SearchEngine:
    """Install ``engine`` under ``engine.name``; re-registration opts in."""
    if engine.name in _ENGINES and not overwrite:
        raise ValueError(f"engine {engine.name!r} already registered")
    _ENGINES[engine.name] = engine
    return engine


def get_engine(name: str) -> SearchEngine:
    try:
        return _ENGINES[name]
    except KeyError:
        raise KeyError(
            f"unknown engine {name!r}; registered: {available_engines()}"
        ) from None


def available_engines() -> list[str]:
    return sorted(_ENGINES)


# --------------------------------------------------------------------------
# "auto" engine resolution — one table entry per execution mode
# --------------------------------------------------------------------------

# The engine ``"auto"`` picks, keyed by (backend, compiled).  compiled=True
# is real XLA/Pallas compilation (REPRO_PALLAS_INTERPRET=0, and the
# default on a TPU — on the CPU the fused walk then runs the XLA-compiled
# `ref_delta_walk_fused`); compiled=False is the Pallas interpreter, where
# lockstep pays the interpreter tax and a miss falls back to "scalar".
# The entries were chosen from CPU timings.  Lockstep is the paper's read
# path, runs one walk or scan kernel per read dispatch (two for
# successor's chase; tests/test_read_dispatch.py), and is the form that
# lowers to the Pallas kernel on TPU; every benchmark cell on the chip
# resolves ``auto`` to it (PERF_LEDGER.jsonl).  No chip cell has yet
# compared it with "scalar": the table is a named debt (ROADMAP.md 3c).
AUTO_TABLE: dict[tuple[str, bool], str] = {
    ("deltatree", True): "lockstep",
    ("forest", True): "lockstep",
}


def resolve_engine(name: str | None, backend: str, *,
                   compiled: bool | None = None) -> str | None:
    """Resolve ``engine="auto"`` to a concrete registered engine.

    Non-"auto" names (including None) pass through untouched.  "auto"
    looks up ``AUTO_TABLE[backend, compiled]`` — ``compiled=None`` reads
    the process execution mode (`ops.default_interpret`) at call time —
    and falls back to "scalar" (the everywhere-correct reference) on a
    table miss, so new backends resolve safely.  ``make_index`` calls
    this before the TreeConfig is built; the resolved name then flows
    through the normal per-backend engine validation.
    """
    if name != "auto":
        return name
    if compiled is None:
        from repro.kernels.ops import default_interpret

        compiled = not default_interpret()
    return AUTO_TABLE.get((backend, bool(compiled)), "scalar")


# --------------------------------------------------------------------------
# dispatch helpers (the entry points deltatree/forest delegate to)
# --------------------------------------------------------------------------


def collecting(cfg) -> bool:
    """Static observability gate (``TreeConfig.collect_stats``): checked
    in Python at trace time, so the False path traces *exactly* the
    pre-obs graph — the HLO-identity contract tests/test_obs.py holds us
    to.  Configs without the field (baselines) never collect."""
    return bool(getattr(cfg, "collect_stats", False))


def collecting_transfers(cfg) -> bool:
    """Static sub-gate for measured ``TransferStats`` (the device-side
    descent replay): only active when ``collect_stats`` already is, so
    the collect_stats=False HLO-identity contract is untouched and the
    replay's extra work is opt-in per config."""
    return collecting(cfg) and bool(getattr(cfg, "collect_transfers", False))


def _read_stats(cfg, t, keys, found, hops):
    """The trailing ``ReadStats`` of a stats-collecting read, derived
    from the dispatch's own outputs: both engines produce bit-identical
    (found, hops) columns (the conformance contract), so the histogram /
    occupancy / buffer-hit parity between engines is structural.  The
    measured-transfer leg replays the descent from (arena, root, keys)
    alone — engine-independent by construction for the same reason."""
    from repro.obs.stats import ReadStats, SearchStats

    keys32 = jnp.asarray(keys, jnp.int32)
    pad = keys32 == layout.ROUTE_LEFT
    bhit = found & DT.buffered_member(cfg, t, keys32)
    transfers = None
    if collecting_transfers(cfg):
        from repro.obs import transfers as OTR

        transfers = OTR.measure(cfg, t, keys32)
    return ReadStats(search=SearchStats.of(hops, pad, bhit),
                     transfers=transfers)


def lookup_cols(cfg, t, keys: jax.Array):
    """The bare engine hook call — always the 3-tuple, never stats.  The
    forest's dense per-shard dispatch reads through this so stats are
    derived exactly once, in the forest's own dispatch layer (mirroring
    the fused path, which also calls raw hooks)."""
    return get_engine(cfg.engine).lookup(cfg, t, keys)


def lookup(cfg, t, keys: jax.Array):
    """Engine-dispatched map-mode read: (found[K], payload[K], hops[K]),
    plus a trailing ``ReadStats`` when ``cfg.collect_stats``."""
    out = lookup_cols(cfg, t, keys)
    if not collecting(cfg):
        return out
    found, payload, hops = out
    return found, payload, hops, _read_stats(cfg, t, keys, found, hops)


def search(cfg, t, keys: jax.Array):
    """Engine-dispatched membership read: (found[K], hops[K]), plus a
    trailing ``ReadStats`` when ``cfg.collect_stats``."""
    if not collecting(cfg):
        found, _, hops = lookup(cfg, t, keys)
        return found, hops
    found, _, hops, stats = lookup(cfg, t, keys)
    return found, hops, stats


def successor(cfg, t, keys: jax.Array):
    """Engine-dispatched ordered read: (found[K], succ[K]) — no stats
    variant: ``ReadStats`` rides the hop-bearing reads only (successor
    reports no transfer column to derive them from).

    Under a non-eager maintenance policy the tree may carry pending items
    in overflow buffers (invariant I5'); those are invisible to the router
    walk, so the dispatch folds the buffered successor floor
    (`deltatree.buffered_floor`) with the engine's tree-side result.  The
    live set is (tree-live ∪ buffered) and the two sides are disjoint, so
    the min of the two successors is the successor over the union.  Eager
    trees skip the fold (buffers are empty between steps — I5), keeping
    the pre-subsystem read bit-identical.
    """
    found, succ = get_engine(cfg.engine).successor(cfg, t, keys)
    policy = getattr(cfg, "maintenance", "eager")
    if policy == "eager" or not hasattr(cfg, "route_left"):
        return found, succ
    return _fold_floor(cfg, DT.buffered_floor(cfg, t, keys), found, succ)


def _fold_floor(cfg, bf, found, succ):
    """Fold a buffered-floor column into a tree-side successor result:
    the live set is (tree-live ∪ buffered) and the sides are disjoint, so
    the min of the two successors is the successor over the union."""
    bfound = bf < cfg.route_left
    bkey = cfg.key_of(bf).astype(succ.dtype)
    better = bfound & (~found | (bkey < succ))
    return found | bfound, jnp.where(better, bkey, succ)


def scan(cfg, t, starts: jax.Array, his: jax.Array, *, max_out: int,
         root=None):
    """Engine-dispatched ordered bulk read: per lane, up to ``max_out``
    live items with ``start < key <= hi`` in key order.

    Returns (out (K, max_out) packed ascending with ``cfg.route_left``
    padding, n (K,), hops (K,), more (K,) bool); ``more`` marks lanes that
    filled their buffer with live items remaining — the continuation
    cursor is ``key_of(out[lane, n-1])``.

    Under a non-eager maintenance policy the engines' tree-side run
    misses pending overflow-buffer items (invariant I5'); the dispatch
    merges them here — ONE shared sorted-buffer merge above both engines
    (`_merge_buffered_run`), so scalar/lockstep bit-parity of the merged
    run is structural, exactly like `successor`'s `_fold_floor`.  Eager
    trees skip the merge (buffers drain every step — I5).
    """
    eng = get_engine(cfg.engine)
    if eng.scan_batch is None:
        raise NotImplementedError(
            f"engine {cfg.engine!r} declares no scan_batch hook")
    out, n, hops, more = eng.scan_batch(cfg, t, starts, his, max_out,
                                        root=root)
    policy = getattr(cfg, "maintenance", "eager")
    if policy == "eager" or not hasattr(cfg, "route_left"):
        return out, n, hops, more
    out, n, more = _merge_buffered_run(cfg, t, starts, his, out, n, more,
                                       max_out)
    return out, n, hops, more


def successor_k(cfg, t, keys: jax.Array, k: int):
    """Engine-dispatched bulk successors: the ``k`` smallest live keys
    strictly greater than each query key — `scan` with an unbounded upper
    band (same return contract; ``more`` = more than ``k`` successors)."""
    keys = jnp.asarray(keys, jnp.int32)
    his = jnp.full(keys.shape, layout.KEY_MAX, jnp.int32)
    return scan(cfg, t, keys, his, max_out=k)


def _merge_buffered_lane(cfg, sorted_buf, start, hi, out, n, more,
                         max_out: int):
    """Merge one lane's I5' buffered items into its emitted tree run.

    ``sorted_buf`` is a packed ascending buffer arena view (``big``
    padding); the lane's eligible band is (start, cap] where ``cap`` is
    the last tree-emitted key when the tree side overflowed (items past
    the truncation point belong to the continuation — unseen *tree* items
    there could precede them) and ``hi`` otherwise.  Leaves and buffers
    are key-disjoint (inserts dedup against both), so the union of two
    sorted runs is strictly sorted and a concat+sort merge is exact.
    """
    big = cfg.route_left
    pm = jnp.asarray(cfg.pmask, cfg.vdtype)
    nb = sorted_buf.shape[0]
    idx0 = jnp.searchsorted(sorted_buf, cfg.qpack(start),
                            side="right").astype(jnp.int32)
    last = out[jnp.clip(n - 1, 0, max_out - 1)]
    cap = jnp.where(more, last | pm, cfg.qpack(hi))
    idxc = jnp.searchsorted(sorted_buf, cap, side="right").astype(jnp.int32)
    bic = idxc - idx0                     # buffered count in (start, cap]
    span = jnp.arange(max_out, dtype=jnp.int32)
    win = jnp.clip(idx0 + span, 0, nb - 1)
    cands = jnp.where(span < bic, sorted_buf[win], big)
    union = jnp.sort(jnp.concatenate([out, cands]))
    return (union[:max_out],
            jnp.minimum(jnp.int32(max_out), n + bic),
            more | (n + bic > max_out))


def _merge_buffered_run(cfg, t, starts, his, out, n, more, max_out: int):
    """Per-lane `_merge_buffered_lane` over one arena's buffers: one
    global sort of the buffer arena + searchsorted windows per lane,
    skipped entirely in the common drained state (`buffered_floor`'s
    shape)."""
    starts = jnp.asarray(starts, jnp.int32)
    his = jnp.asarray(his, jnp.int32)
    big = cfg.route_left

    def with_items(_):
        flat = jnp.where(t.buf != EMPTY, t.buf, big).reshape(-1)
        s = jnp.sort(flat)
        return jax.vmap(
            lambda st, hb, o, nn, mm: _merge_buffered_lane(
                cfg, s, st, hb, o, nn, mm, max_out)
        )(starts, his, out, n, more)

    def drained(_):
        return out, n, more

    return jax.lax.cond(jnp.any(t.bcount > 0), with_items, drained, None)


def forest_batch(cfg) -> ForestBatch | None:
    """``cfg.engine``'s fused forest entry point (None = vmap dispatch)."""
    return get_engine(cfg.engine).forest_batch


# --------------------------------------------------------------------------
# "scalar" — the reference engine (vmap of the per-query while_loop walk)
# --------------------------------------------------------------------------


def _scalar_lookup(cfg, t, keys: jax.Array):
    found, payload, hops = jax.vmap(lambda k: DT.search_one(cfg, t, k))(keys)
    # the reserved ROUTE_LEFT key (router pad lanes, clamped above-domain
    # probes) is born resolved under the lockstep walk sentinel contract:
    # mirror it here — deterministic miss, payload -1, hops 0 — so the
    # engines' bit-identical per-query hops contract holds for every
    # representable query, reserved keys included
    pad = jnp.asarray(keys, jnp.int32) == layout.ROUTE_LEFT
    return (found & ~pad, jnp.where(pad, -1, payload),
            jnp.where(pad, 0, hops))


def _scalar_successor(cfg, t, keys: jax.Array):
    return jax.vmap(lambda k: DT.successor_one(cfg, t, k))(keys)


def _scalar_scan(cfg, t, starts: jax.Array, his: jax.Array, max_out: int,
                 root=None):
    """vmap of the per-lane reference scan (`DT.scan_one`).  ``root`` is
    the fused-view multi-root seed — lockstep-only; the scalar engine has
    no fused forest path so it must stay None."""
    assert root is None, "scalar scan_batch takes no multi-root seeds"
    starts = jnp.asarray(starts, jnp.int32)
    his = jnp.asarray(his, jnp.int32)
    out, n, hops, more = jax.vmap(
        lambda s, h: DT.scan_one(cfg, t, s, h, max_out))(starts, his)
    # reserved ROUTE_LEFT starts are born done under the lockstep pad-lane
    # sentinel contract: mirror it (empty run, hops 0) for bit parity
    pad = starts == layout.ROUTE_LEFT
    big = jnp.asarray(cfg.route_left, cfg.vdtype)
    return (jnp.where(pad[:, None], big, out),
            jnp.where(pad, 0, n), jnp.where(pad, 0, hops), more & ~pad)


register_engine(SearchEngine(
    name="scalar",
    lookup=_scalar_lookup,
    successor=_scalar_successor,
    scan_batch=_scalar_scan,
))


# --------------------------------------------------------------------------
# "lockstep" — frontier rounds driving the Pallas vEB walk kernel
# --------------------------------------------------------------------------


def _walk_queries(cfg, keys: jax.Array) -> jax.Array:
    """``cfg.qpack`` for the walk kernel, with the reserved ROUTE_LEFT
    key mapped to the packed walk sentinel (``walk_big``) so router pad
    lanes are born resolved — terminate in round 0, miss, no successor
    candidate — in map mode too (in set mode ``qpack(ROUTE_LEFT)`` *is*
    the sentinel already).  ROUTE_LEFT is outside the key domain
    (``layout.KEY_MAX`` < INT32_MAX), so no legitimate query is affected.
    """
    from repro.kernels.veb_search import walk_big

    big = jnp.asarray(walk_big(cfg.vdtype), cfg.vdtype)
    return jnp.where(jnp.asarray(keys, jnp.int32) == layout.ROUTE_LEFT,
                     big, cfg.qpack(keys))


def _lockstep_walk(cfg, t, qpacked: jax.Array, root=None):
    """The kernel driver: ``root`` defaults to the tree's root; a (K,)
    array seeds each query at its own root (fused multi-shard view).
    ``cfg.walk_fused`` picks the driver (fused single-launch vs
    per-round) and ``cfg.walk_round_cap`` the geometry-derived round
    bound — both default-safe for configs predating the knobs."""
    from repro.kernels import ops as OPS

    cap = getattr(cfg, "walk_round_cap", None) or cfg.max_rounds
    return OPS.delta_walk(t.value, t.child,
                          t.root if root is None else root, qpacked,
                          height=cfg.height, max_rounds=cap,
                          q_tile=cfg.q_tile or None,
                          fused=getattr(cfg, "walk_fused", None))


def _lockstep_lookup(cfg, t, keys: jax.Array):
    keys = jnp.asarray(keys, jnp.int32)
    lv, lb, dn, hops, _ = _lockstep_walk(cfg, t, _walk_queries(cfg, keys))
    # SEARCHNODE resolution shared verbatim with the scalar engine
    found, payload = DT.searchnode(cfg, t, keys, lv, lb, dn)
    return found, payload, hops


def _successor_chase(cfg, t, keys: jax.Array, root=None, max_chase: int = 8):
    """Lockstep successor core: the walk kernel folds the min left-turn
    router per round (router = min of its right subtree); a final leaf
    check and a bounded liveness chase mirror `DT.successor_one` lane for
    lane.  ``root`` as in `_lockstep_walk` — per-lane seeds let the same
    chase serve the fused multi-shard view (each lane chases entirely
    within its own shard: candidates are routers/leaves of the seed
    arena, and the liveness re-walk starts from the same seed)."""
    keys = jnp.asarray(keys, jnp.int32)
    k = keys.shape[0]
    pos = jnp.asarray(layout.veb_pos_table(cfg.height))
    big = cfg.route_left

    def one_pass(qk):
        lv, lb, dn, _, cand = _lockstep_walk(cfg, t, _walk_queries(cfg, qk),
                                             root)
        leaf_live = (lv != EMPTY) & ~t.mark[dn, pos[lb]]
        leaf_gt = leaf_live & (cfg.key_of(lv) > qk)
        return jnp.where(leaf_gt & (lv < cand), lv, cand)

    def live_of(qk):
        lv, lb, dn, _, _ = _lockstep_walk(cfg, t, _walk_queries(cfg, qk),
                                          root)
        found, _ = DT.searchnode(cfg, t, qk, lv, lb, dn)
        return found

    def chase(s):
        qk, ck, found, active, it = s
        cand = one_pass(qk)
        cknew = cfg.key_of(cand)
        exists = cand < big
        # candidate routers may be tombstones: verify liveness in lockstep
        live = live_of(cknew)
        done_now = ~exists | live
        return (
            jnp.where(active & ~done_now, cknew, qk),
            jnp.where(active, cknew, ck),
            jnp.where(active, done_now & exists, found),
            active & ~done_now,
            it + 1,
        )

    def cond(s):
        return jnp.any(s[3]) & (s[4] < max_chase)

    init = (keys, jnp.zeros((k,), jnp.int32), jnp.zeros((k,), jnp.bool_),
            jnp.ones((k,), jnp.bool_), jnp.int32(0))
    _, ck, found, _, _ = jax.lax.while_loop(cond, chase, init)
    return found, jnp.where(found, ck, 0)


def _lockstep_successor(cfg, t, keys: jax.Array, max_chase: int = 8):
    return _successor_chase(cfg, t, keys, max_chase=max_chase)


def _lockstep_scan(cfg, t, starts: jax.Array, his: jax.Array, max_out: int,
                   root=None):
    """The leaf-run scan frontier: ONE `delta_scan` dispatch for the
    whole scan — every round of every lane (one ΔNode row read, its run
    emitted) inside a single launch (`veb_scan_fused`, or its XLA mirror
    where Pallas cannot lower).  ``root`` as in `_lockstep_walk`: per-lane seeds drive the
    fused multi-shard view, each lane scanning its own arena."""
    from repro.kernels import ops as OPS

    starts = jnp.asarray(starts, jnp.int32)
    his = jnp.asarray(his, jnp.int32)
    return OPS.delta_scan(
        t.value, t.mark, t.child, t.root if root is None else root,
        _walk_queries(cfg, starts), cfg.qpack(his),
        height=cfg.height, max_out=max_out, pmask=int(cfg.pmask),
        q_tile=cfg.q_tile or None)


# ---- fused cross-shard frontier (the forest_batch entry point) ----


def _fused_trees_view(cfg, trees):
    """Stacked (S, M, ...) shard arenas -> one base-offset arena view.

    value/child/root fuse through `kernels.veb_search.fuse_arenas` (the
    shard base is applied to child links once here, never per round); the
    SEARCHNODE/floor-side arrays (mark, buf, per-ΔNode stats) flatten
    alongside so `DT.searchnode` indexes fused ΔNode ids directly.
    Shard-scoped fields (root, freelist, alloc_fail) keep shard 0's value
    and must not be read through the view — walks always pass explicit
    per-query roots.  Returns (view, fused_roots (S,))."""
    from repro.kernels.veb_search import fuse_arenas

    # loud trace-time guard: a future per-ΔNode field kept at its stacked
    # (S, M, ...) shape would be gather-clamped silently by fused ids —
    # new fields must be taught to this view explicitly
    assert set(DT.DeltaTree._fields) == {
        "value", "mark", "child", "buf", "nlive", "bcount", "nchild",
        "parent", "pslot", "alive", "free_stack", "free_top", "root",
        "ins_flag", "del_flag", "alloc_fail",
    }, "new DeltaTree field: teach _fused_trees_view how it fuses"
    s, m = trees.value.shape[0], trees.value.shape[1]
    value, child, roots = fuse_arenas(trees.value, trees.child, trees.root)
    base = jnp.arange(s, dtype=jnp.int32) * jnp.int32(m)

    def flat(x):
        return x.reshape((s * m,) + x.shape[2:])

    view = trees._replace(
        value=value, child=child,
        mark=flat(trees.mark), buf=flat(trees.buf),
        nlive=flat(trees.nlive), bcount=flat(trees.bcount),
        nchild=flat(trees.nchild),
        parent=flat(jnp.where(trees.parent >= 0,
                              trees.parent + base[:, None], trees.parent)),
        pslot=flat(trees.pslot), alive=flat(trees.alive),
        ins_flag=flat(trees.ins_flag), del_flag=flat(trees.del_flag),
        free_stack=flat(trees.free_stack), free_top=trees.free_top[0],
        root=trees.root[0], alloc_fail=trees.alloc_fail[0],
    )
    return view, roots


def _fused_lockstep_lookup(cfg, trees, lid, keys: jax.Array, *, view=None):
    keys = jnp.asarray(keys, jnp.int32)
    view, roots = _fused_trees_view(cfg, trees) if view is None else view
    lv, lb, dn, hops, _ = _lockstep_walk(cfg, view, _walk_queries(cfg, keys),
                                         roots[lid])
    found, payload = DT.searchnode(cfg, view, keys, lv, lb, dn)
    return found, payload, hops


def _fused_fold_buffered(cfg, trees, lid, keys, found, succ):
    """The I5' buffered-floor fold of `successor`, restricted per lane to
    its owner shard: a later shard's pending item must reach a query
    through the cross-shard fallback (shard-min probes), exactly as on
    the vmap dispatch, or the suffix-min combine would double-count it.

    The per-shard vmap + lid pick computes an (S_loc, K) floor matrix and
    keeps one entry per lane — deliberately the *same* per-shard
    `buffered_floor` calls as the vmap dispatch, so the fold stays
    bit-identical by construction.  It only runs under non-eager
    maintenance, and a searchsorted matrix is cheap next to the S× walk
    work the fused frontier removes; a shard-keyed single-sort variant is
    a possible future win (needs a (shard, packed) composite key, which
    set mode can't widen without x64)."""
    policy = getattr(cfg, "maintenance", "eager")
    if policy == "eager":
        return found, succ
    floors = jax.vmap(lambda t: DT.buffered_floor(cfg, t, keys))(trees)
    bf = floors[lid, jnp.arange(keys.shape[0])]
    return _fold_floor(cfg, bf, found, succ)


def _fused_lockstep_successor(cfg, trees, lid, keys: jax.Array,
                              max_chase: int = 8, *, view=None):
    """Fused successor: K query lanes plus one shard-minimum probe lane
    per co-resident shard (successor of KEY_MIN-1 seeded at that shard's
    root — replacing the vmap path's per-shard appended lane) share one
    chase.  Returns (found[K], succ[K], has_min[S_loc], mins[S_loc])."""
    keys = jnp.asarray(keys, jnp.int32)
    k = keys.shape[0]
    s_loc = trees.value.shape[0]
    view, roots = _fused_trees_view(cfg, trees) if view is None else view
    qk = jnp.concatenate(
        [keys, jnp.full((s_loc,), layout.KEY_MIN - 1, jnp.int32)])
    lid_all = jnp.concatenate(
        [jnp.asarray(lid, jnp.int32), jnp.arange(s_loc, dtype=jnp.int32)])
    found, succ = _successor_chase(cfg, view, qk, roots[lid_all],
                                   max_chase=max_chase)
    found, succ = _fused_fold_buffered(cfg, trees, lid_all, qk, found, succ)
    return found[:k], succ[:k], found[k:], succ[k:]


def _fused_lockstep_scan(cfg, trees, lid, starts: jax.Array, his: jax.Array,
                         max_out: int, *, view=None):
    """Fused cross-shard scan: every lane scans inside one shard of the
    base-offset view — lane ``j`` is seeded at shard ``lid[j]``'s fused
    root, so its run is exactly that shard's band of the range and ONE
    `delta_scan` dispatch serves every (lane, shard) pair the forest
    tiles out.  The I5' buffered merge runs per lane against its *own*
    shard's buffers (shards partition the key space, so a pending item is
    only ever mergeable into its owner shard's band) — the same
    `_merge_buffered_lane` the single-arena dispatch uses, so fused/vmap
    bit-parity of the merged run is structural."""
    starts = jnp.asarray(starts, jnp.int32)
    his = jnp.asarray(his, jnp.int32)
    lid = jnp.asarray(lid, jnp.int32)
    view, roots = _fused_trees_view(cfg, trees) if view is None else view
    out, n, hops, more = _lockstep_scan(cfg, view, starts, his, max_out,
                                        root=roots[lid])
    policy = getattr(cfg, "maintenance", "eager")
    if policy == "eager":
        return out, n, hops, more
    big = cfg.route_left

    def with_items(_):
        flat = jnp.where(trees.buf != EMPTY, trees.buf, big)
        per_shard = jnp.sort(flat.reshape(trees.buf.shape[0], -1), axis=1)
        return jax.vmap(
            lambda s_id, st, hb, o, nn, mm: _merge_buffered_lane(
                cfg, per_shard[s_id], st, hb, o, nn, mm, max_out)
        )(lid, starts, his, out, n, more)

    def drained(_):
        return out, n, more

    out, n, more = jax.lax.cond(jnp.any(trees.bcount > 0), with_items,
                                drained, None)
    return out, n, hops, more


register_engine(SearchEngine(
    name="lockstep",
    lookup=_lockstep_lookup,
    successor=_lockstep_successor,
    scan_batch=_lockstep_scan,
    forest_batch=ForestBatch(
        lookup=_fused_lockstep_lookup,
        successor=_fused_lockstep_successor,
        make_view=_fused_trees_view,
        scan=_fused_lockstep_scan,
    ),
))
