"""Pallas TPU kernel: batched search inside ΔNodes (the paper's hot loop).

TPU mapping of the paper's locality argument (DESIGN.md §2): each query's
current ΔNode row (UB keys in vEB order, padded to a 128-lane multiple) is
read as one contiguous row — the dynamic-vEB pointer hop realized as a
data-dependent row read.  Inside the kernel the whole walk is VREG
arithmetic: implicit complete-BST position math, with each level's slot
picked out of the row by its BFS label (a one-hot lane select: Mosaic
lowers no per-lane gather), vectorized across the query tile.

The multi-ΔNode walk runs in lockstep rounds at the JAX level
(`ops.delta_walk`, the driver behind the ``"lockstep"`` SearchEngine):
gather rows for the query frontier, run this kernel (one full in-ΔNode
descent per query), hop to the child ΔNode, repeat.  Round count =
ΔNode-depth of the tree = the paper's O(log_B N) transfer bound — each
round is exactly one "memory transfer" per query.

Rows may be int32 (paper set mode) or int64 (map mode: ``key << bits |
payload`` packed values — ordering by packed value equals ordering by key,
so the walk is unchanged).  Besides the leaf triple the kernel reports the
per-ΔNode *successor candidate*: the minimum router passed on a left turn
(router = min of its right subtree, so it lower-bounds every key to the
query's right) — the lockstep successor folds these across rounds.

The serving-path sibling kernel (`delta_paged_attention`) shows the same
indirection done with scalar-prefetched `BlockSpec index_map` DMA instead
of a pre-gather; both are TPU-idiomatic realizations of a pointer hop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import layout
from repro.core.layout import EMPTY


# Index maps return int32 zeros: a Python 0 traces as int64 under
# JAX_ENABLE_X64, which Mosaic refuses — as it refuses any Python scalar
# the kernels would turn into an array, hence their typed constants.
_Z = np.int32(0)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def walk_big(dtype) -> int:
    """Successor-candidate identity for a row dtype — must equal the tree's
    ROUTE_LEFT sentinel (int32: INT32_MAX; packed int64 map mode: 1 << 62)
    so candidate folding matches the scalar engine bit for bit."""
    if jnp.dtype(dtype) == jnp.int64:
        return 1 << 62
    return int(layout.ROUTE_LEFT)


def _pick(rows, labels, idx):
    """Per-lane select: ``rows[i, j]`` where ``labels[0, j] == idx[i, 0]``.

    Mosaic lowers no per-lane gather, so the lane is picked by a one-hot
    ``where`` over the row plus a lane max.  Every index the walks ask
    for labels exactly one lane, so the max is that lane's value, bit for
    bit."""
    low = jnp.asarray(jnp.iinfo(rows.dtype).min, rows.dtype)
    return jnp.max(jnp.where(labels == idx, rows, low), axis=1,
                   keepdims=True)


def _lanes(n: int):
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _bfs_labels(height: int, ubp: int):
    """(1, ubp) BFS index stored at each vEB lane of a row (0 on the pad
    lanes; BFS indices start at 1) — the labels `_pick` matches a walk
    position ``b`` against, in place of a ``pos[b]`` table gather."""
    bfs = jnp.asarray(layout.veb_inverse_table(height))
    return jnp.pad(bfs, (0, ubp - bfs.shape[0]))[None, :]


def _kernel(height: int, big: int,
            bfs_ref, q_ref, rows_ref, childrows_ref,
            leaf_val_ref, leaf_b_ref, next_dn_ref, cand_ref):
    h = height
    bottom0 = 2 ** (h - 1)
    bfs = bfs_ref[...]                                   # (1, UBp) labels
    v = q_ref[...]                                       # (QT, 1)
    rows = rows_ref[...]                                 # (QT, UBp) VMEM

    def take(b):
        return _pick(rows, bfs, b)

    b = jnp.ones(v.shape, jnp.int32)
    cand = jnp.full(v.shape, big, rows.dtype)
    # fully unrolled H-1 level walk — pure VREG work on VMEM-resident rows
    for _ in range(h - 1):
        router = take(b)
        left = take(jnp.minimum(2 * b, 2 * bottom0 - 1))
        internal = (b < bottom0) & (left != EMPTY)
        go_right = v >= router
        # left turn: router lower-bounds the right subtree's minimum
        go_left = internal & ~go_right
        cand = jnp.where(go_left & (router < cand), router, cand)
        b = jnp.where(internal, 2 * b + go_right.astype(b.dtype), b)

    leaf_val = take(b)
    at_bottom = b >= bottom0
    slot = jnp.where(at_bottom, b - bottom0, _Z)
    childrows = childrows_ref[...]
    child = _pick(childrows, _lanes(childrows.shape[1]), slot)
    nxt = jnp.where(at_bottom, child, jnp.int32(-1))

    leaf_val_ref[...] = leaf_val
    leaf_b_ref[...] = b
    next_dn_ref[...] = nxt
    cand_ref[...] = cand


def _tiles(q_tile: int, width: int = 1):
    """(q_tile, width) blocks walking the query tiles.  Per-lane state
    rides (K, 1) columns — one query per sublane — so it broadcasts
    against the lane's (QT, lanes) row tile."""
    return pl.BlockSpec((q_tile, width), lambda i: (i, _Z))


def _whole(width: int):
    """A (1, width) table mapped whole into every grid cell."""
    return pl.BlockSpec((1, width), lambda i: (_Z, _Z))


def _cols(*xs):
    return [x.reshape(x.shape[0], 1) for x in xs]


@functools.partial(jax.jit, static_argnames=("height", "q_tile", "interpret"))
def veb_walk_rows(rows: jax.Array, childrows: jax.Array, queries: jax.Array,
                  *, height: int, q_tile: int = 256, interpret: bool):
    """One full in-ΔNode descent per query.

    rows:      (K, UBp) int32/int64 — each query's current ΔNode row
               (vEB order; int64 = packed map-mode values)
    childrows: (K, CP)  int32 — matching bottom-slot child ids (-1 none)
    queries:   (K,)     packed, same dtype as rows; K % q_tile == 0

    Returns (leaf_val, leaf_b, next_dn, cand): leaf_val/cand in the row
    dtype, leaf_b/next_dn int32, each (K,).  next_dn = -1 when the walk
    ends inside this ΔNode; cand = min left-turn router (``walk_big`` when
    no left turn happened).
    """
    k = queries.shape[0]
    assert k % q_tile == 0, (k, q_tile)
    assert queries.dtype == rows.dtype, (queries.dtype, rows.dtype)
    n_tiles = k // q_tile
    ubp = rows.shape[1]
    cp = childrows.shape[1]
    big = walk_big(rows.dtype)

    out_shape = [
        jax.ShapeDtypeStruct((k, 1), rows.dtype),   # leaf_val
        jax.ShapeDtypeStruct((k, 1), jnp.int32),    # leaf_b
        jax.ShapeDtypeStruct((k, 1), jnp.int32),    # next_dn
        jax.ShapeDtypeStruct((k, 1), rows.dtype),   # cand
    ]
    out = pl.pallas_call(
        functools.partial(_kernel, height, big),
        grid=(n_tiles,),
        in_specs=[
            _whole(ubp),
            _tiles(q_tile),
            _tiles(q_tile, ubp),
            _tiles(q_tile, cp),
        ],
        out_specs=[_tiles(q_tile)] * 4,
        out_shape=out_shape,
        interpret=interpret,
    )(_bfs_labels(height, ubp), *_cols(queries), rows, childrows)
    return tuple(o.reshape(k) for o in out)


def _gather_rows(dn_ref, pairs):
    """Row reads for the lane frontier: copy arena row ``dn[i]`` of every
    ``(arena_ref, rows_ref)`` pair into row ``i`` of its rows tile.

    Mosaic lowers no 1-D gather over a VMEM arena, but a scalar row id
    does index a ref: each lane's id is read out of its (1, 1) slice and
    drives one dynamic sublane slice per arena."""
    def body(i, carry):
        d = jnp.max(dn_ref[pl.ds(i, 1), :])
        for arena_ref, rows_ref in pairs:
            rows_ref[pl.ds(i, 1), :] = arena_ref[pl.ds(d, 1), :]
        return carry

    jax.lax.fori_loop(0, dn_ref.shape[0], body, 0)


def _fused_kernel(height: int, big: int, max_rounds: int, m: int,
                  bfs_ref, q_ref, root_ref, value_ref, child_ref,
                  leaf_val_ref, leaf_b_ref, final_dn_ref, hops_ref, cand_ref,
                  dn_ref, vrows_ref, crows_ref):
    """Persistent multi-round walk: the whole frontier loop of
    ``ops.delta_walk`` inside one kernel launch (per q_tile grid cell).

    The padded arena is resident in VMEM (the caller budgets it); each
    round reads every lane's ΔNode row into a (QT, UBp) tile
    (`_gather_rows`), then runs a *blind* in-ΔNode descent — one router
    pick per level, always routing right through EMPTY territory (sound
    by the connected-top-tree occupancy invariants; see
    ``ref.ref_delta_walk_fused``, the bit-exact oracle) — followed by the
    bottom-slot child hop.  Rounds stop when every lane is resolved, so
    shallow trees never pay dead iterations.
    """
    h = height
    bottom0 = 2 ** (h - 1)
    bfs = bfs_ref[...]
    lanes = _lanes(crows_ref.shape[1])
    v = q_ref[...]                                        # (QT, 1)
    dt = value_ref.dtype
    dn0 = root_ref[...]

    # lane flags ride the loop carry as int32 0/1: Mosaic carries no
    # boolean vectors across loop iterations
    def cond(s):
        return (jnp.min(s[1]) == 0) & (s[7] < max_rounds)

    def body(s):
        dn, resolved, leaf_val, leaf_b, final_dn, hops, cand, rounds = s
        resolved = resolved != 0
        dn_ref[...] = jnp.clip(dn, _Z, jnp.int32(m - 1))
        _gather_rows(dn_ref, ((value_ref, vrows_ref), (child_ref, crows_ref)))
        rows = vrows_ref[...]
        b = jnp.ones(v.shape, jnp.int32)
        lb = jnp.ones(v.shape, jnp.int32)          # last occupied position
        lv = jnp.zeros(v.shape, dt)
        rcand = jnp.full(v.shape, big, dt)
        routers, bs = [], []
        for _ in range(h):                          # blind descent
            router = _pick(rows, bfs, b)
            routers.append(router)
            bs.append(b)
            occ = router != EMPTY
            lb = jnp.where(occ, b, lb)
            lv = jnp.where(occ, router, lv)
            go_right = v >= router
            b = jnp.where(b < bottom0, 2 * b + go_right.astype(b.dtype), b)
        for router, bi in zip(routers, bs):         # post-hoc cand fold
            fold = ((router != EMPTY) & (bi != lb) & (v < router)
                    & (router < rcand))
            rcand = jnp.where(fold, router, rcand)
        at_bottom = lb >= bottom0
        slot = jnp.where(at_bottom, lb - bottom0, _Z)
        ch = _pick(crows_ref[...], lanes, slot)
        nxt = jnp.where(at_bottom, ch, jnp.int32(-1))
        act = ~resolved
        done_now = act & (nxt < 0)
        return (
            jnp.where(act & (nxt >= 0), nxt, dn),
            (resolved | done_now).astype(jnp.int32),
            jnp.where(done_now, lv, leaf_val),
            jnp.where(done_now, lb, leaf_b),
            jnp.where(done_now, dn, final_dn),
            hops + act.astype(jnp.int32),
            jnp.where(act & (rcand < cand), rcand, cand),
            rounds + 1,
        )

    init = (
        dn0,
        (v == jnp.asarray(big, dt)).astype(jnp.int32),  # sentinels resolved
        jnp.zeros(v.shape, dt),
        jnp.ones(v.shape, jnp.int32),
        dn0,
        jnp.zeros(v.shape, jnp.int32),
        jnp.full(v.shape, big, dt),
        _Z,
    )
    s = jax.lax.while_loop(cond, body, init)
    leaf_val_ref[...] = s[2]
    leaf_b_ref[...] = s[3]
    final_dn_ref[...] = s[4]
    hops_ref[...] = s[5]
    cand_ref[...] = s[6]


# Scoped-VMEM ceiling of the arena-resident kernels (v5e holds 128 MiB of
# VMEM per core; Mosaic's default scoped limit is 16 MiB).  The arena is
# mapped whole into VMEM once per launch (`memory_space=VMEM`, no block
# pipeline, so no second buffer); `ops.FUSED_VMEM_BUDGET_BYTES` keeps the
# arena far enough under this ceiling for the row tiles, the lane state
# and the scan's output tile.
FUSED_VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def _resident():
    return pl.BlockSpec(memory_space=pltpu.VMEM,
                        index_map=lambda i: (_Z, _Z))


@functools.partial(jax.jit,
                   static_argnames=("height", "q_tile", "max_rounds",
                                    "interpret"))
def veb_walk_fused(value_p: jax.Array, child_p: jax.Array, roots: jax.Array,
                   queries: jax.Array, *, height: int, q_tile: int = 256,
                   max_rounds: int = 16, interpret: bool):
    """All walk rounds in one launch (grid over query tiles).

    value_p:  (M, UBp) padded arena rows (`pad_arena`), int32/int64
    child_p:  (M, CP)  padded bottom-slot child ids (-1 none)
    roots:    (K,)     int32 per-query frontier seeds
    queries:  (K,)     packed, same dtype as value_p; K % q_tile == 0

    Returns the full `ops.delta_walk` 5-tuple (leaf_val, leaf_b, final_dn,
    hops, cand), each (K,).  Sentinel queries (``walk_big``) are born
    resolved.  The whole arena is resident in VMEM — callers gate this
    path on `ops.FUSED_VMEM_BUDGET_BYTES` (`ops` runs the XLA mirror
    ``ref.ref_delta_walk_fused`` past it).
    """
    k = queries.shape[0]
    assert k % q_tile == 0, (k, q_tile)
    assert queries.dtype == value_p.dtype, (queries.dtype, value_p.dtype)
    n_tiles = k // q_tile
    m, ubp = value_p.shape
    cp = child_p.shape[1]
    big = walk_big(value_p.dtype)

    out_shape = [
        jax.ShapeDtypeStruct((k, 1), value_p.dtype),   # leaf_val
        jax.ShapeDtypeStruct((k, 1), jnp.int32),       # leaf_b
        jax.ShapeDtypeStruct((k, 1), jnp.int32),       # final_dn
        jax.ShapeDtypeStruct((k, 1), jnp.int32),       # hops
        jax.ShapeDtypeStruct((k, 1), value_p.dtype),   # cand
    ]
    out = pl.pallas_call(
        functools.partial(_fused_kernel, height, big, max_rounds, m),
        grid=(n_tiles,),
        in_specs=[
            _whole(ubp),
            _tiles(q_tile),
            _tiles(q_tile),
            _resident(),
            _resident(),
        ],
        out_specs=[_tiles(q_tile)] * 5,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((q_tile, 1), jnp.int32),          # frontier row ids
            pltpu.VMEM((q_tile, ubp), value_p.dtype),    # value rows
            pltpu.VMEM((q_tile, cp), jnp.int32),         # child rows
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(_bfs_labels(height, ubp), *_cols(queries, roots), value_p, child_p)
    return tuple(o.reshape(k) for o in out)


def _rank_labels(height: int, ubp: int):
    """(1, ubp) in-order rank stored at each vEB lane of a row (-1 on the
    pad lanes) — the labels the scan's `_pick` matches a rank against."""
    lab = np.full(ubp, -1, np.int32)
    lab[layout.inorder_tables(height)["storage"]] = np.arange(
        2 ** height - 1, dtype=np.int32)
    return jnp.asarray(lab)[None, :]


def _scan_kernel(height: int, big: int, pmask: int, max_rounds: int,
                 max_out: int, mo_p: int, m: int,
                 rank_ref, start_ref, hi_ref, root_ref, value_ref, mark_ref,
                 child_ref, out_ref, n_ref, hops_ref, more_ref,
                 dn_ref, vrows_ref, mrows_ref, crows_ref):
    """Persistent leaf-run scan: the whole loop of ``ops.delta_scan``
    inside one kernel launch (per q_tile grid cell).

    Same round structure as ``_fused_kernel`` (row reads, then a blind
    descent, here by in-order rank); each lane additionally carries its
    descent query, its region bound and an emit index into a
    VMEM-resident (QT, mo_p) output tile.  The run is a loop over the
    row's in-order positions: per position a one-hot pick of its value,
    mark, left child and child lanes (`_pick`: Mosaic lowers no per-lane
    gather), a running count that places the key, and the first marker
    at or after the landing.  The pass logic is
    documented on the bit-exact oracle, ``ref.ref_delta_scan_fused``;
    ``mo_p`` is the lane-padded buffer width (emission is still capped at
    ``max_out``).  The mark plane is int32 (nonzero = marked): a bool ref
    would load as an int on TPU.
    """
    h = height
    ub = 2 ** h - 1
    ranks = rank_ref[...]
    lanes = _lanes(crows_ref.shape[1])
    starts = start_ref[...]                              # (QT, 1) packed
    his = hi_ref[...]
    dn0 = root_ref[...]
    dt = value_ref.dtype
    bigv = jnp.asarray(big, dt)
    pm = jnp.asarray(pmask, dt)
    col = _lanes(mo_p)
    none = jnp.int32(ub)

    # lane flags ride the loop carry as int32 0/1 (as in `_fused_kernel`)
    def cond(s):
        return (jnp.min(s[7]) == 0) & (s[8] < max_rounds)

    def body(s):
        dn, q, bound, out, n, hops, more, done, rounds = s
        act = done == 0
        dn_ref[...] = jnp.clip(dn, _Z, jnp.int32(m - 1))
        _gather_rows(dn_ref, ((value_ref, vrows_ref), (mark_ref, mrows_ref),
                              (child_ref, crows_ref)))
        rows = vrows_ref[...]
        r = jnp.full(q.shape, 2 ** (h - 1) - 1, jnp.int32)
        land = r
        for d in range(h):                          # blind descent
            v = _pick(rows, ranks, r)
            land = jnp.where(v != EMPTY, r, land)
            if d < h - 1:
                off = jnp.int32(2 ** (h - 2 - d))
                r = jnp.where(q >= v, r + off, r - off)
        marks = mrows_ref[...]
        crows = crows_ref[...]

        def sweep(rr, c):                           # the run, in in-order
            stop, nxt, count, past_hi, out = c
            x = _pick(rows, ranks, rr)
            occ = x != EMPTY
            low = (rr + 1) & -(rr + 1)              # 2**(h-1-depth)
            ch = _pick(crows, lanes, rr >> 1)       # bottom slot rr // 2
            marker = (low == 1) & occ & (ch >= 0)
            after = (land <= rr) & (stop == none)
            first = after & marker
            left = _pick(rows, ranks, rr - (low >> 1))
            leaf = occ & ~marker & ((low == 1) | (left == EMPTY))
            run = after & leaf & (x != bigv)
            emit = (run & (_pick(marks, ranks, rr) == 0) & (x > starts)
                    & (x <= his) & act)
            return (jnp.where(first, rr, stop), jnp.where(first, ch, nxt),
                    count + emit.astype(jnp.int32),
                    past_hi | (run & (x > his)).astype(jnp.int32),
                    jnp.where(emit & (col == n + count), x, out))

        stop, nxt, count, past_hi, out = jax.lax.fori_loop(
            _Z, none, sweep,
            (jnp.full(q.shape, ub, jnp.int32), jnp.full(q.shape, -1, jnp.int32),
             jnp.zeros(q.shape, jnp.int32), jnp.zeros(q.shape, jnp.int32),
             out))
        room = jnp.int32(max_out) - n
        took = jnp.minimum(count, room)
        full = act & (count > room)
        hop = stop < none
        fold = bound                    # left-turn routers above the marker
        j = stop >> 1
        for d in range(h - 1):
            sh = h - 1 - d
            a = ((2 * (j >> sh) + 1) << sh) - 1
            v = _pick(rows, ranks, a)
            fold = jnp.where((stop < a) & (v < fold), v, fold)
        spent = (bound == bigv) | (bound > his)
        done_now = act & (full | (past_hi != 0) | (~hop & spent))
        go = act & ~done_now
        restart = go & ~hop
        return (
            jnp.where(go & hop, nxt, jnp.where(restart, dn0, dn)),
            jnp.where(restart, bound | pm, q),
            jnp.where(go & hop, fold, jnp.where(restart, bigv, bound)),
            out,
            n + took,
            hops + act.astype(jnp.int32),
            ((more != 0) | full).astype(jnp.int32),
            ((done != 0) | done_now).astype(jnp.int32),
            rounds + 1,
        )

    init = (
        dn0,
        starts,
        jnp.full(starts.shape, big, dt),
        jnp.full((starts.shape[0], mo_p), big, dt),
        jnp.zeros(starts.shape, jnp.int32),
        jnp.zeros(starts.shape, jnp.int32),
        jnp.zeros(starts.shape, jnp.int32),
        (starts == bigv).astype(jnp.int32),         # sentinel lanes done
        _Z,
    )
    s = jax.lax.while_loop(cond, body, init)
    out_ref[...] = s[3]
    n_ref[...] = s[4]
    hops_ref[...] = s[5]
    more_ref[...] = s[6]


@functools.partial(jax.jit,
                   static_argnames=("height", "q_tile", "max_rounds",
                                    "max_out", "pmask", "interpret"))
def veb_scan_fused(value_p: jax.Array, mark_p: jax.Array, child_p: jax.Array,
                   roots: jax.Array, starts: jax.Array, his: jax.Array, *,
                   height: int, max_out: int, pmask: int = 0,
                   q_tile: int = 256, max_rounds: int = 256,
                   interpret: bool):
    """All scan rounds in one launch (grid over query tiles).

    value_p:        (M, UBp) padded arena rows (`pad_arena`), int32/int64
    mark_p:         (M, UBp) int32 mark plane, same padding (nonzero =
                    marked)
    child_p:        (M, CP)  padded bottom-slot child ids (-1 none)
    roots:          (K,)     int32 per-lane frontier seeds
    starts/his:     (K,)     packed qpack bounds (start exclusive, hi
                    inclusive in key space); K % q_tile == 0; a start of
                    ``walk_big`` marks a pad lane (born done)

    Returns the `ops.delta_scan` 4-tuple (out (K, mo_p) packed with the
    lane-padded width ``mo_p = roundup(max_out, 128)`` — callers slice to
    ``max_out`` — n, hops, more(int32)), contract and bit-for-bit results
    documented on ``ref.ref_delta_scan_fused``.  The whole arena is
    resident in VMEM — same budget gate as ``veb_walk_fused``.
    """
    k = starts.shape[0]
    assert k % q_tile == 0, (k, q_tile)
    assert starts.dtype == value_p.dtype, (starts.dtype, value_p.dtype)
    assert mark_p.dtype == jnp.int32, mark_p.dtype
    n_tiles = k // q_tile
    m, ubp = value_p.shape
    cp = child_p.shape[1]
    big = walk_big(value_p.dtype)
    mo_p = _round_up(max_out, 128)

    out_shape = [
        jax.ShapeDtypeStruct((k, mo_p), value_p.dtype),   # out
        jax.ShapeDtypeStruct((k, 1), jnp.int32),          # n
        jax.ShapeDtypeStruct((k, 1), jnp.int32),          # hops
        jax.ShapeDtypeStruct((k, 1), jnp.int32),          # more
    ]
    out, n, hops, more = pl.pallas_call(
        functools.partial(_scan_kernel, height, big, pmask, max_rounds,
                          max_out, mo_p, m),
        grid=(n_tiles,),
        in_specs=[
            _whole(ubp),
            _tiles(q_tile),
            _tiles(q_tile),
            _tiles(q_tile),
            _resident(),
            _resident(),
            _resident(),
        ],
        out_specs=[
            _tiles(q_tile, mo_p),
            _tiles(q_tile),
            _tiles(q_tile),
            _tiles(q_tile),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((q_tile, 1), jnp.int32),          # frontier row ids
            pltpu.VMEM((q_tile, ubp), value_p.dtype),    # value rows
            pltpu.VMEM((q_tile, ubp), jnp.int32),        # mark rows
            pltpu.VMEM((q_tile, cp), jnp.int32),         # child rows
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(_rank_labels(height, ubp), *_cols(starts, his, roots), value_p, mark_p,
      child_p)
    return out, n.reshape(k), hops.reshape(k), more.reshape(k)


def pad_arena(value: jax.Array, child: jax.Array):
    """Pad arena rows to 128-lane multiples for the kernel."""
    ubp = _round_up(value.shape[1], 128)
    cp = _round_up(child.shape[1], 128)
    value_p = jnp.pad(value, ((0, 0), (0, ubp - value.shape[1])))
    child_p = jnp.pad(child, ((0, 0), (0, cp - child.shape[1])),
                      constant_values=-1)
    return value_p, child_p


def fuse_arenas(value: jax.Array, child: jax.Array, root: jax.Array):
    """Concatenate stacked shard arenas into one base-offset arena view.

    value (S, M, UB) / child (S, M, CP) / root (S,) are S independent
    arenas whose ΔNode ids are arena-local.  The fused view is a single
    (S*M, ...) arena in which shard ``s``'s ids shift by ``s*M`` — the
    base offset is applied to child links and roots ONCE, here, never per
    walk round — so a multi-root `ops.delta_walk` (per-query ``root``
    seeds) can drive one shared frontier across every shard.  Child links
    of ``-1`` (none) are preserved; walks seeded at shard ``s``'s fused
    root can only ever reach shard ``s``'s rows (child links never cross
    arenas), so per-query results are bit-identical to S separate walks.

    Returns (fused_value (S*M, UB), fused_child (S*M, CP),
    fused_roots (S,) int32).
    """
    s, m = value.shape[0], value.shape[1]
    base = jnp.arange(s, dtype=jnp.int32) * jnp.int32(m)
    child = jnp.where(child >= 0, child + base[:, None, None], child)
    return (value.reshape((s * m,) + value.shape[2:]),
            child.reshape((s * m,) + child.shape[2:]),
            root.astype(jnp.int32) + base)
