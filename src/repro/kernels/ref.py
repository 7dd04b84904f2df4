"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import layout
from repro.core.layout import EMPTY


@functools.partial(jax.jit, static_argnames=("height",))
def ref_veb_walk_rows(rows: jax.Array, childrows: jax.Array,
                      queries: jax.Array, *, height: int):
    """Pure-jnp mirror of ``veb_search.veb_walk_rows`` (identical contract:
    one full in-ΔNode descent per query over pre-gathered rows, returning
    (leaf_val, leaf_b, next_dn, cand)).

    Besides being the kernel's allclose oracle this is the *compiled*
    non-Pallas walk: `ops` routes here when the Pallas kernel cannot lower
    (int64 packed rows on TPU) — same lockstep round structure, same one
    row gather per query per round, just XLA-compiled gathers instead of a
    hand-written VMEM tile.
    """
    from repro.kernels.veb_search import walk_big

    pos = jnp.asarray(layout.veb_pos_table(height))
    bottom0 = 2 ** (height - 1)
    big = walk_big(rows.dtype)

    def take(b):
        return jnp.take_along_axis(rows, pos[b][:, None], axis=1)[:, 0]

    v = queries
    b = jnp.ones(v.shape, jnp.int32)
    cand = jnp.full(v.shape, big, rows.dtype)
    for _ in range(height - 1):
        router = take(b)
        left = take(jnp.minimum(2 * b, 2 * bottom0 - 1))
        internal = (b < bottom0) & (left != EMPTY)
        go_right = v >= router
        go_left = internal & ~go_right
        cand = jnp.where(go_left & (router < cand), router, cand)
        b = jnp.where(internal, 2 * b + go_right.astype(b.dtype), b)

    leaf_val = take(b)
    at_bottom = b >= bottom0
    slot = jnp.where(at_bottom, b - bottom0, 0)
    child = jnp.take_along_axis(childrows, slot[:, None], axis=1)[:, 0]
    nxt = jnp.where(at_bottom, child, jnp.int32(-1))
    return leaf_val, b, nxt, cand


@functools.partial(jax.jit, static_argnames=("height", "max_rounds"))
def ref_delta_walk_fused(value: jax.Array, child: jax.Array, root: jax.Array,
                         queries: jax.Array, *, height: int,
                         max_rounds: int):
    """Fused multi-round walk, XLA-compiled: the whole frontier loop in one
    program (contract of ``ops.delta_walk`` — (leaf_val, leaf_b, final_dn,
    hops, cand) per query, ``root`` scalar or per-query (K,) seeds, and a
    query equal to ``walk_big(dtype)`` born resolved).

    This is both the allclose oracle for ``veb_search.veb_walk_fused`` and
    the *compiled* fused walk wherever Pallas cannot lower (non-TPU
    backends, int64 packed rows, arenas past the VMEM budget) — the CPU
    compiled-performance path runs here.

    The in-ΔNode descent is *blind*: one router gather per level (instead
    of router + left-child), always routing right through EMPTY territory.
    Sound because occupied slots form a connected top tree (I1/I2: an
    EMPTY slot has no occupied descendants) and packed queries are >= 1 >
    EMPTY, so once the walk leaves the occupied region it only ever sees
    EMPTY routers and the last-occupied position it tracks *is* the leaf
    the eager walk stops at.  The successor candidate is reconstructed
    post-descent: the occupied positions visited above the leaf are
    exactly the internal ancestors, so folding their routers under
    ``v < router`` reproduces the per-level left-turn fold bit for bit.
    """
    from repro.kernels.veb_search import walk_big

    h = height
    bottom0 = 2 ** (h - 1)
    m, ub = value.shape
    pos = jnp.asarray(layout.veb_pos_table(h))
    big = jnp.asarray(walk_big(value.dtype), value.dtype)
    queries = queries.astype(value.dtype)
    k = queries.shape[0]
    vflat = value.reshape(-1)
    dn0 = jnp.broadcast_to(jnp.asarray(root, jnp.int32), (k,))

    state = dict(
        dn=dn0,
        resolved=queries == big,
        leaf_val=jnp.zeros((k,), value.dtype),
        leaf_b=jnp.ones((k,), jnp.int32),
        final_dn=dn0,
        hops=jnp.zeros((k,), jnp.int32),
        cand=jnp.full((k,), big, value.dtype),
        rounds=jnp.int32(0),
    )

    def cond(s):
        return jnp.any(~s["resolved"]) & (s["rounds"] < max_rounds)

    def body(s):
        dnc = jnp.clip(s["dn"], 0, m - 1)
        base = dnc * ub
        v = queries
        b = jnp.ones((k,), jnp.int32)
        lb = jnp.ones((k,), jnp.int32)          # last occupied position
        lv = jnp.zeros((k,), value.dtype)
        routers, bs = [], []
        for _ in range(h):                       # blind descent: h gathers
            router = vflat.at[base + pos[b]].get(mode="promise_in_bounds")
            routers.append(router)
            bs.append(b)
            occ = router != EMPTY
            lb = jnp.where(occ, b, lb)
            lv = jnp.where(occ, router, lv)
            go_right = v >= router               # EMPTY always routes right
            b = jnp.where(b < bottom0, 2 * b + go_right.astype(b.dtype), b)
        # post-hoc candidate fold: occupied non-leaf positions on the path
        # are the internal ancestors; v < router there means a left turn
        cand = jnp.full((k,), big, value.dtype)
        for router, bi in zip(routers, bs):
            fold = (router != EMPTY) & (bi != lb) & (v < router) & (router < cand)
            cand = jnp.where(fold, router, cand)
        at_bottom = lb >= bottom0
        slot = jnp.where(at_bottom, lb - bottom0, 0)
        ch = child.at[dnc, slot].get(mode="promise_in_bounds")
        nxt = jnp.where(at_bottom, ch, jnp.int32(-1))
        act = ~s["resolved"]
        done_now = act & (nxt < 0)
        return dict(
            dn=jnp.where(act & (nxt >= 0), nxt, s["dn"]),
            resolved=s["resolved"] | done_now,
            leaf_val=jnp.where(done_now, lv, s["leaf_val"]),
            leaf_b=jnp.where(done_now, lb, s["leaf_b"]),
            final_dn=jnp.where(done_now, s["dn"], s["final_dn"]),
            hops=s["hops"] + act.astype(jnp.int32),
            cand=jnp.where(act & (cand < s["cand"]), cand, s["cand"]),
            rounds=s["rounds"] + 1,
        )

    s = jax.lax.while_loop(cond, body, state)
    return (s["leaf_val"], s["leaf_b"], s["final_dn"], s["hops"], s["cand"])


def _pick_rank(x, r):
    """``x[r[i], i]`` per lane of a slot-major (N, K) tile: a one-hot
    select and a max down the slots (a per-lane gather lowers poorly on
    the TPU; every rank asked for labels exactly one slot)."""
    hit = jnp.arange(x.shape[0], dtype=jnp.int32)[:, None] == r[None, :]
    return jnp.max(jnp.where(hit, x, jnp.iinfo(x.dtype).min), axis=0)


def _shift(x, s: int, fill):
    """Rows of a slot-major tile moved by a static ``s``: ``y[i] =
    x[i - s]`` (``s`` > 0, down) or ``x[i + |s|]`` (up), ``fill`` where
    that falls outside."""
    if abs(s) >= x.shape[0]:
        return jnp.full_like(x, fill)
    pad = jnp.full((abs(s),) + x.shape[1:], fill, x.dtype)
    if s > 0:
        return jnp.concatenate([pad, x[:-s]], axis=0)
    return jnp.concatenate([x[-s:], pad], axis=0)


def _compact(x, keep, fill):
    """Move the kept rows of each lane to its top rows, in order: row
    ``i`` rises by ``d_i``, the rows dropped above it, one bit of
    ``d_i`` per stage from the lowest.  ``d`` never falls along the kept
    rows, so two never meet in a stage and their order holds.  Rows past
    the kept count hold leftovers."""
    n = x.shape[0]
    drop = (~keep).astype(jnp.int32)
    d, s = drop, 1
    while s < n:                                  # inclusive prefix count
        d = d + _shift(d, s, 0)
        s *= 2
    d = jnp.where(keep, d - drop, 0)
    s = 1
    while s < n:
        moves = keep & ((d & s) != 0)
        lands = _shift(moves, -s, False)
        x = jnp.where(lands, _shift(x, -s, fill), x)
        d = jnp.where(lands, _shift(d, -s, 0), d)
        keep = lands | (keep & ~moves)
        s *= 2
    return x


def _place(x, n, width: int, fill):
    """Rows ``0..`` of each lane moved down to start at row ``n[lane]``,
    cut to ``width`` rows: one static shift per bit of ``n``."""
    if x.shape[0] >= width:
        x = x[:width]
    else:
        pad = jnp.full((width - x.shape[0],) + x.shape[1:], fill, x.dtype)
        x = jnp.concatenate([x, pad], axis=0)
    for b in range(max(width, 1).bit_length()):
        x = jnp.where(((n >> b) & 1)[None, :] != 0, _shift(x, 1 << b, fill), x)
    return x


@functools.partial(
    jax.jit, static_argnames=("height", "max_rounds", "max_out", "pmask"))
def ref_delta_scan_fused(value: jax.Array, mark: jax.Array, child: jax.Array,
                         root: jax.Array, starts: jax.Array, his: jax.Array,
                         *, height: int, max_rounds: int, max_out: int,
                         pmask: int = 0):
    """Fused leaf-run scan frontier, XLA-compiled: the whole loop in one
    program (contract of ``ops.delta_scan``).

    Each lane fills ``out[lane, :]`` with the live *leaf* values in
    ``(start, hi]`` in key order (packed, ascending; ``walk_big`` pads
    unused slots).  ``starts`` and ``his`` are packed ``qpack`` bounds:
    start exclusive, hi inclusive in key space (``v > start_q`` iff
    ``key(v) > start_key`` since qpack packs an all-ones payload).

    One pass kind, one ΔNode row per lane per round.  A lane carries a
    descent query ``q`` (first the start) and a region bound ``U``, the
    smallest left-turn router of the rows above its ΔNode: the first key
    past the ΔNode's region.  Each round it reads its whole row (value,
    mark, child) in in-order (``layout.inorder_tables``), descends it
    blind for ``q`` to the landing leaf, and takes the *run*: the
    key-leaves (occupied, not internal, not markers — I1, I3; sorted by
    I4) from the landing up to the first marker at or after it.  The
    live ones in band are emitted at columns ``n…`` through a prefix
    count, tombstones skipped.  Then the lane
    * hops to the marker's child with the same ``q`` (every key there
      exceeds it, so the child is entered at its leftmost leaf), folding
      the row's left-turn routers above the marker into ``U``; a landing
      on a marker is an empty run and such a hop; or
    * at the end of its ΔNode restarts at its own root seed with
      ``q = U`` taken inclusive (``U | pmask``) and ``U`` reset, or is
      done when no bound is left or ``U > hi``.
    A lane is also done when its run passes a key above ``hi``, or holds
    a live in-band key past ``max_out`` (``more``); a buffer that fills
    exactly at a run's end goes on until it finds the next live in-band
    key or shows there is none.

    The rows ride slot-major (slots × lanes), so the prefix count, the
    compaction and the placement are static row shifts, O(K × UB) per
    round.  Overflow buffers are NOT consulted — the engine dispatch
    merges I5' buffered items into the emitted run
    (``repro.core.engine``), so both engines share one merge and stay
    bit-identical.

    Returns (out (K, max_out) packed, n (K,) int32, hops (K,) int32,
    more (K,) bool).  ``hops`` counts ΔNode visits — exactly the rounds
    the lane stayed active, matching ``delta_walk``'s accounting.
    ``more`` marks lanes that left out a live in-band item; the
    continuation cursor is the last emitted key (``key_of(out[lane,
    n-1])``).  A lane whose start equals ``walk_big`` is born done (the
    q_tile pad contract).
    """
    from repro.kernels.veb_search import walk_big

    h = height
    ub = 2 ** h - 1
    tab = layout.inorder_tables(h)
    storage = jnp.asarray(tab["storage"])
    bottom = jnp.asarray(tab["bottom"])[:, None]
    m = value.shape[0]
    big = jnp.asarray(walk_big(value.dtype), value.dtype)
    starts = starts.astype(value.dtype)
    his = his.astype(value.dtype)
    k = his.shape[0]
    dn0 = jnp.broadcast_to(jnp.asarray(root, jnp.int32), (k,))
    pm = jnp.asarray(pmask, value.dtype)
    rank = jnp.arange(ub, dtype=jnp.int32)[:, None]
    col = jnp.arange(max_out, dtype=jnp.int32)[:, None]

    state = dict(
        dn=dn0,
        q=starts,
        bound=jnp.full((k,), big, value.dtype),
        out=jnp.full((max_out, k), big, value.dtype),
        n=jnp.zeros((k,), jnp.int32),
        hops=jnp.zeros((k,), jnp.int32),
        more=jnp.zeros((k,), jnp.bool_),
        done=starts == big,             # sentinel lanes born done
        rounds=jnp.int32(0),
    )

    def cond(s):
        return jnp.any(~s["done"]) & (s["rounds"] < max_rounds)

    def body(s):
        act = ~s["done"]
        n, bound = s["n"], s["bound"]
        dnc = jnp.clip(s["dn"], 0, m - 1)
        x = jnp.take(value, dnc, axis=0).T[storage]      # (UB, K) in-order
        dead = jnp.take(mark, dnc, axis=0).T[storage]
        ch = jnp.take(child, dnc, axis=0).T              # ((UB + 1) // 2, K)
        # blind descent in rank space: the landing is the last occupied
        r = jnp.full((k,), 2 ** (h - 1) - 1, jnp.int32)
        land = r
        for d in range(h):
            v = _pick_rank(x, r)
            land = jnp.where(v != EMPTY, r, land)
            if d < h - 1:
                off = 2 ** (h - 2 - d)
                r = jnp.where(s["q"] >= v, r + off, r - off)
        occ = x != EMPTY
        marker = bottom & occ & (jnp.repeat(ch, 2, axis=0)[:ub] >= 0)
        internal = ~bottom & occ[tab["left"]]
        after = rank >= land[None, :]
        stop = jnp.min(jnp.where(marker & after, rank, ub), axis=0)
        run = (after & (rank < stop[None, :]) & occ & ~internal & ~marker
               & (x != big))
        emit = (run & ~dead & (x > starts[None, :]) & (x <= his[None, :])
                & act[None, :])
        count = jnp.sum(emit, axis=0, dtype=jnp.int32)
        room = max_out - n
        took = jnp.minimum(count, room)
        out = jnp.where((col >= n[None, :]) & (col < (n + took)[None, :]),
                        _place(_compact(x, emit, big), n, max_out, big),
                        s["out"])
        full = act & (count > room)
        past_hi = jnp.any(run & (x > his[None, :]), axis=0)
        # next region: the marker's child, or back to the root for U
        hop = stop < ub
        j = stop // 2
        nxt = _pick_rank(ch, j)
        fold = bound
        for d in range(h - 1):
            sh = h - 1 - d
            a = ((2 * (j >> sh) + 1) << sh) - 1        # ancestor's rank
            v = _pick_rank(x, a)
            fold = jnp.where((stop < a) & (v < fold), v, fold)
        spent = (bound == big) | (bound > his)
        done_now = act & (full | past_hi | (~hop & spent))
        go = act & ~done_now
        restart = go & ~hop
        return dict(
            dn=jnp.where(go & hop, nxt, jnp.where(restart, dn0, s["dn"])),
            q=jnp.where(restart, bound | pm, s["q"]),
            bound=jnp.where(go & hop, fold, jnp.where(restart, big, bound)),
            out=out,
            n=n + took,
            hops=s["hops"] + act.astype(jnp.int32),
            more=s["more"] | full,
            done=s["done"] | done_now,
            rounds=s["rounds"] + 1,
        )

    s = jax.lax.while_loop(cond, body, state)
    return s["out"].T, s["n"], s["hops"], s["more"]


@functools.partial(jax.jit, static_argnames=("height",))
def ref_delta_search(value: jax.Array, child: jax.Array, root: jax.Array,
                     queries: jax.Array, *, height: int):
    """Oracle for the multi-hop ΔTree search over (value, child) arena rows.

    Returns (leaf_val, leaf_b, final_dn) per query — identical contract to
    `kernels.ops.delta_search`.
    """
    pos = jnp.asarray(layout.veb_pos_table(height))
    bottom0 = 2 ** (height - 1)

    def one(v):
        def cond(s):
            return ~s[2]

        def body(s):
            dn, b, _ = s
            at_bottom = b >= bottom0
            left = jnp.where(
                at_bottom, EMPTY, value[dn, pos[jnp.minimum(2 * b, 2 * bottom0 - 1)]]
            )
            internal = (~at_bottom) & (left != EMPTY)
            router = value[dn, pos[b]]
            slot = jnp.where(at_bottom, b - bottom0, 0)
            ch = jnp.where(at_bottom, child[dn, slot], jnp.int32(-1))
            hop = at_bottom & (ch >= 0)
            nb = jnp.where(internal, 2 * b + (v >= router).astype(jnp.int32), b)
            nb = jnp.where(hop, jnp.int32(1), nb)
            ndn = jnp.where(hop, ch, dn)
            done = (~internal) & (~hop)
            return ndn, nb, done

        dn, b, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(root), jnp.int32(1), jnp.bool_(False))
        )
        return value[dn, pos[b]], b, dn

    return jax.vmap(one)(queries)


@jax.jit
def ref_paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               seq_lens: jax.Array):
    """Oracle for ΔTree-paged decode attention.

    q:            (B, QH, D)
    k/v_pages:    (NP, KVH, PS, D)
    block_tables: (B, MAXP) int32 physical page ids (-1 = unused)
    seq_lens:     (B,) int32

    Gathers each sequence's pages into a contiguous (S, KVH, D) cache, then
    runs masked GQA decode attention in f32. Returns (B, QH, D) in q.dtype.
    """
    b, qh, d = q.shape
    np_, kvh, ps, _ = k_pages.shape
    maxp = block_tables.shape[1]
    g = qh // kvh
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    bt = jnp.maximum(block_tables, 0)
    k = k_pages[bt].swapaxes(2, 3)  # (B, MAXP, PS, KVH, D)
    v = v_pages[bt].swapaxes(2, 3)
    k = k.reshape(b, maxp * ps, kvh, d).astype(jnp.float32)
    v = v.reshape(b, maxp * ps, kvh, d).astype(jnp.float32)

    qf = q.reshape(b, kvh, g, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, k) * scale
    mask = jnp.arange(maxp * ps)[None, :] < seq_lens[:, None]  # (B, S)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v)
    return out.reshape(b, qh, d).astype(q.dtype)
