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


@functools.partial(
    jax.jit, static_argnames=("height", "max_rounds", "max_out", "pmask"))
def ref_delta_scan_fused(value: jax.Array, mark: jax.Array, child: jax.Array,
                         root: jax.Array, starts: jax.Array, his: jax.Array,
                         *, height: int, max_rounds: int, max_out: int,
                         pmask: int = 0):
    """Fused emit-cursor scan frontier, XLA-compiled: the whole
    find/verify/emit loop in one program (contract of ``ops.delta_scan``).

    Each lane carries an emit cursor over the packed key space and fills
    ``out[lane, :]`` with the live *leaf* values in ``(start, hi]`` in key
    order (packed, ascending; ``walk_big`` pads unused slots).  ``starts``
    and ``his`` are packed ``qpack`` bounds: start exclusive, hi inclusive
    in key space (``v > start_q`` iff ``key(v) > start_key`` since qpack
    packs an all-ones payload).  A lane alternates two pass kinds over the
    same blind-descent round structure as ``ref_delta_walk_fused``:

    * FIND — a successor walk from the root for the cursor, folding
      left-turn routers plus the final live leaf into a candidate;
    * VERIFY — an exact walk for the candidate key (candidate routers may
      be tombstones); a live hit is emitted and becomes the new cursor, a
      dead one is chased (cursor advances past it without emitting).

    Overflow buffers are NOT consulted — the engine dispatch merges
    I5' buffered items into the emitted run (``repro.core.engine``), so
    both engines share one merge and stay bit-identical.

    Returns (out (K, max_out) packed, n (K,) int32, hops (K,) int32,
    more (K,) bool).  ``hops`` counts ΔNode visits across every pass —
    exactly the rounds the lane stayed active, matching ``delta_walk``'s
    accounting.  ``more`` marks lanes whose buffer filled with live items
    remaining; the continuation cursor is the last emitted key
    (``key_of(out[lane, n-1])``).  A lane whose start equals ``walk_big``
    is born done (the q_tile pad contract).
    """
    from repro.kernels.veb_search import walk_big

    h = height
    bottom0 = 2 ** (h - 1)
    m, ub = value.shape
    pos = jnp.asarray(layout.veb_pos_table(h))
    big = jnp.asarray(walk_big(value.dtype), value.dtype)
    starts = starts.astype(value.dtype)
    his = his.astype(value.dtype)
    k = starts.shape[0]
    vflat = value.reshape(-1)
    mflat = mark.reshape(-1)
    dn0 = jnp.broadcast_to(jnp.asarray(root, jnp.int32), (k,))
    pm = jnp.asarray(pmask, value.dtype)

    state = dict(
        dn=dn0,
        verify=jnp.zeros((k,), jnp.bool_),
        q=starts,                       # FIND: cursor_q; VERIFY: pending_q
        cursor=starts,                  # start / last emitted (packed qpack)
        cand=jnp.full((k,), big, value.dtype),
        out=jnp.full((k, max_out), big, value.dtype),
        n=jnp.zeros((k,), jnp.int32),
        hops=jnp.zeros((k,), jnp.int32),
        more=jnp.zeros((k,), jnp.bool_),
        done=starts == big,             # sentinel lanes born done
        rounds=jnp.int32(0),
    )

    def cond(s):
        return jnp.any(~s["done"]) & (s["rounds"] < max_rounds)

    def body(s):
        dnc = jnp.clip(s["dn"], 0, m - 1)
        base = dnc * ub
        v = s["q"]
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
        rcand = jnp.full((k,), big, value.dtype)
        for router, bi in zip(routers, bs):      # post-hoc candidate fold
            fold = ((router != EMPTY) & (bi != lb) & (v < router)
                    & (router < rcand))
            rcand = jnp.where(fold, router, rcand)
        at_bottom = lb >= bottom0
        slot = jnp.where(at_bottom, lb - bottom0, 0)
        ch = child.at[dnc, slot].get(mode="promise_in_bounds")
        nxt = jnp.where(at_bottom, ch, jnp.int32(-1))
        act = ~s["done"]
        hopping = act & (nxt >= 0)
        res = act & (nxt < 0)                    # pass resolved this round
        # pass-level candidate fold (FIND passes only)
        cand = jnp.where(act & ~s["verify"] & (rcand < s["cand"]),
                         rcand, s["cand"])
        leaf_mark = mflat.at[base + pos[lb]].get(mode="promise_in_bounds")
        leaf_live = (lv != EMPTY) & ~leaf_mark
        # FIND resolution: fold the final leaf, then accept / stop
        f_res = res & ~s["verify"]
        leaf_fold = f_res & leaf_live & (lv > s["cursor"]) & (lv < cand)
        cand = jnp.where(leaf_fold, lv, cand)
        f_none = f_res & ((cand == big) | (cand > his))
        pending = cand | pm                      # qpack of candidate key
        to_verify = f_res & ~f_none
        # VERIFY resolution: emit a live hit, chase a tombstone
        v_res = res & s["verify"]
        hit = v_res & leaf_live & ((lv | pm) == s["q"])
        can_emit = s["n"] < max_out
        emit = hit & can_emit
        full = hit & ~can_emit
        chase = v_res & ~hit
        col = jnp.arange(max_out, dtype=jnp.int32)[None, :]
        out = jnp.where(emit[:, None] & (col == s["n"][:, None]),
                        lv[:, None], s["out"])
        back_to_find = emit | chase
        restart = to_verify | back_to_find
        return dict(
            dn=jnp.where(hopping, nxt, jnp.where(restart, dn0, s["dn"])),
            verify=jnp.where(to_verify, True,
                             jnp.where(back_to_find, False, s["verify"])),
            q=jnp.where(to_verify, pending, s["q"]),
            cursor=jnp.where(back_to_find, s["q"], s["cursor"]),
            cand=jnp.where(restart, big, cand),
            out=out,
            n=s["n"] + emit.astype(jnp.int32),
            hops=s["hops"] + act.astype(jnp.int32),
            more=s["more"] | full,
            done=s["done"] | f_none | full,
            rounds=s["rounds"] + 1,
        )

    s = jax.lax.while_loop(cond, body, state)
    return s["out"], s["n"], s["hops"], s["more"]


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
