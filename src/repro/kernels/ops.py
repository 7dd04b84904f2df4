"""jit'd public wrappers around the Pallas kernels.

- `delta_walk`         — multi-round lockstep walk: every active query
  descends its current ΔNode fully (one contiguous row DMA — the paper's
  "memory transfer"), hops to the child ΔNode, repeats until it lands on
  its leaf.  Reports per-query hop counts (= rounds active = ΔNodes
  visited) and the folded successor candidate.  ``root`` may be per-query
  (multi-root seeding over a `veb_search.fuse_arenas` view — the fused
  forest frontier, DESIGN.md §8).  This is the engine room of the
  ``"lockstep"`` SearchEngine (repro.core.engine).  Two drivers share the
  contract bit for bit:
    * fused (default): ALL rounds inside one launch —
      `veb_search.veb_walk_fused` (persistent Pallas kernel, arena
      resident in VMEM) where the rule in `_fused_pallas_ok` admits it,
      else the XLA-compiled `kernels.ref.ref_delta_walk_fused` — which is
      what every arena past `FUSED_VMEM_BUDGET_BYTES` runs on TPU;
      `walk_impl` / `scan_impl` name the one a given arena gets;
    * per-round (``fused=False``): the original
      pallas_call-inside-``lax.while_loop`` — one `veb_walk_rows` launch
      per frontier round over XLA-gathered rows; retained as the parity
      oracle (no VMEM budget: it never holds the arena).
- `delta_search`       — legacy 3-tuple contract on top of `delta_walk`.
- `delta_contains`     — paper SEARCHNODE set semantics on top (mark bit +
  overflow buffer check).
- `paged_decode_attention` — re-exported from delta_paged_attention.

Execution-mode resolution (``interpret=None`` everywhere): Pallas compiled
on TPU, interpret mode elsewhere, overridable per call (``interpret=``) or
process-wide via ``REPRO_PALLAS_INTERPRET=0/1``.  Outside interpret mode
Pallas only lowers on TPU (and never for packed int64 rows), so every
compiled non-TPU walk routes through the XLA-compiled jnp mirrors
(`ref_delta_walk_fused` / `ref_veb_walk_rows`) — same round structure,
same bits, no interpreter tax.  ``max_rounds=None`` derives the round cap
from the arena geometry at trace time (`walk_round_cap`), so shallow
trees never carry the historical 64-round bound.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from repro.core import layout
from repro.kernels.delta_paged_attention import paged_decode_attention  # noqa: F401
from repro.kernels.veb_search import (
    _round_up, pad_arena, veb_scan_fused, veb_walk_fused, veb_walk_rows,
    walk_big,
)


def default_interpret() -> bool:
    """Auto-detected Pallas mode: compiled on TPU, interpret elsewhere.

    ``REPRO_PALLAS_INTERPRET=1`` forces interpret mode (kernel debugging on
    TPU), ``=0`` forces compiled lowering; unset (or set empty) defers to
    the backend so TPU runs stop silently paying the interpreter tax."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip()
    if env:
        return env.lower() not in ("0", "false", "no")
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def default_fused() -> bool:
    """Walk-driver default: the fused single-launch walk everywhere
    (bit-identical to the per-round driver; the parity suite pins it).
    ``REPRO_PALLAS_FUSED=0`` flips the process to the per-round driver,
    for kernel debugging."""
    env = os.environ.get("REPRO_PALLAS_FUSED", "").strip()
    if env:
        return env.lower() not in ("0", "false", "no")
    return True


def _resolve_fused(fused: bool | None) -> bool:
    return default_fused() if fused is None else bool(fused)


def walk_round_cap(height: int, max_dnodes: int) -> int:
    """Trace-time walk round bound derived from the arena geometry,
    replacing the historical fixed ``max_rounds=64``.

    An arena of M ΔNodes holds at most ``M * 2**(height-1)`` leaves, so a
    *balanced* ΔNode tree is ``ceil(log2(M * leaf_cap) / (height-1))``
    ΔNodes deep; maintenance (Rebalance/Expand/Merge) keeps the tree
    within a constant factor of that, and the cap doubles the balanced
    depth and adds slack for overflow-chase hops mid-maintenance.  The
    structural depth assertion in ``check_invariants`` and the
    never-hit-the-cap test pin the bound; compiled fused kernels size
    their in-kernel loop with it, so shallow trees stop paying 64 dead
    iterations of lowered loop body.
    """
    leaf_cap = 2 ** (height - 1)
    balanced = math.ceil(
        math.log2(max(max_dnodes, 2) * leaf_cap) / max(height - 1, 1))
    return 2 * balanced + 8


def _resolve_max_rounds(max_rounds: int | None, height: int,
                        max_dnodes: int) -> int:
    if max_rounds is None:
        return walk_round_cap(height, max_dnodes)
    return int(max_rounds)


def _check_q_tile(tile: int, origin: str, lane_aligned: bool) -> int:
    """Shared q_tile validation: positive everywhere; the process-wide
    production knob (``REPRO_PALLAS_QTILE``) additionally requires a
    multiple of 128 so the compiled Pallas block shape stays lane-aligned.
    Explicit per-call tiles stay lenient — tests and interpret-mode runs
    legitimately use small tiles (16/64)."""
    tile = int(tile)
    bad = tile <= 0 or (lane_aligned and tile % 128)
    if bad:
        want = "positive multiple of 128" if lane_aligned else "positive"
        raise ValueError(f"q_tile must be {want}, got {tile} ({origin})")
    return tile


# Query tiles by (height, compiled, bits): `default_q_tile`'s table.  The
# tiles were chosen by timing walks on the CPU; on the chip they only set
# the batch padding of the XLA mirrors (which are tile-free otherwise),
# and no cell has yet measured them on the Pallas grid.  Keys absent here
# (every interpret-mode key among them) fall through to 256.
Q_TILES: dict[tuple[int, bool, int], int] = {
    (5, True, 32): 1024,
    (7, True, 32): 256,
    (9, True, 32): 512,
    (7, True, 64): 512,
}


def default_q_tile(height: int | None = None,
                   payload_bits: int = 0) -> int:
    """Lockstep kernel query tile: ``REPRO_PALLAS_QTILE`` env override,
    else the `Q_TILES` entry for this height and execution mode, else 256
    (two VREG lanes' worth)."""
    env = os.environ.get("REPRO_PALLAS_QTILE", "").strip()
    if env:
        try:
            tile = int(env)
        except ValueError:
            raise ValueError(
                f"REPRO_PALLAS_QTILE must be an integer, got {env!r}"
            ) from None
        return _check_q_tile(tile, f"REPRO_PALLAS_QTILE={env!r}",
                             lane_aligned=True)
    if height is not None:
        key = (height, not default_interpret(), 64 if payload_bits else 32)
        return Q_TILES.get(key, 256)
    return 256


def _resolve_q_tile(q_tile: int | None, height: int | None = None,
                    payload_bits: int = 0) -> int:
    if q_tile is None:
        return default_q_tile(height, payload_bits)
    return _check_q_tile(q_tile, "explicit q_tile", lane_aligned=False)


def _pallas_lowers(dtype, interpret: bool) -> bool:
    """Whether the Pallas walk kernels can actually run: always in
    interpret mode; compiled only on TPU and never for packed int64 rows
    (checked at trace time — compiled non-TPU walks MUST route to the
    XLA jnp mirrors or pallas_call raises at lowering)."""
    if interpret:
        return True
    return jax.default_backend() == "tpu" and jnp.dtype(dtype) != jnp.int64


# Compiled fused-kernel VMEM budget, found by compiling `veb_walk_fused`
# and `veb_scan_fused` for a TPU v5e (128 MiB of VMEM per core) under the
# kernels' explicit scoped limit (`veb_search.FUSED_VMEM_LIMIT_BYTES`,
# 120 MiB).  The arena planes a kernel keeps resident, plus a working set
# of `_TILE_ROWS` row tiles (q_tile x the padded row bytes, the scan's
# output row included), must fit in it.  Heights 5-9 at q_tile 256-1024
# compile at this edge, and the working-set term is what keeps the wide
# tiles inside the limit (tests/test_tpu_compile.py compiles the edge).
# Past it the fused driver runs the XLA mirror (`ref.ref_delta_walk_fused`
# / `ref.ref_delta_scan_fused`): at height 7 that is every arena over
# ~92k ΔNodes (walk) or ~60k (scan) — every deployment-sized index.
FUSED_VMEM_BUDGET_BYTES = 96 * 1024 * 1024
_TILE_ROWS = 16


def fused_arena_cap(widths, q_tile: int, out_width: int = 0) -> int:
    """Most arena rows (ΔNodes) a compiled fused kernel may keep resident:
    planes of the given ``widths`` (lane-padded int32 — int64 never
    lowers) must fit the budget next to `_TILE_ROWS` (q_tile, row) tiles,
    a row of the tile also holding ``out_width`` output lanes."""
    row = 4 * sum(_round_up(w, 128) for w in widths)
    tile_row = row + 4 * _round_up(out_width, 128)
    return (FUSED_VMEM_BUDGET_BYTES - _TILE_ROWS * q_tile * tile_row) // row


def _fused_pallas_ok(value, child, q_tile: int, interpret: bool,
                     max_out: int | None = None) -> bool:
    """The one rule choosing a fused Pallas kernel over its XLA mirror — a
    rule on dtype and arena size, decided at trace time.  The walk keeps
    the value and child planes resident; a scan (``max_out`` given) adds
    the mark plane, as wide as the value plane, and an output tile."""
    if not _pallas_lowers(value.dtype, interpret):
        return False
    widths = (value.shape[1], child.shape[1])
    if max_out is not None:
        widths += (value.shape[1],)
    return interpret or value.shape[0] <= fused_arena_cap(
        widths, q_tile, max_out or 0)


def walk_impl(value, child, *, height: int, q_tile: int | None = None,
              interpret: bool | None = None) -> str:
    """Which fused walk `delta_walk` runs over this arena under the same
    resolution rules: ``"veb_walk_fused"`` (Pallas) or
    ``"ref_delta_walk_fused"`` (the XLA mirror)."""
    q_tile = _resolve_q_tile(q_tile, height,
                             0 if value.dtype == jnp.int32 else 1)
    ok = _fused_pallas_ok(value, child, q_tile, _resolve_interpret(interpret))
    return "veb_walk_fused" if ok else "ref_delta_walk_fused"


def scan_impl(value, child, *, height: int, max_out: int,
              q_tile: int | None = None,
              interpret: bool | None = None) -> str:
    """Which fused scan `delta_scan` runs over this arena:
    ``"veb_scan_fused"`` (Pallas) or ``"ref_delta_scan_fused"`` (XLA)."""
    q_tile = _resolve_q_tile(q_tile, height,
                             0 if value.dtype == jnp.int32 else 1)
    ok = _fused_pallas_ok(value, child, q_tile, _resolve_interpret(interpret),
                          max_out)
    return "veb_scan_fused" if ok else "ref_delta_scan_fused"


def _row_walk(rows, childrows, queries, *, height, q_tile, interpret):
    """One lockstep round: the Pallas kernel, or its compiled jnp mirror
    wherever the kernel cannot lower (any compiled non-TPU backend, and
    int64 packed rows outside interpret mode)."""
    if not _pallas_lowers(rows.dtype, interpret):
        from repro.kernels.ref import ref_veb_walk_rows

        return ref_veb_walk_rows(rows, childrows, queries, height=height)
    return veb_walk_rows(rows, childrows, queries, height=height,
                         q_tile=q_tile, interpret=interpret)


def delta_walk(value: jax.Array, child: jax.Array, root: jax.Array,
               queries: jax.Array, *, height: int, q_tile: int | None = None,
               max_rounds: int | None = None, interpret: bool | None = None,
               fused: bool | None = None):
    """Multi-hop ΔTree walk in lockstep rounds over the query frontier.

    value/child are unpadded arena arrays (value int32, or int64 packed map
    mode); ``queries`` are *packed* values in the same dtype (`cfg.qpack`).
    ``root`` is either a scalar (single-arena walk) or a per-query (K,)
    int32 array of frontier seeds — the multi-root form drives one fused
    frontier across several concatenated arenas (`veb_search.fuse_arenas`
    base-offset view, each query seeded at its owner shard's root).
    Rows are 128-padded here; the query batch is padded to a ``q_tile``
    multiple with a ROUTE_LEFT sentinel that provably matches no stored
    leaf, and padded lanes start *resolved* so they never contribute a
    round to the termination test.  The same sentinel contract extends to
    *real* lanes: a query equal to ``walk_big(dtype)`` (the reserved
    ROUTE_LEFT key, packed) is born resolved — hops 0, miss leaf, no
    successor candidate — which is what lets the forest router pad its
    dense per-shard lanes without buying them a full walk.

    ``interpret=None`` resolves via `default_interpret` *at call time*
    (env/backend changes are honored between calls); callers that trace
    this under an outer jit bake the mode at their own trace time.
    ``q_tile=None`` resolves via `default_q_tile` the same way
    (``REPRO_PALLAS_QTILE`` env override, else the `Q_TILES` table,
    else 256).  ``fused=None`` resolves via
    `default_fused` (``REPRO_PALLAS_FUSED`` override, else the fused
    single-launch driver); ``max_rounds=None`` derives the round cap
    from the arena geometry (`walk_round_cap`).

    Returns per query (batch-padding sliced off):
      leaf_val: packed value at the final position (EMPTY on miss)
      leaf_b:   final BFS position in the final ΔNode
      final_dn: final ΔNode id
      hops:     rounds the query stayed active = ΔNodes visited — exactly
                the scalar engine's `_descend` transfer statistic
      cand:     min left-turn router over the whole walk (successor lower
                bound; ``walk_big(dtype)`` = the dtype's ROUTE_LEFT when no
                left turn happened)
    """
    q_tile = _resolve_q_tile(
        q_tile, height, 0 if value.dtype == jnp.int32 else 1)
    max_rounds = _resolve_max_rounds(max_rounds, height, value.shape[0])
    interpret = _resolve_interpret(interpret)
    if _resolve_fused(fused):
        return _delta_walk_fused(value, child, root, queries,
                                 height=height, q_tile=q_tile,
                                 max_rounds=max_rounds,
                                 interpret=interpret)
    return _delta_walk(value, child, root, queries, height=height,
                       q_tile=q_tile, max_rounds=max_rounds,
                       interpret=interpret)


def delta_walk_fused(value: jax.Array, child: jax.Array, root: jax.Array,
                     queries: jax.Array, *, height: int,
                     q_tile: int | None = None,
                     max_rounds: int | None = None,
                     interpret: bool | None = None):
    """`delta_walk` pinned to the fused single-launch driver (ignores the
    ``REPRO_PALLAS_FUSED`` process default) — the explicit entry point for
    parity tests."""
    return delta_walk(value, child, root, queries, height=height,
                      q_tile=q_tile, max_rounds=max_rounds,
                      interpret=interpret, fused=True)


@functools.partial(
    jax.jit, static_argnames=("height", "q_tile", "max_rounds", "interpret")
)
def _delta_walk_fused(value, child, root, queries, *, height, q_tile,
                      max_rounds, interpret: bool):
    """Fused driver: every walk round inside ONE launch.

    Pallas persistent kernel where it lowers (interpret mode anywhere;
    compiled on TPU for int32 arenas within the VMEM budget), else the
    XLA-compiled blind-descent mirror `ref_delta_walk_fused` — the
    compiled non-TPU (and int64 / oversized-arena) fused path.  Both are
    bit-identical to the per-round driver, per-query ``hops`` included.
    """
    queries = queries.astype(value.dtype)
    k = queries.shape[0]
    dn0 = jnp.broadcast_to(jnp.asarray(root, jnp.int32), (k,))
    if not _fused_pallas_ok(value, child, q_tile, interpret):
        from repro.kernels.ref import ref_delta_walk_fused

        # big-sentinel lanes are born resolved inside the mirror; no
        # q_tile padding — XLA has no tile-shape constraint to satisfy
        return ref_delta_walk_fused(value, child, dn0, queries,
                                    height=height, max_rounds=max_rounds)
    value_p, child_p = pad_arena(value, child)
    kp = (k + q_tile - 1) // q_tile * q_tile
    qpad = jnp.pad(queries, (0, kp - k),
                   constant_values=walk_big(value.dtype))
    dnpad = jnp.pad(dn0, (0, kp - k))
    out = veb_walk_fused(value_p, child_p, dnpad, qpad, height=height,
                         q_tile=q_tile, max_rounds=max_rounds,
                         interpret=interpret)
    return tuple(o[:k] for o in out)


@functools.partial(
    jax.jit, static_argnames=("height", "q_tile", "max_rounds", "interpret")
)
def _delta_walk(value, child, root, queries, *, height, q_tile, max_rounds,
                interpret: bool):
    value_p, child_p = pad_arena(value, child)
    queries = queries.astype(value.dtype)
    k = queries.shape[0]
    kp = (k + q_tile - 1) // q_tile * q_tile
    big = jnp.asarray(walk_big(value.dtype), value.dtype)
    qpad = jnp.pad(queries, (0, kp - k), constant_values=walk_big(value.dtype))
    # scalar root broadcasts (single arena); a (K,) array seeds each query
    # at its own root (fused multi-arena frontier)
    dn0 = jnp.pad(jnp.broadcast_to(jnp.asarray(root, jnp.int32), (k,)),
                  (0, kp - k))

    state = dict(
        dn=dn0,
        # padding lanes AND sentinel-keyed real lanes (router pads) are
        # born resolved: they never gate termination nor count a hop
        resolved=(jnp.arange(kp) >= k) | (qpad == big),
        leaf_val=jnp.zeros((kp,), value.dtype),
        leaf_b=jnp.ones((kp,), jnp.int32),
        final_dn=dn0,
        hops=jnp.zeros((kp,), jnp.int32),
        cand=jnp.full((kp,), big, value.dtype),
        rounds=jnp.int32(0),
    )

    def cond(s):
        return jnp.any(~s["resolved"]) & (s["rounds"] < max_rounds)

    def body(s):
        dnc = jnp.clip(s["dn"], 0, value.shape[0] - 1)
        rows = value_p[dnc]      # (K, UBp) — the per-query ΔNode DMA
        childrows = child_p[dnc]
        lv, lb, nxt, rcand = _row_walk(
            rows, childrows, qpad, height=height, q_tile=q_tile,
            interpret=interpret,
        )
        act = ~s["resolved"]
        done_now = act & (nxt < 0)
        return dict(
            dn=jnp.where(act & (nxt >= 0), nxt, s["dn"]),
            resolved=s["resolved"] | done_now,
            leaf_val=jnp.where(done_now, lv, s["leaf_val"]),
            leaf_b=jnp.where(done_now, lb, s["leaf_b"]),
            final_dn=jnp.where(done_now, s["dn"], s["final_dn"]),
            hops=s["hops"] + act.astype(jnp.int32),
            cand=jnp.where(act & (rcand < s["cand"]), rcand, s["cand"]),
            rounds=s["rounds"] + 1,
        )

    state = jax.lax.while_loop(cond, body, state)
    return (state["leaf_val"][:k], state["leaf_b"][:k],
            state["final_dn"][:k], state["hops"][:k], state["cand"][:k])


def scan_round_cap(height: int, max_dnodes: int) -> int:
    """Trace-time round bound for the leaf-run scan frontier, sound for
    any band and any share of tombstones: a lane finishes each ΔNode at
    most once, and then descends from its root again (at most
    `walk_round_cap` rounds), so it makes at most ``max_dnodes + 1``
    descents, plus one hop into each child it enters from a run.  The
    in-kernel loop exits as soon as every lane is done, so the cap only
    bounds the lowered loop."""
    cap = (max_dnodes + 1) * (walk_round_cap(height, max_dnodes) + 1)
    return min(cap, 2**31 - 1)


def delta_scan(value: jax.Array, mark: jax.Array, child: jax.Array,
               root: jax.Array, starts: jax.Array, his: jax.Array, *,
               height: int, max_out: int, pmask: int = 0,
               q_tile: int | None = None, max_rounds: int | None = None,
               interpret: bool | None = None):
    """Ordered range/successor-k scan in lockstep rounds over the lane
    frontier — the leaf-run variant of `delta_walk` (ONE dispatch for
    the whole scan, every round inside a single launch).

    value/mark/child are unpadded arena arrays; ``starts``/``his`` are
    *packed* ``qpack`` bounds per lane (start exclusive, hi inclusive in
    key space).  ``root`` is scalar or per-lane (K,) seeds — the
    multi-root form drives one fused scan across concatenated shard
    arenas (`veb_search.fuse_arenas`), each lane emitting its owner
    shard's band and restarting at its own seed.  A lane whose start
    equals ``walk_big(dtype)`` is born done (the router's pad-lane
    contract).

    Each round a lane reads its ΔNode's row once, descends it, and emits
    the whole run of live in-band key-leaves from its landing up to the
    first marker; then it hops to that marker's child, or restarts at
    its root for the next region — O(log_B N + k/B) rows per scan, not a
    root walk per key (pass logic documented on the mirror).

    Single-launch discipline matches `delta_walk`: the persistent Pallas
    kernel `veb_search.veb_scan_fused` where it lowers (interpret mode
    anywhere; compiled on TPU for int32 arenas within the VMEM budget),
    else the XLA-compiled mirror `ref.ref_delta_scan_fused` — both
    bit-identical.

    Returns per lane (pad width sliced off):
      out:  (K, max_out) packed live *leaf* values in (start, hi], key
            ascending, ``walk_big`` padding (overflow buffers are merged
            by the engine dispatch — I5' correctness lives there)
      n:    emitted count
      hops: ΔNode rows read, the rounds the lane stayed active
            (`delta_walk` accounting)
      more: bool — a live in-band item was left out; resume from
            ``key_of(out[lane, n-1])``
    """
    q_tile = _resolve_q_tile(
        q_tile, height, 0 if value.dtype == jnp.int32 else 1)
    if max_rounds is None:
        max_rounds = scan_round_cap(height, value.shape[0])
    interpret = _resolve_interpret(interpret)
    return _delta_scan(value, mark, child, root, starts, his,
                       height=height, max_out=max_out, pmask=pmask,
                       q_tile=q_tile, max_rounds=int(max_rounds),
                       interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("height", "max_out", "pmask", "q_tile",
                              "max_rounds", "interpret")
)
def _delta_scan(value, mark, child, root, starts, his, *, height, max_out,
                pmask, q_tile, max_rounds, interpret: bool):
    starts = starts.astype(value.dtype)
    his = his.astype(value.dtype)
    k = starts.shape[0]
    dn0 = jnp.broadcast_to(jnp.asarray(root, jnp.int32), (k,))
    if not _fused_pallas_ok(value, child, q_tile, interpret, max_out):
        from repro.kernels.ref import ref_delta_scan_fused

        return ref_delta_scan_fused(value, mark, child, dn0, starts, his,
                                    height=height, max_rounds=max_rounds,
                                    max_out=max_out, pmask=pmask)
    value_p, child_p = pad_arena(value, child)
    mark_p = jnp.pad(mark.astype(jnp.int32),
                     ((0, 0), (0, value_p.shape[1] - mark.shape[1])))
    kp = (k + q_tile - 1) // q_tile * q_tile
    big = walk_big(value.dtype)
    spad = jnp.pad(starts, (0, kp - k), constant_values=big)
    hpad = jnp.pad(his, (0, kp - k), constant_values=big)
    dnpad = jnp.pad(dn0, (0, kp - k))
    out, n, hops, more = veb_scan_fused(
        value_p, mark_p, child_p, dnpad, spad, hpad, height=height,
        max_out=max_out, pmask=pmask, q_tile=q_tile, max_rounds=max_rounds,
        interpret=interpret)
    return (out[:k, :max_out], n[:k], hops[:k],
            more[:k].astype(jnp.bool_))


def delta_search(value: jax.Array, child: jax.Array, root: jax.Array,
                 queries: jax.Array, *, height: int, q_tile: int | None = None,
                 max_rounds: int | None = None,
                 interpret: bool | None = None, fused: bool | None = None):
    """Legacy 3-tuple walk: (leaf_val, leaf_b, final_dn) per query (same
    contract as `kernels.ref.ref_delta_search`); ``interpret=None`` /
    ``q_tile=None`` / ``max_rounds=None`` / ``fused=None`` = auto-resolved
    at call time like `delta_walk`."""
    lv, lb, dn, _, _ = delta_walk(
        value, child, root, queries,
        height=height, q_tile=q_tile, max_rounds=max_rounds,
        interpret=interpret, fused=fused,
    )
    return lv, lb, dn


def delta_contains(value: jax.Array, mark: jax.Array, child: jax.Array,
                   buf: jax.Array, root: jax.Array, queries: jax.Array, *,
                   height: int, q_tile: int | None = None,
                   max_rounds: int | None = None,
                   interpret: bool | None = None, fused: bool | None = None):
    """Paper SEARCHNODE on top of the kernel walk: leaf match & ~mark, else
    the ΔNode's overflow buffer (paper Fig. 8 lines 9..17)."""
    return _delta_contains(
        value, mark, child, buf, root, queries, height=height,
        q_tile=_resolve_q_tile(
            q_tile, height, 0 if value.dtype == jnp.int32 else 1),
        max_rounds=_resolve_max_rounds(max_rounds, height, value.shape[0]),
        interpret=_resolve_interpret(interpret),
        fused=_resolve_fused(fused))


@functools.partial(
    jax.jit,
    static_argnames=("height", "q_tile", "max_rounds", "interpret", "fused")
)
def _delta_contains(value, mark, child, buf, root, queries, *, height,
                    q_tile, max_rounds, interpret: bool, fused: bool):
    pos = jnp.asarray(layout.veb_pos_table(height))
    lv, lb, dn = delta_search(
        value, child, root, queries,
        height=height, q_tile=q_tile, max_rounds=max_rounds,
        interpret=interpret, fused=fused,
    )
    leaf_hit = lv == queries
    leaf_live = leaf_hit & ~mark[dn, pos[lb]]
    in_buf = jnp.any(buf[dn] == queries[:, None], axis=1)
    return jnp.where(leaf_hit, leaf_live, in_buf)
