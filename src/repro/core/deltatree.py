"""ΔTree — locality-aware concurrent search tree (paper §3–4), batched for SPMD.

Semantics map (paper → this implementation; DESIGN.md §2 has the rationale):

- *wait-free Search* → searches in a step read the immutable pre-step
  snapshot; `search_batch` is fully vectorized (vmap) and touches no locks —
  trivially wait-free, linearized at the step boundary.
- *non-blocking Insert/Delete (CAS leaf-grow / mark-delete)* → a batch of K
  update ops is applied in deterministic batch order (the linearization
  order).  A grow-leaf is the paper's Fig. 9 CAS pair; a delete is the
  paper's mark-CAS (Fig. 9 line 18).
- *buffer + TAS lock + mirror* (paper §3, Fig. 9 lines 87..106) → inserts
  that reach a full bottom leaf append to the ΔNode's overflow ``buf``
  (the paper's ``rootbuffer``); the maintenance sweep (the "lock winner")
  drains buffers by Rebalance (rebuild into a functional mirror and swap —
  here: a pure-functional array update) or Expand (allocate child ΔNodes).
- *Merge* (paper Fig. 10) → a sparse childless ΔNode is unioned with its
  sibling subtree and the parent router is set to ``ROUTE_LEFT`` — the
  implicit-layout equivalent of the paper's grandparent-pointer splice.

Layout: each ΔNode stores a complete binary tree of height ``H`` in vEB
order (``layout.veb_pos_table``); the tree of ΔNodes is linked by int32
indices into a pre-allocated arena (the "dynamic vEB layout", paper §2.3).
Only bottom-row positions may carry child links.  Leaf-oriented BST routing:
``v < router ⇒ left`` where router = min of the right subtree.

MAP MODE (beyond-paper extension; used by the serving pager): with
``payload_bits > 0`` each stored "value" is an int64 ``key << bits |
payload``.  Ordering by packed value equals ordering by key, so routing is
unchanged; *queries* are packed with all-ones payload so that a query for
key k compares ``>=`` any stored packing of k (min-of-right-subtree routers
stay correct).  Equality tests compare ``key_of`` only.  With
``payload_bits == 0`` everything is int32 and byte-identical to the paper's
set semantics.

Occupancy invariants (checked by ``check_invariants`` in
tests/test_deltatree.py):
  I1. value==EMPTY ⇔ slot unoccupied; internal node ⇔ left child occupied.
  I2. an occupied odd position implies its even sibling is occupied.
  I3. child links only at bottom positions whose value is non-EMPTY
      (the value is a cosmetic marker; routing hops unconditionally).
  I4. in-order traversal of live leaves is strictly sorted and consistent
      with every router on the path.
  I5. under the default ``maintenance="eager"`` policy, after
      `update_batch` returns every buffer is empty (maintenance ran to
      fixpoint).  Non-eager policies (``repro.maintenance``) relax this to
  I5'. every buffered value's root descent lands in the ΔNode whose buffer
      holds it — which is what keeps `searchnode`'s final-ΔNode buffer
      probe (and hence every wait-free read) correct over pending items;
      `flush` restores I5.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layout
from repro.core.layout import EMPTY, ROUTE_LEFT
from repro.obs import trace as OT

NONE = jnp.int32(-1)


@dataclasses.dataclass(frozen=True)
class TreeConfig:
    """Static ΔTree parameters (hashable; closed over by jitted fns).

    height:       H; a ΔNode holds UB = 2**H - 1 slots (paper's UB).
    max_dnodes:   arena capacity M.
    buf_cap:      per-ΔNode overflow buffer length (paper: #threads).
    max_rounds:   safety bound on maintenance rounds per step.
    payload_bits: 0 = paper set semantics (int32); >0 = key→payload map
                  (int64 packed values, payload in the low bits).
    engine:       which registered SearchEngine serves the read path —
                  "scalar" (vmap-of-while_loop reference) or "lockstep"
                  (Pallas vEB walk kernel in frontier rounds); see
                  ``repro.core.engine``.  The lockstep engine also routes
                  the update path's position-finding through the kernel
                  (one frontier pass per round).  ``make_index`` callers
                  may pass ``engine="auto"``, which resolves to the
                  bench-table winner for the backend + execution mode
                  (``core.engine.resolve_engine``) before this config is
                  built — a constructed TreeConfig always names a real
                  registered engine.
    walk_fused:   lockstep walk driver: True (default) = the fused
                  single-launch walk (`kernels.ops.delta_walk_fused` —
                  all rounds inside one kernel/program); False = the
                  per-round pallas_call-in-while_loop driver (parity
                  oracle).  Bit-identical results either way.
    walk_rounds:  walk round cap; 0 (default) derives it from the arena
                  geometry at trace time (`kernels.ops.walk_round_cap`)
                  instead of the historical fixed 64 — see the
                  ``walk_round_cap`` property.
    maintenance:  maintenance policy string — "eager" (drain to fixpoint
                  inside every update step; the paper/default semantics),
                  "deferred" (maintenance only on ``flush``), or
                  "budgeted:K" (at most K ΔNode repairs per batch); see
                  ``repro.maintenance``.
    q_tile:       lockstep kernel query tile; 0 = auto (the
                  ``REPRO_PALLAS_QTILE`` env override, else 256).
    collect_stats: observability flag (``repro.obs``): stats-capable
                  reads (search/lookup, forest reads) return a trailing
                  ``ReadStats`` counter pytree.  Static, so the disabled
                  path traces exactly the pre-obs graph — byte-identical
                  lowered HLO (asserted by tests/test_obs.py).
    collect_transfers: sub-gate under ``collect_stats``: additionally
                  derive measured ideal-cache ``TransferStats``
                  (``repro.obs.transfers`` — distinct ΔNode visits and
                  distinct B-block touches per read batch) into
                  ``ReadStats.transfers``.  Separate knob because the
                  device-side descent replay costs real work per batch;
                  off (None leg) it adds nothing to the collect_stats
                  graph, and with collect_stats off the whole read path
                  still lowers byte-identical to the pre-obs graph.
    """

    height: int = 7           # UB = 127, the paper's best (page-sized) ΔNode
    max_dnodes: int = 1024
    buf_cap: int = 32
    max_rounds: int = 64
    payload_bits: int = 0
    parallel_updates: bool = True   # vectorized non-conflicting fast path
    engine: str = "scalar"    # read-path SearchEngine (core.engine registry)
    maintenance: str = "eager"  # scheduler policy (repro.maintenance)
    q_tile: int = 0           # lockstep kernel tile (0 = env/autotune)
    collect_stats: bool = False  # reads return ReadStats (repro.obs)
    collect_transfers: bool = False  # + measured TransferStats sub-gate
    walk_fused: bool = True   # fused single-launch walk driver
    walk_rounds: int = 0      # walk round cap (0 = derive from geometry)

    @property
    def walk_round_cap(self) -> int:
        """Round cap the lockstep walk traces with: the ``walk_rounds``
        override, else derived from (height, max_dnodes) — tight enough
        that compiled fused kernels carry no dead iterations, with the
        structural depth assertion in ``check_invariants`` pinning it."""
        if self.walk_rounds:
            return self.walk_rounds
        from repro.kernels.ops import walk_round_cap

        return walk_round_cap(self.height, self.max_dnodes)

    @property
    def maintenance_policy(self):
        """Parsed ``MaintenancePolicy`` (raises ValueError on a bad spec)."""
        from repro.maintenance.policy import parse_policy

        return parse_policy(self.maintenance)

    @property
    def ub(self) -> int:
        return 2**self.height - 1

    @property
    def leaf_cap(self) -> int:
        return 2 ** (self.height - 1)

    @property
    def bottom0(self) -> int:
        return 2 ** (self.height - 1)

    @property
    def half_cap(self) -> int:
        return self.leaf_cap // 2

    # ---- packing helpers (identity in set mode) ----

    @property
    def vdtype(self):
        return jnp.int64 if self.payload_bits else jnp.int32

    @property
    def pmask(self) -> int:
        return (1 << self.payload_bits) - 1

    @property
    def route_left(self):
        if self.payload_bits:
            return jnp.int64(1) << 62
        return jnp.int32(ROUTE_LEFT)

    def pack(self, key, payload):
        if not self.payload_bits:
            return jnp.asarray(key, jnp.int32)
        return (jnp.asarray(key, jnp.int64) << self.payload_bits) | (
            jnp.asarray(payload, jnp.int64) & self.pmask
        )

    def qpack(self, key):
        """Query packing: all-ones payload so q >= any stored pack of key."""
        if not self.payload_bits:
            return jnp.asarray(key, jnp.int32)
        return (jnp.asarray(key, jnp.int64) << self.payload_bits) | self.pmask

    def key_of(self, x):
        if not self.payload_bits:
            return x
        return (x >> self.payload_bits).astype(jnp.int32)

    def payload_of(self, x):
        if not self.payload_bits:
            return jnp.zeros_like(x)
        return (x & self.pmask).astype(jnp.int32)


class DeltaTree(NamedTuple):
    """Arena-of-ΔNodes pytree. All arrays are per-ΔNode rows."""

    value: jax.Array      # (M, UB) packed values, vEB storage order
    mark: jax.Array       # (M, UB) bool — logical deletion (paper Fig. 9 l.18)
    child: jax.Array      # (M, leaf_cap) int32 child ΔNode id per bottom slot, -1 = none
    buf: jax.Array        # (M, buf_cap) packed overflow buffer (paper rootbuffer)
    nlive: jax.Array      # (M,) live (unmarked, non-marker) leaves
    bcount: jax.Array     # (M,) occupied buffer entries
    nchild: jax.Array     # (M,) number of child links
    parent: jax.Array     # (M,) parent ΔNode id (-1 root)
    pslot: jax.Array      # (M,) bottom slot index within parent
    alive: jax.Array      # (M,) bool allocated
    free_stack: jax.Array # (M,) int32 freelist
    free_top: jax.Array   # () int32 number of free ids on the stack
    root: jax.Array       # () int32 root ΔNode id
    ins_flag: jax.Array   # (M,) bool needs insert-side maintenance
    del_flag: jax.Array   # (M,) bool merge candidate
    alloc_fail: jax.Array # () bool arena exhausted at some point (sticky)


# --------------------------------------------------------------------------
# construction
# --------------------------------------------------------------------------


def empty_layout(cfg: TreeConfig) -> DeltaTree:
    """An empty arena as host (numpy) arrays: the root pre-allocated,
    every other ΔNode on the free stack.  ``empty`` uploads it; the
    forest stacks its shards' layouts and places them, one per device."""
    m, ub, lc = cfg.max_dnodes, cfg.ub, cfg.leaf_cap
    # free stack holds ids M-1 .. 1 (0 is the root, pre-allocated).
    free = np.zeros(m, dtype=np.int32)
    free[: m - 1] = np.arange(m - 1, 0, -1, dtype=np.int32)
    alive = np.zeros(m, dtype=bool)
    alive[0] = True
    return _host_tree(cfg, value=np.full((m, ub), EMPTY, cfg.vdtype),
                      child=np.full((m, lc), -1, np.int32),
                      nlive=np.zeros((m,), np.int32),
                      nchild=np.zeros((m,), np.int32),
                      parent=np.full((m,), -1, np.int32),
                      pslot=np.zeros((m,), np.int32), alive=alive,
                      free_stack=free, free_top=m - 1, root=0)


def _host_tree(cfg: TreeConfig, *, free_top: int, root: int,
               **planes) -> DeltaTree:
    """A host arena from the planes a layout fills; the mark, buffer and
    flag planes start empty."""
    m, ub, bc = cfg.max_dnodes, cfg.ub, cfg.buf_cap
    return DeltaTree(
        mark=np.zeros((m, ub), bool),
        buf=np.full((m, bc), EMPTY, cfg.vdtype),
        bcount=np.zeros((m,), np.int32),
        free_top=np.int32(free_top),
        root=np.int32(root),
        ins_flag=np.zeros((m,), bool),
        del_flag=np.zeros((m,), bool),
        alloc_fail=np.bool_(False),
        **planes,
    )


def empty(cfg: TreeConfig) -> DeltaTree:
    return jax.device_put(empty_layout(cfg))


def _pos(cfg: TreeConfig) -> jnp.ndarray:
    return jnp.asarray(layout.veb_pos_table(cfg.height))


# --------------------------------------------------------------------------
# descend — the memory-transfer path (paper Fig. 8 / Lemma 2.1)
# --------------------------------------------------------------------------


def _descend(cfg: TreeConfig, t: DeltaTree, q, dn0, b0):
    """Walk from (dn0, b0) to the leaf position that owns packed query ``q``.

    Returns (dn, b, hops): ``hops`` counts ΔNode boundary crossings — in the
    relaxed-CO model each hop is O(1) block transfers (Lemma 2.1), so hops is
    the exact transfer-count statistic reported by the benchmarks.
    """
    pos = _pos(cfg)
    bottom0 = cfg.bottom0

    def cond(s):
        return ~s[2]

    def body(s):
        dn, b, _, hops = s
        router = t.value[dn, pos[b]]
        at_bottom = b >= bottom0
        left_val = jnp.where(
            at_bottom, jnp.zeros((), cfg.vdtype),
            t.value[dn, pos[jnp.minimum(2 * b, 2 * bottom0 - 1)]],
        )
        internal = (~at_bottom) & (left_val != EMPTY)
        slot = jnp.where(at_bottom, b - bottom0, 0)
        ch = jnp.where(at_bottom, t.child[dn, slot], NONE)
        hop = at_bottom & (ch >= 0)
        b_next = jnp.where(internal, 2 * b + (q >= router).astype(jnp.int32), b)
        b_next = jnp.where(hop, jnp.int32(1), b_next)
        dn_next = jnp.where(hop, ch, dn)
        done = (~internal) & (~hop)
        return dn_next, b_next, done, hops + hop.astype(jnp.int32)

    dn, b, _, hops = jax.lax.while_loop(
        cond, body, (jnp.int32(dn0), jnp.int32(b0), jnp.bool_(False), jnp.int32(1))
    )
    return dn, b, hops


# --------------------------------------------------------------------------
# Search — wait-free (paper Fig. 8, Lemma 4.1/4.2)
# --------------------------------------------------------------------------


def searchnode(cfg: TreeConfig, t: DeltaTree, keys, leaf_val, leaf_b, dn):
    """Paper SEARCHNODE resolution (Fig. 8 lines 9..17) at the walk's
    final position: leaf match & ~mark, else overflow-buffer membership;
    payload from the matching leaf or buffer slot.

    Shape-polymorphic over scalar ``()`` or batched ``(K,)`` queries, and
    the single source of truth both SearchEngines resolve through — the
    scalar engine per lane (via `search_one`), the lockstep engine on the
    kernel walk's outputs — so the bit-for-bit parity the conformance
    suite asserts cannot drift.  Returns (found, payload | -1).
    """
    pos = _pos(cfg)
    keys = jnp.asarray(keys)
    leaf_hit = (leaf_val != EMPTY) & (cfg.key_of(leaf_val) == keys)
    leaf_found = leaf_hit & ~t.mark[dn, pos[leaf_b]]
    brow = t.buf[dn]                           # (..., buf_cap)
    bhit = (brow != EMPTY) & (cfg.key_of(brow) == keys[..., None])
    in_buf = jnp.any(bhit, axis=-1)
    bsel = jnp.take_along_axis(
        brow, jnp.argmax(bhit, axis=-1)[..., None], axis=-1)[..., 0]
    found = jnp.where(leaf_hit, leaf_found, in_buf)
    payload = jnp.where(leaf_hit, cfg.payload_of(leaf_val),
                        cfg.payload_of(bsel))
    return found, jnp.where(found, payload, -1)


def search_one(cfg: TreeConfig, t: DeltaTree, key):
    """Returns (found: bool, payload: int32, hops: int32)."""
    pos = _pos(cfg)
    q = cfg.qpack(key)
    dn, b, hops = _descend(cfg, t, q, t.root, 1)
    found, payload = searchnode(cfg, t, key, t.value[dn, pos[b]], b, dn)
    return found, payload, hops


def search_batch(cfg: TreeConfig, t: DeltaTree, keys: jax.Array):
    """Vectorized wait-free search via ``cfg.engine``. (found[K], hops[K])."""
    from repro.core import engine as E  # deferred: engine imports this module

    return E.search(cfg, t, keys)


def lookup_batch(cfg: TreeConfig, t: DeltaTree, keys: jax.Array):
    """Map-mode search via ``cfg.engine``: (found[K], payload[K], hops[K])."""
    from repro.core import engine as E  # deferred: engine imports this module

    return E.lookup(cfg, t, keys)


# --------------------------------------------------------------------------
# row access — one ΔNode row of a plane, read or written in place
# --------------------------------------------------------------------------


def _row(plane, r):
    """Row ``r`` of an (M, ...) plane (a dynamic slice)."""
    return jax.lax.dynamic_index_in_dim(plane, r, keepdims=False)


def _put(plane, r, row):
    """``plane`` with row ``r`` replaced (a dynamic-update-slice: inside a
    loop whose carry holds the plane it updates in place, with no copy)."""
    row = jnp.asarray(row, plane.dtype)
    return jax.lax.dynamic_update_index_in_dim(plane, row, r, 0)


def _free(cfg: TreeConfig, t: DeltaTree, dn):
    t = t._replace(
        value=t.value.at[dn].set(EMPTY),
        mark=t.mark.at[dn].set(False),
        child=t.child.at[dn].set(-1),
        buf=t.buf.at[dn].set(EMPTY),
        nlive=t.nlive.at[dn].set(0),
        bcount=t.bcount.at[dn].set(0),
        nchild=t.nchild.at[dn].set(0),
        parent=t.parent.at[dn].set(-1),
        pslot=t.pslot.at[dn].set(0),
        alive=t.alive.at[dn].set(False),
        ins_flag=t.ins_flag.at[dn].set(False),
        del_flag=t.del_flag.at[dn].set(False),
        free_stack=t.free_stack.at[t.free_top].set(dn),
        free_top=t.free_top + jnp.int32(1),
    )
    return t


# --------------------------------------------------------------------------
# ΔNode rebuild (Rebalance core, paper Fig. 10 BALANCETREE)
# --------------------------------------------------------------------------


def _rebuild_row(cfg: TreeConfig, sorted_vals: jax.Array, m: jax.Array,
                 force_bottom: bool = False) -> jax.Array:
    """Build a (UB,) vEB-order value row holding the first ``m`` entries of
    ``sorted_vals`` (packed) as a complete leaf-oriented BST at minimal leaf
    depth (or pinned to the bottom row for ΔNodes carrying child links)."""
    h = cfg.height
    tabs = layout.rebuild_tables(h)
    pos = _pos(cfg)
    mm = jnp.maximum(m, 1)
    d = jnp.ceil(jnp.log2(mm.astype(jnp.float32))).astype(jnp.int32)
    d = jnp.clip(d, 0, h - 1)
    if force_bottom:
        d = jnp.int32(h - 1)
    kind = jnp.asarray(tabs["kind"])[d]            # (2**h,)
    start = jnp.asarray(tabs["range_start"])[d]
    mid = jnp.asarray(tabs["range_mid"])[d]
    pad = jnp.full((1,), EMPTY, cfg.vdtype)
    xv = jnp.concatenate([sorted_vals.astype(cfg.vdtype), pad])
    cap = xv.shape[0] - 1
    empty_v = jnp.zeros((), cfg.vdtype)
    leaf = jnp.where(start < m, xv[jnp.clip(start, 0, cap)], empty_v)
    router = jnp.where(
        start >= m, empty_v,
        jnp.where(mid < m, xv[jnp.clip(mid, 0, cap)], cfg.route_left),
    )
    vals_b = jnp.where(kind == 1, leaf, jnp.where(kind == 2, router, empty_v))
    vals_b = jnp.where(m == 0, jnp.full_like(vals_b, EMPTY), vals_b)
    row = jnp.zeros((cfg.ub,), cfg.vdtype)
    return row.at[pos[1:]].set(vals_b[1:])


def _gather_live(cfg: TreeConfig, t: DeltaTree, dn):
    """Sorted live packed values of ΔNode ``dn`` (own leaves + buffer;
    child-link markers excluded).  Returns (sorted[UB+buf_cap] ascending
    with ROUTE_LEFT padding at the end, count)."""
    pos = _pos(cfg)
    h, bottom0 = cfg.height, cfg.bottom0
    bfs = jnp.arange(1, 2**h, dtype=jnp.int32)
    vals = t.value[dn, pos[bfs]]
    marks = t.mark[dn, pos[bfs]]
    at_bottom = bfs >= bottom0
    left = jnp.where(
        at_bottom, jnp.zeros((), cfg.vdtype),
        t.value[dn, pos[jnp.minimum(2 * bfs, 2 * bottom0 - 1)]],
    )
    is_leaf = at_bottom | (left == EMPTY)
    slot = jnp.where(at_bottom, bfs - bottom0, 0)
    is_marker = at_bottom & (t.child[dn, slot] >= 0)
    live = is_leaf & (vals != EMPTY) & ~marks & ~is_marker
    keep = jnp.where(live, vals, cfg.route_left)  # push pads to the end
    bkeep = jnp.where(t.buf[dn] != EMPTY, t.buf[dn], cfg.route_left)
    allv = jnp.sort(jnp.concatenate([keep, bkeep]))
    count = (jnp.sum(live.astype(jnp.int32)) + t.bcount[dn]).astype(jnp.int32)
    return allv, count


# --------------------------------------------------------------------------
# single-op primitives (paper Fig. 9) — applied in batch order
# --------------------------------------------------------------------------


def _buf_append_row(brow, pv, do=True):
    """Append packed value to a buffer row's first free slot when ``do``
    (paper Fig. 9 line 89).  Returns (row, ok): ``ok`` is False when the
    row is full, and the row is then unchanged."""
    slot_free = brow == EMPTY
    ok = do & jnp.any(slot_free)
    j = jnp.argmax(slot_free)
    return brow.at[j].set(jnp.where(ok, pv, brow[j])), ok


def _grow_rows(cfg: TreeConfig, vrow, mrow, b, pv):
    """Paper Fig. 9 lines 50..72 on one ΔNode's value and mark rows: leaf
    x at BFS ``b`` (above the bottom row) grows into internal(router=max)
    with leaves (min, max).  Preserves x's mark on x's new position."""
    pos = _pos(cfg)
    x = vrow[pos[b]]
    xm = mrow[pos[b]]
    v_lt = cfg.key_of(pv) < cfg.key_of(x)
    lo = jnp.where(v_lt, pv, x)
    hi = jnp.where(v_lt, x, pv)
    x_is_lo = v_lt  # x is hi iff new value is smaller
    lpos, rpos = pos[2 * b], pos[2 * b + 1]
    vrow = vrow.at[lpos].set(lo).at[rpos].set(hi).at[pos[b]].set(hi)
    mrow = (mrow.at[lpos].set(jnp.where(x_is_lo, False, xm))
            .at[rpos].set(jnp.where(x_is_lo, xm, False))
            .at[pos[b]].set(False))
    return vrow, mrow


def _insert_op(cfg: TreeConfig, t: DeltaTree, key, payload,
               dn0=None, b0=None):
    """One INSERTNODE in batch order. Returns (t, success, pending).

    ``(dn0, b0)`` is an optional descent hint — a position known to be on
    the key's root descent path (the lockstep update path passes the
    round-start frontier position; within an op phase structure only grows
    downward, so descending from the hint reaches the true endpoint)."""
    pos = _pos(cfg)
    q = cfg.qpack(key)
    pv = cfg.pack(key, payload)
    if dn0 is None:
        dn0, b0 = t.root, 1
    dn, b, _ = _descend(cfg, t, q, dn0, b0)
    leaf_val = t.value[dn, pos[b]]
    leaf_mark = t.mark[dn, pos[b]]
    leaf_hit = (leaf_val != EMPTY) & (cfg.key_of(leaf_val) == key)
    in_buf = jnp.any((t.buf[dn] != EMPTY) & (cfg.key_of(t.buf[dn]) == key))

    def case_dup(t):  # leaf holds key: revive if deleted (payload refreshed)
        tt = t._replace(
            value=t.value.at[dn, pos[b]].set(
                jnp.where(leaf_mark, pv, leaf_val)),
            mark=t.mark.at[dn, pos[b]].set(False),
            nlive=t.nlive.at[dn].add(jnp.where(leaf_mark, jnp.int32(1), jnp.int32(0))),
        )
        return tt, leaf_mark, jnp.bool_(False)

    def case_place(t):  # unoccupied leaf position (incl. empty root)
        tt = t._replace(
            value=t.value.at[dn, pos[b]].set(pv),
            mark=t.mark.at[dn, pos[b]].set(False),
            nlive=t.nlive.at[dn].add(jnp.int32(1)),
        )
        return tt, jnp.bool_(True), jnp.bool_(False)

    def case_grow(t):
        vrow, mrow = _grow_rows(cfg, _row(t.value, dn), _row(t.mark, dn),
                                b, pv)
        tt = t._replace(value=_put(t.value, dn, vrow),
                        mark=_put(t.mark, dn, mrow),
                        nlive=t.nlive.at[dn].add(jnp.int32(1)))
        return tt, jnp.bool_(True), jnp.bool_(False)

    def case_buffer(t):
        def dup(t):
            return t, jnp.bool_(False), jnp.bool_(False)

        def app(t):
            brow, ok = _buf_append_row(_row(t.buf, dn), pv)
            tt = t._replace(
                buf=_put(t.buf, dn, brow),
                bcount=t.bcount.at[dn].add(ok.astype(jnp.int32)),
                ins_flag=t.ins_flag.at[dn].set(ok | t.ins_flag[dn]))
            # buffer full -> op stays pending, retried after maintenance
            return tt, ok, ~ok

        return jax.lax.cond(in_buf, dup, app, t)

    # a key resident in this ΔNode's buffer routes to case_buffer (dup)
    # whatever leaf kind the descent ended on — under I5' carried items
    # may surface at non-bottom or EMPTY leaves of an Expanded child
    branch = jnp.where(
        leaf_hit, 0,
        jnp.where(in_buf, 3,
                  jnp.where(leaf_val == EMPTY, 1,
                            jnp.where(b < cfg.bottom0, 2, 3))),
    )
    return jax.lax.switch(branch, [case_dup, case_place, case_grow, case_buffer], t)


def _delete_op(cfg: TreeConfig, t: DeltaTree, key, dn0=None, b0=None):
    """One DELETENODE in batch order (mark-delete, paper Fig. 9 l.18).
    ``(dn0, b0)`` is an optional descent hint, as in `_insert_op`."""
    pos = _pos(cfg)
    q = cfg.qpack(key)
    if dn0 is None:
        dn0, b0 = t.root, 1
    dn, b, _ = _descend(cfg, t, q, dn0, b0)
    leaf_val = t.value[dn, pos[b]]
    leaf_mark = t.mark[dn, pos[b]]
    leaf_hit = (leaf_val != EMPTY) & (cfg.key_of(leaf_val) == key)

    def case_leaf(t):
        ok = ~leaf_mark
        nl = t.nlive[dn] - jnp.where(ok, jnp.int32(1), jnp.int32(0))
        tt = t._replace(
            mark=t.mark.at[dn, pos[b]].set(True),
            nlive=t.nlive.at[dn].set(nl),
            del_flag=t.del_flag.at[dn].set(
                t.del_flag[dn] | (ok & (nl < cfg.half_cap // 2))
            ),
        )
        return tt, ok, jnp.bool_(False)

    def case_buf(t):
        hit = (t.buf[dn] != EMPTY) & (cfg.key_of(t.buf[dn]) == key)
        ok = jnp.any(hit)
        j = jnp.argmax(hit)
        tt = t._replace(
            buf=t.buf.at[dn, j].set(
                jnp.where(ok, jnp.zeros((), cfg.vdtype), t.buf[dn, j])),
            bcount=t.bcount.at[dn].add(jnp.where(ok, jnp.int32(-1), jnp.int32(0))),
        )
        return tt, ok, jnp.bool_(False)

    return jax.lax.cond(leaf_hit, case_leaf, case_buf, t)


# --------------------------------------------------------------------------
# maintenance — Rebalance / Expand (paper Fig. 9 lines 92..106)
# --------------------------------------------------------------------------


# what one buffered item of an Expand pass did (``_expand_item``)
EXP_MOVED, EXP_KEPT, EXP_DUP, EXP_PLACE, EXP_GROW, EXP_EXPAND = range(6)


def _expand_item(cfg: TreeConfig, t: DeltaTree, dn, i):
    """Route the item in slot ``i`` of ΔNode ``dn``'s buffer one hop toward
    its home (paper Fig. 9 lines 92..106).  Returns (t, outcome), one of:

    - ``EXP_MOVED``: it descends into a descendant ΔNode, and moves into
      that ΔNode's buffer; ``EXP_KEPT`` when that buffer is full, and the
      item stays in ``dn``'s;
    - ``EXP_DUP``: a leaf of ``dn`` holds its key (revived with the item's
      payload if marked), ``EXP_PLACE``: it fills an empty leaf,
      ``EXP_GROW``: it grows a leaf above the bottom row;
    - ``EXP_EXPAND``: it lands on an occupied childless bottom leaf, which
      is EXPANDed into a fresh child ΔNode (paper Fig. 5b) seeded with the
      leaf's live value; the item moves into the child's buffer and the
      leaf stays as the child's marker.

    The outcome is computed as small values, and the rows it may touch are
    written back unconditionally with dynamic-update-slices, each with its
    old content where the outcome leaves it alone: no branch carries the
    tree, so the arena planes are updated in place, never copied.  The
    writes keep the order of the per-outcome branches this replaces, so an
    exhausted arena (the popped id then aliases a live row) leaves the
    same planes.
    """
    pos = _pos(cfg)
    bottom0 = cfg.bottom0
    dbuf = _row(t.buf, dn)
    pv = dbuf[i]
    key = cfg.key_of(pv)
    # drop from this buffer first; re-added below if it must stay
    dbuf_out = dbuf.at[i].set(EMPTY)
    tdn, b, _ = _descend(cfg, t, cfg.qpack(key), dn, 1)
    vrow, mrow, crow = (_row(t.value, tdn), _row(t.mark, tdn),
                        _row(t.child, tdn))
    p = pos[b]
    leaf_val, leaf_mark = vrow[p], mrow[p]
    leaf_hit = (leaf_val != EMPTY) & (cfg.key_of(leaf_val) == key)
    moved = tdn != dn
    room = jnp.any(_row(t.buf, tdn) == EMPTY)
    kind = jnp.where(
        moved, jnp.where(room, EXP_MOVED, EXP_KEPT),
        jnp.where(leaf_hit, EXP_DUP,
                  jnp.where(leaf_val == EMPTY, EXP_PLACE,
                            jnp.where(b < bottom0, EXP_GROW, EXP_EXPAND))))
    dup, place = kind == EXP_DUP, kind == EXP_PLACE
    grow, expand = kind == EXP_GROW, kind == EXP_EXPAND

    # the child: popped off the free stack (sticky alloc_fail when empty;
    # the popped id is then whatever the stack's bottom holds, maybe live)
    ok = t.free_top > 0
    top = jnp.maximum(t.free_top - 1, 0)
    cid = t.free_stack[top]
    slot = jnp.maximum(b - bottom0, 0)
    x_live = ~leaf_mark
    mseed = (expand & x_live).astype(jnp.int32)
    seed_row = _rebuild_row(cfg, jnp.full(
        (1,), jnp.where(x_live, leaf_val, cfg.route_left), cfg.vdtype),
        mseed)

    # tdn's rows: dup / place write the leaf, grow splits it, expand
    # clears its mark and links the child (its value row stays, so the
    # one value write is the child's seed row)
    gv, gm = _grow_rows(cfg, vrow, mrow, jnp.minimum(b, bottom0 - 1), pv)
    at_p = vrow.at[p].set(jnp.where(dup & ~leaf_mark, leaf_val, pv))
    vrow = jnp.where(grow, gv, jnp.where(dup | place, at_p, vrow))
    mrow = jnp.where(grow, gm, jnp.where(dup | place | expand,
                                         mrow.at[p].set(False), mrow))
    crow = jnp.where(expand, crow.at[slot].set(cid), crow)

    # the item's new buffer: the descendant's, dn's when that is full, or
    # the child's
    dest = jnp.where(kind == EXP_MOVED, tdn, jnp.where(expand, cid, dn))
    brow, app = _buf_append_row(
        jnp.where(dest == dn, dbuf_out, _row(t.buf, dest)), pv,
        moved | expand)

    # Every write below reads only the planes as the item found them (a
    # row read after a write to its plane would make the compiler copy
    # the plane), and writes to one row land in the branchy pass's order:
    # where two rows alias (the child popped from an exhausted arena),
    # the later write wins as it did there.
    app_i = app.astype(jnp.int32)
    bc_dn = t.bcount[dn] - 1
    nl_tdn = jnp.where(expand & (cid == tdn), mseed, t.nlive[tdn])
    nl_tdn += jnp.where(dup, leaf_mark.astype(jnp.int32),
                        jnp.where(place | grow, 1, -mseed))
    t = t._replace(
        value=_put(t.value, jnp.where(expand, cid, tdn),
                   jnp.where(expand, seed_row, vrow)),
        mark=_put(t.mark, tdn, mrow),
        child=_put(t.child, tdn, crow),
        buf=_put(_put(t.buf, dn, dbuf_out), dest, brow),
        bcount=_put(_put(t.bcount, dn, bc_dn), dest,
                    jnp.where(dest == dn, bc_dn, t.bcount[dest]) + app_i),
        ins_flag=_put(t.ins_flag, dest, app | t.ins_flag[dest]),
        nlive=_put(_put(t.nlive, cid,
                        jnp.where(expand, mseed, t.nlive[cid])),
                   tdn, nl_tdn),
        nchild=_put(t.nchild, tdn, t.nchild[tdn] + expand),
        parent=_put(t.parent, cid, jnp.where(expand, tdn, t.parent[cid])),
        pslot=_put(t.pslot, cid, jnp.where(expand, slot, t.pslot[cid])),
        alive=_put(t.alive, cid, expand | t.alive[cid]),
        free_top=jnp.where(expand & ok, top, t.free_top),
        alloc_fail=t.alloc_fail | (expand & ~ok),
    )
    return t, kind


def _process_ins(cfg: TreeConfig, t: DeltaTree, dn):
    """Insert-side repair of ΔNode ``dn`` (Rebalance or Expand).  Returns
    (t, rebuilds, expands) — the int32 deltas feed ``MaintenanceStats``
    (expands counts child ΔNodes allocated).

    The Expand pass runs `_expand_item` on the buffer's occupied slots in
    slot order.  An item that must stay goes to the first free slot, at or
    before its own, so the slots still ahead hold what the pass started
    with: the pass sees exactly the items, in the order, that a pass over
    every slot sees.  Each item writes only the rows it touches, in place;
    the trees are bit for bit those of the per-item ``lax.cond`` /
    ``lax.switch`` pass it replaces (each branch of which returned the
    whole tree, and so made the compiler copy every arena plane it carried
    for every slot — ~0.5 GB per slot at 151,024 ΔNodes).
    """
    dn = jnp.asarray(dn, jnp.int32)
    total = t.nlive[dn] + t.bcount[dn]
    rebuild = (t.nchild[dn] == 0) & (total <= cfg.half_cap)
    # Expand: the buffer's items, none when dn is rebuilt instead
    occ = _row(t.buf, dn) != EMPTY
    slots = jnp.nonzero(occ, size=cfg.buf_cap, fill_value=0)[0]
    ft0 = t.free_top
    t = jax.lax.fori_loop(
        0, jnp.where(rebuild, 0, jnp.sum(occ, dtype=jnp.int32)),
        lambda j, t: _expand_item(cfg, t, dn, slots[j])[0], t)
    # Rebalance (paper REBALANCE): dn's childless tree rebuilt at minimal
    # height from its live leaves + buffer, a functional mirror-swap; an
    # Expanded dn's rows are written back as they are
    allv, m = _gather_live(cfg, t, dn)

    def swap(plane, row):
        return _put(plane, dn, jnp.where(rebuild, row, _row(plane, dn)))

    bcount = jnp.where(rebuild, 0, t.bcount[dn])
    t = t._replace(
        value=swap(t.value, _rebuild_row(cfg, allv, m)),
        mark=swap(t.mark, False),
        buf=swap(t.buf, EMPTY),
        nlive=_put(t.nlive, dn, jnp.where(rebuild, m, t.nlive[dn])),
        bcount=_put(t.bcount, dn, bcount),
        ins_flag=_put(t.ins_flag, dn, bcount > 0),
    )
    return (t, rebuild.astype(jnp.int32),
            (ft0 - t.free_top).astype(jnp.int32))


# --------------------------------------------------------------------------
# maintenance — Merge (paper Fig. 10 MERGETREE)
# --------------------------------------------------------------------------


def _process_del(cfg: TreeConfig, t: DeltaTree, dn):
    """Delete-side repair of ΔNode ``dn`` (Merge).  Returns (t, merged) —
    the int32 delta feeds ``MaintenanceStats``."""
    dn = jnp.asarray(dn, jnp.int32)
    pos = _pos(cfg)
    t = t._replace(del_flag=t.del_flag.at[dn].set(False))
    p = t.parent[dn]
    eligible = (
        t.alive[dn]
        & (p >= 0)
        & (t.nchild[dn] == 0)
        & (t.bcount[dn] == 0)
        & (t.nlive[dn] < cfg.half_cap)
    )

    def merge(t):
        s = t.pslot[dn]
        sib = s ^ 1
        even = s & ~1
        b_dn = cfg.bottom0 + s        # dn's slot, BFS in parent
        b_sib = cfg.bottom0 + sib
        b_par = b_dn // 2             # the depth H-2 router node
        sib_child = t.child[p, sib]
        sib_leaf_val = t.value[p, pos[b_sib]]
        sib_leaf_mark = t.mark[p, pos[b_sib]]
        sib_is_child = sib_child >= 0
        sib_ok = jnp.where(
            sib_is_child,
            (t.nchild[jnp.maximum(sib_child, 0)] == 0)
            & (t.bcount[jnp.maximum(sib_child, 0)] == 0),
            jnp.bool_(True),
        )
        my_vals, my_m = _gather_live(cfg, t, dn)
        sib_vals, sib_m = jax.lax.cond(
            sib_is_child,
            lambda: _gather_live(cfg, t, jnp.maximum(sib_child, 0)),
            lambda: (
                jnp.full_like(my_vals, cfg.route_left).at[0].set(
                    jnp.where(
                        (sib_leaf_val != EMPTY) & ~sib_leaf_mark,
                        sib_leaf_val,
                        cfg.route_left,
                    )
                ),
                ((sib_leaf_val != EMPTY) & ~sib_leaf_mark).astype(jnp.int32),
            ),
        )
        total = my_m + sib_m
        fits = sib_ok & (total <= cfg.half_cap)

        def do(t):
            union = jnp.sort(jnp.concatenate([my_vals, sib_vals]))
            row = _rebuild_row(cfg, union, total)
            # dn becomes the merged ΔNode, re-hung at the even slot; the odd
            # slot is cleared and the router re-set to ROUTE_LEFT — the
            # implicit-layout version of the paper's pointer splice.
            t = t._replace(
                value=t.value.at[dn].set(row),
                mark=t.mark.at[dn].set(False),
                nlive=t.nlive.at[dn].set(total),
            )
            free_sib = sib_is_child
            t = jax.lax.cond(
                free_sib,
                lambda t: _free(cfg, t, jnp.maximum(sib_child, 0)),
                lambda t: t,
                t,
            )
            b_even = cfg.bottom0 + even
            b_odd = b_even + 1
            marker = jnp.where(total > 0, union[0], jnp.ones((), cfg.vdtype))
            t = t._replace(
                child=t.child.at[p, even].set(dn).at[p, even ^ 1].set(-1),
                nchild=t.nchild.at[p].add(jnp.where(sib_is_child, jnp.int32(-1), jnp.int32(0))),
                pslot=t.pslot.at[dn].set(even),
                value=(
                    t.value.at[p, pos[b_even]].set(marker)
                    .at[p, pos[b_odd]].set(EMPTY)
                    .at[p, pos[b_par]].set(cfg.route_left)
                ),
                mark=t.mark.at[p, pos[b_even]].set(False)
                .at[p, pos[b_odd]].set(False),
                # a live sibling leaf value was absorbed downward
                nlive=t.nlive.at[p].add(-sib_m * (~sib_is_child).astype(jnp.int32)),
            )
            return t, jnp.int32(1)

        return jax.lax.cond(fits, do, lambda t: (t, jnp.int32(0)), t)

    return jax.lax.cond(eligible, merge, lambda t: (t, jnp.int32(0)), t)


# --------------------------------------------------------------------------
# batched update step
# --------------------------------------------------------------------------

OP_SEARCH, OP_INSERT, OP_DELETE = 0, 1, 2


def _parallel_fastpath(cfg: TreeConfig, t: DeltaTree, kinds, keys, payloads,
                       results, pending, dns, bs):
    """Vectorized first pass: apply all *non-conflicting* updates with
    batched scatters — the SPMD realization of the paper's non-blocking
    concurrency (ops in distinct ΔNodes/leaves proceed "in parallel";
    conflicting ops lose the CAS and retry via the sequential path).

    ``(dns, bs)`` are the batch's frontier leaf positions, computed by the
    scheduler once per round (one `kernels.ops.delta_walk` pass under the
    lockstep engine, the vmapped scalar descent otherwise).

    Handled vectorized: delete-mark, delete-miss, insert-place, insert-grow,
    insert-revive, insert-dup (leaf or buffer).  Left pending: bottom-leaf
    buffered inserts (the paper's lock/buffer path), ops on keys resident
    in the final ΔNode's overflow buffer (mid-batch inserts, or items
    carried by a non-eager maintenance policy — invariant I5' puts a
    buffered key's descent in its holder, so one probe of the final
    ΔNode's buffer row suffices), and any op conflicting on key or leaf
    position (the earliest-in-batch op wins, preserving a valid
    linearization).
    """
    pos = _pos(cfg)
    k = keys.shape[0]
    m = cfg.max_dnodes
    pv = jax.vmap(cfg.pack)(keys, payloads)

    # earliest-in-batch wins per duplicate key / duplicate leaf slot
    def later_duplicate(ids):
        order = jnp.argsort(ids, stable=True)
        sid = ids[order]
        dup_sorted = jnp.concatenate(
            [jnp.zeros((1,), bool), sid[1:] == sid[:-1]])
        return jnp.zeros((k,), bool).at[order].set(dup_sorted)

    key_loser = later_duplicate(keys)
    slot_loser = later_duplicate(dns * jnp.int32(2 ** cfg.height) + bs)
    elig = pending & ~key_loser & ~slot_loser

    leaf_val = t.value[dns, pos[bs]]
    leaf_mark = t.mark[dns, pos[bs]]
    leaf_hit = (leaf_val != EMPTY) & (cfg.key_of(leaf_val) == keys)
    at_bottom = bs >= cfg.bottom0
    is_ins = kinds == OP_INSERT
    is_del = kinds == OP_DELETE
    # final-ΔNode buffer probe: a buffered key may surface at ANY leaf
    # kind (a freshly-Expanded child seeds its buffer while its only leaf
    # sits at the root position), so every miss consults the buffer row
    brow = t.buf[dns]
    in_buf = jnp.any((brow != EMPTY) & (cfg.key_of(brow) == keys[:, None]),
                     axis=1)

    del_ok = elig & is_del & leaf_hit & ~leaf_mark
    # a buffered hit needs the sequential path (dynamic-slot clear); a miss
    # at a BOTTOM leaf may still race mid-round inserts — defer those too
    del_miss = elig & is_del & (leaf_hit & leaf_mark
                                | (~leaf_hit & ~at_bottom & ~in_buf))
    ins_dup = elig & is_ins & leaf_hit & ~leaf_mark
    ins_bufdup = elig & is_ins & ~leaf_hit & in_buf
    ins_revive = elig & is_ins & leaf_hit & leaf_mark
    ins_place = elig & is_ins & (leaf_val == EMPTY) & ~in_buf
    ins_grow = (elig & is_ins & ~leaf_hit & ~in_buf
                & (leaf_val != EMPTY) & ~at_bottom)

    drop = jnp.int32(m)  # OOB row -> scatter mode="drop"

    def sdn(mask):
        return jnp.where(mask, dns, drop)

    value, mark = t.value, t.mark
    vpos = pos[bs]
    mark = mark.at[sdn(del_ok), vpos].set(True, mode="drop")
    wmask = ins_revive | ins_place
    value = value.at[sdn(wmask), vpos].set(pv, mode="drop")
    mark = mark.at[sdn(wmask), vpos].set(False, mode="drop")
    # grow: leaf x -> internal(router=hi) + leaves (lo, hi); x's mark moves
    v_lt = cfg.key_of(pv) < cfg.key_of(leaf_val)
    lo = jnp.where(v_lt, pv, leaf_val)
    hi = jnp.where(v_lt, leaf_val, pv)
    bsafe = jnp.minimum(bs, cfg.bottom0 - 1)  # 2b in range; masked anyway
    lpos, rpos = pos[2 * bsafe], pos[2 * bsafe + 1]
    gdn = sdn(ins_grow)
    value = value.at[gdn, lpos].set(lo, mode="drop")
    value = value.at[gdn, rpos].set(hi, mode="drop")
    value = value.at[gdn, vpos].set(hi, mode="drop")
    mark = mark.at[gdn, lpos].set(jnp.where(v_lt, False, leaf_mark), mode="drop")
    mark = mark.at[gdn, rpos].set(jnp.where(v_lt, leaf_mark, False), mode="drop")
    mark = mark.at[gdn, vpos].set(False, mode="drop")

    dlt = (jnp.where(ins_revive | ins_place | ins_grow, 1, 0)
           + jnp.where(del_ok, -1, 0)).astype(jnp.int32)
    nlive = t.nlive + jax.ops.segment_sum(
        dlt, jnp.where(elig, dns, drop), num_segments=m + 1)[:m]
    del_flag = t.del_flag | ((nlive < cfg.half_cap // 2) & (nlive < t.nlive))

    done = (del_ok | del_miss | ins_dup | ins_bufdup | ins_revive
            | ins_place | ins_grow)
    ok = del_ok | ins_revive | ins_place | ins_grow
    results = jnp.where(done, ok, results)
    pending = pending & ~done
    # bottom-leaf (buffer-path) inserts and conflict losers stay pending

    t = t._replace(value=value, mark=mark, nlive=nlive, del_flag=del_flag)
    return t, results, pending


def update_batch_impl(cfg: TreeConfig, t: DeltaTree, kinds: jax.Array,
                      keys: jax.Array, payloads: jax.Array | None = None):
    """Apply a batch of update ops (insert/delete) in batch order, then run
    maintenance under ``cfg.maintenance`` (eager: to fixpoint, the paper
    semantics).  Returns (tree, results[K] bool, MaintenanceStats).

    The round loop lives in ``repro.maintenance.scheduler`` — this is the
    stable entry point.  The third element used to be a bare round count;
    ``MaintenanceStats`` still coerces via ``int()`` (DeprecationWarning)
    for old call sites, but new code should read ``stats.rounds`` etc.

    Searches are NOT taken here — use `search_batch` on the snapshot (they
    are wait-free and independent of update ordering within the step).

    This is the untraced body; call sites use the jitted/donating
    ``update_batch`` wrapper below, while the forest dispatcher
    (repro/distributed) lax.maps this impl per shard under shard_map.
    """
    from repro.maintenance import scheduler as MS  # deferred: imports us

    return MS.run_update(cfg, t, kinds, keys, payloads)


def flush_impl(cfg: TreeConfig, t: DeltaTree, budget: int = 64):
    """Drain all pending maintenance to fixpoint (restores invariant I5
    after ``deferred``/``budgeted`` update batches).  Returns
    (tree, MaintenanceStats).  A no-op round count of 0 when nothing is
    flagged — safe to call under any policy."""
    from repro.maintenance import scheduler as MS  # deferred: imports us

    return MS.flush(cfg, t, budget)


# the input tree is DONATED: .at[] updates run in place (callers must
# rebind `t = update_batch(...)[0]`, as all call sites do)
update_batch = functools.partial(
    jax.jit, static_argnums=0, donate_argnums=1)(update_batch_impl)

# flush donates too: rebind `t, stats = flush(cfg, t)`
flush = functools.partial(
    jax.jit, static_argnums=(0, 2), donate_argnums=1)(flush_impl)


def buffered_floor(cfg: TreeConfig, t: DeltaTree, keys: jax.Array):
    """Smallest *buffered* packed value strictly greater than each key
    (``cfg.route_left`` when none) — the successor contribution of pending
    overflow-buffer items under non-eager maintenance (I5' trees).

    One global sort of the buffer arena + a searchsorted per query; the
    engine dispatch folds this with the tree walk's candidate.  Buffered
    items are always live, so no tombstone chase is needed on this side.
    The common drained state (e.g. right after ``flush``) skips the sort
    entirely.
    """
    keys = jnp.asarray(keys, jnp.int32)

    def with_items(_):
        flat = jnp.where(t.buf != EMPTY, t.buf, cfg.route_left).reshape(-1)
        s = jnp.sort(flat)
        q = jax.vmap(cfg.qpack)(keys)
        # qpack packs an all-ones payload, so side="right" lands on the
        # first entry whose *key* is strictly greater (map and set alike)
        idx = jnp.searchsorted(s, q, side="right").astype(jnp.int32)
        safe = jnp.clip(idx, 0, s.shape[0] - 1)
        return jnp.where(idx < s.shape[0], s[safe], cfg.route_left)

    def drained(_):
        return jnp.full(keys.shape, cfg.route_left, cfg.vdtype)

    return jax.lax.cond(jnp.any(t.bcount > 0), with_items, drained, None)


def buffered_member(cfg: TreeConfig, t: DeltaTree, keys: jax.Array):
    """True per key iff the key is pending in some ΔNode's overflow
    buffer (I5' trees).  Leaves and buffers are disjoint (inserts dedup
    against both), so ``found & buffered_member`` is exactly "resolved
    via the buffer" — the ``SearchStats.buffer_hits`` column
    (``repro.obs``), computed in the engine dispatch so it cannot drift
    between engines.  Same shape as `buffered_floor`: one global sort of
    the buffer arena + a searchsorted per query, skipped entirely in the
    common drained state."""
    keys = jnp.asarray(keys, jnp.int32)
    in_domain = (keys >= layout.KEY_MIN) & (keys <= layout.KEY_MAX)

    def with_items(_):
        flat = jnp.where(t.buf != EMPTY, t.buf, cfg.route_left).reshape(-1)
        s = jnp.sort(flat)
        # pack with payload 0: the smallest packed value of this key, so
        # side="left" lands on the key's first stored entry if any
        qlow = cfg.pack(keys, jnp.zeros_like(keys))
        idx = jnp.searchsorted(s, qlow, side="left").astype(jnp.int32)
        safe = jnp.clip(idx, 0, s.shape[0] - 1)
        hit = (idx < s.shape[0]) & (cfg.key_of(s[safe]) == keys)
        return hit & in_domain

    def drained(_):
        return jnp.zeros(keys.shape, jnp.bool_)

    return jax.lax.cond(jnp.any(t.bcount > 0), with_items, drained, None)


@functools.partial(jax.jit, static_argnums=0)
def search_jit(cfg: TreeConfig, t: DeltaTree, keys: jax.Array):
    return search_batch(cfg, t, keys)


@functools.partial(jax.jit, static_argnums=0)
def lookup_jit(cfg: TreeConfig, t: DeltaTree, keys: jax.Array):
    return lookup_batch(cfg, t, keys)


# --------------------------------------------------------------------------
# bulk build (benchmark prefill) — host-side numpy, O(n)
# --------------------------------------------------------------------------


def bulk_build(cfg: TreeConfig, values: np.ndarray,
               payloads: np.ndarray | None = None) -> DeltaTree:
    """Build a half-dense ΔTree from unique keys (any order): the host
    layout (``bulk_layout``), then ``index.build.upload``, the arena to
    the device, waited for."""
    t = bulk_layout(cfg, values, payloads)
    with OT.span("index.build.upload"):
        t = jax.device_put(t)
        jax.block_until_ready(t)
    return t


def bulk_layout(cfg: TreeConfig, values: np.ndarray,
                payloads: np.ndarray | None = None) -> DeltaTree:
    """The half-dense arena of unique keys (any order) as host (numpy)
    arrays of every field.

    Spans (``repro.obs.trace``): ``index.build.sort`` (order and pack
    the keys) and ``index.build.layout`` (the Python loop over ΔNodes)."""
    with OT.span("index.build.sort"):
        values = np.asarray(values, dtype=np.int64)
        order = np.argsort(values)
        values = values[order]
        assert (np.diff(values) > 0).all(), "keys must be unique"
        if payloads is None:
            payloads = np.zeros(len(values), np.int64)
        else:
            payloads = np.asarray(payloads, np.int64)[order]
        assert values.size == 0 or (
            values[0] >= layout.KEY_MIN and values[-1] <= layout.KEY_MAX
        )
        if cfg.payload_bits:
            packed = (values << cfg.payload_bits) | (payloads & cfg.pmask)
            npdt = np.int64
            route_left = np.int64(1) << 62
        else:
            packed = values.astype(np.int32)
            npdt = np.int32
            route_left = np.int32(ROUTE_LEFT)

    with OT.span("index.build.layout"):
        m, ub, lc = cfg.max_dnodes, cfg.ub, cfg.leaf_cap
        g = max(cfg.half_cap, 1)

        value = np.full((m, ub), EMPTY, npdt)
        child = np.full((m, lc), -1, np.int32)
        nlive = np.zeros((m,), np.int32)
        nchild = np.zeros((m,), np.int32)
        parent = np.full((m,), -1, np.int32)
        pslot = np.zeros((m,), np.int32)
        alive = np.zeros((m,), bool)
        next_id = 0

        def new_node():
            nonlocal next_id
            i = next_id
            next_id += 1
            assert i < m, f"bulk_build: arena too small (need > {m} ΔNodes)"
            alive[i] = True
            return i

        def rebuild_np(run, force_bottom=False):
            return layout.rebuild_values_np(
                cfg.height, run, run.size, force_bottom=force_bottom,
                dtype=npdt, route_left=route_left,
            )

        if packed.size == 0:
            ids = [new_node()]
        else:
            ids, mins = [], []
            for s in range(0, packed.size, g):
                run = packed[s : s + g]
                i = new_node()
                value[i] = rebuild_np(run)
                nlive[i] = run.size
                ids.append(i)
                mins.append(run[0])
            while len(ids) > 1:
                nids, nmins = [], []
                for s in range(0, len(ids), g):
                    kids = ids[s : s + g]
                    kmins = np.asarray(mins[s : s + g], npdt)
                    i = new_node()
                    value[i] = rebuild_np(kmins, force_bottom=True)
                    for slot, cid in enumerate(kids):
                        child[i, slot] = cid
                        parent[cid] = i
                        pslot[cid] = slot
                    nchild[i] = len(kids)
                    nids.append(i)
                    nmins.append(kmins[0])
                ids, mins = nids, nmins

        root = ids[0]
        free = np.zeros(m, np.int32)
        nfree = m - next_id
        free[:nfree] = np.arange(m - 1, next_id - 1, -1, dtype=np.int32)
    return _host_tree(cfg, value=value, child=child, nlive=nlive,
                      nchild=nchild, parent=parent, pslot=pslot,
                      alive=alive, free_stack=free, free_top=nfree,
                      root=root)


# --------------------------------------------------------------------------
# debug / verification helpers (host-side)
# --------------------------------------------------------------------------


def live_items(cfg: TreeConfig, t: DeltaTree):
    """All live (key, payload) pairs (host-side; for tests), key-sorted."""
    pos = np.asarray(layout.veb_pos_table(cfg.height))
    value = np.asarray(t.value)
    mark = np.asarray(t.mark)
    child = np.asarray(t.child)
    buf = np.asarray(t.buf)
    alive = np.asarray(t.alive)
    bottom0 = cfg.bottom0
    bits = cfg.payload_bits
    rl = int(np.asarray(cfg.route_left))
    out = []

    def unpack(v):
        v = int(v)
        return (v >> bits, v & cfg.pmask) if bits else (v, 0)

    for dn in range(cfg.max_dnodes):
        if not alive[dn]:
            continue
        for b in range(1, 2**cfg.height):
            v = value[dn, pos[b]]
            if v == EMPTY or v == rl:
                continue
            at_bottom = b >= bottom0
            left = EMPTY if at_bottom else value[dn, pos[2 * b]]
            is_leaf = at_bottom or left == EMPTY
            if not is_leaf:
                continue
            if at_bottom and child[dn, b - bottom0] >= 0:
                continue  # marker
            if mark[dn, pos[b]]:
                continue
            out.append(unpack(v))
        out.extend(unpack(x) for x in buf[dn] if x != EMPTY)
    return sorted(out)


def live_keys(cfg: TreeConfig, t: DeltaTree) -> np.ndarray:
    return np.asarray([k for k, _ in live_items(cfg, t)], dtype=np.int64)


# --------------------------------------------------------------------------
# ordered queries (beyond-paper: the ΔTree is an ordered dictionary)
# --------------------------------------------------------------------------


def successor_one(cfg: TreeConfig, t: DeltaTree, key, max_chase: int = 8):
    """Smallest live key strictly greater than ``key`` (wait-free read).

    Exploits the router invariant (router = min of its right subtree): on
    every left turn the router is a lower bound on the right subtree's
    minimum, so the final candidate is the smallest such router / final
    leaf > key.  A candidate may be stale (mark-deleted leaf still acting
    as router), in which case we chase `successor(candidate)` — bounded by
    ``max_chase`` (tombstone chains are short between Rebalances).

    Returns (found: bool, succ_key: int32 or 0).
    """
    pos = _pos(cfg)
    bottom0 = cfg.bottom0
    big = cfg.route_left

    def one_pass(qkey):
        q = cfg.qpack(qkey)

        def cond(s):
            return ~s[2]

        def body(s):
            dn, b, _, cand = s
            router = t.value[dn, pos[b]]
            at_bottom = b >= bottom0
            left_val = jnp.where(
                at_bottom, jnp.zeros((), cfg.vdtype),
                t.value[dn, pos[jnp.minimum(2 * b, 2 * bottom0 - 1)]],
            )
            internal = (~at_bottom) & (left_val != EMPTY)
            go_left = internal & (q < router)
            # left turn: router bounds the right subtree's min from below
            cand = jnp.where(go_left & (router < cand), router, cand)
            slot = jnp.where(at_bottom, b - bottom0, 0)
            ch = jnp.where(at_bottom, t.child[dn, slot], NONE)
            hop = at_bottom & (ch >= 0)
            nb = jnp.where(internal, 2 * b + (q >= router).astype(jnp.int32), b)
            nb = jnp.where(hop, jnp.int32(1), nb)
            ndn = jnp.where(hop, ch, dn)
            done = (~internal) & (~hop)
            return ndn, nb, done, cand

        dn, b, _, cand = jax.lax.while_loop(
            cond, body, (jnp.int32(t.root), jnp.int32(1), jnp.bool_(False),
                         big))
        leaf_val = t.value[dn, pos[b]]
        leaf_live = (leaf_val != EMPTY) & ~t.mark[dn, pos[b]]
        leaf_gt = leaf_live & (cfg.key_of(leaf_val) > qkey)
        cand = jnp.where(leaf_gt & (leaf_val < cand), leaf_val, cand)
        return cand

    def chase(s):
        qk, _, _, it = s
        cand = one_pass(qk)
        ck = cfg.key_of(cand)
        exists = cand < big
        # verify liveness: the candidate router may be a tombstone
        live, _, _ = search_one(cfg, t, ck)
        done = ~exists | live
        return (jnp.where(done, qk, ck), ck, done & exists, it + 1)

    def ccond(s):
        _, _, done, it = s
        return (~done) & (it < max_chase)

    init = (jnp.asarray(key, jnp.int32), jnp.int32(0), jnp.bool_(False),
            jnp.int32(0))
    _, ck, found, _ = jax.lax.while_loop(ccond, chase, init)
    return found, jnp.where(found, ck, 0)


def successor_batch(cfg: TreeConfig, t: DeltaTree, keys: jax.Array):
    """Vectorized wait-free successor queries via ``cfg.engine``."""
    from repro.core import engine as E  # deferred: engine imports this module

    return E.successor(cfg, t, keys)


@functools.partial(jax.jit, static_argnums=0)
def successor_jit(cfg: TreeConfig, t: DeltaTree, keys: jax.Array):
    """Jitted engine-dispatched successor queries."""
    return successor_batch(cfg, t, keys)


def scan_one(cfg: TreeConfig, t: DeltaTree, start, hi, max_out: int):
    """Scalar reference for the leaf-run scan: emit up to ``max_out``
    live *leaf* items with ``start < key <= hi`` in key order (wait-free
    read; overflow buffers are merged by the engine dispatch, where I5'
    correctness lives).

    One ΔNode row per round, the pass logic of the lockstep scan kernel
    (`kernels.ref.ref_delta_scan_fused` documents it): descend the row
    for the query, emit the live in-band key-leaves from the landing up
    to the first marker, then hop to that marker's child or, at the end
    of the ΔNode, restart at the root for the region bound ``U``.  Keys
    are placed by a scatter on their running count, not by the kernel's
    compaction.  ``hops`` counts the rounds — bit-identical to the
    lockstep accounting.

    Returns (out (max_out,) packed ascending with ``cfg.route_left``
    padding, n int32, hops int32, more bool); ``more`` means a live
    in-band item was left out — resume from ``key_of(out[n-1])``.
    """
    from repro.kernels.ops import scan_round_cap

    h, ub = cfg.height, cfg.ub
    tab = layout.inorder_tables(h)
    storage, left = jnp.asarray(tab["storage"]), jnp.asarray(tab["left"])
    bottom = jnp.asarray(tab["bottom"])
    rank = jnp.arange(ub, dtype=jnp.int32)
    big = cfg.route_left
    pm = jnp.asarray(cfg.pmask, cfg.vdtype)
    start_q = cfg.qpack(jnp.asarray(start, jnp.int32))
    hi_q = cfg.qpack(jnp.asarray(hi, jnp.int32))
    max_rounds = scan_round_cap(h, cfg.max_dnodes)

    def cond(s):
        return (~s["done"]) & (s["rounds"] < max_rounds)

    def body(s):
        x = t.value[s["dn"]][storage]                 # the row in in-order
        dead = t.mark[s["dn"]][storage]
        ch = t.child[s["dn"]][rank // 2]              # bottom rank 2j -> j
        r = jnp.int32(2 ** (h - 1) - 1)
        land = r
        for d in range(h):
            land = jnp.where(x[r] != EMPTY, r, land)
            if d < h - 1:
                off = 2 ** (h - 2 - d)
                r = jnp.where(s["q"] >= x[r], r + off, r - off)
        occ = x != EMPTY
        marker = bottom & occ & (ch >= 0)
        leaf = occ & ~(~bottom & occ[left]) & ~marker & (x != big)
        after = rank >= land
        stop = jnp.min(jnp.where(marker & after, rank, ub))
        run = leaf & after & (rank < stop)
        emit = run & ~dead & (x > start_q) & (x <= hi_q)
        count = jnp.sum(emit, dtype=jnp.int32)
        at = s["n"] + jnp.cumsum(emit.astype(jnp.int32)) - 1
        out = s["out"].at[jnp.where(emit, at, max_out)].set(x, mode="drop")
        full = count > max_out - s["n"]
        past_hi = jnp.any(run & (x > hi_q))
        hop = stop < ub
        # left-turn routers above the marker bound the region after it
        fold = s["bound"]
        bm = cfg.bottom0 + stop // 2                  # the marker's BFS index
        for d in range(h - 1):
            a = bm >> (h - 1 - d)
            v = t.value[s["dn"], _pos(cfg)[a]]
            turn_left = ((bm >> (h - 2 - d)) & 1) == 0
            fold = jnp.where(turn_left & (v < fold), v, fold)
        spent = (s["bound"] == big) | (s["bound"] > hi_q)
        done = full | past_hi | (~hop & spent)
        restart = ~done & ~hop
        return dict(
            dn=jnp.where(done, s["dn"],
                         jnp.where(hop, t.child[s["dn"], stop // 2], t.root)),
            q=jnp.where(restart, s["bound"] | pm, s["q"]),
            bound=jnp.where(restart, big, jnp.where(hop, fold, s["bound"])),
            out=out,
            n=jnp.minimum(s["n"] + count, max_out),
            hops=s["hops"] + 1,
            more=full,
            done=done,
            rounds=s["rounds"] + 1,
        )

    init = dict(dn=jnp.asarray(t.root, jnp.int32), q=start_q,
                bound=jnp.asarray(big, cfg.vdtype),
                out=jnp.full((max_out,), big, cfg.vdtype),
                n=jnp.int32(0), hops=jnp.int32(0), more=jnp.bool_(False),
                done=jnp.bool_(False), rounds=jnp.int32(0))
    s = jax.lax.while_loop(cond, body, init)
    return s["out"], s["n"], s["hops"], s["more"]


def scan_batch(cfg: TreeConfig, t: DeltaTree, starts: jax.Array,
               his: jax.Array, max_out: int):
    """Vectorized ordered scans via ``cfg.engine`` (buffered items merged
    under non-eager maintenance — see `engine.scan`)."""
    from repro.core import engine as E  # deferred: engine imports this module

    return E.scan(cfg, t, starts, his, max_out=max_out)


@functools.partial(jax.jit, static_argnums=(0, 4))
def scan_jit(cfg: TreeConfig, t: DeltaTree, starts: jax.Array,
             his: jax.Array, max_out: int):
    """Jitted engine-dispatched range scans."""
    return scan_batch(cfg, t, starts, his, max_out)


def successor_k_batch(cfg: TreeConfig, t: DeltaTree, keys: jax.Array,
                      k: int):
    """Bulk ordered reads: the ``k`` smallest live keys strictly greater
    than each query key — a scan with an unbounded upper band."""
    keys = jnp.asarray(keys, jnp.int32)
    his = jnp.full(keys.shape, layout.KEY_MAX, jnp.int32)
    return scan_batch(cfg, t, keys, his, k)


@functools.partial(jax.jit, static_argnums=(0, 3))
def successor_k_jit(cfg: TreeConfig, t: DeltaTree, keys: jax.Array, k: int):
    """Jitted engine-dispatched successor_k queries."""
    return successor_k_batch(cfg, t, keys, k)
