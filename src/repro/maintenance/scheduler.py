"""MaintenanceScheduler — the update-step round loop, extracted from
``deltatree.update_batch_impl`` and made policy-driven.

One round = (op phase) + (maintenance phase).  The op phase is shared by
every policy: one *frontier* position pass for the whole pending batch
(``kernels.ops.delta_walk`` under the lockstep engine, a vmapped scalar
descent otherwise), the vectorized non-conflicting fastpath, then the
budgeted sequential leftovers in batch order.  The maintenance phase is
what the policy controls:

- ``eager``:     process every flagged ΔNode, round after round, until the
                 fixpoint (bit-identical to the pre-subsystem semantics —
                 same phase order, same per-phase budget, same round count).
- ``deferred``:  no voluntary maintenance.  *Forced* repairs still run when
                 a full buffer blocks a pending op (the paper's
                 "occasionally blocked by maintenance") or when a repair
                 left I5'-violating residual items behind.
- ``budgeted:k``: up to ``k`` voluntary repairs per update batch, highest
                 buffer occupancy first (then Merge candidates); forced
                 repairs are always allowed on top — correctness over
                 deferral.

Invariant I5' (non-eager policies): every buffered value's root descent
lands in the ΔNode whose buffer holds it, so the wait-free read path
(final-ΔNode buffer probe in ``deltatree.searchnode``) keeps finding
pending items.  An Expand that fails to move an item into a full child
("keep") violates I5' — such nodes are tracked as *residual* and force-
drained (together with every full buffer, which is what blocks a keep)
before the step returns.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import deltatree as DT
from repro.maintenance.policy import MaintenancePolicy, parse_policy
from repro.maintenance.stats import MaintenanceStats

_Work = tuple  # (rebuilds, expands, merges, reclaimed) int32 scalars


def _zero_work() -> _Work:
    z = jnp.int32(0)
    return (z, z, z, z)


def pending_count(cfg, t) -> jax.Array:
    """Buffered items still awaiting maintenance (the I5' carry)."""
    return jnp.sum(jnp.where(t.alive, t.bcount, 0)).astype(jnp.int32)


# --------------------------------------------------------------------------
# frontier positions — the lockstep update descent (ROADMAP item)
# --------------------------------------------------------------------------


def _positions(cfg, t, q):
    """(dn, b) leaf positions for every packed query in ``q``.

    Under the lockstep engine this is ONE ``delta_walk`` frontier pass for
    the whole batch (each round gathers every active query's ΔNode row with
    one contiguous DMA) — the same kernel invocation the read path uses
    (``core.engine._lockstep_walk``, so kernel/tile plumbing cannot
    drift); otherwise the vmapped scalar ``_descend``.  Both return the
    identical positions — the engine-parity suite pins this.
    """
    if cfg.engine == "lockstep":
        from repro.core import engine as E

        _, lb, dn, _, _ = E._lockstep_walk(cfg, t, q)
        return dn, lb
    dns, bs, _ = jax.vmap(lambda qq: DT._descend(cfg, t, qq, t.root, 1))(q)
    return dns, bs


# --------------------------------------------------------------------------
# op phase (policy-independent)
# --------------------------------------------------------------------------


def _ops_phase(cfg, t, results, pending, kinds, keys, payloads, budget):
    """One round's op applications: frontier positions -> vectorized
    fastpath -> budgeted sequential leftovers in batch order.

    Under the lockstep engine the round's positions also seed the
    sequential ops as descent *hints*: within an op phase the structure
    only grows downward (grow/place at leaves; routers and child links
    untouched), so restarting ``_descend`` from the round-start endpoint
    reaches the true endpoint — the scatter half stays scalar, the
    position-finding half is the one kernel pass.

    Returns (t, results, pending, dns): the round-start positions are
    handed back so the relaxed policies' ``forced_mask`` can identify the
    ΔNodes blocking still-pending ops without a second frontier walk
    (valid for buffer-blocked ops — bottom positions don't restructure
    within an op phase; a conflict loser's stale position at worst defers
    its forced repair one round, and the round loop retries it anyway).
    """

    def run(args):
        t, results, pending = args
        q = jax.vmap(cfg.qpack)(keys)
        dns, bs = _positions(cfg, t, q)
        if cfg.parallel_updates:
            t, results, pending = DT._parallel_fastpath(
                cfg, t, kinds, keys, payloads, results, pending, dns, bs)

        def seq_phase(args):
            t, results, pending = args
            k = keys.shape[0]
            pend_ids = jnp.nonzero(pending, size=budget, fill_value=-1)[0]

            def op_body(j, s):
                t, results, pending = s
                i = pend_ids[j]

                def run_op(args):
                    t, results, pending = args
                    ii = jnp.maximum(i, 0)
                    # batch order is the linearization: an op must wait
                    # while an *earlier* op on the same key is still
                    # pending (e.g. an insert blocked on a full buffer),
                    # else a later delete would miss its predecessor
                    blocked = jnp.any(pending & (keys == keys[ii])
                                      & (jnp.arange(k) < ii))
                    if cfg.engine == "lockstep":
                        dn0, b0 = dns[ii], bs[ii]
                    else:
                        dn0 = b0 = None

                    def ins(t):
                        return DT._insert_op(cfg, t, keys[ii], payloads[ii],
                                             dn0, b0)

                    def dele(t):
                        return DT._delete_op(cfg, t, keys[ii], dn0, b0)

                    def do(args):
                        t, results, pending = args
                        tt, ok, pend = jax.lax.cond(
                            kinds[ii] == DT.OP_INSERT, ins, dele, t)
                        return (tt, results.at[ii].set(ok),
                                pending.at[ii].set(pend))

                    return jax.lax.cond(blocked, lambda a: a, do,
                                        (t, results, pending))

                return jax.lax.cond(i >= 0, run_op, lambda a: a,
                                    (t, results, pending))

            return jax.lax.fori_loop(0, budget, op_body,
                                     (t, results, pending))

        t, results, pending = jax.lax.cond(
            jnp.any(pending), seq_phase, lambda a: a, (t, results, pending))
        return t, results, pending, dns

    def skip(args):
        t, results, pending = args
        # nothing pending: positions are unused downstream (forced_mask
        # only reads them where ``pending`` is True)
        return t, results, pending, jnp.zeros(keys.shape, jnp.int32)

    return jax.lax.cond(jnp.any(pending), run, skip,
                        (t, results, pending))


# --------------------------------------------------------------------------
# maintenance sweeps (shared by every policy)
# --------------------------------------------------------------------------


def _ins_sweep(cfg, t, work, mask, budget):
    """Process up to ``budget`` ins-flagged ΔNodes from ``mask`` (Rebalance
    or Expand).  Returns (t, work, processed-mask)."""
    m = cfg.max_dnodes
    ids = jnp.nonzero(mask, size=budget, fill_value=-1)[0]

    def body(j, s):
        t, work = s
        dn = ids[j]

        def run(s):
            t, work = s
            tt, rebuilds, expands = DT._process_ins(cfg, t, dn)
            return tt, (work[0] + rebuilds, work[1] + expands, work[2],
                        work[3])

        return jax.lax.cond(dn >= 0, run, lambda s: s, s)

    t, work = jax.lax.fori_loop(0, budget, body, (t, work))
    pmask = jnp.zeros((m,), bool).at[
        jnp.where(ids >= 0, ids, m)].set(True, mode="drop")
    return t, work, pmask


def _del_sweep(cfg, t, work, mask, budget):
    """Process up to ``budget`` Merge candidates from ``mask``."""
    ids = jnp.nonzero(mask, size=budget, fill_value=-1)[0]

    def body(j, s):
        t, work = s
        dn = ids[j]

        def run(s):
            t, work = s
            tt, merged = DT._process_del(cfg, t, dn)
            # freed arena slots = freelist growth across the splice
            return tt, (work[0], work[1], work[2] + merged,
                        work[3] + (tt.free_top - t.free_top))

        return jax.lax.cond(dn >= 0, run, lambda s: s, s)

    return jax.lax.fori_loop(0, budget, body, (t, work))


def _maint_phases(cfg, t, work, budget):
    """One eager maintenance pass: every ins-flagged ΔNode (Rebalance /
    Expand), then every Merge candidate, each under its own any-flagged
    cond.  Shared verbatim by `_run_eager`'s round body and `flush`'s —
    the "deferred batch + flush == eager, bit for bit" guarantee is
    structural, not copy-maintained."""
    t, work = jax.lax.cond(
        jnp.any(t.ins_flag & t.alive),
        lambda a: _ins_sweep(cfg, a[0], a[1],
                             a[0].ins_flag & a[0].alive, budget)[:2],
        lambda a: a, (t, work))
    t, work = jax.lax.cond(
        jnp.any(t.del_flag & t.alive),
        lambda a: _del_sweep(cfg, a[0], a[1],
                             a[0].del_flag & a[0].alive, budget),
        lambda a: a, (t, work))
    return t, work


# --------------------------------------------------------------------------
# eager — the pre-subsystem fixpoint loop, bit for bit
# --------------------------------------------------------------------------


def _run_eager(cfg, t, kinds, keys, payloads, results, pending, budget):
    def round_cond(s):
        t, _, pending, rounds, _ = s
        busy = jnp.any(pending) | jnp.any(t.ins_flag & t.alive) | jnp.any(
            t.del_flag & t.alive
        )
        return busy & (rounds < cfg.max_rounds)

    def round_body(s):
        t, results, pending, rounds, work = s
        t, results, pending, _ = _ops_phase(cfg, t, results, pending,
                                            kinds, keys, payloads, budget)
        t, work = _maint_phases(cfg, t, work, budget)
        return t, results, pending, rounds + 1, work

    t, results, pending, rounds, work = jax.lax.while_loop(
        round_cond, round_body,
        (t, results, pending, jnp.int32(0), _zero_work()))
    return t, results, rounds, work


# --------------------------------------------------------------------------
# deferred / budgeted — carry flags forward, force only what blocks
# --------------------------------------------------------------------------


def _run_relaxed(cfg, policy: MaintenancePolicy, t, kinds, keys, payloads,
                 results, pending, budget):
    m = cfg.max_dnodes
    vol = policy.budget if policy.kind == "budgeted" else 0
    vol_k = min(vol, m) if vol else 0
    low_water = max(1, m // 8)  # freelist pressure threshold (slots)

    def forced_mask(t, pending, residual, dns):
        """ΔNodes that must be repaired now: targets of *blocked* pending
        ops (full target buffer — an op merely carried past the per-round
        sequential budget retries next round without maintenance),
        residual (I5'-violating) nodes, and — while residual exists —
        every full buffer (a keep's blocker is a full child buffer).
        ``dns`` are the round's op-phase positions (no second walk)."""
        blocked = pending & (t.bcount[jnp.clip(dns, 0, m - 1)]
                             >= cfg.buf_cap)
        mask = jnp.zeros((m,), bool).at[
            jnp.where(blocked, dns, m)].set(True, mode="drop")
        full = t.bcount >= cfg.buf_cap
        mask = mask | residual | (jnp.any(residual) & full)
        return mask & t.ins_flag & t.alive

    def voluntary_phase(args):
        """Budgeted-only: top-occupancy Rebalance/Expand repairs, then
        Merge candidates, sharing one per-batch repair budget."""
        t, work, repairs, residual = args
        occ = jnp.where(t.ins_flag & t.alive, t.bcount, -1)
        vals, ids = jax.lax.top_k(occ, vol_k)

        def ins_body(j, s):
            t, work, repairs, residual = s

            def run(s):
                t, work, repairs, residual = s
                tt, rb, ex = DT._process_ins(cfg, t, ids[j])
                # an Expand that "kept" items (full child) left dn in an
                # I5'-violating state — mark residual so the forced sweep
                # drains it before the step returns, same as forced repairs
                residual = residual.at[ids[j]].set(tt.bcount[ids[j]] > 0)
                return (tt, (work[0] + rb, work[1] + ex, work[2], work[3]),
                        repairs + 1, residual)

            return jax.lax.cond((vals[j] >= 0) & (repairs < vol), run,
                                lambda s: s, s)

        t, work, repairs, residual = jax.lax.fori_loop(
            0, vol_k, ins_body, (t, work, repairs, residual))
        # Merge-candidate selection.  Normally candidates run in arena
        # order (the historical ``nonzero`` order).  When the freelist
        # drops below the low-water mark, rank by the reclaimable-arena
        # estimate instead: candidates whose splice will return a child
        # slot to the freelist (live sibling, no children, drained
        # buffer) run first, so a starved allocator recovers slots
        # before the budget is spent on no-op merges.
        idx = jnp.arange(m, dtype=jnp.int32)
        cand = t.del_flag & t.alive
        sib_ok = t.child[jnp.maximum(t.parent, 0), t.pslot ^ 1] >= 0
        reclaim = ((t.parent >= 0) & sib_ok & (t.nchild == 0)
                   & (t.bcount == 0))
        pressure = t.free_top < low_water
        rank = jnp.where(cand,
                         idx + jnp.where(pressure & ~reclaim, m, 0),
                         2 * m)
        order = jnp.argsort(rank)[:vol_k].astype(jnp.int32)
        del_ids = jnp.where(rank[order] < 2 * m, order, -1)

        def del_body(j, s):
            t, work, repairs, residual = s
            dn = del_ids[j]
            # merging under a parent with buffered items would re-route
            # those items' descents into the merged child (I5' violation
            # that eager's fixpoint self-heals but a budget would strand) —
            # defer the merge until the parent drains
            p = t.parent[jnp.maximum(dn, 0)]
            parent_clear = t.bcount[jnp.maximum(p, 0)] == 0

            def run(s):
                t, work, repairs, residual = s
                tt, mg = DT._process_del(cfg, t, dn)
                return (tt, (work[0], work[1], work[2] + mg,
                             work[3] + (tt.free_top - t.free_top)),
                        repairs + 1, residual)

            return jax.lax.cond(
                (dn >= 0) & (repairs < vol) & parent_clear, run,
                lambda s: s, s)

        return jax.lax.fori_loop(0, vol_k, del_body,
                                 (t, work, repairs, residual))

    def round_cond(s):
        t, _, pending, rounds, work, repairs, residual = s
        busy = jnp.any(pending) | jnp.any(residual & t.alive)
        if vol:
            flagged = (t.ins_flag | t.del_flag) & t.alive
            busy = busy | ((repairs < vol) & jnp.any(flagged))
        return busy & (rounds < cfg.max_rounds)

    def round_body(s):
        t, results, pending, rounds, work, repairs, residual = s
        t, results, pending, dns = _ops_phase(cfg, t, results, pending,
                                              kinds, keys, payloads,
                                              budget)
        if vol:
            t, work, repairs, residual = jax.lax.cond(
                (repairs < vol) & jnp.any((t.ins_flag | t.del_flag)
                                          & t.alive),
                voluntary_phase, lambda a: a, (t, work, repairs, residual))
        fmask = forced_mask(t, pending, residual, dns)

        def forced(args):
            t, work, residual = args
            t, work, pmask = _ins_sweep(cfg, t, work, fmask, budget)
            residual = (residual & ~pmask) | (pmask & (t.bcount > 0)
                                              & t.alive)
            return t, work, residual

        t, work, residual = jax.lax.cond(
            jnp.any(fmask), forced, lambda a: a, (t, work, residual))
        return t, results, pending, rounds + 1, work, repairs, residual

    t, results, pending, rounds, work, _, _ = jax.lax.while_loop(
        round_cond, round_body,
        (t, results, pending, jnp.int32(0), _zero_work(), jnp.int32(0),
         jnp.zeros((m,), bool)))
    return t, results, rounds, work


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------


def run_update(cfg, t, kinds, keys, payloads=None):
    """Apply one update batch under ``cfg.maintenance_policy``.

    Returns (tree, results[K] bool, MaintenanceStats) — the body behind
    ``deltatree.update_batch_impl``.
    """
    policy = parse_policy(cfg.maintenance)
    k = keys.shape[0]
    if payloads is None:
        payloads = jnp.zeros((k,), jnp.int32)
    results = jnp.zeros((k,), jnp.bool_)
    pending = kinds != DT.OP_SEARCH
    budget = min(k, 64)  # sequential work per round (leftovers re-round)

    if policy.eager:
        t, results, rounds, work = _run_eager(
            cfg, t, kinds, keys, payloads, results, pending, budget)
    else:
        t, results, rounds, work = _run_relaxed(
            cfg, policy, t, kinds, keys, payloads, results, pending, budget)
    stats = MaintenanceStats(
        rounds=rounds, rebuilds=work[0], expands=work[1], merges=work[2],
        pending=pending_count(cfg, t), reclaimed=work[3])
    return t, results, stats


def flush(cfg, t, budget: int = 64):
    """Drain every flagged ΔNode to the maintenance fixpoint (restores I5).

    The maintenance-only rounds are structured exactly like the eager
    loop's (same phase order, same per-phase ``budget``): a deferred batch
    followed by ``flush(budget=min(K, 64))`` reproduces the eager tree bit
    for bit whenever no op was force-blocked mid-batch.
    Returns (tree, MaintenanceStats).
    """

    def round_cond(s):
        t, rounds, _ = s
        busy = jnp.any(t.ins_flag & t.alive) | jnp.any(t.del_flag & t.alive)
        return busy & (rounds < cfg.max_rounds)

    def round_body(s):
        t, rounds, work = s
        t, work = _maint_phases(cfg, t, work, budget)
        return t, rounds + 1, work

    t, rounds, work = jax.lax.while_loop(
        round_cond, round_body, (t, jnp.int32(0), _zero_work()))
    stats = MaintenanceStats(
        rounds=rounds, rebuilds=work[0], expands=work[1], merges=work[2],
        pending=pending_count(cfg, t), reclaimed=work[3])
    return t, stats
