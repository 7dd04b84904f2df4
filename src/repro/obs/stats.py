"""Counter pytrees — jit/shard_map-safe telemetry riding the return path.

Every class here follows the contract ``MaintenanceStats`` (PR 4)
established: a ``NamedTuple`` of small jax arrays (so it flows through
``jit`` / ``donate_argnums`` / ``shard_map`` unchanged), a ``zero()``
constructor, a ``reduce()`` that aggregates a stacked (S,) leading axis
(per-shard legs: *rounds-like* fields take the max — shards run
concurrently, so the critical path is what you'd measure — while
*work-like* fields sum), a ``merge()`` that folds two instances (for
accumulating across benchmark steps without a host sync), and a host-side
``asdict()`` for JSON rows and logging.

Collection is gated by the static ``TreeConfig.collect_stats`` flag and
happens in the *dispatch* layers (``repro.core.engine``,
``repro.distributed.forest``), never inside an engine hook — both
SearchEngines produce bit-identical ``found``/``hops`` columns
(conformance-tested), so computing ``SearchStats`` from those columns
makes the cross-engine histogram parity structural rather than something
each engine must re-earn.

This module imports only jax — no ``repro`` modules — so any layer of
the stack (kernels included) can depend on it without cycles.
"""

from __future__ import annotations

import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HOP_BINS = 16          # SearchStats histogram bins (hops clip to the last)
OCC_ROUNDS = 16        # SearchStats per-round occupancy window
LATENCY_RESERVOIR = 512  # ServeStats ring-buffer capacity (decode steps)
TRANSFER_BLOCK_SIZES = (8, 16, 32, 64)  # TransferStats block-size sweep (B)


class MaintenanceStats(NamedTuple):
    """Why and how much maintenance ran during one update step.

    Returned (alongside the tree and per-op results) by every
    ``update_batch`` / forest ``update_batch`` / ``Index.update`` call,
    and by ``flush``.  Re-homed from ``repro.maintenance.stats`` (which
    still re-exports it) when ``repro.obs`` became the home of every
    counter pytree.
    """

    rounds: jax.Array    # () int32 — scheduler rounds taken
    rebuilds: jax.Array  # () int32 — Rebalance mirror-swaps
    expands: jax.Array   # () int32 — child ΔNodes allocated by Expand
    merges: jax.Array    # () int32 — successful Merge splices
    pending: jax.Array   # () int32 — buffered items carried forward (I5')
    reclaimed: jax.Array = jnp.int32(0)  # () int32 — arena slots freed
    #                      by Merge splicing away a child ΔNode (the
    #                      freelist-pressure signal the budgeted Merge
    #                      ranking feeds on; trailing default keeps older
    #                      5-field construction sites valid)

    @classmethod
    def zero(cls) -> "MaintenanceStats":
        z = jnp.int32(0)
        return cls(rounds=z, rebuilds=z, expands=z, merges=z, pending=z,
                   reclaimed=z)

    @classmethod
    def reduce(cls, stacked: "MaintenanceStats") -> "MaintenanceStats":
        """Aggregate per-shard (S,) stats: rounds is the critical path
        (max over shards — shards run concurrently), work counters sum."""
        return cls(
            rounds=jnp.max(stacked.rounds),
            rebuilds=jnp.sum(stacked.rebuilds),
            expands=jnp.sum(stacked.expands),
            merges=jnp.sum(stacked.merges),
            pending=jnp.sum(stacked.pending),
            reclaimed=jnp.sum(stacked.reclaimed),
        )

    def merge(self, other: "MaintenanceStats") -> "MaintenanceStats":
        """Fold two steps' stats (rounds max, work sums; pending is the
        latest step's carry — the earlier one was superseded)."""
        return MaintenanceStats(
            rounds=jnp.maximum(self.rounds, other.rounds),
            rebuilds=self.rebuilds + other.rebuilds,
            expands=self.expands + other.expands,
            merges=self.merges + other.merges,
            pending=other.pending,
            reclaimed=self.reclaimed + other.reclaimed,
        )

    def asdict(self) -> dict:
        """Host-side plain-int view (for JSON benchmark rows / logging)."""
        return {k: int(v) for k, v in self._asdict().items()}

    # ---- deprecation shim: the old third tuple element was ``rounds`` ----

    def __int__(self) -> int:
        warnings.warn(
            "update_batch now returns MaintenanceStats as its third "
            "element; use stats.rounds instead of treating it as the "
            "round count",
            DeprecationWarning,
            stacklevel=2,
        )
        return int(self.rounds)

    __index__ = __int__


class SearchStats(NamedTuple):
    """One read batch, as the paper would measure it (§5, Table 1).

    ``hops`` is the per-query transfer statistic both engines report
    bit-identically (ΔNode boundary crossings == lockstep rounds active),
    so every field derives from the same columns on either engine:
    ``rounds`` is the frontier's round count (max hops over the batch —
    the lockstep walk runs exactly that many kernel launches), and
    ``occupancy[r]`` counts the lanes still active entering round r (a
    query with h hops is active in rounds 0..h-1) — the frontier decay
    profile the compiled campaign needs to size ``q_tile``.
    """

    queries: jax.Array      # () int32 — lanes in the batch (pads included)
    pad_lanes: jax.Array    # () int32 — born-resolved ROUTE_LEFT lanes
    hops_sum: jax.Array     # () int32 — total ΔNode transfers
    hops_max: jax.Array     # () int32 — deepest walk in the batch
    rounds: jax.Array       # () int32 — lockstep frontier rounds (= hops_max)
    buffer_hits: jax.Array  # () int32 — queries resolved from overflow buffers
    hops_hist: jax.Array    # (HOP_BINS,) int32 — hops histogram (clipped)
    occupancy: jax.Array    # (OCC_ROUNDS,) int32 — active lanes per round

    @classmethod
    def zero(cls) -> "SearchStats":
        z = jnp.int32(0)
        return cls(queries=z, pad_lanes=z, hops_sum=z, hops_max=z, rounds=z,
                   buffer_hits=z,
                   hops_hist=jnp.zeros((HOP_BINS,), jnp.int32),
                   occupancy=jnp.zeros((OCC_ROUNDS,), jnp.int32))

    @classmethod
    def of(cls, hops: jax.Array, pad: jax.Array,
           buffer_hit: jax.Array) -> "SearchStats":
        """Derive the batch's stats from its per-query columns:
        ``hops[K]`` int32, ``pad[K]`` bool (sentinel lanes), and
        ``buffer_hit[K]`` bool (found via an overflow buffer)."""
        hops = jnp.asarray(hops, jnp.int32)
        hmax = jnp.max(hops)
        hist = jnp.zeros((HOP_BINS,), jnp.int32).at[
            jnp.clip(hops, 0, HOP_BINS - 1)].add(1)
        occ = jnp.sum(
            hops[None, :] > jnp.arange(OCC_ROUNDS, dtype=jnp.int32)[:, None],
            axis=1, dtype=jnp.int32)
        return cls(
            queries=jnp.int32(hops.shape[0]),
            pad_lanes=jnp.sum(pad, dtype=jnp.int32),
            hops_sum=jnp.sum(hops),
            hops_max=hmax,
            rounds=hmax,
            buffer_hits=jnp.sum(buffer_hit, dtype=jnp.int32),
            hops_hist=hist,
            occupancy=occ,
        )

    @classmethod
    def reduce(cls, stacked: "SearchStats") -> "SearchStats":
        """Aggregate stacked (S,) legs: rounds-like fields max (concurrent
        frontiers — the critical path), work-like fields sum."""
        return cls(
            queries=jnp.sum(stacked.queries),
            pad_lanes=jnp.sum(stacked.pad_lanes),
            hops_sum=jnp.sum(stacked.hops_sum),
            hops_max=jnp.max(stacked.hops_max),
            rounds=jnp.max(stacked.rounds),
            buffer_hits=jnp.sum(stacked.buffer_hits),
            hops_hist=jnp.sum(stacked.hops_hist, axis=0),
            occupancy=jnp.sum(stacked.occupancy, axis=0),
        )

    def merge(self, other: "SearchStats") -> "SearchStats":
        """Fold another batch's stats in (benchmark-loop accumulation;
        stays device-side — no host sync mid-loop)."""
        return SearchStats(
            queries=self.queries + other.queries,
            pad_lanes=self.pad_lanes + other.pad_lanes,
            hops_sum=self.hops_sum + other.hops_sum,
            hops_max=jnp.maximum(self.hops_max, other.hops_max),
            rounds=jnp.maximum(self.rounds, other.rounds),
            buffer_hits=self.buffer_hits + other.buffer_hits,
            hops_hist=self.hops_hist + other.hops_hist,
            occupancy=self.occupancy + other.occupancy,
        )

    def asdict(self) -> dict:
        real = max(int(self.queries) - int(self.pad_lanes), 1)
        return {
            "queries": int(self.queries),
            "pad_lanes": int(self.pad_lanes),
            "hops_sum": int(self.hops_sum),
            "hops_max": int(self.hops_max),
            "hops_mean": round(int(self.hops_sum) / real, 3),
            "rounds": int(self.rounds),
            "buffer_hits": int(self.buffer_hits),
            "hops_hist": np.asarray(self.hops_hist).tolist(),
            "occupancy": np.asarray(self.occupancy).tolist(),
        }


class RouterStats(NamedTuple):
    """One routed batch through the forest router (skew telemetry — the
    load-adaptive ROADMAP item's input signal)."""

    lanes: jax.Array    # (S,) int32 — ops routed to each shard
    clamped: jax.Array  # () int32 — out-of-domain keys clamped by the router
    batches: jax.Array  # () int32 — batches folded in (1 for a fresh batch)

    @classmethod
    def zero(cls, num_shards: int) -> "RouterStats":
        return cls(lanes=jnp.zeros((num_shards,), jnp.int32),
                   clamped=jnp.int32(0), batches=jnp.int32(0))

    @classmethod
    def of(cls, lanes: jax.Array, clamped) -> "RouterStats":
        return cls(lanes=jnp.asarray(lanes, jnp.int32),
                   clamped=jnp.asarray(clamped, jnp.int32),
                   batches=jnp.int32(1))

    @classmethod
    def reduce(cls, stacked: "RouterStats") -> "RouterStats":
        """Aggregate stacked (N, S) legs (lane counts and clamps are all
        work-like: everything sums)."""
        return cls(lanes=jnp.sum(stacked.lanes, axis=0),
                   clamped=jnp.sum(stacked.clamped),
                   batches=jnp.sum(stacked.batches))

    def merge(self, other: "RouterStats") -> "RouterStats":
        return RouterStats(lanes=self.lanes + other.lanes,
                           clamped=self.clamped + other.clamped,
                           batches=self.batches + other.batches)

    def skew(self) -> float:
        """max/mean shard load — 1.0 is a perfectly balanced router."""
        lanes = np.asarray(self.lanes, np.float64)
        mean = lanes.mean()
        return float(lanes.max() / mean) if mean > 0 else 1.0

    def asdict(self) -> dict:
        return {
            "lanes": np.asarray(self.lanes).tolist(),
            "clamped": int(self.clamped),
            "batches": int(self.batches),
            "skew": round(self.skew(), 3),
        }


class TransferStats(NamedTuple):
    """Measured memory transfers of one read batch in the ideal-cache
    model (the paper's O(log_B N) claim, Table 1 / Lemma 2.1).

    Derived in the dispatch layers by replaying the walk's per-level
    gather indices device-side (`repro.obs.transfers`) — the replay
    depends only on (arena, roots, keys), never on which engine or
    dispatch produced the result, so cross-engine bit-parity is
    structural like ``SearchStats``.  ``blocks[i]`` is the batch total of
    *distinct* ``TRANSFER_BLOCK_SIZES[i]``-element blocks touched per
    query (what `core.baselines.count_block_transfers` counts, exactly);
    ``buffer_probes`` (SEARCHNODE's branchless overflow-buffer row read,
    one per resolved real query) is kept out of the block counts — the
    analytical model excludes it too.
    """

    queries: jax.Array         # () int32 — lanes in the batch (pads included)
    pad_lanes: jax.Array       # () int32 — born-resolved ROUTE_LEFT lanes
    dnode_visits: jax.Array    # () int32 — distinct ΔNodes entered (batch sum)
    router_touches: jax.Array  # () int32 — element reads steering the walk
    leaf_touches: jax.Array    # () int32 — terminal leaf-test reads
    buffer_probes: jax.Array   # () int32 — SEARCHNODE buffer-row probes
    blocks: jax.Array          # (len(TRANSFER_BLOCK_SIZES),) int32 totals
    batches: jax.Array         # () int32 — batches folded in

    @classmethod
    def zero(cls) -> "TransferStats":
        z = jnp.int32(0)
        return cls(queries=z, pad_lanes=z, dnode_visits=z, router_touches=z,
                   leaf_touches=z, buffer_probes=z,
                   blocks=jnp.zeros((len(TRANSFER_BLOCK_SIZES),), jnp.int32),
                   batches=z)

    @classmethod
    def of(cls, pad: jax.Array, visits: jax.Array, router: jax.Array,
           leaf: jax.Array, blocks: jax.Array) -> "TransferStats":
        """Derive the batch's stats from per-query columns: ``pad[K]``
        bool, ``visits[K]`` / ``router[K]`` / ``leaf[K]`` int32 counts,
        and ``blocks[K, len(TRANSFER_BLOCK_SIZES)]`` distinct-block
        counts (all already zero on pad lanes — `obs.transfers`)."""
        real = jnp.sum(~pad, dtype=jnp.int32)
        return cls(
            queries=jnp.int32(pad.shape[0]),
            pad_lanes=jnp.sum(pad, dtype=jnp.int32),
            dnode_visits=jnp.sum(visits, dtype=jnp.int32),
            router_touches=jnp.sum(router, dtype=jnp.int32),
            leaf_touches=jnp.sum(leaf, dtype=jnp.int32),
            buffer_probes=real,
            blocks=jnp.sum(blocks, axis=0, dtype=jnp.int32),
            batches=jnp.int32(1),
        )

    @classmethod
    def reduce(cls, stacked: "TransferStats") -> "TransferStats":
        """Aggregate stacked (S,) legs: transfers are all work-like —
        everything sums (concurrent shards still move every block)."""
        return cls(*(jnp.sum(x, axis=0) for x in stacked))

    def merge(self, other: "TransferStats") -> "TransferStats":
        return TransferStats(*(a + b for a, b in zip(self, other)))

    def asdict(self) -> dict:
        real = max(int(self.queries) - int(self.pad_lanes), 1)
        out = {
            "queries": int(self.queries),
            "pad_lanes": int(self.pad_lanes),
            "dnode_visits": int(self.dnode_visits),
            "router_touches": int(self.router_touches),
            "leaf_touches": int(self.leaf_touches),
            "buffer_probes": int(self.buffer_probes),
            "batches": int(self.batches),
            "visits_mean": round(int(self.dnode_visits) / real, 3),
        }
        for i, b in enumerate(TRANSFER_BLOCK_SIZES):
            out[f"blocks_b{b}"] = int(self.blocks[i])
            out[f"blocks_b{b}_mean"] = round(int(self.blocks[i]) / real, 3)
        return out


class ReadStats(NamedTuple):
    """What a stats-collecting read returns as its trailing element:
    the batch's ``SearchStats`` plus, on the forest dispatch, the
    router's ``RouterStats``, plus — under the ``collect_transfers``
    sub-gate — the measured ``TransferStats`` (``None`` legs flatten to
    nothing, so the jitted entry points stay shape-static either way)."""

    search: SearchStats
    router: RouterStats | None = None
    transfers: TransferStats | None = None


class ScanStats(NamedTuple):
    """Range-scan / bulk-ordered-read telemetry.  One ``of`` per scan
    dispatch (a whole lane batch); counters fold with ``merge`` and
    stacked legs aggregate with ``reduce`` like the other stats classes.
    ``truncated`` counts lanes whose output buffer filled before the
    range was exhausted (``more=True`` — the caller holds a continuation
    cursor), which is the honest signal that a sweep under-sized
    ``max_items``."""

    scans: jax.Array      # () int32 — scan dispatches folded in
    lanes: jax.Array      # () int32 — scan lanes served
    emitted: jax.Array    # () int32 — (key, payload) rows emitted
    truncated: jax.Array  # () int32 — lanes that filled max_items (more)
    hops_sum: jax.Array   # () int32 — total ΔNode visits across lanes
    hops_max: jax.Array   # () int32 — worst single-lane ΔNode visits

    @classmethod
    def zero(cls) -> "ScanStats":
        z = jnp.int32(0)
        return cls(scans=z, lanes=z, emitted=z, truncated=z,
                   hops_sum=z, hops_max=z)

    @classmethod
    def of(cls, n: jax.Array, hops: jax.Array,
           more: jax.Array) -> "ScanStats":
        """Build from one scan dispatch's per-lane columns (the engine's
        ``(out, n, hops, more)`` tail)."""
        return cls(scans=jnp.int32(1),
                   lanes=jnp.int32(n.shape[0]),
                   emitted=jnp.sum(n).astype(jnp.int32),
                   truncated=jnp.sum(more.astype(jnp.int32)),
                   hops_sum=jnp.sum(hops).astype(jnp.int32),
                   hops_max=jnp.max(hops).astype(jnp.int32))

    def merge(self, other: "ScanStats") -> "ScanStats":
        return ScanStats(scans=self.scans + other.scans,
                         lanes=self.lanes + other.lanes,
                         emitted=self.emitted + other.emitted,
                         truncated=self.truncated + other.truncated,
                         hops_sum=self.hops_sum + other.hops_sum,
                         hops_max=jnp.maximum(self.hops_max, other.hops_max))

    @classmethod
    def reduce(cls, stacked: "ScanStats") -> "ScanStats":
        """Aggregate stacked (S,) legs: counters sum, hops_max maxes."""
        return cls(scans=jnp.sum(stacked.scans),
                   lanes=jnp.sum(stacked.lanes),
                   emitted=jnp.sum(stacked.emitted),
                   truncated=jnp.sum(stacked.truncated),
                   hops_sum=jnp.sum(stacked.hops_sum),
                   hops_max=jnp.max(stacked.hops_max))

    def asdict(self) -> dict:
        return {k: int(v) for k, v in self._asdict().items()}


class ServeStats(NamedTuple):
    """Decode-loop telemetry: a fixed-size latency reservoir (ring buffer
    over the last ``LATENCY_RESERVOIR`` decode steps — p50/p99 come from
    it host-side) plus flush/pending counters.  Host-driven like the
    ServeEngine itself, but a pytree so it can ride jitted state.

    The serve-scheduler fields (queue depth high-water, admission waits,
    combined ops, fused-view cache hits/builds) default to zero on every
    ``record`` call, so the legacy lockstep decode loop keeps recording
    through the same class unchanged."""

    steps: jax.Array        # () int32 — decode steps recorded
    flushes: jax.Array      # () int32 — background flushes triggered
    pending_hwm: jax.Array  # () int32 — max pending maintenance seen
    queue_hwm: jax.Array    # () int32 — max waiting-queue depth seen
    admitted: jax.Array     # () int32 — requests admitted into live slots
    admit_wait: jax.Array   # () int32 — total steps admitted reqs waited
    combined: jax.Array     # () int32 — ops eliminated by op-combining
    view_hits: jax.Array    # () int32 — fused-view cache hits observed
    view_builds: jax.Array  # () int32 — fused-view cache builds observed
    probe_queries: jax.Array  # () int32 — read-service probe lookups issued
    probe_hits: jax.Array     # () int32 — probes that resolved a mapping
    lat_us: jax.Array       # (LATENCY_RESERVOIR,) float32 — step latencies

    @classmethod
    def zero(cls) -> "ServeStats":
        z = jnp.int32(0)
        return cls(steps=z, flushes=z, pending_hwm=z, queue_hwm=z,
                   admitted=z, admit_wait=z, combined=z, view_hits=z,
                   view_builds=z, probe_queries=z, probe_hits=z,
                   lat_us=jnp.zeros((LATENCY_RESERVOIR,), jnp.float32))

    def record(self, seconds, *, pending: int = 0, flushed: bool = False,
               queue_depth: int = 0, admitted: int = 0, admit_wait: int = 0,
               combined: int = 0, view_hits: int = 0,
               view_builds: int = 0) -> "ServeStats":
        """Fold one decode step in (ring-buffer write at ``steps`` mod
        capacity).  Host-side floats/bools or traced values both work."""
        idx = self.steps % self.lat_us.shape[0]
        return ServeStats(
            steps=self.steps + 1,
            flushes=self.flushes + jnp.int32(flushed),
            pending_hwm=jnp.maximum(self.pending_hwm, jnp.int32(pending)),
            queue_hwm=jnp.maximum(self.queue_hwm, jnp.int32(queue_depth)),
            admitted=self.admitted + jnp.int32(admitted),
            admit_wait=self.admit_wait + jnp.int32(admit_wait),
            combined=self.combined + jnp.int32(combined),
            view_hits=self.view_hits + jnp.int32(view_hits),
            view_builds=self.view_builds + jnp.int32(view_builds),
            probe_queries=self.probe_queries,
            probe_hits=self.probe_hits,
            lat_us=self.lat_us.at[idx].set(jnp.float32(seconds) * 1e6),
        )

    def record_probe(self, queries: int, hits: int) -> "ServeStats":
        """Fold one read-service ``probe`` call in (between decode steps
        — advances no step counter and writes no latency sample)."""
        return self._replace(
            probe_queries=self.probe_queries + jnp.int32(queries),
            probe_hits=self.probe_hits + jnp.int32(hits))

    @classmethod
    def reduce(cls, stacked: "ServeStats") -> "ServeStats":
        """Aggregate stacked (N,) legs: counters sum, the high-water marks
        max, and the reservoirs concatenate (percentiles over the union)."""
        return cls(steps=jnp.sum(stacked.steps),
                   flushes=jnp.sum(stacked.flushes),
                   pending_hwm=jnp.max(stacked.pending_hwm),
                   queue_hwm=jnp.max(stacked.queue_hwm),
                   admitted=jnp.sum(stacked.admitted),
                   admit_wait=jnp.sum(stacked.admit_wait),
                   combined=jnp.sum(stacked.combined),
                   view_hits=jnp.sum(stacked.view_hits),
                   view_builds=jnp.sum(stacked.view_builds),
                   probe_queries=jnp.sum(stacked.probe_queries),
                   probe_hits=jnp.sum(stacked.probe_hits),
                   lat_us=stacked.lat_us.reshape(-1))

    def valid_latencies(self) -> np.ndarray:
        """Host-side view of the recorded step latencies (µs)."""
        n = min(int(self.steps), int(self.lat_us.shape[0]))
        return np.asarray(self.lat_us)[:n] if n else np.zeros((0,), np.float32)

    def percentiles(self, qs=(50, 99)) -> dict:
        lat = self.valid_latencies()
        if lat.size == 0:
            return {f"p{q}_us": 0.0 for q in qs}
        return {f"p{q}_us": round(float(np.percentile(lat, q)), 1)
                for q in qs}

    def asdict(self) -> dict:
        out = {"steps": int(self.steps), "flushes": int(self.flushes),
               "pending_hwm": int(self.pending_hwm),
               "queue_hwm": int(self.queue_hwm),
               "admitted": int(self.admitted),
               "admit_wait": int(self.admit_wait),
               "combined": int(self.combined),
               "view_hits": int(self.view_hits),
               "view_builds": int(self.view_builds),
               "probe_queries": int(self.probe_queries),
               "probe_hits": int(self.probe_hits)}
        out.update(self.percentiles())
        return out
