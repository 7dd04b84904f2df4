"""Index-backed KV-cache pager: the paper's structure on the serving hot path.

The (seq_id, logical_block) → physical_page mapping is any map-capable
``repro.api.Index`` (key = seq_id * max_blocks + block + 1; payload = page
id).  Every decode step resolves block tables with a wait-free batched
lookup; page allocation is a batched insert; sequence teardown is a batched
delete (+ Merge keeps a ΔTree index compact).  This is exactly the paper's
claimed workload mix — search-dominant with occasional updates — so the
serving benchmark doubles as a ΔTree macro-benchmark.

The default index is ``make_index("deltatree", cfg=cfg.tree_config)``;
``ShardedDeltaPager`` defaults to the forest backend and band-interleaves
the key encoding.  Any handle with ``Capability.map_mode`` can be injected
via the ``index=`` argument — the pager protocol never touches backend
internals.  ``PagerConfig.engine`` picks the SearchEngine the block-table
lookups run under (``"lockstep"`` = the Pallas vEB walk on the decode hot
path); ``PagerConfig.maintenance`` the index maintenance policy (with
``"deferred"`` + ``maint_high_water=N`` the serve scheduler's
MaintenanceWorker drains structural maintenance whenever N items are
buffered — ``flush_every`` is the deprecated stride-based trigger); both
thread through ``tree_config`` / ``forest_config`` into the default index.

Two mutation surfaces: the *immediate* protocol (``allocate`` /
``free_seq`` — one index update per call, the lockstep engine's path)
and the *staged* protocol (``stage_allocate`` / ``stage_free`` /
``apply_staged`` — host bookkeeping now, one combined index update per
scheduler step, with the same-key elimination pass of
``repro.serve.combine`` run over the whole staged batch).  Page
accounting (free list, per-seq block counts) is identical under both;
only the index-update batching differs.

Requires 64-bit mode (packed int64 values): callers must run with
JAX_ENABLE_X64=1 or `jax.config.update("jax_enable_x64", True)`.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np

from repro.api import Index, OpBatch, make_index
from repro.api.opbatch import OP_DELETE, OP_INSERT
from repro.core.deltatree import TreeConfig
from repro.obs import trace as TR


@dataclasses.dataclass(frozen=True)
class PagerConfig:
    num_pages: int = 4096
    page_size: int = 16
    max_seqs: int = 256
    max_blocks: int = 1024        # logical blocks per sequence
    tree_height: int = 7          # UB=127 ΔNodes (paper's best)
    engine: str = "scalar"        # SearchEngine for block-table lookups
    maintenance: str = "eager"    # index maintenance policy (repro.maintenance)
    maint_high_water: int = 0     # drain maintenance when this many items
    #                               sit buffered (MaintenanceStats.pending);
    #                               0 = no high-water trigger.  Only useful
    #                               with a non-eager policy — amortizes
    #                               Rebalance/Expand/Merge off the decode
    #                               path (serve.MaintenanceWorker / the
    #                               engines' step barrier)
    flush_every: int = 0          # DEPRECATED: flush() every N decode steps
    #                               regardless of how much work is actually
    #                               buffered; use maint_high_water.  Still
    #                               honored (with a DeprecationWarning) so
    #                               existing configs keep their behavior

    def __post_init__(self):
        if self.flush_every:
            warnings.warn(
                "PagerConfig.flush_every is deprecated: the fixed stride "
                "flushes on the decode path no matter how little work is "
                "buffered; set maint_high_water=N to drain when N items "
                "are pending instead",
                DeprecationWarning,
                stacklevel=3,
            )

    @property
    def payload_bits(self) -> int:
        return max(int(np.ceil(np.log2(self.num_pages))), 1)

    @property
    def tree_config(self) -> TreeConfig:
        # arena: every page mapped -> ~num_pages keys; half-dense ΔNodes
        need = max(64, int(4 * self.num_pages / (2 ** (self.tree_height - 1))))
        return TreeConfig(
            height=self.tree_height,
            max_dnodes=need,
            buf_cap=64,
            payload_bits=self.payload_bits,
            engine=self.engine,
            maintenance=self.maintenance,
        )

    def make_index(self) -> Index:
        """Default index for this config (single-arena ΔTree, map mode)."""
        return make_index("deltatree", cfg=self.tree_config)


class DeltaPager:
    """Host-driven pager over any map-capable Index handle.

    The key encoding (`_key`) is the only other extension point —
    `ShardedDeltaPager` overrides it (and the default index) to fan the
    block-table index out over a DeltaForest without touching the pager
    protocol.
    """

    def __init__(self, cfg: PagerConfig, index: Index | None = None):
        self.cfg = cfg
        self.index = index if index is not None else cfg.make_index()
        assert self.index.capability.map_mode, (
            f"pager needs a map-mode index, got {self.index!r} with "
            f"{self.index.capability}")
        self.free_pages = list(range(cfg.num_pages - 1, -1, -1))
        self.seq_blocks: dict[int, int] = {}   # seq -> allocated blocks
        self.pending = 0   # buffered items awaiting maintenance (I5' carry)
        self._staged: list[tuple[int, int, int]] = []  # (kind, key, payload)
        self._staged_pages: dict[int, list[int]] = {}  # seq -> pages (staged)
        self.stats = {"searches": 0, "inserts": 0, "deletes": 0, "hops": 0,
                      "flushes": 0, "maint_rebuilds": 0, "maint_expands": 0,
                      "maint_merges": 0, "combined": 0, "inline_maint": 0}
        # most recent ReadStats from a stats-collecting index (None when
        # the index doesn't collect) — the metrics-export snapshot source
        self.last_read_stats = None

    # ---- key encoding (overridden by ShardedDeltaPager) ----
    def _key(self, seq_id, block) -> np.ndarray:
        return (np.asarray(seq_id, np.int64) * self.cfg.max_blocks
                + np.asarray(block, np.int64) + 1).astype(np.int32)

    # ---- index protocol ----
    def _lookup(self, keys: np.ndarray):
        """(found, payload, hops) for a key batch (wait-free lookup).
        Tolerates a stats-collecting index (the trailing ReadStats is
        kept as ``last_read_stats`` for metrics export, not returned)."""
        out = self.index.lookup(jnp.asarray(keys))
        if len(out) > 3:
            self.last_read_stats = out[3]
        return out[0], out[1], out[2]

    def _update(self, kinds: np.ndarray, keys: np.ndarray,
                payloads: np.ndarray):
        """Apply a batched insert/delete step; returns per-op results.
        ``stats["inline_maint"]`` accumulates the structural maintenance
        (Rebalance + Expand + Merge) these update batches paid *on* the
        decode path — the number a background-worker policy drives to
        zero (the drained work shows up in ``maint_*`` instead)."""
        self.index, res, mstats = self.index.update(
            OpBatch.mixed(kinds, keys, payloads))
        if mstats is not None:
            self.pending = int(mstats.pending)
            self.stats["inline_maint"] += (
                int(mstats.rebuilds) + int(mstats.expands)
                + int(mstats.merges))
        assert not self.index.alloc_failed(), "pager index arena exhausted"
        return res

    # ---- mutations ----
    def allocate(self, seq_id: int, n_blocks: int) -> list[int]:
        """Allocate pages for logical blocks [cur, cur + n_blocks)."""
        start = self.seq_blocks.get(seq_id, 0)
        assert len(self.free_pages) >= n_blocks, "pager OOM"
        pages = [self.free_pages.pop() for _ in range(n_blocks)]
        keys = self._key(seq_id, np.arange(start, start + n_blocks))
        kinds = np.full(len(pages), OP_INSERT, np.int32)
        res = self._update(kinds, keys, np.asarray(pages, np.int32))
        assert bool(np.asarray(res).all()), "duplicate block allocation"
        self.seq_blocks[seq_id] = start + n_blocks
        self.stats["inserts"] += n_blocks
        return pages

    def free_seq(self, seq_id: int) -> None:
        n = self.seq_blocks.pop(seq_id, 0)
        if n == 0:
            return
        keys = self._key(seq_id, np.arange(n))
        found, pages, _ = self._lookup(keys)
        assert bool(np.asarray(found).all())
        kinds = np.full(n, OP_DELETE, np.int32)
        res = self._update(kinds, keys, np.zeros(n, np.int32))
        assert bool(np.asarray(res).all())
        self.free_pages.extend(int(p) for p in np.asarray(pages))
        self.stats["deletes"] += n

    # ---- staged mutations (the serve scheduler's protocol) ----

    def stage_allocate(self, seq_id: int, n_blocks: int) -> list[int]:
        """``allocate`` split in two: page accounting now (free-list pop,
        block-count increment — the scheduler needs the page ids to scatter
        prefill K/V), index inserts staged for the step's one combined
        ``apply_staged`` batch."""
        start = self.seq_blocks.get(seq_id, 0)
        assert len(self.free_pages) >= n_blocks, "pager OOM"
        pages = [self.free_pages.pop() for _ in range(n_blocks)]
        keys = self._key(seq_id, np.arange(start, start + n_blocks))
        self._staged.extend(
            (OP_INSERT, int(k), int(p)) for k, p in zip(keys, pages))
        self._staged_pages.setdefault(seq_id, []).extend(pages)
        self.seq_blocks[seq_id] = start + n_blocks
        self.stats["inserts"] += n_blocks
        return pages

    def stage_free(self, seq_id: int) -> None:
        """``free_seq`` for staged sequences: pages return to the free
        list now (host accounting — a same-step admission may recycle
        them under different keys), index deletes ride the next
        ``apply_staged`` batch.  No lookup needed: the staged protocol
        tracks each sequence's pages host-side, so freeing works even
        while the sequence's own inserts are still staged (in which case
        the combine pass annihilates the pair)."""
        n = self.seq_blocks.pop(seq_id, 0)
        if n == 0:
            return
        pages = self._staged_pages.pop(seq_id)
        assert len(pages) == n, (seq_id, len(pages), n)
        keys = self._key(seq_id, np.arange(n))
        self._staged.extend((OP_DELETE, int(k), 0) for k in keys)
        self.free_pages.extend(pages)
        self.stats["deletes"] += n

    def apply_staged(self) -> dict:
        """Apply all staged ops as ONE combined index update: the
        same-key elimination pass (`repro.serve.combine.combine_ops`)
        runs over the whole batch first, then a single ``_update`` —
        batch order preserved, so this is a valid linearization of the
        staged sequence.  Returns {"applied", "combined", "inline_maint"}
        for the step's obs row."""
        from repro.serve.combine import combine_ops

        if not self._staged:
            return {"applied": 0, "combined": 0, "inline_maint": 0}
        kinds, keys, pays = (np.asarray(c) for c in zip(*self._staged))
        self._staged.clear()
        kinds, keys, pays, combined = combine_ops(kinds, keys, pays)
        self.stats["combined"] += combined
        inline0 = self.stats["inline_maint"]
        if len(kinds):
            res = self._update(kinds.astype(np.int32), keys.astype(np.int32),
                               pays.astype(np.int32))
            assert bool(np.asarray(res).all()), \
                "staged batch violated the pager discipline"
        return {"applied": int(len(kinds)), "combined": combined,
                "inline_maint": self.stats["inline_maint"] - inline0}

    def flush(self):
        """Drain the index's pending maintenance (no-op under "eager").

        The serve scheduler's MaintenanceWorker calls this when
        ``pending`` crosses ``cfg.maint_high_water`` (the legacy engine:
        every ``cfg.flush_every`` decode steps) — the background hook
        that amortizes structural maintenance across serving steps
        instead of paying it inside allocate/free.
        Returns the MaintenanceStats (or None)."""
        self.index, mstats = self.index.flush()
        if mstats is not None:
            self.pending = int(mstats.pending)
            self.stats["flushes"] += 1
            self.stats["maint_rebuilds"] += int(mstats.rebuilds)
            self.stats["maint_expands"] += int(mstats.expands)
            self.stats["maint_merges"] += int(mstats.merges)
        return mstats

    # ---- the decode-step hot path ----
    def block_tables(self, seq_ids, max_blocks: int) -> np.ndarray:
        """(B, max_blocks) physical page table via wait-free Index lookup."""
        seq_ids = np.asarray(seq_ids)
        b = len(seq_ids)
        keys = self._key(
            np.repeat(seq_ids, max_blocks),
            np.tile(np.arange(max_blocks), b),
        )
        with TR.span("pager.block_tables"):
            found, pages, hops = self._lookup(keys)
        self.stats["searches"] += len(keys)
        self.stats["hops"] += int(np.asarray(hops).sum())
        table = np.where(np.asarray(found), np.asarray(pages), -1)
        return table.reshape(b, max_blocks).astype(np.int32)


def make_pager(cfg: PagerConfig, index: Index | None = None) -> DeltaPager:
    """Pager for a config: ShardedPagerConfig gets the band-interleaved
    ShardedDeltaPager, anything else the plain DeltaPager.  ``index``
    overrides the config's default backend (any map-capable handle)."""
    from repro.serving.sharded_pager import ShardedDeltaPager, ShardedPagerConfig

    if isinstance(cfg, ShardedPagerConfig):
        return ShardedDeltaPager(cfg, index)
    return DeltaPager(cfg, index)
