"""Continuous-batching serve scheduler (DESIGN.md §10).

Replaces the lockstep ``ServeEngine`` loop: instead of stepping a fixed
set of sequences and flushing maintenance on a stride, each step
composes its batch from the live decode lanes plus whatever the
admission queue can fill into free slots, runs every staged index op as
one combined update, and leaves structural index maintenance to the
``MaintenanceWorker`` at the step barrier.

One ``step()``:

  1. reap departures — cancelled live lanes release their slot and stage
     page frees (plus frees staged by last step's finishers are still
     pending here);
  2. admit — free slots fill FIFO from the waiting queue; each admission
     prefills (dense prefill, K/V scattered into staged-allocated pages)
     and joins this step's decode batch;
  3. grow — live lanes crossing a page boundary stage tail allocations;
  4. apply — all staged ops (admission inserts + growth inserts + the
     departures' deletes) run the same-key elimination pass and hit the
     index as ONE update batch (`DeltaPager.apply_staged`);
  5. decode — one `paged_decode_step` over the live lanes (block tables
     via wait-free lookup — with a forest index the hoisted fused view
     makes consecutive steps reuse one `fuse_arenas` build);
  6. finish — lanes reaching ``max_new`` release their slot and stage
     frees, then a second admission pass re-fills the freed lanes the
     same step (slot recycling; these prefill now, decode next step);
  7. barrier — ``MaintenanceWorker.maybe_drain`` runs off the decode
     path, triggered by the pending high-water mark.  No read is in
     flight at the barrier, so draining to fixpoint preserves the I5′
     read-correctness argument.

Under "no churn + eager maintenance" the pipeline degenerates to the
lockstep loop's behavior exactly (the static-trace parity test holds the
two bit-identical); churn and deferred maintenance are where the
scheduler earns its keep.
"""

from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro.api import Index
from repro.distributed import forest as DF
from repro.models.config import ModelConfig
from repro.obs import ring as OR
from repro.obs import trace as OT
from repro.obs.stats import ScanStats, ServeStats
from repro.serve import decode as D
from repro.serve.combine import dedupe_lookups
from repro.serve.queue import RequestQueue, ServeRequest
from repro.serve.worker import MaintenanceWorker
from repro.serving.pager import DeltaPager, PagerConfig, make_pager

__all__ = ["SchedulerConfig", "ServeScheduler"]


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Static scheduler knobs (the model/pager configs ride separately).

    max_live:    decode-lane count — the bounded live-batch size.
    max_waiting: admission-control bound on the waiting FIFO (0 = none;
                 rejected submissions count in ``queue.rejected``).
    maint_high_water: overrides the pager config's field when not None.
    combine:     run the same-key elimination pass over staged batches.
    """

    max_live: int = 8
    max_waiting: int = 0
    maint_high_water: int | None = None
    combine: bool = True


class ServeScheduler:
    """Continuous-batching scheduler over the paged-KV DeltaPager.

    Compat surface (what the legacy lockstep engine exposed and the
    tests/benchmarks consume): ``submit() -> sid``, ``step() -> {sid:
    tok}``, ``active[sid].out``, ``pager``, ``obs``.  New surface:
    ``cancel``, ``probe``, ``queue``, ``worker``, ``run_trace``.
    """

    def __init__(self, cfg: ModelConfig, params, pager_cfg: PagerConfig,
                 sched: SchedulerConfig | None = None, *,
                 index: Index | None = None, pager: DeltaPager | None = None):
        assert cfg.family in ("dense", "moe", "vlm"), cfg.family
        assert not cfg.mla, "scheduler supports GQA caches"
        self.cfg = cfg
        self.params = params
        self.sched = sched if sched is not None else SchedulerConfig()
        self.pager = pager if pager is not None else make_pager(pager_cfg,
                                                                index)
        pager_cfg = self.pager.cfg
        self.ps = pager_cfg.page_size
        self.queue = RequestQueue(self.sched.max_live,
                                  self.sched.max_waiting)
        self.worker = MaintenanceWorker(
            self.pager, high_water=self.sched.maint_high_water)
        if not self.sched.combine:
            self.pager.apply_staged = self._apply_uncombined  # type: ignore
        L, NP = cfg.num_layers, pager_cfg.num_pages
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        dt = jnp.dtype(cfg.dtype)
        self.k_pages = jnp.zeros((L, NP, kvh, self.ps, hd), dt)
        self.v_pages = jnp.zeros((L, NP, kvh, self.ps, hd), dt)
        self.active: dict[int, ServeRequest] = {}   # every request ever
        self.lengths: dict[int, int] = {}
        self._next_id = 0
        self._steps = 0
        self._probe_combined = 0
        self._combined_mark = 0   # combined ops already folded into obs
        self.obs = ServeStats.zero()
        self.scan_obs = ScanStats.zero()
        self.last_step_info: dict = {}

    def _apply_uncombined(self):
        """combine=False: same staged protocol, elimination pass skipped
        (ablation / conformance baseline)."""
        pg = self.pager
        if not pg._staged:
            return {"applied": 0, "combined": 0, "inline_maint": 0}
        kinds, keys, pays = (np.asarray(c) for c in zip(*pg._staged))
        pg._staged.clear()
        inline0 = pg.stats["inline_maint"]
        res = pg._update(kinds.astype(np.int32), keys.astype(np.int32),
                         pays.astype(np.int32))
        assert bool(np.asarray(res).all())
        return {"applied": int(len(kinds)), "combined": 0,
                "inline_maint": pg.stats["inline_maint"] - inline0}

    # ------------------------------------------------------------- arrival ---

    def submit(self, prompt: np.ndarray, max_new: int = 16) -> int:
        """Enqueue a request (admission happens inside ``step``).
        Returns its seq id; a rejected submission (bounded waiting FIFO)
        still gets an id, with ``active[sid].cancelled`` set."""
        sid = self._next_id
        self._next_id += 1
        req = ServeRequest(sid, np.asarray(prompt, np.int32), max_new,
                           submit_step=self._steps)
        self.active[sid] = req
        self.queue.submit(req)
        return sid

    def cancel(self, sid: int) -> str:
        """Departure mid-flight; live lanes are reaped at the next step."""
        return self.queue.cancel(sid)

    # ---------------------------------------------------------------- step ---

    def step(self) -> dict[int, int]:
        """One scheduler step; returns {sid: token} for decoded lanes.

        Records one ``ServeStats`` sample whenever any work happened —
        latency, queue depth, admission waits, combined ops, fused-view
        cache hits, pending high-water, worker drains."""
        t0 = time.perf_counter()
        v0 = DF.fused_view_cache_stats()
        with OT.span("serve.sched_step"):
            out, info = self._step()
        v1 = DF.fused_view_cache_stats()
        # combining is cumulative across the staged batches AND the probe
        # service (which runs between steps): report everything since the
        # last recorded step, not just what this step's apply eliminated
        total_combined = self.pager.stats["combined"] + self._probe_combined
        info.update(
            queue_depth=self.queue.depth,
            combined=total_combined - self._combined_mark,
            view_hits=v1["hits"] - v0["hits"],
            view_builds=v1["builds"] - v0["builds"],
        )
        self._combined_mark = total_combined
        self.last_step_info = info
        if out or info["admitted"] or info["applied"]:
            self.obs = self.obs.record(
                time.perf_counter() - t0,
                pending=self.pager.pending,
                flushed=info["drained"],
                queue_depth=info["queue_depth"],
                admitted=info["admitted"],
                admit_wait=info["admit_wait"],
                combined=info["combined"],
                view_hits=info["view_hits"],
                view_builds=info["view_builds"],
            )
        return out

    def _admit(self) -> list[tuple[int, ServeRequest]]:
        """One admission pass: fill free slots, stage page allocations,
        prefill (dense prefill + K/V scatter into the staged pages)."""
        admitted = self.queue.admit(self._steps)
        for _, req in admitted:
            n_blocks = -(-len(req.prompt) // self.ps)
            pages = self.pager.stage_allocate(req.seq_id, n_blocks)
            with OT.span("serve.prefill"):
                self.k_pages, self.v_pages, s, tok = D.prefill_to_pages(
                    self.cfg, self.params, self.ps, self.k_pages,
                    self.v_pages, req.prompt, pages)
            self.lengths[req.seq_id] = s
            req.out.append(tok)
        return admitted

    def _retire(self, slot: int, req: ServeRequest) -> None:
        """Departure: release the lane, stage the sequence's page frees
        (deletes ride the next combined batch; pages recycle now)."""
        self.queue.release(slot)
        self.pager.stage_free(req.seq_id)
        self.lengths.pop(req.seq_id, None)

    def _step(self):
        # 1. reap departures marked since the last barrier
        for slot, req in self.queue.live():
            if req.cancelled:
                self._retire(slot, req)
        # 2. admission: freed/initial slots join this step's decode
        admitted = self._admit()
        # 3. growth: lanes whose next token crosses a page boundary
        for _, req in self.queue.live():
            sid = req.seq_id
            needed = self.lengths[sid] // self.ps + 1
            have = self.pager.seq_blocks[sid]
            if needed > have:
                self.pager.stage_allocate(sid, needed - have)
        # 4. one combined index update for everything staged
        applied = self.pager.apply_staged()
        # 5. decode all live lanes (slot order)
        out: dict[int, int] = {}
        lanes = self.queue.live()
        if lanes:
            sids = [r.seq_id for _, r in lanes]
            lens = np.asarray([self.lengths[s] for s in sids], np.int32)
            maxp = int(max(lens)) // self.ps + 1
            bt = self.pager.block_tables(sids, maxp)   # ΔTree hot path
            tokens = jnp.asarray([[self.active[s].out[-1]] for s in sids],
                                 jnp.int32)
            with OT.span("serve.decode"):
                logits, self.k_pages, self.v_pages = D.paged_decode_step(
                    self.params, self.cfg, D.layer_params(self.cfg,
                                                          self.params),
                    tokens, self.k_pages, self.v_pages, jnp.asarray(bt),
                    jnp.asarray(lens), self.ps)
            for bi, (slot, req) in enumerate(lanes):
                tok = int(jnp.argmax(logits[bi, 0]))
                req.out.append(tok)
                out[req.seq_id] = tok
                self.lengths[req.seq_id] += 1
                # 6a. finish check after the decode append (legacy rule:
                # the prefill token alone never finishes a request)
                if len(req.out) >= req.max_new:
                    req.done = True
                    self._retire(slot, req)
        self._steps += 1
        # 6b. slot recycling: re-fill lanes freed by this step's
        # finishers now (prefill this step, decode joins the next)
        admitted += self._admit()
        # 7. step barrier: background maintenance off the decode path
        drained = self.worker.maybe_drain(self._steps)
        info = dict(
            admitted=len(admitted),
            admit_wait=sum(r.wait_steps for _, r in admitted),
            applied=applied["applied"],
            inline_maint=applied["inline_maint"],
            drained=drained,
        )
        return out, info

    # ------------------------------------------------------- read service ---

    def probe(self, seq_ids) -> np.ndarray:
        """Read-side service traffic: resolve the head-block page of each
        referenced sequence (−1 when unmapped) through one wait-free
        lookup.  Duplicate references — the common case under zipfian
        traffic — collapse to one shard op each (`dedupe_lookups`)."""
        keys = self.pager._key(np.asarray(seq_ids, np.int64),
                               np.zeros(len(seq_ids), np.int64))
        uniq, inverse, combined = dedupe_lookups(keys)
        self._probe_combined += combined
        with OT.span("serve.probe"):
            found, pages, hops = self.pager._lookup(uniq)
        self.pager.stats["searches"] += len(uniq)
        self.pager.stats["hops"] += int(np.asarray(hops).sum())
        out = np.where(np.asarray(found), np.asarray(pages), -1)[inverse]
        # probe reads previously bypassed ServeStats entirely; count the
        # caller-visible traffic (pre-dedupe refs, resolved mappings)
        self.obs = self.obs.record_probe(len(seq_ids),
                                         int((out >= 0).sum()))
        return out

    def scan(self, seq_ids, max_items: int | None = None):
        """Ordered read service: each referenced sequence's full
        block -> page mapping in block order, resolved through ONE
        engine scan dispatch (one leaf-run scan lane per sequence over the
        pager index's contiguous per-sequence key band) — the bulk
        companion to ``probe``'s point lookups.  Like ``probe`` it runs
        between steps against the current wait-free snapshot; staged
        (unapplied) allocations are invisible until the step barrier's
        combined update lands.

        Returns ``{seq_id: np.ndarray of page ids in block order}``
        (empty array for unmapped sequences).  Folds the call's
        ``repro.obs`` ring row into ``self.scan_obs`` (exported by
        ``metrics()``)."""
        pg = self.pager
        ix = pg.index
        if max_items is None:
            max_items = pg.cfg.max_blocks
        sids = np.asarray(seq_ids, np.int64)
        # per-sequence key band: blocks of sid pack contiguously, so the
        # band (key(sid, -1), key(sid, max_blocks - 1)] is exactly its
        # block table (start bound is exclusive in the scan contract)
        starts = jnp.asarray(pg._key(sids, np.full(sids.shape, -1)),
                             jnp.int32)
        his = jnp.asarray(pg._key(sids, np.full(sids.shape,
                                                pg.cfg.max_blocks - 1)),
                          jnp.int32)
        with OT.span("serve.scan"):
            _, pages, n, _, _ = ix.scan(starts, his, max_items)
        pages, n = np.asarray(pages), np.asarray(n)
        row = OR.calls([OR.last_seq()])[0]
        pg.stats["searches"] += len(sids)
        pg.stats["hops"] += int(row["hops_sum"])
        self.scan_obs = self.scan_obs.merge(ScanStats(
            scans=jnp.int32(1),
            **{f: jnp.int32(row[f]) for f in ScanStats._fields[1:]}))
        return {int(s): pages[i, : n[i]] for i, s in enumerate(sids)}

    # ---------------------------------------------------------- metrics ---

    def metrics(self, fmt: str = "dict"):
        """Point-in-time metrics snapshot across every stats source the
        scheduler touches: the decode loop's ``ServeStats``, the
        maintenance worker's drain counters, the pager's host-side op
        counters, the read path's last ``ReadStats`` legs (search /
        router / measured transfers — present when the underlying index
        was built with ``collect_stats``), and the process's running
        totals of every ``Index`` call and span (groups ``index`` and
        ``spans``).  ``fmt``: "dict" (nested plain dict), "prometheus"
        (text exposition), or "json"."""
        from repro.obs import export as OX

        rs = self.pager.last_read_stats
        snap = OX.snapshot(
            **OX.program_totals(),
            serve=self.obs,
            scan=self.scan_obs,
            maintenance=self.worker.stats(),
            pager=self.pager.stats,
            search=rs.search if rs is not None else None,
            router=rs.router if rs is not None else None,
            transfers=rs.transfers if rs is not None else None,
        )
        if fmt == "prometheus":
            return OX.to_prometheus(snap)
        if fmt == "json":
            return OX.to_json(snap)
        assert fmt == "dict", f"unknown metrics fmt {fmt!r}"
        return snap

    # ------------------------------------------------------------ trace ---

    def run_trace(self, plans, *, drain: bool = True) -> dict:
        """Replay a ``synth_trace`` plan: per step submit the arrivals,
        issue the cancels and zipf probe traffic, then ``step()``.
        Submission-order indices in the plan map 1:1 onto seq ids (ids
        are handed out sequentially).  Returns a summary dict."""
        tokens = 0
        for plan in plans:
            for prompt, max_new in plan.arrivals:
                self.submit(prompt, max_new=max_new)
            for ref in plan.cancels:
                self.cancel(ref)
            if len(plan.probe_refs):
                self.probe(plan.probe_refs)
            tokens += len(self.step())
        if drain:
            self.drain()
        finished = sum(r.done for r in self.active.values())
        return {
            "submitted": self._next_id,
            "finished": finished,
            "rejected": self.queue.rejected,
            "decode_tokens": tokens,
            "steps": self._steps,
        }

    # ------------------------------------------------------------ drain ---

    def drain(self, max_steps: int = 10_000) -> None:
        """Step until every submitted request departed, then apply any
        staged frees and force a final maintenance drain."""
        for _ in range(max_steps):
            if not self.queue.live() and not self.queue.waiting:
                break
            self.step()
        self.pager.apply_staged()
        self.worker.maybe_drain(self._steps, force=True)
