"""Serving engines over the ΔTree-paged KV cache.

``ServeEngine`` — the public name tests/benchmarks construct — is now a
thin compat shim over the continuous-batching scheduler
(`repro.serve.scheduler.ServeScheduler`): same constructor signature
(``max_batch`` maps to the scheduler's live-lane count), same
``submit/step/active`` surface, strictly more behavior (admission
control, slot recycling, combined staged updates, background
maintenance).

``LockstepServeEngine`` is the pre-scheduler loop, kept verbatim as the
parity oracle: it steps all live requests in rigid lockstep, applies
every pager mutation immediately, and drains maintenance *on* the decode
path — either on the deprecated ``flush_every`` stride or when
``PagerConfig.maint_high_water`` items sit buffered.  The static-trace
parity test holds the scheduler bit-identical to it under no-churn +
eager maintenance.

Both engines share the exact same model-side machinery
(`repro.serve.decode`): dense prefill scattered into pages, then per
step one `delta_paged_attention` pass over the pager-resolved block
tables (wait-free batched search — the paper's hot path).
"""

from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro.api import Index
from repro.models.config import ModelConfig
from repro.obs import trace as OT
from repro.obs.stats import ServeStats
from repro.serve import decode as D
from repro.serve.scheduler import SchedulerConfig, ServeScheduler
from repro.serving.pager import DeltaPager, PagerConfig, make_pager


class ServeEngine(ServeScheduler):
    """Compat shim: the legacy constructor over the new scheduler.

    ``max_batch`` becomes ``SchedulerConfig.max_live`` — the bounded
    decode-lane count the admission queue fills.  Everything else
    (admission control bounds, combining, the maintenance high-water)
    comes from the pager config / scheduler defaults."""

    def __init__(self, cfg: ModelConfig, params, pager_cfg: PagerConfig,
                 max_batch: int = 8, *, index: Index | None = None,
                 pager: DeltaPager | None = None):
        super().__init__(cfg, params, pager_cfg,
                         SchedulerConfig(max_live=max_batch),
                         index=index, pager=pager)
        self.max_batch = max_batch


@dataclasses.dataclass
class Request:
    seq_id: int
    prompt: np.ndarray
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class LockstepServeEngine:
    """The legacy loop: submit prefills immediately, every step decodes
    all live requests (capped at ``max_batch``), mutations hit the index
    one call at a time, maintenance drains inline."""

    def __init__(self, cfg: ModelConfig, params, pager_cfg: PagerConfig,
                 max_batch: int = 8, *, index: Index | None = None,
                 pager: DeltaPager | None = None):
        """``index`` may be any map-capable Index handle (deltatree, forest,
        or a future backend) — the engine never branches on the backend;
        ``pager`` injects a fully custom pager protocol."""
        assert cfg.family in ("dense", "moe", "vlm"), cfg.family
        assert not cfg.mla, "engine supports GQA caches"
        self.cfg = cfg
        self.params = params
        self.pager = pager if pager is not None else make_pager(pager_cfg, index)
        pager_cfg = self.pager.cfg
        self.ps = pager_cfg.page_size
        self.max_batch = max_batch
        L, NP = cfg.num_layers, pager_cfg.num_pages
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        dt = jnp.dtype(cfg.dtype)
        self.k_pages = jnp.zeros((L, NP, kvh, self.ps, hd), dt)
        self.v_pages = jnp.zeros((L, NP, kvh, self.ps, hd), dt)
        self.active: dict[int, Request] = {}
        self.lengths: dict[int, int] = {}
        self._next_id = 0
        self._steps = 0   # decode steps taken (drives the inline flush)
        self.obs = ServeStats.zero()   # decode-latency reservoir + flush log

    # ------------------------------------------------------------- submit ---

    def submit(self, prompt: np.ndarray, max_new: int = 16) -> int:
        sid = self._next_id
        self._next_id += 1
        req = Request(sid, np.asarray(prompt, np.int32), max_new)
        n_blocks = -(-len(req.prompt) // self.ps)
        pages = self.pager.allocate(sid, n_blocks)
        self.k_pages, self.v_pages, s, tok = D.prefill_to_pages(
            self.cfg, self.params, self.ps, self.k_pages, self.v_pages,
            req.prompt, pages)
        self.lengths[sid] = s
        req.out.append(tok)
        self.active[sid] = req
        return sid

    # --------------------------------------------------------------- step ---

    def step(self) -> dict[int, int]:
        """One decode step for all active sequences; returns {seq: token}.

        Every non-empty step records one sample into ``self.obs`` (the
        decode-latency reservoir + flush log + pending high-water) — the
        serve benchmark's p50/p99 come straight from it."""
        t0 = time.perf_counter()
        with OT.span("serve.step"):
            out, flushed = self._step()
        if out:
            self.obs = self.obs.record(time.perf_counter() - t0,
                                       pending=self.pager.pending,
                                       flushed=flushed)
        return out

    def _step(self):
        cfg = self.cfg
        sids = [s for s, r in self.active.items() if not r.done][: self.max_batch]
        if not sids:
            return {}, False
        # grow pages where the next token crosses a page boundary
        for sid in sids:
            needed = self.lengths[sid] // self.ps + 1
            have = self.pager.seq_blocks[sid]
            if needed > have:
                self.pager.allocate(sid, needed - have)

        lens = np.asarray([self.lengths[s] for s in sids], np.int32)
        maxp = int(max(lens)) // self.ps + 1
        bt = self.pager.block_tables(sids, maxp)          # ΔTree hot path
        tokens = jnp.asarray([[self.active[s].out[-1]] for s in sids], jnp.int32)

        logits, self.k_pages, self.v_pages = D.paged_decode_step(
            self.params, cfg, D.layer_params(cfg, self.params), tokens,
            self.k_pages, self.v_pages, jnp.asarray(bt), jnp.asarray(lens),
            self.ps,
        )
        for sid in sids:
            self.lengths[sid] += 1
        self._steps += 1
        # inline maintenance: with a non-eager pager policy, updates
        # (allocate/free) only append/mark and the structural work drains
        # here — on the pending high-water mark (preferred) or the
        # deprecated fixed stride.  Both fields are explicit PagerConfig
        # surface now, no duck-typed getattr probe.
        hw = self.pager.cfg.maint_high_water
        fe = self.pager.cfg.flush_every
        flushed = bool((hw and self.pager.pending >= hw)
                       or (fe and self._steps % fe == 0))
        if flushed:
            self.pager.flush()
        out = {}
        for bi, sid in enumerate(sids):
            tok = int(jnp.argmax(logits[bi, 0]))
            req = self.active[sid]
            req.out.append(tok)
            out[sid] = tok
            if len(req.out) >= req.max_new:
                req.done = True
                self.finish(sid)
        return out, flushed

    def finish(self, sid: int):
        self.pager.free_seq(sid)
        self.lengths.pop(sid, None)
