"""The Index handle: one method-style surface over every backend.

An ``Index`` is (static spec, dynamic state):

- ``spec`` — the registered ``BackendSpec`` (a table of pure functions)
  plus the backend's hashable static config.  Static: it is the pytree
  aux_data, so jitted functions closing over an ``Index`` specialize on
  backend + config exactly like they specialize on ``TreeConfig`` today.
- ``state`` — the backend's array state (a ``DeltaTree``, ``Forest``,
  ``SortedArrayState``, ...).  Dynamic: it is the pytree child, so an
  ``Index`` flows through ``jit`` / ``donate_argnums`` / ``shard_map``.

Methods delegate through the spec; ``capability`` says which ones a
backend supports (``CapabilityError`` otherwise).  ``insert_delete``
returns a *new* handle — backends may donate the old state's buffers, so
callers must rebind: ``ix, res = ix.insert_delete(batch)``.

Every concrete read, scan and update call opens one ``index.<method>``
span (``repro.obs.trace``) that carries the call's sequence number
``seq`` and its ``lanes`` (scans also ``k``), and records the call's
counters in the device ring of ``repro.obs.ring`` right after its own
program is dispatched.  Calls made while JAX traces record nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.opbatch import OpBatch
from repro.obs import ring as OR
from repro.obs import trace as OT


@dataclasses.dataclass(frozen=True)
class Capability:
    """What an Index backend supports (conformance tests skip on these)."""

    map_mode: bool = False    # key -> payload lookups (else set semantics)
    successor: bool = False   # ordered successor queries
    sharded: bool = False     # state fans out over a device mesh
    updates: bool = True      # insert_delete supported at all
    deferred_maintenance: bool = False  # non-eager policies + flush()
    fused_forest: bool = False  # sharded reads share one fused frontier
    #                             (engine provides forest_batch + enabled)
    range_scan: bool = False  # ordered range pages (range_scan + cursors)
    successor_k: bool = False  # bulk k-successor reads (successor_k)


class CapabilityError(NotImplementedError):
    """Raised when an Index method is not in the backend's Capability."""


def cfg_attr(cfg, name: str, default=None):
    """Probe a config knob on ``cfg`` or its nested ``cfg.tree`` (the
    forest/pager configs wrap a TreeConfig) — the one resolution rule for
    ``engine`` / ``maintenance`` / ``q_tile`` style knobs."""
    v = getattr(cfg, name, None)
    if v is None:
        v = getattr(getattr(cfg, "tree", None), name, None)
    return default if v is None else v


def _lanes(keys) -> int:
    return int(np.shape(keys)[0])


def _free_top(state):
    """The arena's free-list depth (a forest's, over all its shards);
    None for backends without an arena."""
    ft = getattr(state, "free_top", None)
    if ft is None:
        ft = getattr(getattr(state, "trees", None), "free_top", None)
    return ft


def _scan_counts(out):
    return {"n": out[2], "hops": out[3], "more": out[4]}


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Registry entry: a table of pure functions over (cfg, state).

    Required hooks: ``make``, ``capability``, ``search``, ``update``,
    ``live_items``, ``size``.  Optional hooks may be None and are gated by
    ``capability(cfg)``: ``lookup`` (map_mode), ``successor``.  ``touch``
    (ideal-cache touch traces, Table 1) and ``alloc_failed`` (sticky
    arena-exhaustion flag) are optional diagnostics.

    ``engines`` lists the SearchEngine names the backend's read path can
    run under.  The special entry ``"*"`` means the backend dispatches
    reads through the ``repro.core.engine`` registry and accepts every
    engine registered there *at selection time* (so engines registered
    after import are selectable) — the ΔTree-core backends declare this.
    Single-read-path backends keep the default ``("scalar",)`` and
    ``make_index(..., engine=)`` rejects anything else; a backend with
    its own private engines declares them literally.  Resolve with
    ``repro.api.supported_engines``.

    ``maintenance`` analogously lists the scheduler policy *kinds*
    (``repro.maintenance.KINDS``) the backend accepts via
    ``make_index(maintenance=)``; ``("*",)`` = every kind the scheduler
    knows.  ``update`` returns a third element — a ``MaintenanceStats``
    pytree, or None for backends without a maintenance scheduler — and
    ``flush`` (optional) drains deferred maintenance to fixpoint.
    """

    name: str
    make: Callable[..., tuple[Any, Any]]        # (initial, payloads, **kw)
    capability: Callable[[Any], Capability]     # cfg -> Capability
    search: Callable[..., Any]                  # (cfg, state, keys) -> (found, hops)
    update: Callable[..., Any]                  # (cfg, state, OpBatch) -> (state, results, stats|None)
    live_items: Callable[..., Any]              # (cfg, state) -> [(key, payload)]
    size: Callable[..., int]                    # (cfg, state) -> int
    lookup: Callable[..., Any] | None = None    # (cfg, state, keys) -> (found, payload, hops)
    successor: Callable[..., Any] | None = None  # (cfg, state, keys) -> (found, succ)
    scan: Callable[..., Any] | None = None      # (cfg, state, starts, his, max_items)
    #                                             -> (keys, payloads, n, hops, more);
    #                                             starts EXCLUSIVE / his INCLUSIVE,
    #                                             (K, max_items) rows ascending,
    #                                             zero-padded past n; hops 0 for
    #                                             backends with no tree walk
    successor_k: Callable[..., Any] | None = None  # (cfg, state, keys, k) -> same contract
    touch: Callable[..., Any] | None = None     # (cfg, state) -> (key -> [flat indices])
    alloc_failed: Callable[..., bool] | None = None  # (cfg, state) -> bool
    flush: Callable[..., Any] | None = None     # (cfg, state) -> (state, stats)
    engines: tuple[str, ...] = ("scalar",)      # selectable read engines
    maintenance: tuple[str, ...] = ("eager",)   # selectable policy kinds


@dataclasses.dataclass(frozen=True)
class IndexSpec:
    """Hashable static half of an Index (pytree aux_data)."""

    backend: BackendSpec
    cfg: Any


class Index:
    """Handle over one backend instance. Pytree: state child, spec static."""

    __slots__ = ("spec", "state")

    def __init__(self, spec: IndexSpec, state: Any):
        object.__setattr__(self, "spec", spec)
        object.__setattr__(self, "state", state)

    def __setattr__(self, name, value):
        raise AttributeError(
            "Index is immutable; rebind the handle returned by insert_delete")

    def __repr__(self):
        return (f"Index(backend={self.spec.backend.name!r}, "
                f"cfg={self.spec.cfg!r})")

    # ---- static introspection ----

    @property
    def backend(self) -> str:
        return self.spec.backend.name

    @property
    def cfg(self) -> Any:
        return self.spec.cfg

    @property
    def capability(self) -> Capability:
        return self.spec.backend.capability(self.spec.cfg)

    @property
    def engine(self) -> str:
        """Active SearchEngine name ("scalar" for single-engine backends)."""
        return cfg_attr(self.spec.cfg, "engine") or "scalar"

    @property
    def maintenance(self) -> str:
        """Active maintenance policy string ("eager" when the backend has
        no maintenance scheduler)."""
        return cfg_attr(self.spec.cfg, "maintenance") or "eager"

    @property
    def collect_stats(self) -> bool:
        """True when this handle's hop-bearing reads return a trailing
        ``repro.obs.stats.ReadStats`` (``TreeConfig.collect_stats``;
        always False for backends without the knob)."""
        return bool(cfg_attr(self.spec.cfg, "collect_stats", False))

    def _require(self, flag: str, hook) -> None:
        if not getattr(self.capability, flag) or hook is None:
            raise CapabilityError(
                f"backend {self.backend!r} does not support {flag!r} "
                f"(capability: {self.capability})")

    def _observed(self, kind: str, lanes: int, run, counts, **args):
        """``run()`` under its ``index.<kind>`` span, then the call's
        counters (``counts(out)``) into the ring; plain ``run()`` while
        JAX traces."""
        if not OT.top_level():
            return run()
        seq = OR.next_seq()
        with OT.span(f"index.{kind}", seq=seq, lanes=lanes, **args):
            out = run()
            OR.record(kind, seq, lanes, **counts(out))
        return out

    # ---- wait-free reads ----

    def search(self, keys: jax.Array):
        """Membership on the current snapshot. Returns (found[K], hops[K])
        — plus a trailing ``ReadStats`` when ``self.collect_stats``."""
        return self._observed(
            "search", _lanes(keys),
            lambda: self.spec.backend.search(self.spec.cfg, self.state, keys),
            lambda out: {"hops": out[1]})

    def lookup(self, keys: jax.Array):
        """Map-mode read. Returns (found[K], payload[K], hops[K]) — plus
        a trailing ``ReadStats`` when ``self.collect_stats``."""
        self._require("map_mode", self.spec.backend.lookup)
        return self._observed(
            "lookup", _lanes(keys),
            lambda: self.spec.backend.lookup(self.spec.cfg, self.state, keys),
            lambda out: {"hops": out[2]})

    def successor(self, keys: jax.Array):
        """Smallest stored key strictly greater. Returns (found[K], succ[K])."""
        self._require("successor", self.spec.backend.successor)
        return self._observed(
            "successor", _lanes(keys),
            lambda: self.spec.backend.successor(
                self.spec.cfg, self.state, keys),
            lambda out: {})

    def range_scan(self, lo: int, hi: int, *, max_items: int = 128,
                   cursor: "ScanCursor | None" = None) -> "ScanResult":
        """One ordered page of the live set: up to ``max_items`` (key,
        payload) rows with ``lo <= key <= hi``, ascending.  Host-facing
        (returns numpy views).  When the page fills before the range is
        exhausted, ``result.more`` is True and ``result.cursor`` resumes
        the next page: ``ix.range_scan(lo, hi, cursor=result.cursor)``
        (the cursor's bounds override ``lo``/``hi``).  Each page reads
        the *current* snapshot — concurrent updates between pages are
        seen from their page boundary onward, like any wait-free read."""
        from repro.core import layout
        from repro.core.scan import ScanCursor, ScanResult

        if cursor is not None:
            lo, hi = cursor.last_key + 1, cursor.hi
        hi = min(int(hi), layout.KEY_MAX)
        start = jnp.asarray([max(int(lo) - 1, 0)], jnp.int32)
        his = jnp.asarray([hi], jnp.int32)
        ks, ps, n, _, more = self.scan(start, his, max_items)
        count = int(n[0])
        truncated = bool(more[0]) and count > 0
        keys = np.asarray(ks[0])[:count]
        pays = np.asarray(ps[0])[:count]
        cur = (ScanCursor(last_key=int(keys[-1]), hi=hi)
               if truncated else None)
        return ScanResult(keys=keys, payloads=pays, more=truncated,
                          cursor=cur)

    def scan(self, starts: jax.Array, his: jax.Array, max_items: int):
        """Bulk ordered range read: per lane, up to ``max_items`` live
        (key, payload) rows with ``start < key <= hi`` (start EXCLUSIVE,
        hi INCLUSIVE), ascending.  Returns the ``successor_k`` 5-tuple."""
        self._require("range_scan", self.spec.backend.scan)
        return self._observed(
            "scan", _lanes(starts),
            lambda: self.spec.backend.scan(
                self.spec.cfg, self.state, starts, his, max_items),
            _scan_counts, k=max_items)

    def successor_k(self, keys: jax.Array, k: int):
        """Bulk ordered read: per query, the ``k`` smallest live keys
        strictly greater.  Returns (keys (K, k) int32 ascending rows,
        payloads (K, k) int32, n (K,) int32, hops (K,) int32, more (K,)
        bool) — rows are zero-padded past ``n``; ``more`` marks queries
        with further successors beyond the ``k`` returned; ``hops`` is 0
        for backends with no tree walk."""
        self._require("successor_k", self.spec.backend.successor_k)
        return self._observed(
            "successor_k", _lanes(keys),
            lambda: self.spec.backend.successor_k(
                self.spec.cfg, self.state, keys, k),
            _scan_counts, k=k)

    # ---- updates ----

    def insert_delete(self, batch: OpBatch):
        """Apply one OpBatch in batch order. Returns (new Index, results[K]).

        OP_SEARCH rows are no-ops with result False.  The old handle's
        state may be donated — always rebind to the returned Index.
        (`update` is the same call keeping the MaintenanceStats.)
        """
        ix, results, _ = self.update(batch)
        return ix, results

    def update(self, batch: OpBatch):
        """`insert_delete` returning telemetry: (new Index, results[K],
        MaintenanceStats | None) — stats is None for backends without a
        maintenance scheduler (baselines)."""
        self._require("updates", self.spec.backend.update)
        state, results, stats = self._observed(
            "update", _lanes(batch.keys),
            lambda: self.spec.backend.update(
                self.spec.cfg, self.state, batch),
            lambda out: {"maint": out[2], "free_top": _free_top(out[0])})
        return Index(self.spec, state), results, stats

    def flush(self):
        """Drain pending maintenance to fixpoint (restores invariant I5
        after ``deferred``/``budgeted`` update batches).  Returns
        (new Index, MaintenanceStats | None); a no-op (stats None) for
        backends without a maintenance scheduler.  The old handle's state
        may be donated — always rebind."""
        if self.spec.backend.flush is None:
            return self, None
        state, stats = self._observed(
            "flush", 0,
            lambda: self.spec.backend.flush(self.spec.cfg, self.state),
            lambda out: {"maint": out[1], "free_top": _free_top(out[0])})
        return Index(self.spec, state), stats

    # ---- host-side diagnostics ----

    def size(self) -> int:
        """Number of live keys (host-side)."""
        return int(self.spec.backend.size(self.spec.cfg, self.state))

    def live_items(self) -> list[tuple[int, int]]:
        """All live (key, payload) pairs in ascending GLOBAL key order
        (host-side, for tests).  The ordering is a contract, not a
        convenience: sharded backends must return split-order shard
        outputs concatenated (shard order == key order), so this list is
        the conformance oracle `range_scan`/`successor_k` pages are
        checked against — a full scan replays ``live_items`` exactly."""
        return list(self.spec.backend.live_items(self.spec.cfg, self.state))

    def touch_fn(self):
        """Host touch-trace fn (ideal-cache transfer counting) or None."""
        if self.spec.backend.touch is None:
            return None
        return self.spec.backend.touch(self.spec.cfg, self.state)

    def alloc_failed(self) -> bool:
        """Sticky arena-exhaustion flag (False for unbounded backends)."""
        if self.spec.backend.alloc_failed is None:
            return False
        return bool(self.spec.backend.alloc_failed(self.spec.cfg, self.state))


def _flatten(ix: Index):
    return (ix.state,), ix.spec


def _unflatten(spec: IndexSpec, children) -> Index:
    return Index(spec, children[0])


jax.tree_util.register_pytree_node(Index, _flatten, _unflatten)
