"""Built-in Index backends: deltatree, forest, sorted_array (+ the paper's
comparison structures pointer_bst and static_veb).

Each entry adapts one existing engine to the uniform ``BackendSpec``
contract — (cfg, state) construction, wait-free reads, batch-order
``OpBatch`` updates with OP_SEARCH rows as no-ops, host-side debug views.
Backends whose update kernel only understands insert/delete rows
(``sorted_array``, ``pointer_bst``, ``static_veb``) neutralize search rows
via ``OpBatch.mask_searches`` (a delete of key 0, which is never stored).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.index import BackendSpec, Capability
from repro.api.opbatch import OpBatch
from repro.api.registry import register_backend
from repro.core import baselines as BL
from repro.core import layout
from repro.core import deltatree as DT
from repro.core import transfers as TR
from repro.core.deltatree import TreeConfig
from repro.distributed import forest as F
from repro.distributed.forest import ForestConfig

_TREE_FIELDS = {f.name for f in dataclasses.fields(TreeConfig)}


def _as_cfg(cls, cfg, kw):
    if cfg is None:
        return cls(**kw)
    return dataclasses.replace(cfg, **kw) if kw else cfg


# --------------------------------------------------------------------------
# deltatree — the paper's structure (repro.core single arena)
# --------------------------------------------------------------------------


def _dt_make(initial, payloads, cfg=None, **kw):
    cfg = _as_cfg(TreeConfig, cfg, kw)
    if initial is None:
        return cfg, DT.empty(cfg)
    return cfg, DT.bulk_build(cfg, np.asarray(initial), payloads)


def _dt_update(cfg, t, batch: OpBatch):
    return DT.update_batch(cfg, t, batch.kinds, batch.keys, batch.payloads)


def _unpack_scan(cfg, out, n, hops, more):
    """Packed engine-scan rows -> the BackendSpec scan contract: (keys,
    payloads, n, hops, more) with (K, max_items) int32 rows zero-padded
    past ``n`` (0 is outside the key domain, so the pad is unambiguous)."""
    span = jnp.arange(out.shape[1], dtype=jnp.int32)
    valid = span[None, :] < n[:, None]
    keys = jnp.where(valid, cfg.key_of(out).astype(jnp.int32), 0)
    pays = jnp.where(valid, cfg.payload_of(out).astype(jnp.int32), 0)
    return keys, pays, n, hops, more


def _dt_scan(cfg, t, starts, his, max_items):
    return _unpack_scan(cfg, *DT.scan_jit(cfg, t, starts, his, max_items))


def _dt_successor_k(cfg, t, keys, k):
    return _unpack_scan(cfg, *DT.successor_k_jit(cfg, t, keys, k))


def _dt_size(cfg, t) -> int:
    # I5/I5': between steps every live item is a live leaf or a buffered
    # entry (never both — inserts dedup against the buffer), so
    # nlive+bcount over live arenas is exact under every maintenance
    # policy (cross-checked vs the oracle by the conformance suite).
    return int(jnp.sum(jnp.where(t.alive, t.nlive + t.bcount, 0)))


register_backend(BackendSpec(
    name="deltatree",
    make=_dt_make,
    capability=lambda cfg: Capability(
        map_mode=cfg.payload_bits > 0, successor=True, sharded=False,
        deferred_maintenance=True, range_scan=True, successor_k=True),
    search=DT.search_jit,
    lookup=DT.lookup_jit,
    update=_dt_update,
    successor=DT.successor_jit,
    scan=_dt_scan,
    successor_k=_dt_successor_k,
    live_items=DT.live_items,
    size=_dt_size,
    touch=TR.delta_touch_fn,
    alloc_failed=lambda cfg, t: bool(t.alloc_fail),
    flush=DT.flush,
    engines=("*",),   # reads dispatch on cfg.engine: any registered engine
    maintenance=("*",),   # scheduler dispatch on cfg.maintenance: any policy
))


# --------------------------------------------------------------------------
# forest — key-range-sharded DeltaForest (repro.distributed)
# --------------------------------------------------------------------------


def _forest_make(initial, payloads, cfg=None, splits=None, **kw):
    if cfg is None:
        tree_kw = {k: kw.pop(k) for k in list(kw) if k in _TREE_FIELDS}
        tree = kw.pop("tree", None)
        tree = (dataclasses.replace(tree, **tree_kw) if tree is not None
                else TreeConfig(**tree_kw))
        cfg = ForestConfig(tree=tree, **kw)
    else:
        # TreeConfig knobs (notably ``engine``) land on cfg.tree, the rest
        # on the ForestConfig itself
        tree_kw = {k: kw.pop(k) for k in list(kw) if k in _TREE_FIELDS}
        if tree_kw:
            cfg = dataclasses.replace(
                cfg, tree=dataclasses.replace(cfg.tree, **tree_kw))
        if kw:
            cfg = dataclasses.replace(cfg, **kw)
    if initial is None:
        return cfg, F.empty(cfg, splits)
    return cfg, F.bulk_build(cfg, np.asarray(initial), payloads, splits)


def _forest_fused(cfg: ForestConfig) -> bool:
    """True when this config's forest reads run the fused cross-shard
    frontier (``cfg.fused`` enabled AND the selected engine provides a
    ``forest_batch`` entry point — see ``repro.core.engine``)."""
    from repro.core import engine as E

    try:
        eng = E.get_engine(cfg.tree.engine)
    except KeyError:
        return False   # bad engine names fail later in make_index
    return bool(cfg.fused) and eng.forest_batch is not None


def _forest_update(cfg, f, batch: OpBatch):
    return F.update_batch(cfg, f, batch.kinds, batch.keys, batch.payloads)


def _forest_scan(cfg, f, starts, his, max_items):
    return _unpack_scan(
        cfg.tree, *F.scan_batch(cfg, f, starts, his, max_items=max_items))


def _forest_successor_k(cfg, f, keys, k):
    return _unpack_scan(cfg.tree, *F.successor_k(cfg, f, keys, k))


def _forest_size(cfg, f) -> int:
    t = f.trees
    return int(jnp.sum(jnp.where(t.alive, t.nlive + t.bcount, 0)))


register_backend(BackendSpec(
    name="forest",
    make=_forest_make,
    capability=lambda cfg: Capability(
        map_mode=cfg.tree.payload_bits > 0, successor=True, sharded=True,
        deferred_maintenance=True, fused_forest=_forest_fused(cfg),
        range_scan=True, successor_k=True),
    search=F.search_batch,
    lookup=F.lookup_batch,
    update=_forest_update,
    successor=F.successor_jit,
    scan=_forest_scan,
    successor_k=_forest_successor_k,
    live_items=F.live_items,
    size=_forest_size,
    alloc_failed=lambda cfg, f: F.alloc_failed(f),
    flush=F.flush,
    routing=F.routing,
    engines=("*",),   # per-shard reads dispatch on cfg.tree.engine
    maintenance=("*",),   # per-shard scheduler dispatch on cfg.tree.maintenance
))


# --------------------------------------------------------------------------
# sorted_array — binary search + sort-merge rebuild (core.baselines)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SortedArrayConfig:
    cap: int | None = None   # None: build auto-sizes to 2x the initial keys


def _sa_make(initial, payloads, cfg=None, **kw):
    cfg = _as_cfg(SortedArrayConfig, cfg, kw)
    vals = np.asarray(initial) if initial is not None else np.zeros(0, np.int32)
    return cfg, BL.SortedArray.build(vals, cap=cfg.cap)


@jax.jit
def _sa_search(state, keys):
    found = BL.SortedArray.search(state, keys)
    return found, jnp.zeros_like(keys)


def _sa_update(cfg, state, batch: OpBatch):
    kinds, keys, is_update = batch.mask_searches()
    state, res = BL.SortedArray.update(state, kinds, keys)
    return state, res & is_update, None  # no maintenance scheduler


@jax.jit
def _sa_successor(state, keys):
    keys = jnp.asarray(keys, jnp.int32)
    i = jnp.searchsorted(state.vals, keys, side="right").astype(jnp.int32)
    found = i < state.n
    safe = jnp.clip(i, 0, state.vals.shape[0] - 1)
    return found, jnp.where(found, state.vals[safe], 0)


def _sa_live_items(cfg, state):
    n = int(state.n)
    return [(int(v), 0) for v in np.asarray(state.vals)[:n]]


@functools.partial(jax.jit, static_argnums=3)
def _sa_scan(state, starts, his, max_items):
    """Dense-scan honesty baseline: the page is one searchsorted window
    per lane over the flat sorted array — no tree walk at all, which is
    exactly why it should win dense ranges in the scan sweep."""
    starts = jnp.asarray(starts, jnp.int32)
    his = jnp.asarray(his, jnp.int32)
    i0 = jnp.searchsorted(state.vals, starts, side="right").astype(jnp.int32)
    ic = jnp.searchsorted(state.vals, his, side="right").astype(jnp.int32)
    total = jnp.maximum(
        jnp.minimum(ic, state.n) - jnp.minimum(i0, state.n), 0)
    span = jnp.arange(max_items, dtype=jnp.int32)
    idx = jnp.clip(i0[:, None] + span[None, :], 0, state.vals.shape[0] - 1)
    valid = span[None, :] < total[:, None]
    keys = jnp.where(valid, state.vals[idx], 0)
    return (keys, jnp.zeros_like(keys),
            jnp.minimum(total, jnp.int32(max_items)),
            jnp.zeros_like(starts), total > max_items)


def _sa_successor_k(cfg, state, keys, k):
    keys = jnp.asarray(keys, jnp.int32)
    his = jnp.full(keys.shape, layout.KEY_MAX, jnp.int32)
    return _sa_scan(state, keys, his, k)


register_backend(BackendSpec(
    name="sorted_array",
    make=_sa_make,
    capability=lambda cfg: Capability(successor=True, range_scan=True,
                                      successor_k=True),
    search=lambda cfg, state, keys: _sa_search(state, keys),
    update=_sa_update,
    successor=lambda cfg, state, keys: _sa_successor(state, keys),
    scan=lambda cfg, state, starts, his, mi: _sa_scan(state, starts, his, mi),
    successor_k=_sa_successor_k,
    live_items=_sa_live_items,
    size=lambda cfg, state: int(state.n),
    touch=lambda cfg, state: BL.SortedArray.touch_fn(state),
))


# --------------------------------------------------------------------------
# pointer_bst — heap-allocated BST analog (no locality; core.baselines)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PointerBSTConfig:
    cap: int | None = None   # None: build auto-sizes to 2x the initial keys
    seed: int = 0


def _bst_make(initial, payloads, cfg=None, **kw):
    cfg = _as_cfg(PointerBSTConfig, cfg, kw)
    vals = np.asarray(initial) if initial is not None else np.zeros(0, np.int32)
    return cfg, BL.PointerBST.build(vals, cap=cfg.cap, seed=cfg.seed)


@jax.jit
def _bst_search(state, keys):
    return BL.PointerBST.search(state, keys), jnp.zeros_like(keys)


def _bst_update(cfg, state, batch: OpBatch):
    kinds, keys, is_update = batch.mask_searches()
    state, res = BL.PointerBST.update(state, kinds, keys)
    return state, res & is_update, None  # no maintenance scheduler


def _bst_live_items(cfg, state):
    n = int(state.n)
    vals = np.asarray(state.val)[:n]
    mark = np.asarray(state.mark)[:n]
    return [(int(v), 0) for v in np.sort(vals[~mark])]


register_backend(BackendSpec(
    name="pointer_bst",
    make=_bst_make,
    capability=lambda cfg: Capability(),
    search=lambda cfg, state, keys: _bst_search(state, keys),
    update=_bst_update,
    live_items=_bst_live_items,
    size=lambda cfg, state: int(state.n) - int(np.asarray(
        state.mark)[: int(state.n)].sum()),
    touch=lambda cfg, state: BL.PointerBST.touch_fn(state),
))


# --------------------------------------------------------------------------
# static_veb — VTMtree analog: search-optimal, whole-layout rebuild updates
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StaticVEBConfig:
    height: int | None = None   # None: minimal height for the build


def _sv_make(initial, payloads, cfg=None, **kw):
    cfg = _as_cfg(StaticVEBConfig, cfg, kw)
    vals = np.asarray(initial) if initial is not None else np.zeros(0, np.int32)
    return cfg, BL.StaticVEB.build(vals, height=cfg.height)


def _sv_search(cfg, state, keys):
    keys = jnp.asarray(keys, jnp.int32)
    return BL.StaticVEB.search(state, keys), jnp.zeros_like(keys)


def _sv_update(cfg, state, batch: OpBatch):
    kinds = np.asarray(batch.kinds)
    keys = np.asarray(batch.keys)
    mask = kinds != DT.OP_SEARCH
    res = np.zeros(len(keys), bool)
    if mask.any():
        state, sub = BL.StaticVEB.update(state, kinds[mask], keys[mask])
        if cfg.height is not None and state.height != cfg.height:
            # BL.StaticVEB.update rebuilds at minimal height; re-pin the
            # configured layout (build still grows h if the set outgrew it)
            state = BL.StaticVEB.build(BL.StaticVEB.to_sorted(state),
                                       height=cfg.height)
        res[mask] = np.asarray(sub)
    return state, jnp.asarray(res), None  # no maintenance scheduler


def _sv_live_items(cfg, state):
    return [(int(v), 0) for v in BL.StaticVEB.to_sorted(state)]


def _sv_scan(cfg, state, starts, his, max_items):
    """Host-side scan over the recovered sorted key set (the VTMtree
    analog rebuilds wholesale anyway, so its ordered reads are honest as
    a host replay of the layout's in-order traversal)."""
    vals = np.asarray(BL.StaticVEB.to_sorted(state), np.int32)
    starts = np.asarray(starts, np.int32)
    his = np.asarray(his, np.int32)
    i0 = np.searchsorted(vals, starts, side="right")
    ic = np.searchsorted(vals, his, side="right")
    total = np.maximum(ic - i0, 0)
    keys = np.zeros((starts.shape[0], max_items), np.int32)
    for j in range(starts.shape[0]):
        got = vals[i0[j]: ic[j]][:max_items]
        keys[j, : got.size] = got
    return (jnp.asarray(keys), jnp.zeros_like(jnp.asarray(keys)),
            jnp.asarray(np.minimum(total, max_items), jnp.int32),
            jnp.zeros((starts.shape[0],), jnp.int32),
            jnp.asarray(total > max_items))


def _sv_successor_k(cfg, state, keys, k):
    his = np.full(np.asarray(keys).shape, layout.KEY_MAX, np.int32)
    return _sv_scan(cfg, state, keys, his, k)


register_backend(BackendSpec(
    name="static_veb",
    make=_sv_make,
    capability=lambda cfg: Capability(range_scan=True, successor_k=True),
    search=_sv_search,
    update=_sv_update,
    scan=_sv_scan,
    successor_k=_sv_successor_k,
    live_items=_sv_live_items,
    size=lambda cfg, state: int(BL.StaticVEB.to_sorted(state).size),
    touch=lambda cfg, state: BL.StaticVEB.touch_fn(state),
))
