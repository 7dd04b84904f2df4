"""Baselines the paper compares ΔTree against (§5), adapted to batched JAX.

- PointerBST  — analog of the concurrent AVL/RB/speculation-friendly trees:
  explicit left/right child indices, nodes scattered in allocation order (no
  locality). Insert = leaf append (randomly-built ⇒ expected O(log n) height,
  same assumption as the paper's Lemma 4.5); delete = logical mark.
- StaticVEB   — the paper's VTMtree: one monolithic complete BST in static
  vEB order, values at internal nodes. Search-optimal, but ANY update
  rebuilds the whole layout (the paper's motivating weakness).
- SortedArray — binary search; batched updates = sort-merge rebuild.
- HashTable   — open-addressing linear probing (not in the paper; extra
  locality point of reference).

Every structure exposes:
  build(values) -> state            (host)
  search(state, keys) -> found[K]   (jitted)
  update(state, kinds, keys) -> (state, results[K])   (jitted or host)
  touched(state, key) -> list[int]  (host; flat element indices read on the
                                     search path, for ideal-cache transfer
                                     counting — Table 1 analog)

`count_block_transfers` converts touched-index traces into the number of
distinct size-B memory blocks transferred (the ideal-cache model the paper
analyses; B in elements).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layout
from repro.core.layout import EMPTY

OP_SEARCH, OP_INSERT, OP_DELETE = 0, 1, 2


def count_block_transfers(touch_fn, keys, block_elems: int) -> float:
    """Mean number of distinct B-element blocks touched per search."""
    total = 0
    for k in keys:
        idxs = touch_fn(int(k))
        total += len({i // block_elems for i in idxs})
    return total / max(len(keys), 1)


# --------------------------------------------------------------------------
# Sorted array
# --------------------------------------------------------------------------


class SortedArrayState(NamedTuple):
    vals: jax.Array  # (cap,) int32 ascending, padded with INT32_MAX
    n: jax.Array     # () int32


class SortedArray:
    name = "sorted_array"

    @staticmethod
    def build(values: np.ndarray, cap: int | None = None) -> SortedArrayState:
        values = np.unique(np.asarray(values, np.int32))
        cap = cap or max(16, 2 * len(values))
        pad = np.full(cap, np.iinfo(np.int32).max, np.int32)
        pad[: len(values)] = values
        return SortedArrayState(jnp.asarray(pad), jnp.int32(len(values)))

    @staticmethod
    @jax.jit
    def search(state: SortedArrayState, keys: jax.Array):
        i = jnp.searchsorted(state.vals, keys)
        i = jnp.clip(i, 0, state.vals.shape[0] - 1)
        return state.vals[i] == keys

    @staticmethod
    @jax.jit
    def update(state: SortedArrayState, kinds: jax.Array, keys: jax.Array):
        # batched rebuild: results computed sequentially against a bitmap
        def body(i, s):
            vals, n, res = s
            v = keys[i]
            idx = jnp.clip(jnp.searchsorted(vals, v), 0, vals.shape[0] - 1)
            present = vals[idx] == v

            def ins(args):
                vals, n = args
                # shift right from idx (O(cap) dynamic slice emulation)
                shifted = jnp.where(
                    jnp.arange(vals.shape[0]) > idx, jnp.roll(vals, 1), vals
                )
                return shifted.at[idx].set(v), n + 1

            def dele(args):
                vals, n = args
                rolled = jnp.roll(vals, -1)
                newv = jnp.where(jnp.arange(vals.shape[0]) >= idx, rolled, vals)
                return newv.at[vals.shape[0] - 1].set(jnp.iinfo(jnp.int32).max), n - 1

            is_ins = kinds[i] == OP_INSERT
            ok = jnp.where(is_ins, ~present, present)
            do = jnp.where(is_ins, ok, jnp.bool_(False))
            vals, n = jax.lax.cond(is_ins & ok, ins, lambda a: a, (vals, n))
            vals, n = jax.lax.cond((~is_ins) & ok, dele, lambda a: a, (vals, n))
            return vals, n, res.at[i].set(ok)

        vals, n, res = jax.lax.fori_loop(
            0, keys.shape[0], body, (state.vals, state.n, jnp.zeros(keys.shape, bool))
        )
        return SortedArrayState(vals, n), res

    @staticmethod
    def touch_fn(state: SortedArrayState):
        vals = np.asarray(state.vals)
        n = int(state.n)

        def touched(key: int) -> list[int]:
            lo, hi, out = 0, n, []
            while lo < hi:
                mid = (lo + hi) // 2
                out.append(mid)
                if vals[mid] < key:
                    lo = mid + 1
                elif vals[mid] > key:
                    hi = mid
                else:
                    break
            return out

        return touched


# --------------------------------------------------------------------------
# Static vEB monolith (VTMtree analog)
# --------------------------------------------------------------------------


class StaticVEBState(NamedTuple):
    store: jax.Array   # (2**h - 1,) int32 in vEB order, node-oriented BST
    height: int        # static


class StaticVEB:
    name = "static_veb"

    @staticmethod
    def _bst_values(values: np.ndarray, h: int) -> np.ndarray:
        """Place sorted values into a complete node-oriented BST (BFS index),
        in-order = sorted; empty slots get EMPTY."""
        n = 2**h
        out = np.full(n, EMPTY, np.int32)
        def fill(b, lo, hi):  # values[lo:hi] in subtree rooted at BFS b
            if lo >= hi:
                return
            # in-order position of root: size of a complete left subtree
            depth_left = h - (b.bit_length())  # height below b
            cap_left = 2**depth_left - 1 if depth_left > 0 else 0
            size = hi - lo
            left = min(cap_left, max(size - 1 - min(cap_left, size - 1), 0))
            # standard: fill left subtree as full as possible
            left = min(cap_left, size - 1)
            # keep right subtree non-degenerate: classic balanced split
            left = (size - 1) // 2 if cap_left >= (size - 1) // 2 else cap_left
            root = lo + left
            out[b] = values[root]
            fill(2 * b, lo, root)
            fill(2 * b + 1, root + 1, hi)
        import sys
        old = sys.getrecursionlimit()
        sys.setrecursionlimit(10000)
        try:
            fill(1, 0, len(values))
        finally:
            sys.setrecursionlimit(old)
        return out

    @staticmethod
    def build(values: np.ndarray, height: int | None = None) -> StaticVEBState:
        values = np.unique(np.asarray(values, np.int32))
        h = height or max(1, int(np.ceil(np.log2(len(values) + 2))))
        while 2**h - 1 < len(values):
            h += 1
        bfs_vals = StaticVEB._bst_values(values, h)
        pos = layout.veb_pos_table(h)
        store = np.full(2**h - 1, EMPTY, np.int32)
        for b in range(1, 2**h):
            store[pos[b]] = bfs_vals[b]
        return StaticVEBState(jnp.asarray(store), h)

    @staticmethod
    @functools.partial(jax.jit, static_argnums=2)
    def _search(store: jax.Array, keys: jax.Array, h: int):
        pos = jnp.asarray(layout.veb_pos_table(h))

        def one(v):
            def cond(s):
                b, found, dead = s
                return (~found) & (~dead)

            def body(s):
                b, found, dead = s
                x = store[pos[b]]
                found = x == v
                nb = 2 * b + (v > x).astype(jnp.int32)
                dead = (x == EMPTY) | (nb >= 2**h)
                return jnp.where(found | dead, b, nb), found, dead

            _, found, _ = jax.lax.while_loop(
                cond, body, (jnp.int32(1), jnp.bool_(False), jnp.bool_(False))
            )
            return found

        return jax.vmap(one)(keys)

    @staticmethod
    def search(state: StaticVEBState, keys: jax.Array):
        return StaticVEB._search(state.store, keys, state.height)

    @staticmethod
    def update(state: StaticVEBState, kinds, keys):
        """The paper's point: a static vEB layout cannot update in place —
        the whole layout is rebuilt (host-side), blocking everything."""
        vals_np = StaticVEB.to_sorted(state)
        s = set(vals_np.tolist())
        res = np.zeros(len(keys), bool)
        for i, (k, v) in enumerate(zip(np.asarray(kinds), np.asarray(keys))):
            v = int(v)
            if k == OP_INSERT:
                res[i] = v not in s
                s.add(v)
            elif k == OP_DELETE:
                res[i] = v in s
                s.discard(v)
        return StaticVEB.build(np.asarray(sorted(s), np.int32), None), jnp.asarray(res)

    @staticmethod
    def to_sorted(state: StaticVEBState) -> np.ndarray:
        store = np.asarray(state.store)
        vals = store[store != EMPTY]
        return np.sort(vals)

    @staticmethod
    def touch_fn(state: StaticVEBState):
        store = np.asarray(state.store)
        h = state.height
        pos = layout.veb_pos_table(h)

        def touched(key: int) -> list[int]:
            b, out = 1, []
            while b < 2**h:
                p = int(pos[b])
                out.append(p)
                x = store[p]
                if x == key or x == EMPTY:
                    break
                b = 2 * b + (1 if key > x else 0)
            return out

        return touched


# --------------------------------------------------------------------------
# Pointer BST (concurrent AVL/RB/SF-tree analog: no locality)
# --------------------------------------------------------------------------


class PointerBSTState(NamedTuple):
    val: jax.Array    # (cap,) int32
    left: jax.Array   # (cap,) int32, -1 none
    right: jax.Array  # (cap,) int32
    mark: jax.Array   # (cap,) bool
    n: jax.Array      # () int32 — nodes allocated
    root: jax.Array   # () int32


class PointerBST:
    name = "pointer_bst"

    @staticmethod
    def build(values: np.ndarray, cap: int | None = None,
              shuffle_layout: bool = True, seed: int = 0) -> PointerBSTState:
        """Insert in random order (expected O(log n) height), node ids in
        *allocation order* — i.e., memory layout uncorrelated with tree
        structure, like heap-allocated nodes of the Synchrobench trees."""
        values = np.unique(np.asarray(values, np.int32))
        rng = np.random.default_rng(seed)
        order = rng.permutation(len(values))
        cap = cap or max(16, 2 * len(values) + 16)
        val = np.zeros(cap, np.int32)
        left = np.full(cap, -1, np.int32)
        right = np.full(cap, -1, np.int32)
        n = 0
        root = -1
        for i in order:
            v = values[i]
            if root < 0:
                root = n
            else:
                c = root
                while True:
                    if v < val[c]:
                        if left[c] < 0:
                            left[c] = n
                            break
                        c = left[c]
                    else:
                        if right[c] < 0:
                            right[c] = n
                            break
                        c = right[c]
            val[n] = v
            n += 1
        return PointerBSTState(
            jnp.asarray(val), jnp.asarray(left), jnp.asarray(right),
            jnp.zeros(cap, jnp.bool_), jnp.int32(n), jnp.int32(root),
        )

    @staticmethod
    @jax.jit
    def search(state: PointerBSTState, keys: jax.Array):
        def one(v):
            def cond(s):
                c, found = s
                return (c >= 0) & (~found)

            def body(s):
                c, _ = s
                x = state.val[c]
                hit = (x == v) & ~state.mark[c]
                stop = x == v
                nc = jnp.where(v < x, state.left[c], state.right[c])
                return jnp.where(stop, jnp.int32(-1), nc), hit

            _, found = jax.lax.while_loop(cond, body, (state.root, jnp.bool_(False)))
            return found

        return jax.vmap(one)(keys)

    @staticmethod
    @jax.jit
    def update(state: PointerBSTState, kinds: jax.Array, keys: jax.Array):
        def body(i, s):
            st, res = s
            v = keys[i]

            # descend to the match or the attach point
            def cond(x):
                c, parent, went_left, done = x
                return ~done

            def bd(x):
                c, parent, went_left, done = x
                xv = st.val[c]
                hit = xv == v
                nl = jnp.where(v < xv, st.left[c], st.right[c])
                done = hit | (nl < 0)
                return (
                    jnp.where(done, c, nl),
                    jnp.where(done, parent, c),
                    jnp.where(done, went_left, v < xv),
                    done,
                )

            c, parent, went_left, _ = jax.lax.while_loop(
                cond, bd, (st.root, jnp.int32(-1), jnp.bool_(False), st.n == 0)
            )
            xv = st.val[c]
            hit = (st.n > 0) & (xv == v)
            is_ins = kinds[i] == OP_INSERT

            def do_ins(st):
                def revive(st):
                    return st._replace(mark=st.mark.at[c].set(False))

                def attach(st):
                    nid = st.n
                    stv = st._replace(
                        val=st.val.at[nid].set(v),
                        n=st.n + 1,
                        root=jnp.where(st.n == 0, nid, st.root),
                    )
                    go_left = v < xv
                    stv = stv._replace(
                        left=jnp.where(
                            (st.n > 0) & go_left, stv.left.at[c].set(nid), stv.left
                        ),
                        right=jnp.where(
                            (st.n > 0) & ~go_left, stv.right.at[c].set(nid), stv.right
                        ),
                    )
                    return stv

                return jax.lax.cond(hit, revive, attach, st)

            def do_del(st):
                return st._replace(
                    mark=jnp.where(hit, st.mark.at[c].set(True), st.mark)
                )

            ok = jnp.where(
                is_ins, jnp.where(hit, st.mark[c], True), hit & ~st.mark[c]
            )
            st = jax.lax.cond(is_ins & ok, do_ins, lambda s: s, st)
            st = jax.lax.cond((~is_ins) & ok, do_del, lambda s: s, st)
            return st, res.at[i].set(ok)

        st, res = jax.lax.fori_loop(
            0, keys.shape[0], body, (state, jnp.zeros(keys.shape, bool))
        )
        return st, res

    @staticmethod
    def touch_fn(state: PointerBSTState):
        val = np.asarray(state.val)
        left = np.asarray(state.left)
        right = np.asarray(state.right)
        root = int(state.root)
        n = int(state.n)

        def touched(key: int) -> list[int]:
            # each node = val + 2 pointers; model 4 elements per node
            out, c = [], root if n > 0 else -1
            while c >= 0:
                out.extend([4 * c, 4 * c + 1, 4 * c + 2])
                if val[c] == key:
                    break
                c = left[c] if key < val[c] else right[c]
            return out

        return touched


# --------------------------------------------------------------------------
# Open-addressing hash table (extra baseline, not in the paper)
# --------------------------------------------------------------------------


class HashState(NamedTuple):
    slots: jax.Array  # (cap,) int32, EMPTY free, -1 tombstone... use 0 free
    cap: int


class HashTable:
    name = "hash"
    TOMB = -1

    @staticmethod
    def _h(v, cap):
        return (v.astype(jnp.uint32) * jnp.uint32(2654435761) % jnp.uint32(cap)).astype(
            jnp.int32
        )

    @staticmethod
    def build(values: np.ndarray, cap: int | None = None) -> HashState:
        values = np.unique(np.asarray(values, np.int32))
        cap = cap or int(2 ** np.ceil(np.log2(max(4 * len(values), 16))))
        slots = np.full(cap, EMPTY, np.int32)
        for v in values:
            i = int((int(v) * 2654435761) % (2**32) % cap)
            while slots[i] != EMPTY:
                i = (i + 1) % cap
            slots[i] = v
        return HashState(jnp.asarray(slots), cap)

    @staticmethod
    @functools.partial(jax.jit, static_argnums=1)
    def _search(slots, cap, keys):
        def one(v):
            def cond(s):
                i, found, dead, steps = s
                return (~found) & (~dead) & (steps < cap)

            def body(s):
                i, found, dead, steps = s
                x = slots[i]
                found = x == v
                dead = x == EMPTY
                return (i + 1) % cap, found, dead, steps + 1

            i0 = HashTable._h(v, cap)
            _, found, _, _ = jax.lax.while_loop(
                cond, body, (i0, jnp.bool_(False), jnp.bool_(False), jnp.int32(0))
            )
            return found

        return jax.vmap(one)(keys)

    @staticmethod
    def search(state: HashState, keys: jax.Array):
        return HashTable._search(state.slots, state.cap, keys)

    @staticmethod
    def touch_fn(state: HashState):
        slots = np.asarray(state.slots)
        cap = state.cap

        def touched(key: int) -> list[int]:
            i = int((int(key) * 2654435761) % (2**32) % cap)
            out = []
            for _ in range(cap):
                out.append(i)
                if slots[i] == key or slots[i] == EMPTY:
                    break
                i = (i + 1) % cap
            return out

        return touched


ALL_BASELINES = [SortedArray, StaticVEB, PointerBST, HashTable]
