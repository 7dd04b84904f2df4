"""The Expand pass writes rows, and leaves every plane as the branchy pass did.

``deltatree._process_ins`` drains a ΔNode's overflow buffer item by item
in slot order: each item is placed or grown in the ΔNode, revived over
its own tombstone, Expanded into a fresh child ΔNode, moved into a
descendant's buffer, or kept when that buffer is full.  These tests hold
it to the trees the earlier pass (one ``lax.cond``/``lax.switch`` per
item, each returning the whole tree) left, bit for bit:

- seeded insert-heavy and mixed insert/delete batches through
  ``update_batch`` under every policy (eager, deferred with ``flush``,
  budgeted) and both engines, every plane of the tree after every batch
  hashed into one digest pinned from that pass, and every result and the
  live set checked against ``SetOracle``/``MapOracle``;
- a sweep of ``_process_ins`` calls over states built to reach every
  outcome — place, grow, dup over a live or a marked leaf (revive),
  Expand of a live or a marked leaf (the route-left seed), moved, kept
  because the child's buffer is full, and an exhausted arena (sticky
  ``alloc_fail``) — pinned the same way, each outcome asserted as seen.

Set mode runs in process, map mode (int64 rows) under x64 in a
subprocess.  The lockstep engine's positions come from the compiled XLA
mirror (``REPRO_PALLAS_INTERPRET=0``), as on the chip.
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import numpy as np
import pytest

from tests._subproc import run_py

ROOT = Path(__file__).resolve().parents[1]
KW = dict(height=4, max_dnodes=160, buf_cap=8)
KEY_HI = 5000
K = 40
POLICIES = ("eager", "deferred", "budgeted:2")
ENGINES = ("scalar", "lockstep")
KINDS = ("moved", "kept", "dup", "place", "grow", "expand")


def _hash_tree(h, t):
    for name, x in zip(t._fields, t):
        a = np.asarray(x)
        h.update(f"{name}:{a.dtype.str}:{a.shape}:".encode())
        h.update(np.ascontiguousarray(a).tobytes())


def _batch(rng, step, live):
    """Step 0 and 2: inserts, most in one narrow band (Expands deepen
    it).  Step 1 and 3: half deletes of live keys, ten of them in the
    band (tombstones that step 2's Expands seed with), half inserts, six
    re-inserting a key deleted earlier in the same batch."""
    if step % 2 == 0:
        keys = np.r_[rng.integers(1200, 1330, 32),
                     rng.integers(1, KEY_HI, 8)]
        kinds = np.full(K, 1)
    else:
        lv = np.asarray(sorted(live))
        band = lv[(lv >= 1200) & (lv < 1330)]
        near = rng.choice(band, min(10, band.size), replace=False)
        rest = rng.choice(np.setdiff1d(lv, near), 20 - near.size,
                          replace=False)
        dels = np.r_[near, rest]
        keys = np.r_[dels, dels[:6], rng.integers(1, KEY_HI, 14)]
        kinds = np.r_[np.full(20, 2), np.full(20, 1)]
    pays = rng.integers(0, 1 << 30, K)
    return kinds.astype(np.int32), keys.astype(np.int32), pays.astype(
        np.int32)


def trace_batches(bits, policy, engine):
    """Four batches (and flushes) through ``update_batch``: the rolling
    digest of every tree, the wrong results and live-set mismatches
    against the oracle, Expands counted and ``alloc_fail``."""
    import jax.numpy as jnp

    from repro.core import deltatree as DT
    from repro.core.oracle import MapOracle, SetOracle

    cfg = DT.TreeConfig(**KW, payload_bits=bits, engine=engine,
                        maintenance=policy)
    rng = np.random.default_rng(1700 + bits)
    initial = np.unique(rng.integers(1, KEY_HI, 60))
    pays = rng.integers(0, 1 << 30, initial.size)
    t = DT.bulk_build(cfg, initial, pays if bits else None)
    oracle = (MapOracle(zip(initial.tolist(), pays.tolist())) if bits
              else SetOracle(initial))
    h = hashlib.sha256()
    _hash_tree(h, t)
    out = dict(wrong=0, live_wrong=0, expands=0)

    def flush(t):
        t, st = DT.flush(cfg, t)
        out["expands"] += int(st.expands)
        _hash_tree(h, t)
        return t

    for step in range(4):
        live = oracle.d if bits else oracle.s
        kinds, keys, kp = _batch(rng, step, live)
        t, res, st = DT.update_batch(cfg, t, jnp.asarray(kinds),
                                     jnp.asarray(keys),
                                     jnp.asarray(kp) if bits else None)
        out["expands"] += int(st.expands)
        want = (oracle.apply_updates(kinds, keys, kp) if bits
                else oracle.apply_updates(kinds, keys))
        out["wrong"] += int((np.asarray(res) != want).sum())
        _hash_tree(h, t)
        if policy == "deferred" and step % 2:
            t = flush(t)
        items = DT.live_items(cfg, t)
        want_items = (oracle.items() if bits
                      else [(k, 0) for k in sorted(oracle.s)])
        out["live_wrong"] += int(items != want_items)
    if policy != "eager":
        t = flush(t)
    out["digest"] = h.hexdigest()
    out["alloc_fail"] = bool(t.alloc_fail)
    return out


def _with_buffer(t, dn, vals):
    """``t`` with ΔNode ``dn``'s buffer holding ``vals`` (packed), its
    count and ins flag set to match."""
    import jax.numpy as jnp

    buf = np.asarray(t.buf).copy()
    buf[dn] = 0
    buf[dn, :len(vals)] = vals
    bcount = np.asarray(t.bcount).copy()
    bcount[dn] = len(vals)
    flag = np.asarray(t.ins_flag).copy()
    flag[dn] = True
    return t._replace(buf=jnp.asarray(buf), bcount=jnp.asarray(bcount),
                      ins_flag=jnp.asarray(flag))


def sweep_cases(bits, replay=False):
    """``_process_ins`` over states built to reach every outcome of an
    item.  Returns the rolling digest of every state before and after
    each call, ``alloc_fail`` at the end and, with ``replay``, the item
    outcomes seen (``_expand_item`` run item by item on the same states,
    its end state held to ``_process_ins``'s)."""
    import jax
    import jax.numpy as jnp

    from repro.core import deltatree as DT
    from repro.core import layout

    cfg = DT.TreeConfig(**KW, payload_bits=bits, maintenance="deferred")
    pos = np.asarray(layout.veb_pos_table(cfg.height))
    proc = jax.jit(DT._process_ins, static_argnums=0)
    descend = jax.jit(DT._descend, static_argnums=0)
    h = hashlib.sha256()
    seen = set()

    def pack(keys, salt=0):
        keys = np.asarray(keys, np.int64)
        return (keys << bits) | ((keys * 7 + salt) % 1000) if bits else keys

    def run(t, dn):
        _hash_tree(h, t)
        new = proc(cfg, t, jnp.int32(dn))[0]
        if replay:
            replay_items(t, dn, new)
        _hash_tree(h, new)
        return new

    def replay_items(t, dn, want):
        nlive, bcount = np.asarray(t.nlive), np.asarray(t.bcount)
        if np.asarray(t.nchild)[dn] == 0 and (nlive[dn] + bcount[dn]
                                             <= cfg.half_cap):
            return                                   # a Rebalance
        item = jax.jit(DT._expand_item, static_argnums=0)
        for i in np.flatnonzero(np.asarray(t.buf)[dn] != 0):
            pv = np.asarray(t.buf)[dn, i]
            q = int(pv) | cfg.pmask if bits else int(pv)
            tdn, b, _ = descend(cfg, t, jnp.asarray(q, cfg.vdtype),
                                jnp.int32(dn), jnp.int32(1))
            marked = bool(np.asarray(t.mark)[int(tdn), pos[int(b)]])
            t, kind = item(cfg, t, jnp.int32(dn), jnp.int32(i))
            name = KINDS[int(kind)]
            seen.add(name + ("_marked" if marked and name in ("dup",
                                                              "expand")
                             else ""))
        flag = np.asarray(t.ins_flag).copy()
        flag[dn] = np.asarray(t.bcount)[dn] > 0
        t = t._replace(ins_flag=jnp.asarray(flag))
        for name, a, b in zip(t._fields, t, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)

    def sweep(t):
        for _ in range(16):
            todo = np.flatnonzero(np.asarray(t.ins_flag & t.alive))
            for dn in todo:
                t = run(t, int(dn))
        assert not np.asarray(t.ins_flag & t.alive).any()
        return t

    def update(t, kinds, keys):
        # padded with OP_SEARCH rows to one batch shape
        kinds = np.r_[kinds, np.zeros(24 - len(kinds))].astype(np.int32)
        keys = np.r_[keys, np.ones(24 - len(keys))].astype(np.int32)
        t, _, _ = DT.update_batch(cfg, t, jnp.asarray(kinds),
                                  jnp.asarray(keys),
                                  jnp.asarray(keys * 7 % 1000) if bits
                                  else None)
        return t

    base = np.arange(150, 150 * 29, 150)
    t = DT.bulk_build(cfg, base, base * 7 % 1000 if bits else None)
    # deletes leave tombstones; the band's inserts buffer behind full
    # bottom leaves, and their sweep Expands (live and marked seeds),
    # moves into the fresh children and grows and places there
    t = update(t, [2] * 4, [600, 750, 1050, 1200])
    t = update(t, [1] * 24, np.r_[601:613, 1051:1063])
    t = sweep(t)
    # revive and dup: a ΔNode's buffer holding its own tombstone's key
    # (with a new payload) and a live key it already holds, beside items
    # that grow it
    t = update(t, [2], [1650])
    rows = np.asarray(t.value)
    dn = int(np.flatnonzero((rows == pack([1650])[0]).any(1)
                            & np.asarray(t.alive))[0])
    vals = np.r_[pack([1650], salt=1), pack([1800, 1651, 1652, 1653])]
    t = run(_with_buffer(t, dn, vals), dn)
    t = sweep(t)
    # Expand of a marked leaf: an item grows a tombstone's leaf to the
    # bottom row, the next lands on the tombstone and Expands it into an
    # empty child (the route-left seed); four more follow it there, and
    # the child's own pass places the first on its empty root
    t = update(t, [2], [3900])
    dn = int(np.flatnonzero((np.asarray(t.value) == pack([3900])[0]).any(1)
                            & np.asarray(t.alive))[0])
    t = run(_with_buffer(t, dn, pack([3950, 3920, 3921, 3922, 3923,
                                      3924])), dn)
    t = sweep(t)
    # kept: a child whose buffer is full, and an item of its parent's
    # buffer that descends into it
    parent, alive = np.asarray(t.parent), np.asarray(t.alive)
    nchild, nlive = np.asarray(t.nchild), np.asarray(t.nlive)
    kids = [c for c in np.flatnonzero(alive & (parent >= 0) & (nchild == 0)
                                      & (nlive > 0))
            if parent[c] != int(t.root)]
    c = int(kids[0])
    p = int(parent[c])
    present = {k for k, _ in DT.live_items(cfg, t)}
    lo = min(k for k, _ in DT.live_items(cfg, t._replace(
        alive=jnp.asarray(np.arange(cfg.max_dnodes) == c))))
    into = [k for k in range(lo + 1, lo + 60) if k not in present
            and int(descend(cfg, t, jnp.asarray(
                pack([k])[0] | (cfg.pmask if bits else 0), cfg.vdtype),
                jnp.int32(p), jnp.int32(1))[0]) == c]
    t = _with_buffer(t, c, pack(into[:cfg.buf_cap]))
    t = run(_with_buffer(t, p, pack(into[cfg.buf_cap:cfg.buf_cap + 2])), p)
    # an exhausted arena: an item grows a leaf to the bottom row and the
    # next one Expands it, popping past the free stack's end; alloc_fail
    # is set, and stays set through a later pass that only grows.  Last,
    # the popped id is the Expanding ΔNode itself, whose rows the child's
    # writes then land on
    def holder(key, leaf=True):
        hit = (np.asarray(t.value) == pack([key])[0]).any(1)
        if leaf:
            hit &= np.asarray(t.nchild) == 0
        return int(np.flatnonzero(hit & np.asarray(t.alive))[0])

    t = t._replace(free_top=jnp.int32(0))
    dn = holder(3150)
    t = run(_with_buffer(t, dn, pack([3160, 3170])), dn)
    dn = holder(4050, leaf=False)
    t = run(_with_buffer(t, dn, pack([4100])), dn)
    dn = holder(2550)
    t = t._replace(free_stack=t.free_stack.at[0].set(dn))
    t = run(_with_buffer(t, dn, pack([2560, 2570])), dn)
    return dict(digest=h.hexdigest(), alloc_fail=bool(t.alloc_fail),
                seen=sorted(seen))


# Digests of the branchy Expand pass, one per case: the functions above
# run on it (``python tests/test_expand_rows.py``, map mode with
# JAX_ENABLE_X64=1).
PINNED = {
    "set/eager/scalar":
        "db8ded88891f548c8147d4878a283db6"
        "5fad71438ebcbfaa55ddf84d1a3237d8",
    "set/eager/lockstep":
        "db8ded88891f548c8147d4878a283db6"
        "5fad71438ebcbfaa55ddf84d1a3237d8",
    "set/deferred/scalar":
        "a2fa55bbc1aae6963759c7759fc0c557"
        "c87f8a8de804f294c172431d03337c83",
    "set/deferred/lockstep":
        "a2fa55bbc1aae6963759c7759fc0c557"
        "c87f8a8de804f294c172431d03337c83",
    "set/budgeted:2/scalar":
        "3601fce1c9adb8e5ea17643f8f2a62a8"
        "69fc0b98be121bed265d7391c617cc7c",
    "set/budgeted:2/lockstep":
        "3601fce1c9adb8e5ea17643f8f2a62a8"
        "69fc0b98be121bed265d7391c617cc7c",
    "map/eager/scalar":
        "3d14882e77559a18f934d7a346d6fbf6"
        "3adc10c9dcc4a99f1e52d571befce2a5",
    "map/eager/lockstep":
        "3d14882e77559a18f934d7a346d6fbf6"
        "3adc10c9dcc4a99f1e52d571befce2a5",
    "map/deferred/scalar":
        "5aec9ac3cd893f7fcc7e77f2cd52166a"
        "a2406f870f04f2e23e5533dec7ae6da5",
    "map/deferred/lockstep":
        "5aec9ac3cd893f7fcc7e77f2cd52166a"
        "a2406f870f04f2e23e5533dec7ae6da5",
    "map/budgeted:2/scalar":
        "9647f85c98e4034c363885cd32b8806e"
        "21cb1ae40f3f73bb4bc497cc56ef626c",
    "map/budgeted:2/lockstep":
        "9647f85c98e4034c363885cd32b8806e"
        "21cb1ae40f3f73bb4bc497cc56ef626c",
    "set/cases":
        "628ca66f073bc3a6af1dcf715795e7e2"
        "7595190032fa1d1f4ac630f6ab602612",
    "map/cases":
        "11420cacdfb98fd6948e194f5306a973"
        "7844938a9324642a7d321d94b27f1aa8",
}


def _map_mode(call):
    code = (f"import json, sys, os\n"
            f"os.environ['REPRO_PALLAS_INTERPRET'] = '0'\n"
            f"sys.path.insert(0, {str(ROOT)!r})\n"
            f"from tests import test_expand_rows as T\n"
            f"print('RESULT ' + json.dumps({call}))\n")
    out = run_py(code, x64=True)
    line = next(ln for ln in out.splitlines() if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


@pytest.fixture(scope="module")
def map_traces():
    calls = ", ".join(f"{p!r}: {{e: T.trace_batches(32, {p!r}, e) for e in "
                      f"{ENGINES!r}}}" for p in POLICIES)
    return _map_mode("{" + calls + "}")


def _check_trace(got, key):
    assert got["wrong"] == 0 and got["live_wrong"] == 0, got
    assert got["expands"] > 0 and not got["alloc_fail"], got
    assert got["digest"] == PINNED[key], key


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("policy", POLICIES)
def test_batches_match_pinned_trees_set_mode(policy, engine, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    _check_trace(trace_batches(0, policy, engine), f"set/{policy}/{engine}")


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("policy", POLICIES)
def test_batches_match_pinned_trees_map_mode_x64(map_traces, policy,
                                                  engine):
    _check_trace(map_traces[policy][engine], f"map/{policy}/{engine}")


ALL_SEEN = ["dup", "dup_marked", "expand", "expand_marked", "grow", "kept",
            "moved", "place"]


def test_every_item_outcome_matches_pinned_trees_set_mode():
    got = sweep_cases(0, replay=True)
    assert got["seen"] == ALL_SEEN
    assert got["alloc_fail"]
    assert got["digest"] == PINNED["set/cases"]


def test_every_item_outcome_matches_pinned_trees_map_mode_x64():
    got = _map_mode("T.sweep_cases(32, replay=True)")
    assert got["seen"] == ALL_SEEN
    assert got["alloc_fail"]
    assert got["digest"] == PINNED["map/cases"]


if __name__ == "__main__":
    # print the digests of the tree this runs on, as PINNED holds them
    import sys

    os.environ.setdefault("REPRO_PALLAS_INTERPRET", "0")
    bits = 32 if os.environ.get("JAX_ENABLE_X64") else 0
    mode = "map" if bits else "set"
    res = {f"{mode}/{p}/{e}": trace_batches(bits, p, e)
           for p in POLICIES for e in ENGINES}
    res[f"{mode}/cases"] = sweep_cases(bits)
    json.dump(res, sys.stdout, indent=1)
