"""A map-mode forest of 4 shards on 4 devices, built from host memory.

One subprocess with 4 host devices and x64 (map mode) builds the forest
through ``make_index("forest", ...)`` and reports what the tests check:

- every arena leaf is sharded ``P("shards")``, one ``(1, ...)`` shard per
  device, placed under the ``index.build.place`` span;
- lookups of present and absent keys equal ``MapOracle.snapshot_lookup``
  before and after a batch of inserts, and ``successor_k`` equals the
  next k of ``MapOracle.items()``;
- the ring rows of forest lookups count the router: ``lanes_walked`` is
  4 x K (the fused frontier's (D, K) lanes) and ``lanes_dev_max`` the
  largest per-device count of the keys' owner shards, computed here with
  numpy; every other row holds 0 in both;
- ``deltatree.bulk_build`` and ``empty``, now a host layout and an
  upload, give every field bit for bit as the single upload before the
  split did (digests pinned from it), in set and map mode.
"""

from __future__ import annotations

import json

import pytest

from tests._subproc import run_py

K = 512

_SCRIPT = """
import hashlib, json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.api import OP_INSERT, OpBatch, make_index
from repro.core import deltatree as DT
from repro.core.deltatree import TreeConfig
from repro.core.oracle import MapOracle
from repro.obs import calls, ring, trace

K = %(K)d
out = {}
rng = np.random.default_rng(16)
keys = rng.choice(np.arange(1, 1 << 24), 3000, replace=False).astype(np.int64)
ids = rng.integers(0, 1 << 31, keys.size)
before = trace.totals()
ix = make_index("forest", initial=keys, payloads=ids, num_shards=4,
                height=4, max_dnodes=512, payload_bits=32, engine="lockstep")
after = trace.totals()
out["spans"] = {n: after[n]["count"] - before.get(n, {"count": 0})["count"]
                for n in ("index.build", "index.build.place",
                          "index.build.layout", "index.build.upload")
                if n in after}
out["placement"] = [
    {"named": isinstance(x.sharding, NamedSharding),
     "spec": str(x.sharding.spec),
     "devices": sorted(s.device.id for s in x.addressable_shards),
     "lead": sorted({s.data.shape[0] for s in x.addressable_shards}),
     "rest": sorted({s.data.shape[1:] == x.shape[1:]
                     for s in x.addressable_shards})}
    for x in jax.tree.leaves(ix.state.trees)]
splits = np.asarray(ix.state.splits)
oracle = MapOracle(zip(keys.tolist(), ids.tolist()))


def probes():
    live = np.asarray([k for k, _ in oracle.items()], np.int64)
    q = np.concatenate([rng.choice(live, K // 2), rng.choice(live, K // 2) + 1])
    return q.astype(np.int32)


def check_lookups(tag):
    q = probes()
    views = trace.totals().get("index.forest.view", {"count": 0})["count"]
    found, pay, _ = ix.lookup(jnp.asarray(q))
    out[tag + "_views"] = trace.totals()["index.forest.view"]["count"] - views
    (row,) = calls([ring.last_seq()])
    exp_f, exp_p = oracle.snapshot_lookup(q)
    found, pay = np.asarray(found), np.asarray(pay)
    out[tag] = {"found_wrong": int((found != exp_f).sum()),
                "payload_wrong": int((found & (pay != exp_p)).sum()),
                "present": int(exp_f.sum()), "absent": int((~exp_f).sum())}
    dev_counts = np.bincount(np.searchsorted(splits, q, "right"),
                             minlength=4)
    out.setdefault("lookup_rows", []).append(
        {"walked": int(row["lanes_walked"]),
         "dev_max": int(row["lanes_dev_max"]),
         "want_dev_max": int(dev_counts.max()), "lanes": int(row["lanes"])})


check_lookups("before")
others = []
new = np.setdiff1d(rng.integers(1, 1 << 24, 400), keys)[:256].astype(np.int32)
new_ids = rng.integers(0, 1 << 31, new.size).astype(np.int32)
ix, res = ix.insert_delete(OpBatch.inserts(jnp.asarray(new),
                                           jnp.asarray(new_ids)))
others.append(("forest update", ring.last_seq()))
exp = oracle.apply_updates(np.full(new.size, OP_INSERT), new, new_ids)
out["insert_wrong"] = int((np.asarray(res) != exp).sum())
check_lookups("after")

starts = rng.integers(0, 1 << 24, 64).astype(np.int32)
sk, sp, sn, _, _ = ix.successor_k(jnp.asarray(starts), 8)
others.append(("forest successor_k", ring.last_seq()))
items = oracle.items()
live = np.asarray([k for k, _ in items], np.int64)
pays = np.asarray([p for _, p in items], np.int64)
bad = 0
for i, s in enumerate(starts):
    j = np.searchsorted(live, s, "right")
    wk, wp = live[j:j + 8], pays[j:j + 8]
    n = int(sn[i])
    bad += int(n != wk.size or (np.asarray(sk[i][:n]) != wk).any()
               or (np.asarray(sp[i][:n]) != wp).any())
out["successor_k_wrong"] = bad

dt = make_index("deltatree", initial=keys, payloads=ids, height=4,
                max_dnodes=2048, payload_bits=32, engine="lockstep")
q = jnp.asarray(probes())
dt.lookup(q)
others.append(("deltatree lookup", ring.last_seq()))
dt.search(q)
others.append(("deltatree search", ring.last_seq()))
dt.successor_k(q[:64], 4)
others.append(("deltatree successor_k", ring.last_seq()))
dt, _ = dt.insert_delete(OpBatch.inserts(jnp.asarray(new), jnp.asarray(new_ids)))
others.append(("deltatree update", ring.last_seq()))
rows = calls([s for _, s in others])
out["other_rows"] = {n: [int(r["lanes_dev_max"]), int(r["lanes_walked"])]
                     for (n, _), r in zip(others, rows)}


def digest(t):
    h = hashlib.sha256()
    for name, x in zip(t._fields, t):
        a = np.asarray(x)
        h.update(f"{name}:{a.dtype.str}:{a.shape}:".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


r2 = np.random.default_rng(20261019)
bkeys = r2.choice(np.arange(1, 1 << 20), 3000, replace=False).astype(np.int64)
bpays = r2.integers(0, 1 << 31, 3000)
out["digests"] = {}
for bits in (0, 32):
    cfg = TreeConfig(height=4, max_dnodes=2048, payload_bits=bits)
    t = DT.bulk_build(cfg, bkeys, bpays if bits else None)
    e = DT.empty(cfg)
    out["digests"][str(bits)] = {
        "bulk_build": digest(t), "empty": digest(e),
        "weak": any(x.weak_type for x in list(t) + list(e)),
        "devices": sorted({d.id for x in list(t) + list(e)
                           for d in x.devices()})}
print("RESULT " + json.dumps(out))
"""

# every field of bulk_build / empty before the build was split into a
# host layout and an upload (seeded keys of the script, height 4, 2,048
# ΔNodes), per payload_bits
PINNED = {
    "0": {"bulk_build": "c01ad11e9aae7342d148128556278364"
                        "007018120636b11120367399168685f6",
          "empty": "687cd1e288799fa3731eba79b7d09375"
                   "c54a27647b4cbed26beae07e6296b603"},
    "32": {"bulk_build": "a17af82a11cc5643f2f53ce51790925a"
                         "f4c8e0a20a79c8df03d8d724b5e0cfd8",
           "empty": "06baa68de75cf431ac90b1bc36bd10ee"
                    "90d0a52abfe96ba5bfff9f98b9a70ee7"},
}


@pytest.fixture(scope="module")
def forest_facts():
    stdout = run_py(_SCRIPT % {"K": K}, devices=4, x64=True)
    line = next(ln for ln in stdout.splitlines() if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_forest_shards_are_placed_one_per_device(forest_facts):
    for leaf in forest_facts["placement"]:
        assert leaf["named"] and leaf["spec"] == "PartitionSpec('shards',)"
        assert leaf["devices"] == [0, 1, 2, 3], leaf
        assert leaf["lead"] == [1] and leaf["rest"] == [True], leaf
    spans = forest_facts["spans"]
    assert spans["index.build"] == 1 and spans["index.build.place"] == 1
    # each shard laid out on the host; nothing uploaded shard by shard
    assert spans["index.build.layout"] == 4
    assert "index.build.upload" not in spans


@pytest.mark.parametrize("when", ["before", "after"])
def test_forest_lookups_match_the_oracle(forest_facts, when):
    got = forest_facts[when]
    assert got["found_wrong"] == 0 and got["payload_wrong"] == 0, got
    assert got["present"] > 0 and got["absent"] > 0, got
    if when == "after":
        assert forest_facts["insert_wrong"] == 0
    # the fused view is built once per arena: again after the inserts
    assert forest_facts[f"{when}_views"] == 1


def test_forest_successor_k_matches_the_oracle(forest_facts):
    assert forest_facts["successor_k_wrong"] == 0


def test_ring_rows_of_forest_lookups_count_the_router(forest_facts):
    rows = forest_facts["lookup_rows"]
    assert len(rows) == 2
    for r in rows:
        assert r["lanes"] == K
        assert r["walked"] == 4 * K
        assert r["dev_max"] == r["want_dev_max"]
        assert K // 4 <= r["dev_max"] < K


def test_other_calls_write_zero_router_columns(forest_facts):
    rows = forest_facts["other_rows"]
    assert set(rows) == {"forest update", "forest successor_k",
                         "deltatree lookup", "deltatree search",
                         "deltatree successor_k", "deltatree update"}
    for name, cols in rows.items():
        assert cols == [0, 0], name


@pytest.mark.parametrize("bits", ["0", "32"])
def test_bulk_build_is_bit_identical_through_the_host_layout(forest_facts,
                                                             bits):
    got = forest_facts["digests"][bits]
    assert got["bulk_build"] == PINNED[bits]["bulk_build"]
    assert got["empty"] == PINNED[bits]["empty"]
    assert not got["weak"]
    assert got["devices"] == [0]
