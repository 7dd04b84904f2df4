"""What one read dispatch runs, read from the program itself.

- Kernels per read: under the lockstep engine each of the five reads of
  both backends dispatches a fixed number of walk or scan kernels — one,
  and two for ``successor``, whose chase walks again from the first
  walk's candidate.  They are counted as the ``pallas_call`` equations of
  the read's traced program in interpret mode, named by the kernel
  function each one runs.
- The benchmark cells' programs: under compiled mode, ``make_index`` with
  the ``index`` keys of each configuration under ``bench/configs``
  resolves ``engine="auto"`` to ``lockstep`` and its walk's query tile to
  512.
- The query-tile table ``ops.default_q_tile`` reads.

Map mode (``lookup``) packs int64 rows, so the programs are traced in
x64 subprocesses.
"""

import json
from pathlib import Path

import pytest

from _subproc import run_py

REPO = Path(__file__).resolve().parent.parent

READS = ("search", "lookup", "successor", "range_scan", "successor_k")

# kernel function -> count, per read, for both backends
KERNELS = {
    "search": {"_fused_kernel": 1},
    "lookup": {"_fused_kernel": 1},
    "successor": {"_fused_kernel": 2},
    "range_scan": {"_scan_kernel": 1},
    "successor_k": {"_scan_kernel": 1},
}

_COUNT = """
import json
from collections import Counter
import numpy as np, jax, jax.numpy as jnp
from jax.extend import core as jcore
from repro.api import make_index

def kernels(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            src = eqn.params["jaxpr"].debug_info.func_src_info
            out[src.split()[0]] += 1
            continue
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(x, jcore.ClosedJaxpr):
                    kernels(x.jaxpr, out)
                elif isinstance(x, jcore.Jaxpr):
                    kernels(x, out)
    return out

rng = np.random.default_rng(0)
keys = np.unique(rng.integers(1, 5000, 300).astype(np.int32))
q = jnp.asarray(rng.integers(1, 5000, 64).astype(np.int32))
reads = {
    "search": lambda ix: ix.search(q),
    "lookup": lambda ix: ix.lookup(q),
    "successor": lambda ix: ix.successor(q),
    # range_scan's device dispatch: one page of one range
    "range_scan": lambda ix: ix.scan(q[:1], q[:1] + 100, 16),
    "successor_k": lambda ix: ix.successor_k(q, 4),
}
out = {}
for backend, kw in (("deltatree", dict(max_dnodes=256)),
                    ("forest", dict(num_shards=2, max_dnodes=128))):
    ix = make_index(backend, initial=keys, payloads=keys % 1000,
                    engine="lockstep", height=4, payload_bits=16, **kw)
    out[backend] = {name: dict(kernels(jax.make_jaxpr(fn)(ix).jaxpr,
                                       Counter()))
                    for name, fn in reads.items()}
print("COUNTS", json.dumps(out))
"""


@pytest.fixture(scope="module")
def kernel_counts():
    out = run_py(_COUNT, x64=True)
    line = next(ln for ln in out.splitlines() if ln.startswith("COUNTS "))
    return json.loads(line[len("COUNTS "):])


@pytest.mark.parametrize("read", READS)
@pytest.mark.parametrize("backend", ["deltatree", "forest"])
def test_kernels_per_read_dispatch(kernel_counts, backend, read):
    assert kernel_counts[backend][read] == KERNELS[read]


@pytest.mark.parametrize("config", ["ycsb-4m", "ycsb-4m-forest4"])
def test_bench_config_resolves_engine_and_q_tile(config):
    """The configuration file is read as it stands; a few hundred keys
    stand in for its records (the arena is the file's size)."""
    path = REPO / "bench" / "configs" / f"{config}.json"
    out = run_py(f"""
import json, os
os.environ["REPRO_PALLAS_INTERPRET"] = "0"
import numpy as np, jax.numpy as jnp
from repro.api import make_index
from repro.api.index import cfg_attr
from repro.kernels import ops

index = dict(json.load(open({str(path)!r}))["index"])
assert index["engine"] == "auto", index
keys = np.arange(1, 2000, 3, dtype=np.int32)
ix = make_index(index.pop("backend"), initial=keys, payloads=keys, **index)
arena = ix.state.trees if ix.capability.sharded else ix.state
tile = ops._resolve_q_tile(cfg_attr(ix.cfg, "q_tile") or None,
                           cfg_attr(ix.cfg, "height"),
                           0 if arena.value.dtype == jnp.int32 else 1)
print("RESOLVED", ix.engine, tile)
""", x64=True)
    assert "RESOLVED lockstep 512" in out


def test_default_q_tile_table(monkeypatch):
    """Each table entry under its execution mode; 256 off the table."""
    from repro.kernels import ops

    monkeypatch.delenv("REPRO_PALLAS_QTILE", raising=False)
    for compiled in (False, True):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0" if compiled else "1")
        for (height, comp, bits), tile in ops.Q_TILES.items():
            want = tile if comp == compiled else 256
            assert ops.default_q_tile(height, bits == 64) == want
        assert ops.default_q_tile(6) == 256
        assert ops.default_q_tile() == 256
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.default_q_tile(7, payload_bits=32) == 512
    assert ops.default_q_tile(7) == 256
