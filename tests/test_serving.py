"""Serving engine + ΔTree pager (subprocess: needs JAX_ENABLE_X64)."""

from tests._subproc import run_py


def test_pager_map_semantics():
    out = run_py("""
import numpy as np
from repro.serving.pager import DeltaPager, PagerConfig

pc = PagerConfig(num_pages=128, page_size=4, max_seqs=32, max_blocks=64,
                 tree_height=4)
pg = DeltaPager(pc)
p0 = pg.allocate(0, 3)
p1 = pg.allocate(1, 2)
assert len(set(p0) | set(p1)) == 5
bt = pg.block_tables([0, 1], 4)
assert (bt[0, :3] == p0).all() and bt[0, 3] == -1
assert (bt[1, :2] == p1).all() and (bt[1, 2:] == -1).all()
# grow seq 0
p0b = pg.allocate(0, 2)
bt = pg.block_tables([0], 5)
assert (bt[0] == p0 + p0b).all()
pg.free_seq(0)
assert len(pg.free_pages) == 128 - 2
bt = pg.block_tables([0, 1], 4)
assert (bt[0] == -1).all()
pg.free_seq(1)
assert sorted(pg.free_pages) == list(range(128))
print("PAGER OK", pg.stats)
""", x64=True)
    assert "PAGER OK" in out


def test_engine_matches_dense_decode():
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.models.registry import api
from repro.serving import ServeEngine, PagerConfig

cfg = get_smoke_config("granite_8b")
m = api(cfg)
params = m.init_params(jax.random.PRNGKey(0))
rng = np.random.default_rng(7)
pc = PagerConfig(num_pages=64, page_size=4, max_seqs=16, max_blocks=64,
                 tree_height=4)
eng = ServeEngine(cfg, params, pc, max_batch=4)
prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
           for n in (5, 9, 3)]
sids = [eng.submit(p, max_new=6) for p in prompts]
for _ in range(8):
    eng.step()
for p, sid in zip(prompts, sids):
    caches = m.init_caches(1, 64)
    logits, caches = m.prefill(params, jnp.asarray(p)[None], caches)
    toks = [int(jnp.argmax(logits[0, -1]))]
    ln = len(p)
    for _ in range(5):
        lg, caches = m.decode_step(params,
            jnp.asarray([[toks[-1]]], jnp.int32), caches,
            jnp.asarray([ln], jnp.int32))
        toks.append(int(jnp.argmax(lg[0, 0])))
        ln += 1
    assert eng.active[sid].out == toks, (sid, eng.active[sid].out, toks)
assert len(eng.pager.free_pages) == pc.num_pages  # all pages reclaimed
assert eng.pager.stats["searches"] > 0
print("ENGINE OK")
""", x64=True, timeout=1200)
    assert "ENGINE OK" in out


