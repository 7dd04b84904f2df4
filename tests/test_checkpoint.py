"""Checkpoint roundtrips: synchronous save/restore, async save with GC, and
a restore that reshards onto a smaller mesh."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager, latest_step

from _subproc import run_py


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(10, dtype=jnp.float32),
            "b": [jnp.ones((2, 3)), {"c": jnp.asarray(7)}]}
    ck = CheckpointManager(tmp_path, async_save=False)
    ck.save(3, tree, extra={"data_step": 3})
    ck.save(9, tree, extra={"data_step": 9})
    assert latest_step(tmp_path) == 9
    step, tree2, extra = ck.restore(None, tree)
    assert step == 9 and extra["data_step"] == 9
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(tree2)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_async_and_gc(tmp_path):
    tree = {"w": jnp.zeros(4)}
    ck = CheckpointManager(tmp_path, keep_last=2, async_save=True)
    for s in (1, 2, 3, 4):
        ck.save(s, tree)
    ck.wait()
    steps = sorted(p.name for p in tmp_path.glob("step_*"))
    assert len(steps) == 2 and steps[-1] == "step_00000004"


def test_checkpoint_resharding_restore(tmp_path):
    """Save arrays sharded over 4 devices, restore them onto a 2-device
    mesh: the restore reshards to the target shardings."""
    out = run_py(f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager

tree = {{"rows": jnp.arange(64 * 8, dtype=jnp.int32).reshape(64, 8),
         "cols": jnp.arange(32, dtype=jnp.float32)}}
mesh_a = jax.make_mesh((4,), ("x",), devices=jax.devices()[:4])
a = {{"rows": jax.device_put(tree["rows"], NamedSharding(mesh_a, P("x"))),
      "cols": jax.device_put(tree["cols"], NamedSharding(mesh_a, P("x")))}}
ck = CheckpointManager(r'{tmp_path}', async_save=False)
ck.save(1, a)

mesh_b = jax.make_mesh((2,), ("x",), devices=jax.devices()[:2])
sh_b = {{"rows": NamedSharding(mesh_b, P("x")),
         "cols": NamedSharding(mesh_b, P(None))}}
step, b, _ = ck.restore(None, tree, shardings=sh_b)
assert step == 1
for k in tree:
    np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert b[k].sharding == sh_b[k], (k, b[k].sharding)
    assert len(b[k].sharding.device_set) == 2
print("RESHARD OK")
""", devices=8)
    assert "RESHARD OK" in out
