"""Logical activation-axis sharding (MaxText-style logical rules).

Models annotate activations with *logical* axis names; a thread-local rule
set (installed with `logical_rules` under a mesh) maps them to mesh axes.
Outside a rules context every annotation is a no-op, so model code runs
unchanged on a single CPU device.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_state = threading.local()

# default logical-name -> mesh-axes mapping used by the production mesh
DEFAULT_RULES: dict[str, tuple[str, ...] | str | None] = {
    "batch": ("pod", "data"),     # pod axis collapses onto data when absent
    "seq": None,
    "decode_seq": "model",        # sharded KV cache length (split-K decode)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "expert_cap": ("pod", "data"),
    "ssm_inner": "model",
    "state": None,
}


@contextlib.contextmanager
def logical_rules(mesh, rules: dict | None = None):
    """Activate logical-axis constraint rules for `constrain` calls."""
    _state.mesh = mesh
    _state.rules = dict(DEFAULT_RULES if rules is None else rules)
    try:
        yield
    finally:
        _state.mesh = None
        _state.rules = None


def spec_for(*names: str | None) -> P:
    """Translate logical names to a PartitionSpec under the active rules."""
    rules = getattr(_state, "rules", None)
    mesh = getattr(_state, "mesh", None)
    parts = []
    for n in names:
        axes = rules.get(n) if (rules and n) else None
        if axes is None:
            parts.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        present = tuple(a for a in axes if mesh is not None and a in mesh.axis_names)
        parts.append(present if len(present) > 1 else (present[0] if present else None))
    return P(*parts)


def constrain(x, *names: str | None):
    """with_sharding_constraint using logical names; no-op without rules."""
    mesh = getattr(_state, "mesh", None)
    if mesh is None:
        return x
    assert len(names) == x.ndim, (names, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for(*names))
    )
