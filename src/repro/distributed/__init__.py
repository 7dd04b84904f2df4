"""DeltaForest — key-range-sharded ΔTree subsystem (DESIGN.md §4).

``__all__`` is the single source of truth for this package's surface
(tests/test_exports.py asserts every name imports).  Types and the
``router`` / ``splits`` submodules are stable; the free-function entry
points are *deprecated shims* for the handle-based Index API:

    from repro.api import make_index
    ix = make_index("forest", initial=keys, num_shards=4, height=7)

Accessing a deprecated name still works (it resolves to
``repro.distributed.forest``) but emits ``DeprecationWarning``.  Internal
code imports ``repro.distributed.forest`` directly and never hits the shim.
"""

import warnings

from repro.distributed import router, splits
from repro.distributed.forest import Forest, ForestConfig
from repro.distributed.router import make_forest_mesh

__all__ = [
    "Forest",
    "ForestConfig",
    "alloc_failed",
    "bulk_build",
    "empty",
    "flush",
    "live_items",
    "live_keys",
    "lookup_batch",
    "make_forest_mesh",
    "router",
    "search_batch",
    "shard_tree",
    "splits",
    "successor_jit",
    "update_batch",
]

_DEPRECATED = sorted(set(__all__) - set(globals()))


def __getattr__(name: str):
    if name in _DEPRECATED:
        warnings.warn(
            f"repro.distributed.{name} is deprecated; use the Index API "
            f"(repro.api.make_index('forest', ...)) or import "
            f"repro.distributed.forest.{name} directly",
            DeprecationWarning,
            stacklevel=2,
        )
        from repro.distributed import forest

        return getattr(forest, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
