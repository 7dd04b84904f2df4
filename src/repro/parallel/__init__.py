"""Logical-axis sharding rules for the serve path's model code (`ax`).

``__all__`` is the single source of truth for this package's surface
(tests/test_exports.py asserts every name imports).  The DeltaForest's
"shards" mesh lives with its router (`repro.distributed.router`).
"""

from repro.parallel.ax import DEFAULT_RULES, constrain, logical_rules, spec_for

__all__ = [
    "DEFAULT_RULES",
    "constrain",
    "logical_rules",
    "spec_for",
]
