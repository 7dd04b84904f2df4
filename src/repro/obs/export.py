"""Metrics export: point-in-time snapshots of the stats pytrees.

Every counter pytree in ``repro.obs.stats`` already knows how to render
itself host-side (``asdict``).  This module composes those dicts into
one named snapshot and serializes it two ways:

- ``to_prometheus(snap)``: Prometheus text exposition (version 0.0.4) —
  scalars become gauges, list-valued counters (histogram bins, per-shard
  lanes, round occupancy) become labeled series with an ``index`` label.
- ``to_json(snap)``: the same snapshot as a JSON document (for BENCH
  rows, dashboards that ingest JSON, or plain logging).

``program_totals()`` gives the process's running totals as two more
groups: the per-call counters of every ``Index`` call (``index``,
``repro.obs.ring.totals``) and the host totals of every span
(``spans``, ``repro.obs.trace.totals``).  ``ServeScheduler.metrics()`` is
the live producer: it snapshots the decode loop's ``ServeStats``, the
maintenance worker's drain counters, the pager's host-side op counters
and those totals each call.  Rendering a snapshot traces nothing; it
reads the device only where its groups do: the ``asdict`` host reads of
the stats classes, and ``program_totals``, which waits for the ``Index``
calls recorded so far.
"""

from __future__ import annotations

import json
import numbers

import numpy as np

__all__ = ["program_totals", "snapshot", "to_prometheus", "to_json"]


def _plain(v):
    """Coerce one metric value to a JSON/Prometheus-safe plain type."""
    if isinstance(v, (bool, np.bool_)):
        return int(v)
    if isinstance(v, numbers.Number):
        return v.item() if hasattr(v, "item") else v
    if isinstance(v, (list, tuple, np.ndarray)):
        return [_plain(x) for x in np.asarray(v).tolist()]
    if hasattr(v, "item"):  # 0-d jax array
        return v.item()
    return v


def program_totals() -> dict:
    """The running totals as ``snapshot`` groups (None while empty):
    ``index`` (per-call counters by kind) and ``spans`` (``<span>_count``,
    ``_total_s``, ``_max_s``; dots in span names become underscores) —
    ``snapshot(**program_totals(), serve=...)``."""
    from repro.obs import ring, trace

    spans = {}
    for name, row in sorted(trace.totals().items()):
        for k, v in row.items():
            spans[f"{name.replace('.', '_')}_{k}"] = v
    return {"index": ring.totals() or None, "spans": spans or None}


def snapshot(**groups) -> dict:
    """Compose named stats into one plain-python snapshot.

    Each keyword is a group name mapping to a stats pytree (anything
    with ``asdict``), a plain dict of numbers, or ``None`` (dropped) —
    e.g. ``snapshot(search=rs.search, transfers=rs.transfers,
    serve=sched.obs, maintenance=worker.stats())``.
    """
    out = {}
    for name, obj in groups.items():
        if obj is None:
            continue
        d = obj.asdict() if hasattr(obj, "asdict") else dict(obj)
        out[name] = {k: _plain(v) for k, v in d.items()}
    return out


def to_prometheus(snap: dict, prefix: str = "repro") -> str:
    """Render a ``snapshot`` as Prometheus text exposition."""
    lines = []
    for group in sorted(snap):
        for key in snap[group]:
            v = snap[group][key]
            name = f"{prefix}_{group}_{key}"
            lines.append(f"# TYPE {name} gauge")
            if isinstance(v, list):
                lines.extend(
                    f'{name}{{index="{i}"}} {_num(x)}'
                    for i, x in enumerate(v))
            else:
                lines.append(f"{name} {_num(v)}")
    return "\n".join(lines) + "\n" if lines else ""


def _num(v) -> str:
    if isinstance(v, bool):
        return str(int(v))
    if isinstance(v, float):
        return repr(v)
    if isinstance(v, numbers.Number):
        return str(v)
    raise TypeError(f"non-numeric metric value {v!r}")


def to_json(snap: dict, **dump_kw) -> str:
    dump_kw.setdefault("sort_keys", True)
    return json.dumps(snap, **dump_kw)
