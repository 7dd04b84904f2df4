"""repro.obs — cross-cutting observability (DESIGN.md §9).

One always-on span and counter mechanism, plus the paper's gated
measurements:

- **Spans** (`obs.trace`): ``span(name, **args)`` is a
  ``jax.profiler.TraceAnnotation`` on the profiler's clock, so a
  captured xplane lays the program's host spans beside the device ops;
  it adds a process-wide host total per name (``trace.totals()``) and
  never touches a compiled program.  Every concrete ``Index`` call opens
  ``index.<method>`` with its sequence number ``seq``; ``make_index``
  opens ``index.build`` (children ``index.build.sort``, ``.layout``,
  ``.upload``, and a forest's ``.place``); a forest's fused-view build
  opens ``index.forest.view``; the serve scheduler opens ``serve.*``.
  ``trace.capture(logdir)`` records an xplane of a region.
- **Per-call counters** (`obs.ring`): each concrete ``Index`` call
  writes one row (``seq``, kind, lanes, ``hops_sum``/``hops_max``, a
  scan's ``emitted``/``truncated``, an update's ``MaintenanceStats`` and
  ``free_top``, a sharded point read's ``lanes_dev_max``/``lanes_walked``)
  into an int32 ring on the device, by a small program
  dispatched after the call's own; the host waits on it only when it is
  read: ``obs.calls(seqs)`` (numpy rows, joined to the spans by
  ``seq``) and ``ring.totals()`` (running sums, also in
  ``export.program_totals()``).
- **Counter pytrees** (`obs.stats`): jit/shard_map-safe NamedTuples of
  int32 counters riding the return path — ``MaintenanceStats``,
  ``ScanStats``, ``SearchStats`` (inside ``ReadStats``), ``RouterStats``,
  ``ServeStats``.  ``ReadStats`` is the paper's Table 1 measurement,
  gated by the *static* ``TreeConfig.collect_stats`` flag: the disabled
  path lowers to HLO byte-identical to a build without the stats code
  (asserted by ``tests/test_obs.py``).
- **Measured transfers** (`obs.transfers`): a device-side replay of the
  descent deriving ``TransferStats``, equal on a quiescent tree to the
  analytical `core.baselines.count_block_transfers` exactly, gated by
  ``TreeConfig.collect_transfers`` under ``collect_stats`` (DESIGN.md
  §14).
- **Metrics export** (`obs.export`): stats pytrees and running totals →
  one named snapshot → Prometheus text exposition / JSON
  (``ServeScheduler.metrics()`` is the live producer).
- **Benchmark reports** (`obs.report`): a stdlib-only CLI that renders
  consolidated ``BENCH_*.json`` files as per-suite tables and diffs them::

      python -m repro.obs.report BENCH_NEW.json --diff BENCH_OLD.json
      python -m repro.obs.report BENCH_*.json --history
"""

from repro.obs import export, report, ring, stats, trace, transfers
from repro.obs.stats import (
    MaintenanceStats,
    ReadStats,
    RouterStats,
    ScanStats,
    SearchStats,
    ServeStats,
    TransferStats,
)

calls = ring.calls   # obs.calls(seqs): the ring's rows of those calls

__all__ = [
    "MaintenanceStats",
    "ReadStats",
    "RouterStats",
    "ScanStats",
    "SearchStats",
    "ServeStats",
    "TransferStats",
    "calls",
    "export",
    "report",
    "ring",
    "stats",
    "trace",
    "transfers",
]
