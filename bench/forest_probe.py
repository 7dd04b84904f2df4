"""Run a key-range-sharded forest through the harness, as a cell that
``BENCHMARK.json`` does not name yet would run.

    python bench/forest_probe.py --records 16000000 --max-dnodes 151024 \\
        --seed 7 --seconds 20

For each base cell (``--cells``, default YCSB C and E on ``ycsb-4m``) it
takes the cell's configuration and mix, makes the index a ``forest`` of
4 shards (``max_dnodes`` per shard) on one chip per shard, or on every
chip where the machine has fewer, and drives ``harness.run`` once: build, warm-up, window, and the
check of every answer against the plain reference.  With ``--fault`` it
runs each cell again with that fault of ``bench/faults.py`` planted.  One
JSON line per run: ``correct``, the compared numbers, the
implementations, the device's memory by chip, the set-up split and the
check's seconds.

``--tiny`` shrinks each cell as ``bench/rehearse.py`` does and runs on
whatever JAX finds; give the CPU 4 devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  Without it the
run needs a TPU, as ``bench/run.py`` does.  As in
the rehearsal, JAX runs with x64 and Pallas on its compiled path
(importing ``rehearse`` sets both).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]

import faults  # noqa: E402
import harness  # noqa: E402
import rehearse  # noqa: E402

SHARDS = 4      # one per chip of a 4-chip host


def forest_cell(cell: harness.Cell, shards: int, chips: int,
                records: int | None, max_dnodes: int | None) -> harness.Cell:
    """``cell`` with its index made a forest of ``shards`` shards on
    ``chips`` chips."""
    index = dict(cell.config["index"], backend="forest", num_shards=shards)
    if max_dnodes is not None:
        index["max_dnodes"] = max_dnodes
    config = dict(cell.config, index=index)
    if records is not None:
        config["recordcount"] = records
    return dataclasses.replace(cell, name=f"{cell.name}.forest{shards}",
                               chips=chips, config=config)


def probe(cell: harness.Cell, seed: int, seconds: float,
          fault: str | None, t_process: float) -> dict:
    factory = harness.IndexSystem
    if fault is not None:
        def factory(config, keys, ids):
            return faults.FAULTS[fault](harness.IndexSystem(config, keys, ids))
    res = harness.run(cell, seed, seconds, False, t_process,
                      system_factory=factory)
    keys = ("correct", "attempted", "failed", "batches", "metrics", "device",
            "implementations", "setup_split", "check_s",
            "mismatches_by_kind", "checks")
    return {"workload": cell.name, "fault": fault} | {k: res[k] for k in keys}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", nargs="+", default=["ycsb_c.4m", "ycsb_e.4m"])
    ap.add_argument("--records", type=int)
    ap.add_argument("--max-dnodes", type=int)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax

    if not args.tiny:
        from repro.launch.compile_cache import enable_compile_cache
        from run import require_chip

        require_chip(1)
        enable_compile_cache()
        # cache every program, so that a warm run compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    chips = min(SHARDS, jax.device_count())
    cells = []
    for name in args.cells:
        cell = harness.load_cell(name)
        if args.tiny:
            cell = rehearse.tiny(cell)
        cells.append(forest_cell(cell, SHARDS, chips, args.records,
                                 args.max_dnodes))
    t_process = T_PROCESS
    for cell in cells:
        for fault in (None, args.fault) if args.fault else (None,):
            print(json.dumps(probe(cell, args.seed, args.seconds, fault,
                                   t_process)), flush=True)
            # the next run's set-up starts here: JAX is up already
            t_process = time.perf_counter()


if __name__ == "__main__":
    main()
