"""CPU rehearsal of every cell's code path at a tiny size.

    JAX_PLATFORMS=cpu python bench/rehearse.py [--seed N] [--seconds S]

For each cell of ``BENCHMARK.json`` it shrinks the configuration (20,000
records) and the mix (an eighth of each op count), then drives the run
exactly as ``bench/run.py`` does — build through ``make_index``, warm-up,
closed-loop window, check of every answer — without the look for a chip.
Pallas is sent to its compiled path (``REPRO_PALLAS_INTERPRET=0``), which
off the TPU runs the XLA mirrors, so ``engine="auto"`` resolves to the
lockstep engine as on the chip.  JAX runs with x64 on, as map mode needs
on the chip too.  Where a cell asks for 4 chips, the CPU gets 4 host
devices (``--xla_force_host_platform_device_count``, unless ``XLA_FLAGS``
already sets a count), so that such a cell rehearses on as many devices
as it runs on.  Then it plants one altered answer under
each cell's window and shows that the run reads ``correct: false``.

It prints each run's ``correct`` and compared numbers, and no device
metric: a CPU run says nothing about the chip's speed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("REPRO_PALLAS_INTERPRET", "0")
os.environ.setdefault("JAX_ENABLE_X64", "1")   # map mode, before JAX
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

import faults  # noqa: E402
import harness  # noqa: E402

TINY_RECORDS = 20_000
TINY_DNODES = 2048
SHRINK = 8
HOST_DEVICES = "--xla_force_host_platform_device_count"


def with_host_devices(xla_flags: str, spec: dict) -> str:
    """``XLA_FLAGS`` giving the CPU as many devices as the most chips a
    cell of ``spec`` asks for; as it was where it already sets a count or
    every cell takes one chip."""
    chips = max(int(w["chips"]) for w in spec["workloads"])
    if chips == 1 or HOST_DEVICES in xla_flags:
        return xla_flags
    return f"{xla_flags} {HOST_DEVICES}={chips}".strip()


def tiny(cell: harness.Cell) -> harness.Cell:
    """The cell at rehearsal size: same shapes of traffic, fewer of each."""
    config = dict(cell.config, recordcount=TINY_RECORDS,
                  index=dict(cell.config["index"], max_dnodes=TINY_DNODES))
    mix = dict(cell.mix, pool_batches=4)
    for k in ("read", "scan", "insert"):
        if mix.get(k):
            mix[k] = max(1, int(mix[k]) // SHRINK)
    if mix.get("insert"):
        mix["insert_batches"] = 256
    return dataclasses.replace(cell, config=config, mix=mix)


def rehearse(cell: harness.Cell, seed: int, seconds: float,
             fault=None) -> dict:
    factory = harness.IndexSystem
    if fault is not None:
        def factory(config, keys, ids):
            return fault(harness.IndexSystem(config, keys, ids))
    res = harness.run(cell, seed, seconds, False, time.perf_counter(),
                      system_factory=factory)
    return {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "implementations": res["implementations"],
            "compiles_in_window": res["compiles_in_window"],
            "checks": {k: v["value"] for k, v in res["checks"].items()},
            "mismatches_by_kind": res["mismatches_by_kind"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2**31 + 5)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    flags = with_host_devices(os.environ.get("XLA_FLAGS", ""), spec)
    if flags:
        os.environ["XLA_FLAGS"] = flags    # before JAX starts its backend
    ok = True
    for w in spec["workloads"]:
        cell = tiny(harness.load_cell(w["name"]))
        clean = rehearse(cell, args.seed, args.seconds)
        bad = rehearse(cell, args.seed, args.seconds, faults.AnswerAltered)
        print(json.dumps({"workload": w["name"], "clean": clean,
                          "answer_altered": bad}), flush=True)
        ok &= clean["correct"] and not bad["correct"]
    print(json.dumps({"rehearsal_ok": ok}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
