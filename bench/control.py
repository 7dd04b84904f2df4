"""The control: the plain reference put in the program's place, with one
guarantee of the configuration broken, must read ``correct: false``.

The configurations state that every acknowledged insert is read back by
every later batch.  ``StaleReplica`` breaks that one: it answers reads and
scans from a replica one acknowledged write batch behind — the load
counted as batches of one batch's ops, in record order, and each window
batch's inserts acknowledged from the primary but applied to the replica
only after the next batch.  It is the step that would tempt a later
change: serve reads from a copy that is updated off the critical path.

    python bench/control.py --workload ycsb_e.4m --seeds 11 12 13 --seconds 10

runs the cell's window over the control at the cell's own size, once per
seed, and prints each seed's compared numbers; every seed must fail one.
It needs the cell's chip like ``bench/run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

from reference import SortedMap  # noqa: E402


class StaleReplica:
    """Reads from a replica that lags the acknowledged writes by one batch."""

    def __init__(self, config: dict, keys: np.ndarray, ids: np.ndarray,
                 batch_ops: int):
        keys = np.asarray(keys, np.int32)
        ids = np.asarray(ids, np.int32)
        self.primary = SortedMap(keys, ids)
        self.replica = SortedMap(keys[:-batch_ops], ids[:-batch_ops])
        self.pending = keys[-batch_ops:], ids[-batch_ops:]

    def read(self, keys):
        found, pay = self.replica.lookup(keys)
        return found, pay, np.zeros(keys.size, np.int32)

    def scan(self, starts, width: int):
        return self.replica.scan(starts, np.full(starts.size, width,
                                                 np.int32), width)

    def insert(self, keys, ids):
        res = self.primary.insert(keys, ids)
        self.replica.insert(*self.pending)
        self.pending = keys[res], ids[res]
        return res

    def warm_insert(self, n: int):
        return np.zeros(n, bool)

    def fetch(self, out):
        return out

    def size(self) -> int:
        return len(self.replica)

    def alloc_failed(self) -> bool:
        return False


def factory(batch_ops: int):
    return lambda config, keys, ids: StaleReplica(config, keys, ids,
                                                  batch_ops)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    import harness
    from run import require_chip

    cell = harness.load_cell(args.workload)
    if harness.needs_x64(cell.config):
        os.environ["JAX_ENABLE_X64"] = "1"   # before JAX is imported
    require_chip(cell.chips)
    ops = sum(int(cell.mix.get(k, 0)) for k in ("read", "scan", "insert"))
    for seed in args.seeds:
        res = harness.run(cell, seed, args.seconds, False,
                          time.perf_counter(), system_factory=factory(ops))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "checks": res["checks"],
                          "by_kind": res["mismatches_by_kind"]}), flush=True)


if __name__ == "__main__":
    main()
