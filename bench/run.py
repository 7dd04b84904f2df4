"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload ycsb_c.4m --seed 7 --seconds 30 --trace 0

``--trace 0`` measures the cell's end-to-end metrics over a window of
``--seconds``; ``--trace 1`` records a profiler trace of a shorter window
and reports the per-layer metrics instead.  Either way every answer of the
window is checked against the plain reference (``bench/reference.py``),
and the last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ..., ``checks``).

The run refuses, with a non-zero exit and no result, where JAX finds no
TPU, fewer chips than the cell asks for, or Pallas would run in interpret
mode.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))


def require_chip(chips: int) -> None:
    import jax
    from repro.kernels import ops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    if ops.default_interpret():
        sys.exit("bench: Pallas would run in interpret mode "
                 "(REPRO_PALLAS_INTERPRET); unset it")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import harness

    cell = harness.load_cell(args.workload)
    if harness.needs_x64(cell.config):
        os.environ["JAX_ENABLE_X64"] = "1"   # before JAX is imported
    require_chip(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # cache every program, the small eager ones included, so that a warm
    # run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.log(f"bench: {args.workload} seed {args.seed}, compile cache "
                f"{cache}, pid {os.getpid()}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_PROCESS)
    harness.report(result)


if __name__ == "__main__":
    main()
