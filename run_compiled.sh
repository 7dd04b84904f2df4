#!/usr/bin/env bash
# Compiled-mode launch harness: run any repo Python entry point with the
# kernels in compiled mode (REPRO_PALLAS_INTERPRET=0) and the process
# environment tuned for steady benchmark numbers.
#
#   ./run_compiled.sh benchmarks/run.py --compiled --only engines
#   ./run_compiled.sh benchmarks/autotune_qtile.py --heights 5,7,9
#   REPRO_DEVICES=8 ./run_compiled.sh benchmarks/run.py --smoke --compiled
#
# What it pins, and why (see DESIGN.md "Compiled performance"):
#   * REPRO_PALLAS_INTERPRET=0 — Pallas lowers for real on TPU; on CPU the
#     walk routes through the XLA-compiled fused mirror instead of the
#     Pallas interpreter (no interpreter tax either way).
#   * tcmalloc LD_PRELOAD when present — XLA's host allocator churn is a
#     real fraction of small-batch walk time; tcmalloc flattens it.
#   * TF_CPP_MIN_LOG_LEVEL=4 — keeps XLA/TSL chatter off the timed stdout
#     (benchmark rows are parsed off stdout line by line).
#   * XLA_FLAGS --xla_force_host_platform_device_count=$REPRO_DEVICES —
#     opt-in fake-device mesh for sharded (forest) runs on one host.
#   * JAX_ENABLE_X64 passes through untouched: benchmarks/run.py turns
#     x64 on for its own process, which runs every suite.
set -euo pipefail

cd "$(dirname "$0")"

if [[ $# -eq 0 ]]; then
    echo "usage: $0 <script.py> [args...]   (e.g. benchmarks/run.py --compiled)" >&2
    exit 2
fi

export REPRO_PALLAS_INTERPRET=0
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"
export PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}"

# Fake host devices for sharded runs: only when asked — a forced device
# count changes single-arena numbers too (XLA partitions its thread pool).
if [[ -n "${REPRO_DEVICES:-}" ]]; then
    export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=${REPRO_DEVICES}"
fi

# tcmalloc, when the container has it (no install here — probe only).
if [[ -z "${LD_PRELOAD:-}" ]]; then
    for so in /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
              /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
              /usr/lib/libtcmalloc_minimal.so.4; do
        if [[ -e "$so" ]]; then
            export LD_PRELOAD="$so"
            break
        fi
    done
fi

exec python "$@"
