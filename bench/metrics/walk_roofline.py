"""Share of the HBM roofline (%) that the walk reaches in the point-read
program: the bytes the walk needs, one row of a ΔNode ((2**height - 1)
int64 packed values, map mode's rows) per ΔNode visited, with the visits
summed from the ``hops`` that ``Index.lookup`` returns, over the read
program's device time at the chip's peak HBM bandwidth
(``bench/peaks.json``).  The walk does comparisons and no arithmetic to
speak of, so bandwidth alone bounds it."""

MODULE = "lookup_jit"


def walk_bytes(hops: int, height: int) -> int:
    return hops * (2 ** height - 1) * 8


def read(view):
    ns = view.trace.module_ns(lambda name: MODULE in name)
    if ns <= 0 or not view.hops:
        return None
    need = walk_bytes(view.hops, int(view.cell.config["index"]["height"]))
    return 100.0 * need / (ns * 1e-9 * view.peaks["hbm_bytes_per_s"])
