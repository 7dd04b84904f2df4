"""Share of the HBM roofline (%) that the forest's walk reaches over all
its chips: the bytes the walk needs, one row of a ΔNode ((2**height - 1)
int64 packed values) per ΔNode visited, the visits summed from the
``hops`` that ``Index.lookup`` returns, over the ``forest_lookup``
modules' device time summed over the chips, each at the chip's peak HBM
bandwidth (``bench/peaks.json``).  ``walk_roofline`` counts the same
bytes for one chip."""

MODULE = "forest_lookup"


def read(view):
    by_device = view.trace.module_ns_by_device(lambda name: MODULE in name)
    if not by_device or sum(by_device) <= 0 or not view.hops:
        return None
    height = int(view.cell.config["index"]["height"])
    need = view.hops * (2 ** height - 1) * 8
    return 100.0 * need / (sum(by_device) * 1e-9
                           * view.peaks["hbm_bytes_per_s"])
