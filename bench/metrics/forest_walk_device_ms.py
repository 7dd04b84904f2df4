"""Device time per batch (ms) of the forest's read program on its slowest
chip: the ``forest_lookup`` modules' device time on each chip
(``Reduced.module_ns_by_device``), the largest, over the window's
batches.  Every chip runs the same program over its own shard's lanes,
and a batch waits for the slowest."""

MODULE = "forest_lookup"


def read(view):
    by_device = view.trace.module_ns_by_device(lambda name: MODULE in name)
    n = len(view.window.results)
    if not by_device or max(by_device) <= 0 or n == 0:
        return None
    return max(by_device) * 1e-6 / n
