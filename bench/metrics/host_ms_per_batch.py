"""Host time per batch (ms) that the device waits for: the wall time of
the ``bench.batch`` spans (the window's loop steps) less the time the
device was busy inside them, over the window's batches.  Host work that
overlaps device work (batches dispatched ahead) does not count.  Layer:
the api host path (``repro.api.Index``, dispatch, transfer, fetch)."""


def read(view):
    red = view.trace
    n = len(view.window.results)
    if len(red.batches) == 0 or not red.busy or n == 0:
        return None
    host_ns = sum((e - s) - red.busy_ns(s, e) for s, e in red.batches)
    return host_ns * 1e-6 / n
