"""Device time per batch (ms) of the scan path: the ``successor_k``
program and the eager ops that unpack its rows.  In a cell whose batches
scan and insert, that is every XLA module but the update program (the
insert batch is built from host arrays and adds no device op)."""

UPDATE = "update_batch"


def read(view):
    red = view.trace
    n = len(view.window.results)
    if not view.traffic.n_scan or n == 0:
        return None
    ns = red.module_ns(lambda name: UPDATE not in name)
    return ns * 1e-6 / n if ns > 0 else None
