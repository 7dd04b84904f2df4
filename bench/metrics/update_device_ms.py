"""Device time per batch (ms) of the update program
(``update_batch_impl``: the batch's inserts, then eager maintenance)."""

UPDATE = "update_batch"


def read(view):
    ns = view.trace.module_ns(lambda name: UPDATE in name)
    n = len(view.window.results)
    if ns <= 0 or n == 0:
        return None
    return ns * 1e-6 / n
