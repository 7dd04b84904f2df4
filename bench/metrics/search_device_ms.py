"""Device time per batch (ms) of the point-read program (``lookup_jit``,
the read engine's lockstep walk), summed over its XLA module
executions."""

MODULE = "lookup_jit"


def read(view):
    ns = view.trace.module_ns(lambda name: MODULE in name)
    n = len(view.window.results)
    if ns <= 0 or n == 0:
        return None
    return ns * 1e-6 / n
