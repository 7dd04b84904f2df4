"""Device time per round of the scan's lockstep loop (us): the device
time of the ``successor_k_jit`` modules over Σ ``hops_max`` of the
window's ``successor_k`` calls (``repro.obs.calls``), a call's
``hops_max`` being its loop's round count."""

import program_trace as PT

MODULE = "successor_k_jit"


def read(view):
    rows = PT.window_rows(view, "index.successor_k")
    ns = view.trace.module_ns(lambda name: MODULE in name)
    if rows is None or ns <= 0 or rows["hops_max"].sum() <= 0:
        return None
    return ns * 1e-3 / float(rows["hops_max"].sum())
