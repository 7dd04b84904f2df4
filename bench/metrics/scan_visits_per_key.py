"""ΔNode visits per key the scan emits: Σ ``hops_sum`` over Σ ``emitted``
of the window's ``successor_k`` calls, from the program's per-call
counters (``repro.obs.calls``).  A FIND walk and a VERIFY walk from the
root per emitted key put it near twice the tree's depth in ΔNodes."""

import program_trace as PT


def read(view):
    rows = PT.window_rows(view, "index.successor_k")
    if rows is None or rows["emitted"].sum() <= 0:
        return None
    return float(rows["hops_sum"].sum()) / float(rows["emitted"].sum())
