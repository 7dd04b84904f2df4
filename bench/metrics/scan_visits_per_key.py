"""ΔNode visits per key the scan emits: Σ ``hops_sum`` over Σ ``emitted``
of the window's ``successor_k`` calls, from the program's per-call
counters (``repro.obs.calls``).  A visit is one ΔNode row read by one
lane.  A scan engine that descends once per lane and emits a leaf
ΔNode's whole run of keys per landing reads about the tree's depth
divided by the keys of a leaf ΔNode per key, well under one; one that
walked from the root per key read about twice the depth."""

import program_trace as PT


def read(view):
    rows = PT.window_rows(view, "index.successor_k")
    if rows is None or rows["emitted"].sum() <= 0:
        return None
    return float(rows["hops_sum"].sum()) / float(rows["emitted"].sum())
