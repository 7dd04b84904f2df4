"""Lanes the read dispatch walks per point read: Σ ``lanes_walked`` over
Σ ``lanes`` of the window's ``lookup`` calls (``repro.obs.calls``).  A
forest's fused dispatch on D chips pads each chip's lanes to the whole
batch, D x K lanes for K reads: D here; one unpadded walk reads 1.
None where the program's ring has no such column or its backend routes
nothing."""

import program_trace as PT


def read(view):
    rows = PT.window_rows(view, "index.lookup")
    if rows is None or "lanes_walked" not in rows.dtype.names:
        return None
    walked, lanes = rows["lanes_walked"].sum(), rows["lanes"].sum()
    if walked <= 0 or lanes <= 0:
        return None
    return float(walked) / float(lanes)
