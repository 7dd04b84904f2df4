"""The hot chip's share of a batch (%): 100 x Σ ``lanes_dev_max`` over
Σ ``lanes`` of the window's ``lookup`` calls (``repro.obs.calls``),
``lanes_dev_max`` being the real lanes of the chip that owns the most
of a call's keys.  100 / D is an even split over D chips.  None where
the program's ring has no such column or its backend routes nothing."""

import program_trace as PT


def read(view):
    rows = PT.window_rows(view, "index.lookup")
    if rows is None or "lanes_dev_max" not in rows.dtype.names:
        return None
    hot, lanes = rows["lanes_dev_max"].sum(), rows["lanes"].sum()
    if hot <= 0 or lanes <= 0:
        return None
    return 100.0 * float(hot) / float(lanes)
