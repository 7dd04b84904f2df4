"""Seconds of ``make_index`` (the ``index.build`` span's host total,
``repro.obs.trace``): sort, the layout loop over ΔNodes, and the
upload of the arena.  It runs before the profiler starts, so the
program's own total is its record."""

import program_trace as PT


def read(view):
    return PT.span_total_s("index.build")
