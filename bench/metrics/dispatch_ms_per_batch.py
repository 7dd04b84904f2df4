"""The program's own host time per batch (ms): the time covered by the
``index.*`` spans of the window's ``Index`` calls (``repro.obs.trace``;
each spans the call's dispatch and the dispatch of its counter record),
over the window's batches.  Layer: the api host path; what is left of
``host_ms_per_batch`` lies outside the program (the fetch, the
transfers, the client).  Host work only where one batch is in flight:
with several, a dispatch waits until the runtime accepts the program,
and the span holds that wait (so E has no ``.scans``)."""

import program_trace as PT


def read(view):
    evs = PT.window_events(view)
    n = len(view.window.results)
    if evs is None or n == 0:
        return None
    return PT.self_ns(evs) * 1e-6 / n
