"""Trace spans: one always-on host span helper on the profiler's clock.

- ``span(name, **args)`` is a ``jax.profiler.TraceAnnotation(name,
  **args)``: with a profiler session active the span lands on the host
  plane of the xplane beside the device's ops, its keyword arguments as
  integer stats on the event (``index.lookup`` carries ``seq`` and
  ``lanes``); with none active it costs about a microsecond.  It adds
  no device-side scope, so it never changes a compiled program.  Every
  completed span also adds to a process-wide table of host totals per
  name (``totals()``: count, total and max seconds) — set-up runs before
  any profiler session starts, so the totals are its only record.  A
  span entered while JAX is tracing (under ``jit``/``vmap``) records
  nothing: there it would time the trace, not the run.
- ``capture(logdir)`` / ``trace_run`` wrap a region in
  ``jax.profiler.start_trace`` / ``stop_trace``.

The totals table is guarded by one module lock: the serve layer's
maintenance worker may close spans from its own thread.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

_LOCK = threading.Lock()
_TOTALS: dict[str, list] = {}      # name -> [count, total_s, max_s]


def top_level() -> bool:
    """False while JAX traces a function (jit, vmap, grad ...)."""
    return jax.core.trace_ctx.is_top_level()


class _Span:
    __slots__ = ("name", "ann", "t0")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.ann = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        with _LOCK:
            row = _TOTALS.get(self.name)
            if row is None:
                _TOTALS[self.name] = [1, dt, dt]
            else:
                row[0] += 1
                row[1] += dt
                row[2] = max(row[2], dt)
        return False


def span(name: str, **args):
    """Host span ``name`` with integer ``args`` as profiler stats."""
    if not top_level():
        return contextlib.nullcontext()
    return _Span(name, args)


def totals() -> dict[str, dict]:
    """{span name: {"count", "total_s", "max_s"}} over the process."""
    with _LOCK:
        return {k: {"count": c, "total_s": t, "max_s": m}
                for k, (c, t, m) in _TOTALS.items()}


@contextlib.contextmanager
def capture(logdir: str):
    """Dump an xprof/perfetto trace of the enclosed region to ``logdir``
    (view with xprof / tensorboard-profile / perfetto)."""
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def trace_run(fn, *args, logdir: str, **kwargs):
    """Run ``fn(*args, **kwargs)`` under ``capture`` and block until its
    results land, so the dump covers the real device work — the one-call
    helper for profiling a jitted read/update on hardware."""
    with capture(logdir):
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    return out
