"""Faults planted under the timed path, to show that the check catches
them.  Each wraps a system (``harness.IndexSystem`` or the control) and
breaks one thing; a run over it must read ``correct: false``.

- ``AnswerAltered``: one answer of one batch altered where it is produced.
- ``HalfBatch``: the second half of every batch left out; its ops come
  back empty, and its inserts are never applied.
- ``StateUnchanged``: the insert step acknowledges every insert and
  leaves the map as it was.
- ``ArenaExhausted``: the index reports an exhausted arena.
- ``AllFound``: every read reports its key found, as a walk that skips
  its final key compare would.
"""

from __future__ import annotations

import numpy as np


class Fault:
    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)


class AnswerAltered(Fault):
    """Alter the first answer of the ``at``-th fetched batch (the warm-up
    fetches two)."""

    def __init__(self, inner, at: int = 3):
        super().__init__(inner)
        self.fetches, self.at = 0, at

    def fetch(self, out):
        got = self.inner.fetch(out)
        if not isinstance(got, dict):     # the check's read-back
            return got
        self.fetches += 1
        if self.fetches != self.at:
            return got
        if "read" in got:
            found, pay, hops = got["read"]
            pay = pay.copy()
            pay[0] += 1
            got["read"] = (found, pay, hops)
        elif "scan" in got:
            rows, pays, n = got["scan"]
            rows = rows.copy()
            rows[0, 0] += 1
            got["scan"] = (rows, pays, n)
        elif "insert" in got:
            res = got["insert"].copy()
            res[0] = ~res[0]
            got["insert"] = res
        return got


class HalfBatch(Fault):
    """Serve the first half of each batch only."""

    def insert(self, keys, ids):
        q = keys.copy()
        q[keys.size // 2:] = keys[0]     # repeats of one key: no-ops
        return self.inner.insert(q, ids)

    def fetch(self, out):
        got = self.inner.fetch(out)
        if not isinstance(got, dict):     # the check's read-back
            return got
        if "read" in got:
            found, pay, hops = got["read"]
            found = found.copy()
            found[found.size // 2:] = False
            got["read"] = (found, pay, hops)
        if "scan" in got:
            rows, pays, n = got["scan"]
            rows, n = rows.copy(), n.copy()
            rows[n.size // 2:] = 0
            n[n.size // 2:] = 0
            got["scan"] = (rows, pays, n)
        return got


class StateUnchanged(Fault):
    """Acknowledge every insert and apply none."""

    def insert(self, keys, ids):
        self.inner.warm_insert(keys.size)
        return np.ones(keys.size, bool)


class ArenaExhausted(Fault):
    """Report the arena exhausted (``alloc_failed``) after the window."""

    def alloc_failed(self) -> bool:
        return True


class AllFound(Fault):
    """Report every read's key as found."""

    def read(self, keys):
        found, pay, hops = self.inner.read(keys)
        return found | True, pay, hops


FAULTS = {"answer_altered": AnswerAltered, "half_batch": HalfBatch,
          "state_unchanged": StateUnchanged,
          "arena_exhausted": ArenaExhausted, "all_found": AllFound}
