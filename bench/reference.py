"""The plain reference: an ordered map from key to record id, held as two
numpy arrays sorted by key.

It gives the answers the index under test must give, op by op, under the
guarantees the configuration files state: reads see the map as it was
before their batch, a batch's inserts apply in batch order (an insert of
a key already present fails and leaves its record as it was), and every
acknowledged insert is seen by every later batch.  It is independent of
the program: numpy only, nothing imported from ``repro``.
"""

from __future__ import annotations

import numpy as np


class SortedMap:
    """int32 keys to int32 payloads; every op is vectorised."""

    def __init__(self, keys, payloads):
        keys = np.asarray(keys, np.int32)
        payloads = np.asarray(payloads, np.int32)
        if np.unique(keys).size != keys.size:
            raise ValueError("the loaded keys must be unique")
        order = np.argsort(keys, kind="stable")
        self.keys, self.payloads = keys[order], payloads[order]

    def __len__(self) -> int:
        return int(self.keys.size)

    def _at(self, q):
        q = np.asarray(q, np.int32)
        at = np.searchsorted(self.keys, q, side="left")
        safe = np.minimum(at, max(self.keys.size - 1, 0))
        found = (at < self.keys.size) & (self.keys[safe] == q)
        return found, safe

    def contains(self, q) -> np.ndarray:
        """found[i]: ``q[i]`` is in the map."""
        return self._at(q)[0]

    def lookup(self, q):
        """(found (K,) bool, payload (K,) int32, 0 where not found)."""
        found, safe = self._at(q)
        return found, np.where(found, self.payloads[safe], 0).astype(np.int32)

    def scan(self, starts, lengths, width: int):
        """For each op, the ``lengths[i]`` smallest keys ``>= starts[i]``
        and their payloads.

        Returns (keys (K, width) int32, payloads (K, width) int32, both
        zero-padded past each count, counts (K,) int32)."""
        starts = np.asarray(starts, np.int32)
        lengths = np.asarray(lengths, np.int32)
        at = np.searchsorted(self.keys, starts, side="left")
        counts = np.minimum(lengths, self.keys.size - at).astype(np.int32)
        span = np.arange(width, dtype=np.int64)
        idx = np.maximum(np.minimum(at[:, None] + span[None, :],
                                    self.keys.size - 1), 0)
        valid = span[None, :] < counts[:, None]
        rows = np.where(valid, self.keys[idx], 0).astype(np.int32)
        pays = np.where(valid, self.payloads[idx], 0).astype(np.int32)
        return rows, pays, counts

    def insert(self, new, payloads) -> np.ndarray:
        """Insert in batch order; result[i] is True where ``new[i]`` was
        not in the map before it (nor earlier in the batch)."""
        new = np.asarray(new, np.int32)
        payloads = np.asarray(payloads, np.int32)
        _, first = np.unique(new, return_index=True)
        fresh = np.zeros(new.size, bool)
        fresh[first] = True
        result = fresh & ~self.contains(new)
        order = np.argsort(new[result], kind="stable")
        add, add_p = new[result][order], payloads[result][order]
        at = np.searchsorted(self.keys, add)
        self.keys = np.insert(self.keys, at, add)
        self.payloads = np.insert(self.payloads, at, add_p)
        return result
