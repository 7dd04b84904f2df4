"""Per-call counters of every concrete ``Index`` call, in a ring on the
device.

Each call gets a host sequence number (``next_seq``).  Right after the
call dispatches its own program, ``record`` dispatches one small jitted
program that writes row ``seq mod ROWS`` of a donated int32 ring and adds
the row to running per-kind totals.  Its inputs are the call's own
outputs (per-lane ``hops``, a scan's ``n`` and ``more``, an update's
``MaintenanceStats`` and the arena's ``free_top``), so the counters are
computed outside the read, scan and update programs, which stay as they
were.  The host never waits on the ring until it is read:

- ``calls(seqs)``: the rows of those calls, a structured numpy array
  with one int64 field per entry of ``COLUMNS``; a call the ring no
  longer holds comes back with ``seq`` -1;
- ``totals()``: running sums over every call since the process started,
  keyed ``<kind>_<column>`` (``lookup_calls``, ``successor_k_hops_sum``).

Both readers wait for the device outside the module lock, so a reader
never holds up another thread's ``record``.

The columns reuse the names of ``SearchStats``, ``ScanStats`` and
``MaintenanceStats``.  ``hops_max`` is the call's lockstep round count;
a call whose backend returns no ``hops`` records ``lanes`` alone.

Two columns count what a sharded backend's router adds to a point read
(``search``, ``lookup``), from the boundaries and the keys the call
passes (``splits``, ``keys``) and the layout of its dispatch:

- ``lanes_dev_max``: the real lanes of the device that owns the most of
  the call's keys (a key's shard by ``searchsorted(splits, key,
  "right")``, its device by the mesh's even split of the shards);
- ``lanes_walked``: the lanes the dispatch walks, ``lane_rows`` rows of
  the call's lanes each: one row per device for the fused frontier (D x
  K on D devices, K on one), one per shard for the vmap dispatch.

Every other call, and every call of an unsharded backend, writes 0 in
both.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

KINDS = ("search", "lookup", "successor", "successor_k", "scan",
         "update", "flush")
COLUMNS = ("seq", "kind", "lanes", "hops_sum", "hops_max", "emitted",
           "truncated", "rounds", "rebuilds", "expands", "merges",
           "pending", "reclaimed", "free_top", "lanes_dev_max",
           "lanes_walked")
ROWS = 4096                  # 4096 x 16 int32 = 256 KiB on the device
# columns that add up over calls; pending and free_top are levels
SUMMED = ("lanes", "hops_sum", "hops_max", "emitted", "truncated",
          "rounds", "rebuilds", "expands", "merges", "reclaimed",
          "lanes_dev_max", "lanes_walked")
_MAINT = ("rounds", "rebuilds", "expands", "merges", "pending",
          "reclaimed")
_SUM_AT = [COLUMNS.index(c) for c in SUMMED]
_LIMB = 20                   # totals are (lo, hi) limbs of 20 bits + rest
_MASK = (1 << _LIMB) - 1

_LOCK = threading.Lock()
_LOCAL = threading.local()


class _State:
    seq = 0
    ring = None              # (ROWS, len(COLUMNS)) int32
    tot = None               # (len(KINDS), 1 + len(SUMMED), 2) int32


_S = _State()


def next_seq() -> int:
    """The next call's sequence number; also this thread's ``last_seq``."""
    with _LOCK:
        seq = _S.seq
        _S.seq += 1
    _LOCAL.seq = seq
    return seq


def last_seq() -> int | None:
    """Sequence number of the last call this thread numbered."""
    return getattr(_LOCAL, "seq", None)


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3, 4))
def _record(kind, devices, lane_rows, ring, tot, seq, lanes, hops, n, more,
            maint, free_top, splits, keys):
    z = jnp.int32(0)
    i32 = lambda x: jnp.asarray(x).astype(jnp.int32)
    col = dict.fromkeys(COLUMNS, z)
    col.update(seq=i32(seq), kind=jnp.int32(kind), lanes=i32(lanes))
    if hops is not None:
        col.update(hops_sum=jnp.sum(i32(hops)), hops_max=jnp.max(i32(hops)))
    if n is not None:
        col.update(emitted=jnp.sum(i32(n)),
                   truncated=jnp.sum(i32(more)))
    if maint is not None:
        col.update({f: i32(getattr(maint, f)) for f in _MAINT})
    if free_top is not None:
        col.update(free_top=jnp.sum(i32(free_top)))
    if splits is not None:
        # the boundaries widen to the key dtype, as the router's do
        sid = jnp.searchsorted(splits.astype(keys.dtype), keys, side="right")
        dev = sid // ((splits.shape[0] + 1) // devices)
        col.update(
            lanes_dev_max=jnp.max(jnp.zeros((devices,), jnp.int32).at[
                dev].add(1)),
            lanes_walked=jnp.int32(lane_rows * keys.shape[0]))
    row = jnp.stack([i32(col[c]) for c in COLUMNS])
    ring = ring.at[row[0] % ROWS].set(row)
    add = jnp.concatenate([jnp.ones((1,), jnp.int32), row[jnp.asarray(
        _SUM_AT)]])
    lo = tot[kind, :, 0] + (add & _MASK)
    hi = tot[kind, :, 1] + (add >> _LIMB) + (lo >> _LIMB)
    tot = tot.at[kind].set(jnp.stack([lo & _MASK, hi], axis=1))
    return ring, tot


def _placed(args, sharding):
    """Move the call's outputs next to the ring when they live elsewhere
    (a forest's outputs are spread over its mesh)."""
    for x in args:
        if isinstance(x, jax.Array) and x.sharding != sharding:
            return jax.device_put(args, sharding)
    return args


def record(kind: str, seq: int, lanes: int, *, hops=None, n=None,
           more=None, maint=None, free_top=None, splits=None, keys=None,
           devices: int = 1, lane_rows: int = 0) -> None:
    """Write call ``seq``'s row: dispatches one small program, waits for
    nothing.  A sharded backend's point read also passes its
    ``splits``, its ``keys``, the ``devices`` its shards spread over and
    the ``lane_rows`` its dispatch walks (see the module docstring)."""
    k = KINDS.index(kind)
    with _LOCK:
        if _S.ring is None:   # uploaded from the host: no program to load
            _S.ring = jnp.asarray(np.full((ROWS, len(COLUMNS)), -1, np.int32))
            _S.tot = jnp.asarray(
                np.zeros((len(KINDS), 1 + len(SUMMED), 2), np.int32))
        args = _placed((hops, n, more, maint, free_top, splits, keys),
                       _S.ring.sharding)
        _S.ring, _S.tot = _record(k, devices, lane_rows, _S.ring, _S.tot,
                                  np.int32(seq), np.int32(lanes), *args)


@jax.jit
def _take(ring, idx):
    return ring[idx]


def calls(seqs) -> np.ndarray:
    """Rows of the calls ``seqs`` (reads the ring: waits for its writes).
    Fields are ``COLUMNS``; a row the ring no longer holds has seq -1."""
    seqs = np.asarray(seqs, np.int64).reshape(-1)
    out = np.full(seqs.shape, -1, [(c, np.int64) for c in COLUMNS])
    if seqs.size == 0:
        return out
    # gather just those rows on the device (padded to a power of two, so
    # few shapes compile), under the lock so that no later record has
    # donated the ring yet; wait for them outside it
    idx = np.zeros(1 << (seqs.size - 1).bit_length(), np.int32)
    idx[:seqs.size] = seqs % ROWS
    with _LOCK:
        taken = None if _S.ring is None else _take(_S.ring, idx)
    if taken is None:
        return out
    rows = np.asarray(taken)[:seqs.size].astype(np.int64)
    held = rows[:, 0] == seqs
    for j, c in enumerate(COLUMNS):
        out[c] = np.where(held, rows[:, j], -1)
    return out


def totals() -> dict[str, int]:
    """Running sums per kind over every recorded call:
    ``<kind>_calls`` and ``<kind>_<column>`` for each of ``SUMMED``."""
    with _LOCK:      # a copy: the next record donates ``_S.tot``
        tot = None if _S.tot is None else jnp.copy(_S.tot)
    if tot is None:
        return {}
    tot = np.asarray(tot)
    val = (tot[..., 1].astype(np.int64) << _LIMB) + tot[..., 0]
    out = {}
    for k, kind in enumerate(KINDS):
        if val[k, 0]:
            for j, c in enumerate(("calls",) + SUMMED):
                out[f"{kind}_{c}"] = int(val[k, j])
    return out
