"""The one traffic generator: reads a mix file and a configuration file and
draws every batch of a run from ``--seed``.

Record keys follow YCSB's hashed insert order: record ``i``'s key is the
FNV-1a hash of ``i`` folded into the key domain, with collisions hashed
again so that keys stay unique.  As in YCSB, they do not depend on the
seed: every seed loads the same records and inserts the same new ones in
the same order, so that the index evolves alike under every seed, and the
seed draws the requests.  Read and scan targets are record ids drawn by
YCSB's ``ScrambledZipfianGenerator``; inserts take the next record ids, so
they are new keys spread uniformly over the domain.  Each key maps to its
record id, the payload that stands for the pointer to YCSB's 1 KB record.

Reads and scans come from a pool of ``pool_batches`` distinct batches that
the window cycles through; inserts are fresh in every batch, for up to
``insert_batches`` batches.  Both are drawn before the window opens.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# YCSB core: Utils.fnvhash64 and ScrambledZipfianGenerator's constants
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
ZIPF_ITEM_COUNT = 10_000_000_000
ZIPF_CONSTANT = 0.99
ZIPF_ZETAN = 26.46902820178302   # zeta(ZIPF_ITEM_COUNT, 0.99), from YCSB


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1a over the 8 low-first octets of
    each value, then the absolute value of the signed result."""
    v = np.asarray(v).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= prime
        v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian(u: np.ndarray) -> np.ndarray:
    """YCSB ``ZipfianGenerator(0, ZIPF_ITEM_COUNT, 0.99, ZIPF_ZETAN)``
    applied to uniform draws ``u`` in [0, 1)."""
    theta = ZIPF_CONSTANT
    items = ZIPF_ITEM_COUNT + 1
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / ZIPF_ZETAN)
    uz = u * ZIPF_ZETAN
    ret = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    ret = np.where(uz < 1.0 + 0.5 ** theta, 1, ret)
    return np.where(uz < 1.0, 0, ret)


def scrambled_zipfian(rng, n_records: int, size) -> np.ndarray:
    """YCSB ``ScrambledZipfianGenerator(0, n_records - 1)``: record ids."""
    return fnvhash64(zipfian(rng.random(size))) % n_records


def record_keys(n: int, lo: int, hi: int) -> np.ndarray:
    """Keys of record ids ``0..n-1``: FNV-1a of the id folded into
    [lo, hi); a later id whose key collides with an earlier one is hashed
    again until every key is unique."""
    raw = fnvhash64(np.arange(n, dtype=np.uint64))
    keys = lo + raw % (hi - lo)
    while True:
        order = np.argsort(keys, kind="stable")
        s = keys[order]
        dup = order[1:][s[1:] == s[:-1]]
        if dup.size == 0:
            return keys.astype(np.int32)
        raw[dup] = fnvhash64(raw[dup])
        keys[dup] = lo + raw[dup] % (hi - lo)


@dataclasses.dataclass
class Batch:
    """One batch of ops, in the order the window issues them."""

    read: np.ndarray | None         # (R,) int32 keys
    scan_start: np.ndarray | None   # (S,) int32 first key of each scan
    scan_len: np.ndarray | None     # (S,) int32 keys wanted per scan
    insert: np.ndarray | None       # (I,) int32 new keys
    insert_id: np.ndarray | None    # (I,) int32 their record ids


@dataclasses.dataclass
class Traffic:
    """Every input of one run, drawn from the seed before the window."""

    loaded: np.ndarray              # (N,) keys of records 0..N-1
    reads: np.ndarray | None        # (P, R)
    scan_starts: np.ndarray | None  # (P, S)
    scan_lens: np.ndarray | None    # (P, S)
    inserts: np.ndarray | None      # (B, I) keys of records N, N+1, ...
    scan_width: int                 # the longest scan: ``maxscanlength``
    in_flight: int = 1              # batches the client keeps in flight

    @property
    def loaded_ids(self) -> np.ndarray:
        return np.arange(self.loaded.size, dtype=np.int32)

    @property
    def n_read(self) -> int:
        return 0 if self.reads is None else self.reads.shape[1]

    @property
    def n_scan(self) -> int:
        return 0 if self.scan_starts is None else self.scan_starts.shape[1]

    @property
    def n_insert(self) -> int:
        return 0 if self.inserts is None else self.inserts.shape[1]

    @property
    def batch_ops(self) -> int:
        return self.n_read + self.n_scan + self.n_insert

    @property
    def max_batches(self) -> int | None:
        """Batches the drawn inserts last for (None: no inserts)."""
        return None if self.inserts is None else self.inserts.shape[0]

    def batch(self, b: int) -> Batch:
        if self.inserts is not None and b >= self.inserts.shape[0]:
            raise RuntimeError(
                f"batch {b}: the mix drew inserts for {self.inserts.shape[0]}"
                " batches; raise insert_batches in the mix file")
        p = b % self.pool_batches
        return Batch(
            read=None if self.reads is None else self.reads[p],
            scan_start=None if self.scan_starts is None
            else self.scan_starts[p],
            scan_len=None if self.scan_lens is None else self.scan_lens[p],
            insert=None if self.inserts is None else self.inserts[b],
            insert_id=None if self.inserts is None else (
                self.loaded.size + b * self.n_insert
                + np.arange(self.n_insert, dtype=np.int32)))

    @property
    def pool_batches(self) -> int:
        for a in (self.reads, self.scan_starts):
            if a is not None:
                return a.shape[0]
        return 1


def make_traffic(config: dict, mix: dict, seed: int) -> Traffic:
    """A run's records, and its batches drawn from ``seed``.

    ``config`` gives ``recordcount`` and ``key_domain`` [lo, hi); ``mix``
    gives per-batch op counts (``read``, ``scan``, ``insert``),
    ``requestdistribution`` (``zipfian``),
    ``maxscanlength`` with ``scanlengthdistribution`` ``uniform``,
    ``pool_batches``, ``insert_batches`` and ``in_flight``, the batches
    the client keeps in flight (1 where the mix does not say)."""
    rng = np.random.default_rng(seed)
    n = int(config["recordcount"])
    lo, hi = (int(x) for x in config["key_domain"])
    counts = {k: int(mix.get(k, 0)) for k in ("read", "scan", "insert")}
    pool = int(mix["pool_batches"])
    n_ins_batches = int(mix.get("insert_batches", 0)) if counts["insert"] else 0
    keys = record_keys(n + n_ins_batches * counts["insert"], lo, hi)

    if mix["requestdistribution"] != "zipfian":
        raise ValueError("requestdistribution must be zipfian")
    if float(mix.get("zipfian_constant", ZIPF_CONSTANT)) != ZIPF_CONSTANT:
        raise ValueError("only YCSB's zipfian constant 0.99 is drawn")

    def targets(size):
        return keys[scrambled_zipfian(rng, n, size)]

    reads = targets((pool, counts["read"])) if counts["read"] else None
    starts = lens = None
    width = int(mix.get("maxscanlength", 0))
    if counts["scan"]:
        if mix.get("scanlengthdistribution", "uniform") != "uniform":
            raise ValueError("scanlengthdistribution must be uniform")
        starts = targets((pool, counts["scan"]))
        lens = rng.integers(1, width + 1, (pool, counts["scan"]),
                            dtype=np.int32)
    inserts = (keys[n:].reshape(n_ins_batches, counts["insert"])
               if counts["insert"] else None)
    return Traffic(loaded=keys[:n], reads=reads, scan_starts=starts,
                   scan_lens=lens, inserts=inserts, scan_width=width,
                   in_flight=int(mix.get("in_flight", 1)))
