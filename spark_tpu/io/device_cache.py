"""Device-resident table cache: loaded scans stay in HBM across queries.

The round-3 headline perf failure was re-ingesting every scan on every
execution (full Parquet read + dictionary-encode + device_put per
query). The reference avoids this with `CacheManager.scala:1`'s
plan-fingerprint cache and the BlockManager's storage tier; here the
analog is a process-level LRU over loaded device Batches keyed on
(source identity stamp, pruned columns, pushed filters, the mesh's
devices), with a byte budget (`spark_tpu.sql.io.deviceCacheBytes`) —
HBM is the storage memory pool of `UnifiedMemoryManager.scala:49`,
with LRU eviction playing the role of its storage-eviction policy.

Under a mesh a scan's device copy is made once, sharded over the data
axis (`columnar.ShardedPlacement`: each chip is sent its stripe from
the host), and kept that way: the budget is a chip's, an entry counts
what its fullest shard holds, and the key's last part names the
devices the arrays were laid out for, so a re-plan under another mesh
(or none) misses and loads its own.

Source identity stamps make staleness structural rather than
time-based: an Arrow-backed source gets a fresh monotonic token per
source object (re-registering a table name creates a new source, so
stale hits are impossible), and a Parquet source stamps the file list
with (size, mtime) pairs, so rewritten files miss the cache.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Optional, Tuple

CACHE_BYTES_KEY = "spark_tpu.sql.io.deviceCacheBytes"


def _chip_nbytes(x) -> int:
    """What the fullest device holds of `x`: all of an array on one
    device, a shard of one laid over a mesh."""
    shards = getattr(x, "addressable_shards", None)
    if shards:
        return max(s.data.nbytes for s in shards)
    return getattr(x, "nbytes", 0)


def batch_nbytes(batch) -> int:
    """A batch's bytes on the chip that holds most of it."""
    total = 0
    for col in batch.columns.values():
        total += _chip_nbytes(col.data)
        if col.validity is not None:
            total += _chip_nbytes(col.validity)
    sel = batch.selection
    if sel is not None:
        total += _chip_nbytes(sel)
    return total


class DeviceTableCache:
    """LRU cache of loaded device Batches with a byte budget.

    Lock-guarded: the SQL service runs concurrent queries whose scans
    hit/fill/evict this cache from worker threads, and the resource
    arbiter (service/arbiter.py) evicts it under lease pressure."""

    def __init__(self):
        self._lock = threading.RLock()
        self._entries: "OrderedDict[Tuple, Tuple[object, int]]" = \
            OrderedDict()
        #: pin counts per key: entries a RUNNING query was admitted
        #: against (the arbiter pins them) — lease-pressure eviction
        #: must skip these, because evicting a batch another query
        #: still references frees no HBM (the reference stays live)
        #: while the accounting would credit its bytes as free
        self._pins: Dict[Tuple, int] = {}
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        #: entries dropped by budget pressure or OOM-ladder clears (the
        #: storage-eviction observable; never reset with clear())
        self.evictions = 0
        #: the live rows each shard was dealt, for an entry laid over
        #: a mesh (host knowledge of the load; `dealt`)
        self._dealt: Dict[Tuple, Tuple[int, ...]] = {}
        #: entries that were laid over a mesh
        self.sharded_loads = 0

    def get(self, key) -> Optional[object]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return entry[0]

    def contains(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def dealt(self, key) -> Optional[Tuple[int, ...]]:
        """The live rows a shard of the entry's, where it was put
        with them; None for an entry on one device, or for none."""
        with self._lock:
            return self._dealt.get(key)

    def _drop(self, key) -> int:
        """Forget the entry (the lock is the caller's): its bytes."""
        _, nbytes = self._entries.pop(key)
        self._dealt.pop(key, None)
        self._bytes -= nbytes
        return nbytes

    def put(self, key, batch, budget: int,
            dealt: Optional[Tuple[int, ...]] = None) -> None:
        nbytes = batch_nbytes(batch)
        if nbytes > budget:
            return  # larger than the whole budget: don't thrash
        with self._lock:
            if key in self._entries:
                self._drop(key)
            self._entries[key] = (batch, nbytes)
            self._bytes += nbytes
            if dealt is not None:
                self._dealt[key] = dealt
                self.sharded_loads += 1
            while self._bytes > budget:
                # LRU, but skip the just-inserted key and pinned
                # entries — running queries still reference those, so
                # evicting them frees no HBM (evict_bytes discipline)
                victim = next((k for k in self._entries
                               if k != key and not self._pins.get(k)),
                              None)
                if victim is None:
                    break
                self._drop(victim)
                self.evictions += 1

    def evict_bytes(self, nbytes: int) -> int:
        """Evict LRU entries until at least `nbytes` are freed (or
        only pinned entries remain); returns bytes actually freed. The
        storage-eviction lever the cross-query arbiter pulls when an
        execution lease can't fit next to cached tables. Pinned
        entries (in use by a running query) are skipped: their bytes
        would not actually be freed."""
        freed = 0
        with self._lock:
            for key in list(self._entries):
                if freed >= nbytes:
                    break
                if self._pins.get(key):
                    continue
                freed += self._drop(key)
                self.evictions += 1
        return freed

    def pin(self, key) -> bool:
        """Mark `key` in-use by a running query (counted); False when
        the entry is not present (caller falls back to leasing)."""
        with self._lock:
            if key not in self._entries:
                return False
            self._pins[key] = self._pins.get(key, 0) + 1
            return True

    def unpin(self, key) -> None:
        with self._lock:
            n = self._pins.get(key)
            if n is not None:
                if n <= 1:
                    del self._pins[key]
                else:
                    self._pins[key] = n - 1

    def invalidate_token(self, token) -> None:
        """Drop every entry whose source stamp is `token`."""
        with self._lock:
            for k in [k for k in self._entries if k[0] == token]:
                self._drop(k)

    def clear(self) -> None:
        with self._lock:
            self.evictions += len(self._entries)
            self._entries.clear()
            self._dealt.clear()
            self._pins.clear()  # unpin on ghost keys is a no-op
            self._bytes = 0

    @property
    def nbytes(self) -> int:
        return self._bytes

    def stats(self) -> Dict[str, int]:
        """Observability snapshot (the metrics listener publishes these
        as device_cache_* gauges at every query end)."""
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions, "bytes": self._bytes,
                    "entries": len(self._entries),
                    "sharded_loads": self.sharded_loads}


#: process-level cache (the session is effectively a singleton; HBM is a
#: process resource either way, like the reference's block manager)
CACHE = DeviceTableCache()


def scan_mesh(scan, mesh):
    """The mesh a scan's device copy is laid over: `mesh`, or None
    where there is none or the scan reads a list column, whose offsets
    are absolute into the flattened values and cannot be cut into
    stripes (such a scan loads as it does on one device)."""
    if mesh is None:
        return None
    from .. import types as T
    if any(isinstance(f.dtype, T.ArrayType) for f in scan.schema().fields):
        return None
    return mesh


def scan_cache_key(scan, mesh=None) -> Optional[Tuple]:
    """Cache key for a ScanExec, or None when the source is
    uncacheable. Its last part is the devices of the mesh the entry's
    arrays are laid over (`mesh`: what `scan_mesh` gave for this
    scan), None for the default device (the one default among the
    functions that take a mesh here: `benchmark/tests/cache_entries.py`
    asks with the scan alone)."""
    token = scan.source.cache_token()
    if token is None:
        return None
    cols = None if scan.required_columns is None \
        else tuple(scan.required_columns)
    filters = tuple(repr(f) for f in scan.pushed_filters)
    devices = None if mesh is None \
        else tuple(int(d.id) for d in mesh.devices.flat)
    return (token, cols, filters, devices)


def estimated_scan_bytes(scan) -> Optional[int]:
    """Rough post-prune device footprint of a scan (for the stream-vs-
    resident decision): rows x per-column width, with 2x headroom for
    capacity bucketing. None when the source can't estimate rows."""
    from .. import types as T
    est = scan.source.estimated_rows()
    if est is None:
        return None
    width = 0
    for f in scan.schema().fields:
        if isinstance(f.dtype, T.StringType):
            width += 4  # dictionary codes (dictionary bytes stay host-side)
        elif isinstance(f.dtype, T.DecimalType):
            width += 16
        elif isinstance(f.dtype, (T.IntegerType, T.DateType, T.FloatType)):
            width += 4
        elif isinstance(f.dtype, T.BooleanType):
            width += 1
        else:
            width += 8
        if f.nullable:
            width += 1
    return 2 * est * width


def is_cached(scan, mesh) -> bool:
    key = scan_cache_key(scan, mesh)
    return key is not None and CACHE.contains(key)


def chip_share(nbytes: Optional[int], mesh) -> Optional[int]:
    """What one chip holds of `nbytes` laid over `mesh`: all of it on
    one device, a shard's part (rounded up) under a mesh. Budgets,
    leases and the cache's own count are all a chip's."""
    if nbytes is None or mesh is None:
        return nbytes
    return -(-nbytes // int(mesh.devices.size))


def load_scan(scan, conf, mesh) -> Tuple[object, Optional[Tuple[int, ...]]]:
    """Load a ScanExec's Batch through the device cache: onto the
    default device, or sharded over the data axis of `mesh` (what
    `scan_mesh` gave for this scan), cached or not. Beside the batch,
    the live rows each shard was dealt (None on one device)."""
    budget = int(conf.get(CACHE_BYTES_KEY))
    key = scan_cache_key(scan, mesh) if budget > 0 else None
    if key is not None:
        batch = CACHE.get(key)
        if batch is not None:
            return batch, CACHE.dealt(key)
    if mesh is None:
        batch, dealt = scan.load(), None
    else:
        from ..columnar import ShardedPlacement
        from ..parallel.mesh import AXIS
        placement = ShardedPlacement(mesh, AXIS)
        batch = scan.load(placement)
        dealt = placement.dealt
    if key is not None:
        CACHE.put(key, batch, budget, dealt)
        # the bytes now count as STORAGE (headroom subtracts
        # CACHE.nbytes): a residency lease the running query took for
        # this scan would double-count — convert it to a pin
        from ..service.arbiter import note_scan_cached
        note_scan_cached(key)
    return batch, dealt
