"""Padded host buffers that are used again: the streamed scan's staging.

A chunk of a streamed scan is filled into one numpy buffer a column
(`columnar.fill_padded`) and handed to `jax.device_put`. What such a
buffer costs is not the copy but the first touch of its pages, so a
buffer whose transfer is over goes back here and the next chunk, of
this stream or of the next request, is filled into warm memory.

The one rule: **a buffer is handed out again only when the device
array made from it reports ready, and never where that array reads the
buffer's own memory.** Both are observed, not configured: `is_ready()`
of the array, and, for an array on the CPU backend (which takes a
suitably aligned host buffer as the array's storage instead of copying
it), whether its buffer pointer lies inside the host buffer. A resident
load (`Batch.from_arrow`) never draws here: a device-table cache entry
must not pin pooled memory.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

#: sets of buffers a stream can have out at once: one being filled,
#: one in the prefetcher's size-1 queue or being put, one whose
#: transfers may still be in flight
SETS_PER_STREAM = 3


def _new_buffer(dtype: np.dtype, capacity: int) -> np.ndarray:
    """Zeroed, and 16 bytes past a 64-byte line, where a large
    `np.zeros` lies anyway: the CPU backend copies such a buffer, while
    one it took as an array's storage could never be used again."""
    raw = np.zeros(capacity * dtype.itemsize + 64, dtype=np.uint8)
    start = (16 - raw.ctypes.data) % 64
    return raw[start:start + capacity * dtype.itemsize].view(dtype)


def _reads_host_memory(arr: jax.Array, buf: np.ndarray) -> bool:
    """Whether a device array made from `buf` has `buf`'s memory as
    its own storage (the CPU backend's zero-copy put). An
    accelerator's memory is not the host's."""
    lo = buf.ctypes.data
    for shard in arr.addressable_shards:
        if shard.device.platform == "cpu" and \
                lo <= shard.data.unsafe_buffer_pointer() < lo + buf.nbytes:
            return True
    return False


class HostBufferPool:
    """Buffers by (numpy dtype, capacity), owned by the process and
    kept between requests. `take` hands one out, `give` takes it back
    with the device array that was made from it; what idles here is
    bounded by `trim`, which a stream calls with its own shape when it
    ends."""

    def __init__(self):
        self._lock = threading.Lock()
        self._free: Dict[tuple, List[np.ndarray]] = {}
        #: given back while the array made from them may still be in
        #: flight: (buffer, array), looked at again by `_reclaim`
        self._in_flight: List[Tuple[np.ndarray, jax.Array]] = []

    @staticmethod
    def key(dtype, capacity: int) -> tuple:
        return (np.dtype(dtype).str, int(capacity))

    @classmethod
    def key_of(cls, buf: np.ndarray) -> tuple:
        return cls.key(buf.dtype, len(buf))

    def _reclaim(self) -> None:
        """Move what is ready from `_in_flight` to `_free`; a buffer
        its array still reads, or whose array was donated away before
        anyone could ask, is let go. Never waits."""
        with self._lock:
            waiting = []
            for buf, arr in self._in_flight:
                if arr.is_deleted():
                    continue
                if not arr.is_ready():
                    waiting.append((buf, arr))
                elif not _reads_host_memory(arr, buf):
                    self._free.setdefault(self.key_of(buf), []).append(buf)
            self._in_flight = waiting

    def take(self, dtype, capacity: int) -> Tuple[np.ndarray, bool]:
        """(a buffer of `capacity` items of `dtype`, whether it was
        used before). A used one holds an older chunk's rows; a new one
        is zero. Where every buffer of the shape is in flight, waits
        for the oldest rather than touch fresh pages."""
        key = self.key(dtype, capacity)
        while True:
            self._reclaim()
            with self._lock:
                free = self._free.get(key)
                if free:
                    return free.pop(), True
                pending = next((arr for buf, arr in self._in_flight
                                if self.key_of(buf) == key), None)
            if pending is None:
                return _new_buffer(np.dtype(dtype), capacity), False
            try:
                jax.block_until_ready(pending)
            except RuntimeError:
                pass  # donated away meanwhile: `_reclaim` lets it go

    def give(self, buf: np.ndarray,
             arr: Optional[jax.Array] = None) -> None:
        """Take `buf` back. `arr` is the device array that was made
        from it, None where it was never put."""
        with self._lock:
            if arr is None:
                self._free.setdefault(self.key_of(buf), []).append(buf)
            else:
                self._in_flight.append((buf, arr))

    def trim(self, shape: Dict[tuple, int]) -> None:
        """Keep what `SETS_PER_STREAM` sets of a stream of `shape`
        ({key: buffers a chunk}) need, those in flight counted, and
        let everything else that idles go."""
        self._reclaim()
        with self._lock:
            kept = {}
            for key, per_chunk in shape.items():
                in_flight = sum(1 for buf, _ in self._in_flight
                                if self.key_of(buf) == key)
                room = max(SETS_PER_STREAM * per_chunk - in_flight, 0)
                if room and self._free.get(key):
                    kept[key] = self._free[key][:room]
            self._free = kept

    def idle_bytes(self) -> int:
        """Bytes held and not lent out, those in flight included."""
        with self._lock:
            return sum(b.nbytes for bufs in self._free.values()
                       for b in bufs) \
                + sum(buf.nbytes for buf, _ in self._in_flight)


#: the process's pool (`ChunkIterator` draws here unless handed another)
POOL = HostBufferPool()
