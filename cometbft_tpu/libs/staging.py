"""Pinned double-buffered host staging for device uploads.

Every verify flush used to allocate fresh numpy arrays (np.zeros per
bucket shape per flush) for the packed signature rows. Under streaming
load that is pure allocator churn on the hot path, and it defeats
overlap: the dispatcher cannot pack flush k+1 into the same memory the
device is still copying for flush k. This pool keeps `slots` (default
2) persistent arrays per (name, shape, dtype) and rotates them — the
classic double buffer: while the device consumes buffer A of a shape,
the host packs into buffer B.

A buffer may be written again only after the flight that read it has
been COLLECTED. JAX's host-to-device copy of a numpy argument is
asynchronous on a local TPU: the jitted call returns while the runtime
still reads the host buffer, and a write in that window changes what
the kernel sees (measured on a v5e in PR 21: 20 of 20 results changed
when the buffer was cleared right after dispatch). Fetching a flight's
result is the one proof its inputs have been read, so every consumer
here keeps fewer flights uncollected than its pool has slots.

Depth must track the pipeline: a consumer keeping K transfers in
flight needs K+1 slots so the pack never lands in a buffer a flight
still reads from. The verify plane's flight deck sizes its private
pool `pipeline_flights + 1` deep (a hardcoded 2 would silently alias
the third concurrent pack); blocksync keeps its own 3-deep pool for
its 2-in-flight window — the same rule. The rotation is strictly
round-robin per key, NOT free-slot-aware: a consumer that completes
transfers out of order must still retire them within the rotation
window (the plane force-lands any flight older than `flights` packs
before packing — plane.py's rotation-window bound), or pack m would
zero the buffer pack m-(slots) left pinned.

The arrays are ordinary host memory (numpy cannot ask for pinned
allocations; steady reuse keeps the pages hot and resident).
Donation-safety: the pool only ever hands out HOST buffers —
device-resident caches (valset tables, window tables) are never staged
through it, so enabling jit donation on the rows argument can never
free a cached table buffer.
"""
from __future__ import annotations

import threading
import weakref
from typing import Dict, List, Tuple

import numpy as np

# every live pool, weakly held: the device observatory's residency
# sampler (libs/deviceledger) attributes ALL pinned staging bytes —
# the global crypto.batch pool, plane-private pools, blocksync's —
# without each owner having to register anywhere
_POOLS: "weakref.WeakSet" = weakref.WeakSet()


def live_pools() -> List["StagingPool"]:
    """Snapshot of every StagingPool still alive in this process."""
    return list(_POOLS)


class StagingPool:
    """Rotating preallocated host arrays, `slots` deep per shape."""

    def __init__(self, slots: int = 2):
        self.slots = max(1, int(slots))
        self._lock = threading.Lock()
        self._bufs: Dict[tuple, list] = {}
        self._next: Dict[tuple, int] = {}
        self.hits = 0
        self.misses = 0
        _POOLS.add(self)

    def get(self, name: str, shape: Tuple[int, ...], dtype,
            zero: bool = True) -> np.ndarray:
        """The next staging buffer for (name, shape, dtype); zeroed by
        default. Callers must be done writing a buffer before asking
        for `slots` more of the same key (the rotation contract)."""
        key = (name, tuple(int(s) for s in shape), np.dtype(dtype).str)
        with self._lock:
            bufs = self._bufs.get(key)
            if bufs is None:
                bufs = self._bufs[key] = []
            if len(bufs) < self.slots:
                buf = np.zeros(key[1], dtype)
                bufs.append(buf)
                self._next[key] = len(bufs) % self.slots
                self.misses += 1
                return buf
            i = self._next[key]
            self._next[key] = (i + 1) % self.slots
            buf = bufs[i]
            self.hits += 1
        if zero:
            buf.fill(0)
        return buf

    def nbytes(self) -> int:
        with self._lock:
            return sum(b.nbytes for bufs in self._bufs.values()
                       for b in bufs)

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "shapes": len(self._bufs),
                "resident_bytes": sum(
                    b.nbytes for bufs in self._bufs.values() for b in bufs
                ),
            }

    def clear(self) -> None:
        with self._lock:
            self._bufs.clear()
            self._next.clear()
