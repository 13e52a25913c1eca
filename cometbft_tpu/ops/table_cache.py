"""Bounded, evicting caches for device-resident valset tables.

The jax-free core of the ops/ed25519_cached cache stack. Before this
module the table caches trimmed on a hard-coded count with no
observability: a node that re-elects its committee every few hours
(PAPERS.md arXiv 2004.12990's proportional election; ROADMAP item 5)
retires one valset per epoch, and "how much device+host memory do the
retired epochs still pin, and did the live epoch's table survive the
churn" had no answer. Everything capacity- and eviction-shaped lives
here so that:

  * capacities are CONFIGURABLE ([crypto] table_cache_* knobs) and
    enforced with real LRU eviction, counted per cache kind;
  * ``resident_bytes`` is maintained incrementally (O(1) per
    insert/evict) and served to /metrics at scrape time — epoch churn
    must hold it flat, and the eviction-pressure tests assert exactly
    that;
  * the next-epoch table warmer (verifyplane/warmer.py) can mark the
    keys it pre-built and the first post-rotation lookup attributes
    its hit honestly (``warmed_hits``) — the cold-vs-warmed evidence;
  * none of it imports jax, so the bounding/eviction/warm-attribution
    logic is testable (tests/test_warmer.py) on the 1-core
    tier-1 host without a device or a minutes-long interpret compile.

Thread-safety: callers synchronize on :data:`LOCK` (ed25519_cached
routes every cache touch through it — the lock object lives HERE so
jax-free consumers and the jax-heavy kernel module share one).

LIVE-epoch safety: eviction is strictly LRU and every cache hit
refreshes recency, so the table a steady flush stream is using is by
construction the most-recently-used entry — inserting epoch e+1's
warmed table evicts the OLDEST retired epoch, never the live one.
``set_capacities`` clamps every capacity to >= 2 so a warm insert can
never evict the live table out from under an in-flight flush even on
a pathological config. (A flush that already holds a table reference
keeps the device buffers alive regardless — eviction drops the cache's
pin, it never frees memory a flight still uses.)
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Iterator, Optional

# the ONE lock for the whole table-cache stack (ed25519_cached aliases
# it as _TABLE_LOCK); RLock so a near-miss scan that consults a second
# cache under the same lock never self-deadlocks
LOCK = threading.RLock()

# steady-state observability + the zero-copy hot path's regression
# guard: a healthy consensus stream should be ~all hits. The shard_*
# kinds count the per-mesh sharded-table cache; the evictions_* kinds
# count entries each bounded cache dropped under churn pressure;
# warmed_hits counts lookups answered by a table the next-epoch warmer
# pre-built (the first commit after a rotation, when the warmer won).
# misses count lookups that computed curve columns (a full build or an
# incremental patch); rekeyed counts tables made from a cached table of
# the same keys instead, their powers uploaded and slots permuted (by
# a lookup or a warm, as incremental_patches counts a patch).
STATS = {"hits": 0, "misses": 0, "rekeyed": 0, "key_memo_hits": 0,
         "valset_hits": 0, "valset_misses": 0,
         "shard_hits": 0, "shard_misses": 0,
         "template_hits": 0, "template_misses": 0,
         "evictions_tables": 0, "evictions_shard": 0,
         "evictions_valset_memo": 0, "evictions_key_memo": 0,
         "evictions_templates": 0,
         "warmed_hits": 0, "incremental_patches": 0}


def default_size(value) -> int:
    """Best-effort byte size of a cached table: the device arrays'
    nbytes plus the host-side pubkey/power copies. Duck-typed so the
    jax-free tests can size fake tables through a bare ``nbytes``
    attribute."""
    n = getattr(value, "nbytes", None)
    if isinstance(n, (int, float)):
        return int(n)
    total = 0
    for attr in ("tab", "ok", "power5"):
        a = getattr(value, attr, None)
        nb = getattr(a, "nbytes", None)
        if isinstance(nb, (int, float)):
            total += int(nb)
    ph = getattr(value, "pubs_host", None)
    if ph:
        total += sum(len(p) for p in ph)
    pw = getattr(value, "powers_host", None)
    nb = getattr(pw, "nbytes", None)
    if isinstance(nb, (int, float)):
        total += int(nb)
    return total


def key_set(pubs_host) -> Optional[tuple]:
    """A table's keys taken as a set: its padded per-slot key bytes,
    sorted (dead slots are ``b""``). Two tables with one key set hold
    the same curve columns in some slot order. Whole keys, not digests:
    an index entry is found by comparing every byte of every key (see
    ``ValsetTable.pubs_host``). None for a value without host keys."""
    return None if pubs_host is None else tuple(sorted(pubs_host))


def table_key_set(table) -> Optional[tuple]:
    """:func:`key_set` of a cached table's ``pubs_host`` (None for a
    table without one): the index of :data:`TABLES`."""
    return key_set(getattr(table, "pubs_host", None))


class BoundedLRU:
    """An LRU mapping with a settable capacity, per-kind eviction
    accounting in :data:`STATS`, and incrementally-maintained resident
    bytes. With an ``index_fn`` it also keeps, for each index key of a
    held value, the newest entry inserted under it (:meth:`find`).
    NOT internally locked — callers hold :data:`LOCK` (the
    ed25519_cached contract)."""

    __slots__ = ("kind", "capacity", "_od", "_size_fn", "_bytes",
                 "_index_fn", "_ikey", "_index")

    def __init__(self, kind: str, capacity: int,
                 size_fn: Optional[Callable] = None,
                 index_fn: Optional[Callable] = None):
        self.kind = kind
        self.capacity = max(2, int(capacity))
        self._od: "OrderedDict" = OrderedDict()
        self._size_fn = size_fn
        self._bytes = 0
        self._index_fn = index_fn
        self._ikey: dict = {}   # cache key -> its value's index key
        self._index: dict = {}  # index key -> the cache key find() names

    def __len__(self) -> int:
        return len(self._od)

    def __contains__(self, key) -> bool:
        return key in self._od

    def get(self, key):
        """Value for key (refreshing recency) or None."""
        v = self._od.get(key)
        if v is not None:
            self._od.move_to_end(key)
        return v

    def peek(self, key):
        """Value for key WITHOUT refreshing recency (scans)."""
        return self._od.get(key)

    def find(self, ikey):
        """The value the index names for `ikey`, WITHOUT refreshing
        recency: the newest inserted under it, or once that one left,
        the most recently used other; None without one or without an
        ``index_fn``."""
        key = self._index.get(ikey)
        return None if key is None else self._od.get(key)

    def put(self, key, value) -> None:
        old = self._od.get(key)
        if old is not None:
            self._forget(key, old)
        self._od[key] = value
        self._od.move_to_end(key)
        if self._size_fn is not None:
            self._bytes += self._size_fn(value)
        if self._index_fn is not None:
            ikey = self._index_fn(value)
            if ikey is not None:
                self._ikey[key] = ikey
                self._index[ikey] = key
        self._trim()

    def pop(self, key) -> None:
        v = self._od.pop(key, None)
        if v is not None:
            self._forget(key, v)

    def values(self) -> Iterator:
        return self._od.values()

    def clear(self) -> None:
        self._od.clear()
        self._bytes = 0
        self._ikey.clear()
        self._index.clear()

    def resident_bytes(self) -> int:
        return self._bytes

    def set_capacity(self, capacity: int) -> None:
        """Shrink takes effect immediately (evictions are counted)."""
        self.capacity = max(2, int(capacity))
        self._trim()

    def _trim(self) -> None:
        while len(self._od) > self.capacity:
            k, v = self._od.popitem(last=False)
            self._forget(k, v)
            STATS["evictions_" + self.kind] += 1

    def _forget(self, key, value) -> None:
        """Account for `value` leaving `key`: its bytes, and its index
        entry, which passes to the most recently used other entry of
        the same index key, or goes."""
        if self._size_fn is not None:
            self._bytes -= self._size_fn(value)
        ikey = self._ikey.pop(key, None)
        if ikey is None or self._index.get(ikey) != key:
            return
        del self._index[ikey]
        for k in reversed(self._od):
            if k != key and self._ikey.get(k) == ikey:
                self._index[ikey] = k
                return


# -- the cache instances ---------------------------------------------------
# LRU of built tables keyed by the pubkey-list content digest
# (order-sensitive: the validator INDEX is the gather key). Commit
# verification presents the same valset in the same order every block,
# so this hits ~always; epoch churn inserts one new table per epoch
# and the OLDEST retired epoch evicts. Indexed by key set: a set whose
# powers moved (and maybe its order with them) finds the newest table
# of its keys, whose columns it reuses.
TABLES = BoundedLRU("tables", 8, size_fn=default_size,
                    index_fn=table_key_set)
# (content key, mesh identity) -> ShardedValsetTable: a node serves one
# live valset per mesh in the steady state; churn evicts.
SHARDS = BoundedLRU("shard", 4, size_fn=default_size)
# id(pubs tuple) -> (pubs, powers, content key): the identity memo over
# the O(valset) content digest. Entries pin the tuples themselves —
# bounded so retired QuorumGroup valset tuples (10k pubkeys each) stop
# accumulating across epochs.
KEY_MEMO = BoundedLRU("key_memo", 16)
# id(ValidatorSet) -> (set, validators list, table): pins whole
# ValidatorSet objects (10k Validator dataclasses per epoch) — the
# biggest host-side churn leak surface, bounded here.
VALSET_MEMO = BoundedLRU("valset_memo", 8)
# stamp-site content key -> device-resident encoded template (ISSUE 19
# device-side sign-bytes stamping). One entry per template family the
# delta path flushes against (~a few hundred bytes each, next to the
# valset window tables it rides with). Same live-entry safety as the
# tables: capacity >= 2, every hit refreshes recency, and a plan that
# holds an entry keeps its device buffers alive even across an evict —
# the live template is never freed mid-flush.
TEMPLATES = BoundedLRU("templates", 8, size_fn=default_size)

_CACHES = {"tables": TABLES, "shard_tables": SHARDS,
           "key_memo": KEY_MEMO, "valset_memo": VALSET_MEMO,
           "templates": TEMPLATES}


def set_capacities(tables: Optional[int] = None,
                   shard_tables: Optional[int] = None,
                   key_memo: Optional[int] = None,
                   valset_memo: Optional[int] = None,
                   templates: Optional[int] = None) -> None:
    """Configure cache capacities ([crypto] table_cache_* knobs).
    Each is clamped to >= 2 (capacity 1 would let a next-epoch warm
    insert evict the LIVE epoch's table mid-flush)."""
    with LOCK:
        if tables is not None:
            TABLES.set_capacity(tables)
        if shard_tables is not None:
            SHARDS.set_capacity(shard_tables)
        if key_memo is not None:
            KEY_MEMO.set_capacity(key_memo)
        if valset_memo is not None:
            VALSET_MEMO.set_capacity(valset_memo)
        if templates is not None:
            TEMPLATES.set_capacity(templates)


def capacities() -> dict:
    with LOCK:
        return {name: c.capacity for name, c in _CACHES.items()}


def stats() -> dict:
    with LOCK:
        return dict(STATS)


def snapshot_values(kind: str) -> list:
    """The entries of one cache, snapshotted under :data:`LOCK`
    WITHOUT refreshing recency — the device observatory's residency
    sampler (libs/deviceledger) walks these to attribute per-device
    bytes/slots; a scrape must never perturb eviction order."""
    with LOCK:
        return list(_CACHES[kind]._od.values())


def resident_bytes() -> int:
    """Host+device bytes pinned by the TABLE caches (the memo caches
    pin only references whose owners are sized elsewhere)."""
    with LOCK:
        return TABLES.resident_bytes() + SHARDS.resident_bytes()


# -- warmer attribution ----------------------------------------------------
# Content keys the next-epoch warmer pre-built, awaiting their first
# lookup: the first post-rotation hit on one consumes it and counts a
# warmed_hit — the honest signal that the warmer (not steady-state
# reuse) saved the cold build. Bounded: a warmer that outruns lookups
# must not grow without bound.
_WARMED: "OrderedDict" = OrderedDict()
_WARMED_MAX = 16


def note_warmed(key: bytes) -> None:
    with LOCK:
        _WARMED[key] = True
        _WARMED.move_to_end(key)
        while len(_WARMED) > _WARMED_MAX:
            _WARMED.popitem(last=False)


def consume_warmed(key: bytes) -> bool:
    """True (once) when `key` was pre-built by the warmer; counts the
    warmed_hit. Callers hold :data:`LOCK` via their own cache path or
    call this bare — the RLock makes both safe."""
    with LOCK:
        if _WARMED.pop(key, None) is not None:
            STATS["warmed_hits"] += 1
            return True
        return False


def reset_for_tests() -> None:
    """Clear every cache, stat, and warm mark (test isolation only)."""
    with LOCK:
        for c in _CACHES.values():
            c.clear()
        _WARMED.clear()
        for k in STATS:
            STATS[k] = 0
