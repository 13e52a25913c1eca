"""Cached-valset ed25519 verification: per-validator window tables.

The general Pallas kernel (ops.ed25519_pallas) pays, per signature, a
full point decompression of the pubkey A plus 252 accumulator doublings
for h*(-A). But consensus verifies thousands of commits against the SAME
validator set — valsets change slowly (one update per block at most), so
the A-side work hoists into a device-resident table built once per
valset (and incrementally patched on epoch churn, `update_table`):

  for each validator, precompute  [d] * (2^(32j) * (-A))  for the 8 base
  points j=0..7 and window digits d=0..15, stored in affine "niels" form
  (y-x, y+x, 2d*t). Then

      h*(-A) = sum_w 16^w * sum_j [digit_{8j+w}] * base_j

  is a Horner loop of only 7x4 = 28 doublings + 64 mixed adds (7 muls
  each) — versus 252 doublings + 63 unified adds (9 muls) + a 15-add
  per-signature table build in the general kernel.

Round-5 design (this file):
  * the table lives in the kernel's OWN input layout — tile i of a
    batch reads exactly table block (i mod M/128) via a static
    BlockSpec index_map, and the per-lane 16-way entry select is a
    4-level where-tree over in-VMEM int16 slices. (The round-4 design
    gathered entries with an MXU one-hot einsum OUTSIDE the kernel;
    its HBM traffic + transposes cost more than the curve math.)
  * the whole ZIP-215 check stays in ONE kernel: R decompression,
    8W == identity with a single width-doubled canonical pass. (A
    torsion-candidate variant that avoided decompressing R — compare
    W + T over E[8] against the R encoding — was built, oracle-
    validated and benchmarked this round; its XLA epilogue cost more
    than the sqrt chain it removed, 18 vs 11.6 ms resident at 10k
    sigs, so it was reverted. See git history.)
  * voting power rides in the table (valset data), so per-commit
    uploads carry only R/s/h/flags — 27 rows = 108 B/signature.

This mirrors the amortization the reference gets from its ed25519 batch
verifier over long-lived validator sets (crypto/ed25519/ed25519.go:
208-241 BatchVerifier; types/validation.go:153 verifyCommitBatch;
types/validator_set.go:589-651 updateWithChangeSet for the churn path).

Semantics are identical ZIP-215 (differential tests against the
pure-Python oracle incl. small-order/non-canonical/-0 edge cases in
tests/test_ed25519_cached).
"""
from __future__ import annotations

import functools
import hashlib
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.libs import tracing
from cometbft_tpu.libs.deviceledger import rows_bucket
from cometbft_tpu.ops import table_cache as tc
from cometbft_tpu.ops import curve25519 as curve
from cometbft_tpu.ops import ed25519_kernel as ek
from cometbft_tpu.ops.field import F25519, NLIMBS
from cometbft_tpu.ops.ed25519_pallas import (
    B_TILE,
    F,
    _D_T,
    _D2_T,
    _M13,
    _SQRT_M1_T,
    decompress,
    pt_add,
    pt_add_noT,
    pt_double,
    pt_double_p,
    pt_identity,
    pt_neg,
)
from cometbft_tpu.ops.field_lf import const_col, interpret_mode

NJ = 8          # split bases per validator: base_j = 2^(32j) * (-A)
NW = 8          # 4-bit Horner windows per base (8*8 nibbles = 256 bits)
NENT = 16       # table entries per (validator, base): [0..15] * base_j
# niels form: (y-x, y+x, 2d*t) = 60 limb rows, padded to 64 so every
# in-kernel entry slice is 8-sublane aligned (Mosaic generates slow
# rotation code for misaligned dynamic sublane slices)
NIELS_ROWS = 3 * NLIMBS
ROWS_PER_ENT = 64

# Compact packed-row layout for the cached path. No pubkey rows (the
# table IS the pubkey), no validator-index row (vidx[b] == b mod M by
# construction, so the device derives it from an iota), and no power
# rows (voting power is VALSET data — it rides in the device table,
# uploaded once per valset, not per commit). Every row left is upload a
# commit pays again.
V_RY = 0        # 10 rows: sig R y limb pairs, word = l[i] | l[i+10] << 13
V_S8 = 10       # 8 rows: byte digits of s (comb), digit d at row d%8
V_H4 = 18       # 8 rows: nibble digits of h, digit d at row d%8
V_FLAGS = 26    # rsign | precheck<<1 | counted<<2 | commit_id<<3
V_KROWS = 27    # kernel block height (rows below are tally-side only)
V_THRESH = 27   # flattened (n_commits, TALLY_LIMBS) thresholds


# --------------------------------------------------------------------------
# table build (XLA, once per validator set)
# --------------------------------------------------------------------------


@jax.jit
def _build_core(ay, asign):
    """(n, NLIMBS) pubkey y limbs + (n,) sign bits -> niels window table.

    Returns (tbl (n*128, 60) int32, ok (n,) bool). Entry layout:
    row (v*128 + j*16 + d) holds [d] * (2^(32j) * (-A_v)) as canonical
    (y-x, y+x, 2d*t) limbs; invalid pubkeys get identity entries with
    ok=False (identity keeps every Z nonzero for the batched inversion).
    """
    n = ay.shape[0]
    A, ok = curve.decompress(ay, asign)
    negA = curve.select(ok, curve.neg(A), curve.identity((n,)))

    bases = [negA]
    for _ in range(NJ - 1):
        bases.append(
            jax.lax.fori_loop(
                0, 32, lambda i, p: curve.double(p), bases[-1]
            )
        )
    flat = jnp.stack(bases).reshape(NJ * n, 4, NLIMBS)  # (8n, 4, L)

    ident = curve.identity((NJ * n,))

    def ent_step(prev, _):
        nxt = curve.add(prev, flat)
        return nxt, nxt

    _, ents = jax.lax.scan(ent_step, ident, None, length=NENT - 1)
    ents = jnp.concatenate([ident[None], ents], axis=0)  # (16, 8n, 4, L)
    # -> (j, d) major over a 128-long inversion chain per validator
    ents = (
        ents.reshape(NENT, NJ, n, 4, NLIMBS)
        .transpose(1, 0, 2, 3, 4)
        .reshape(NJ * NENT, n, 4, NLIMBS)
    )
    X, Y, Z = ents[:, :, 0], ents[:, :, 1], ents[:, :, 2]

    # Montgomery batch inversion of all 128 Z's per validator: one
    # Fermat inversion + ~3x128 muls instead of 128 inversions.
    one = jnp.zeros_like(Z[0]).at[..., 0].set(1)

    def fwd(carry, z):
        return F25519.mul(carry, z), carry  # emit EXCLUSIVE prefix

    total, pref = jax.lax.scan(fwd, one, Z)
    inv_total = F25519.inv(total)

    def bwd(carry, zp):
        z, p = zp
        return F25519.mul(carry, z), F25519.mul(carry, p)

    _, invs = jax.lax.scan(bwd, inv_total, (Z, pref), reverse=True)

    x = F25519.mul(X, invs)
    y = F25519.mul(Y, invs)
    ym = F25519.canonical(F25519.sub(y, x))
    yp = F25519.canonical(F25519.add(y, x))
    t2d = F25519.canonical(
        F25519.mul(F25519.mul(x, y), jnp.asarray(curve._D2))
    )
    tbl = jnp.stack([ym, yp, t2d], axis=2)  # (128, n, 3, L)
    tbl = tbl.transpose(1, 0, 2, 3).reshape(n * NJ * NENT, NIELS_ROWS)
    tbl = jnp.pad(tbl, ((0, 0), (0, ROWS_PER_ENT - NIELS_ROWS)))
    return tbl.astype(jnp.int32), ok


@jax.jit
def _blocked_i16(tbl):
    """(M*128, 64) int32 -> one (M/128 * 8192, 128) int16 array.

    Kernel-native layout: row (blk*8192 + e*64 + r), lane v%128 holds
    limb-row r of entry e for validator v = blk*128 + lane. Tile i of a
    verification batch reads exactly block (i mod M/128) — a static
    BlockSpec index_map, so the "gather" costs nothing outside the
    kernel (the round-4 einsum gather burnt ~7 ms/10k-batch in HBM
    traffic + transposes). Canonical 13-bit limbs fit int16 exactly —
    same bytes as an int8 lo/hi split but half the in-kernel select
    ops."""
    M = tbl.shape[0] // (NJ * NENT)
    t = tbl.reshape(M // 128, 128, NJ * NENT * ROWS_PER_ENT)
    t = t.transpose(0, 2, 1).reshape(-1, 128)
    return t.astype(jnp.int16)


ENT_BLOCK = NJ * NENT * ROWS_PER_ENT  # 8192 table rows per 128 validators


class ValsetTable:
    """Device-resident window table for one validator set.

    n_vals is the PADDED size M (multiple of 128); verification batches
    must carry vidx[b] == b mod M (commit rows are naturally in valset
    order, so this holds by construction — see pack_rows_cached).

    Voting power lives here too: it is valset data, so it uploads once
    with the table instead of riding every per-commit row batch."""

    def __init__(self, tab, ok, power5, n_vals: int,
                 pubs_host: Optional[tuple] = None,
                 powers_host: Optional[np.ndarray] = None,
                 pub_raw=None):
        self.tab = tab          # (M/128 * 8192, 128) int16, device
        self.ok = ok            # (M,) bool, device
        self.power5 = power5    # (M, POWER_LIMBS) int32, device
        self.n_vals = n_vals
        # (M, 32) uint8 device copy of the raw pubkeys: the A operand
        # the device stamping prologue hashes (SHA-512(R||A||msg)) when
        # a flush ships deltas instead of packed rows. Valset data like
        # power5 — rides the table upload, never the per-flush stage.
        # None (pre-stamping tables, stub builders) disables the delta
        # path for this table; fused.plan_fused falls back to host pack.
        self.pub_raw = pub_raw
        # per-slot ACTUAL pubkey bytes + host power copy — lets
        # table_for_pubs find a cached table of the same keys (the key-
        # set index) or a near-miss one and compute the exact (pubkey,
        # power) delta without a device round trip.
        # Full bytes, not digests: the round-5 advisory showed an
        # 8-byte unkeyed digest lets a 2^32-work birthday collision
        # pin a retired key into cached tables (the reference likewise
        # compares whole keys in updateWithChangeSet).
        self.pubs_host = pubs_host
        self.powers_host = powers_host


def table_pad(n: int) -> int:
    """Padded table size M: >= 128 (one lane tile) and bucketed."""
    return max(128, ek.bucket_size(max(n, 1)))


def _pubs_host(pub_bytes: Sequence[bytes], padded: int) -> tuple:
    """Padded per-slot pubkey bytes (b"" for dead slots)."""
    out = list(pub_bytes[:padded])
    out.extend(b"" for _ in range(padded - len(out)))
    return tuple(out)


def _power_dev(powers, padded: int):
    p5 = np.zeros((padded, ek.POWER_LIMBS), np.int32)
    if powers is not None:
        n = len(powers)
        p5[:n] = ek.power_limbs(np.asarray(powers, np.int64))
    return jax.device_put(p5)


def _powers_host(powers, padded: int) -> np.ndarray:
    ph = np.zeros((padded,), np.int64)
    if powers is not None:
        ph[: len(powers)] = np.asarray(powers, np.int64)
    return ph


def _pub_raw(pub_bytes: Sequence[bytes], padded: int):
    """(padded, 32) uint8 device array of the raw pubkey bytes (dead
    and malformed slots zero). Separate from _pack_pub_arrays on
    purpose: that helper's (ay, asign, lenok) return is aliased by the
    shardplane test prog and must keep its arity."""
    a = np.zeros((padded, 32), np.uint8)
    for i, p in enumerate(pub_bytes[:padded]):
        if len(p) == 32:
            a[i] = np.frombuffer(p, np.uint8)
    return jax.device_put(a)


def _pack_pub_arrays(pub_bytes: Sequence[bytes], padded: int):
    a_raw = np.zeros((padded, 32), np.uint8)
    lenok = np.zeros(padded, np.bool_)
    for i, p in enumerate(pub_bytes):
        if len(p) == 32:
            a_raw[i] = np.frombuffer(p, np.uint8)
            lenok[i] = True
    ay = F25519.from_bytes_le(a_raw, nbits=255)
    asign = (a_raw[:, 31] >> 7).astype(np.int32)
    return ay, asign, lenok


def build_table(pub_bytes: Sequence[bytes],
                powers=None) -> ValsetTable:
    """Build the device table for a list of 32-byte ed25519 pubkeys."""
    n = len(pub_bytes)
    padded = table_pad(n)
    ay, asign, lenok = _pack_pub_arrays(pub_bytes, padded)
    tbl, ok = _build_core(jnp.asarray(ay), jnp.asarray(asign))
    ok = ok & jnp.asarray(lenok)
    return ValsetTable(_blocked_i16(tbl), ok,
                       _power_dev(powers, padded),
                       padded, _pubs_host(pub_bytes, padded),
                       _powers_host(powers, padded),
                       _pub_raw(pub_bytes, padded))


# -- a set of cached keys (powers moved, maybe the order) ------------------
# The curve columns of a slot depend on its key alone, so a set whose
# keys, taken as a set, are those of a cached table needs no column
# built: same slots share the base's device arrays, other slots are
# one gather of them. Only the powers upload (M x POWER_LIMBS int32).

_CHURN_WARMED: set = set()  # padded sizes whose churn programs ran


@jax.jit
def _permute_slots(tab, ok, pub_raw, perm):
    """Slot i of the result holds slot perm[i] of the table: the window
    columns of the blocked (M/128 * ENT_BLOCK, 128) layout, ok and the
    raw keys. Gathers whole 16 KB columns, slot-major."""
    m = perm.shape[0]
    cols = (tab.reshape(m // 128, ENT_BLOCK, 128).transpose(0, 2, 1)
            .reshape(m, ENT_BLOCK))[perm]
    tab = (cols.reshape(m // 128, 128, ENT_BLOCK).transpose(0, 2, 1)
           .reshape(-1, 128))
    return tab, ok[perm], (None if pub_raw is None else pub_raw[perm])


def _rekey(base: ValsetTable, target: tuple, powers,
           padded: int) -> ValsetTable:
    """The table of `target` (padded per-slot keys) under `powers`,
    from `base`, a table of the same keys taken as a set: byte for byte
    what build_table would build, with no curve column computed.
    Counted under `rekeyed`, by a lookup and a warm alike (as
    `incremental_patches` counts a patch)."""
    with _TABLE_LOCK:
        _TABLE_STATS["rekeyed"] += 1
        warm = padded not in _CHURN_WARMED
        _CHURN_WARMED.add(padded)
    if warm:
        _warm_churn(base)
    power5 = _power_dev(powers, padded)
    ph = _powers_host(powers, padded)
    if base.pubs_host == target:  # powers moved, the order did not
        return ValsetTable(base.tab, base.ok, power5, padded,
                           base.pubs_host, ph, base.pub_raw)
    # every slot of one key holds the same columns (dead slots and
    # malformed keys alike), so any base slot of the key will do
    slot = {p: i for i, p in enumerate(base.pubs_host)}
    perm = np.fromiter((slot[p] for p in target), np.int32, padded)
    tab, ok, pub_raw = _permute_slots(base.tab, base.ok, base.pub_raw,
                                      jnp.asarray(perm))
    return ValsetTable(tab, ok, power5, padded, target, ph, pub_raw)


def _warm_churn(base: ValsetTable) -> None:
    """Run, once per padded size, the two programs that the later sets
    of a chain whose powers move will need: the slot gather of a
    re-sort, and the patch of a seat change (update_table, here slot 0
    rewritten with its own key; the result is dropped). Before sets
    were rekeyed, a chain's first power change compiled the patch.
    Without this warm, a seat change deep into the stream compiles it
    there: 38 s on a TPU v5e, with a cold compile cache."""
    _permute_slots(base.tab, base.ok, base.pub_raw,
                   jnp.arange(base.n_vals, dtype=jnp.int32))
    update_table(base, [(0, base.pubs_host[0])])


# -- incremental update (validator-set churn) ------------------------------

UPDATE_PAD = 128  # one lane tile: the epoch-delta build shape


@jax.jit
def _update_core(tab, ok, power5, ay, asign, lenok, idxs, sel,
                 new_p5, psel):
    """Device-pure incremental update — NOTHING round-trips the host:
    the built columns are scattered into the resident table in place.

    idxs: (UPDATE_PAD,) target slots (dead slots repeat slot 0 with
    sel=0). sel masks which slots actually write; psel which powers.
    """
    tbl, ok_new = _build_core.__wrapped__(ay, asign)
    ok_new = ok_new & lenok
    # built rows (v*128 + e, 64) -> per-validator (ENT_BLOCK,) column
    cols = tbl.reshape(UPDATE_PAD, ENT_BLOCK).astype(jnp.int16)

    def body(k, st):
        tab, ok, p5 = st
        i = idxs[k]
        col = jnp.where(
            sel[k] != 0, cols[k],
            jax.lax.dynamic_slice(
                tab, ((i // 128) * ENT_BLOCK, i % 128), (ENT_BLOCK, 1)
            )[:, 0],
        )
        tab = jax.lax.dynamic_update_slice(
            tab, col[:, None], ((i // 128) * ENT_BLOCK, i % 128))
        ok = ok.at[i].set(jnp.where(sel[k] != 0, ok_new[k], ok[i]))
        p5 = p5.at[i].set(jnp.where(psel[k] != 0, new_p5[k], p5[i]))
        return tab, ok, p5

    return jax.lax.fori_loop(0, UPDATE_PAD, body, (tab, ok, power5))


def update_table(table: ValsetTable, changes,
                 powers_by_idx=None) -> ValsetTable:
    """Incremental table update for a validator-set delta.

    changes: list of (index, pubkey_bytes) for slots whose key changed
    (or appeared — index may extend up to the table's padded size).
    powers_by_idx: optional {index: power} for slots whose power
    changed (power changes alone don't touch the curve table).

    Epoch churn touches a handful of validators
    (types/validator_set.go:589-651 updateWithChangeSet); rebuilding
    all 10k costs a full table build (~1 s warm), while this path
    builds only the changed windows (128-slot bucket) and scatters
    them in place on device.
    """
    idx_list = [i for i, _ in changes]
    if not all(0 <= i < table.n_vals for i in idx_list):
        raise ValueError("change index beyond the table's padded size")
    pw_items = list((powers_by_idx or {}).items())
    if not all(0 <= i < table.n_vals for i, _ in pw_items):
        raise ValueError("power index beyond the table's padded size")
    # slots needing a write: key changes plus power-only changes that
    # don't coincide with a key change
    extra_pw = [i for i, _ in pw_items if i not in set(idx_list)]
    if len(idx_list) + len(extra_pw) > UPDATE_PAD:
        raise ValueError(
            f"delta of {len(idx_list)}+{len(extra_pw)} slots exceeds "
            f"UPDATE_PAD={UPDATE_PAD}; rebuild the table instead"
        )
    if not changes and not pw_items:
        return table
    pubs = [p for _, p in changes]
    ay, asign, lenok = _pack_pub_arrays(pubs, UPDATE_PAD)
    idxs = np.zeros(UPDATE_PAD, np.int32)
    sel = np.zeros(UPDATE_PAD, np.int32)
    idxs[: len(idx_list)] = idx_list
    sel[: len(idx_list)] = 1
    new_p5 = np.zeros((UPDATE_PAD, ek.POWER_LIMBS), np.int32)
    psel = np.zeros(UPDATE_PAD, np.int32)
    # power updates ride the same padded loop: slot k of the loop may
    # write table column idxs[k] and/or power row pidx[k]; merge power
    # targets into free slots' idxs when they don't coincide
    pw_map = dict(pw_items)
    for k, i in enumerate(idx_list):
        if i in pw_map:
            new_p5[k] = ek.power_limbs(
                np.asarray([pw_map.pop(i)], np.int64))[0]
            psel[k] = 1
    free = len(idx_list)
    for i, pw in pw_map.items():
        assert free < UPDATE_PAD, "too many combined updates"
        idxs[free] = i
        new_p5[free] = ek.power_limbs(np.asarray([pw], np.int64))[0]
        psel[free] = 1
        free += 1
    tab, ok, power5 = _update_core(
        table.tab, table.ok, table.power5, jnp.asarray(ay),
        jnp.asarray(asign), jnp.asarray(lenok), jnp.asarray(idxs),
        jnp.asarray(sel), jnp.asarray(new_p5), jnp.asarray(psel),
    )
    pubs_host = None
    if table.pubs_host is not None:
        lst = list(table.pubs_host)
        for (i, p) in changes:
            lst[i] = p
        pubs_host = tuple(lst)
    ph = None
    if table.powers_host is not None:
        ph = table.powers_host.copy()
        for i, pw in pw_items:
            ph[i] = pw
    # pub_raw is tiny (M*32 bytes vs the 2 MB/128-slot curve table), so
    # unlike the window columns a host-side patch + re-upload is cheaper
    # than any device scatter program
    pr = table.pub_raw
    if pr is not None and changes:
        if pubs_host is not None:
            pr = _pub_raw(pubs_host, table.n_vals)
        else:
            arr = np.asarray(pr).copy()
            for i, p in changes:
                arr[i] = (np.frombuffer(p, np.uint8)
                          if len(p) == 32 else 0)
            pr = jax.device_put(arr)
    return ValsetTable(tab, ok, power5, table.n_vals, pubs_host, ph,
                       pr)


# The whole cache stack below (built tables, sharded tables, the two
# identity memos) is BOUNDED and EVICTING: instances, capacities,
# eviction/warm accounting, and the shared lock live in the jax-free
# cometbft_tpu.ops.table_cache — epoch churn retires one valset per
# epoch and the retired epochs' tables must not accumulate forever
# (ROADMAP item 5). This module wires the kernel-side lookups through
# those caches.
#
# _TABLE_CACHE: LRU of built tables keyed by the pubkey list
# (order-sensitive: the validator INDEX is the gather key). Commit
# verification presents the same valset in the same order every block,
# so this hits ~always; on a miss, a cached table of the same keys in
# any order (a power change) is rekeyed without building a column, and
# one for a near-identical list (epoch churn) is updated incrementally
# instead of rebuilt.
_TABLE_CACHE = tc.TABLES
_TABLE_LOCK = tc.LOCK
_TABLE_STATS = tc.STATS
MAX_INCREMENTAL = 64  # fall back to full rebuild above this delta

note_warmed = tc.note_warmed  # the warmer's attribution seam


def table_cache_stats() -> dict:
    """Steady-state observability + the zero-copy hot path's regression
    guard: a healthy consensus stream should be ~all hits. shard_* count
    the per-mesh sharded-table cache the multichip verify plane rides
    (steady-state sharded flushes must be all shard_hits — zero table
    re-uploads); evictions_* count churn-pressure drops per bounded
    cache; warmed_hits count lookups the next-epoch warmer pre-built."""
    return tc.stats()


def table_cache_resident_bytes() -> int:
    """Bytes pinned by the (bounded) table caches — the figure epoch
    churn must hold flat; /metrics samples it at scrape time."""
    return tc.resident_bytes()


def _cache_key(pub_bytes: Sequence[bytes], powers) -> bytes:
    h = hashlib.sha256()
    for p in pub_bytes:
        # length-prefix each key so the digest is injective over the
        # list (bare concat collides when key lengths vary, mapping a
        # signature to the wrong slot's table entries)
        h.update(len(p).to_bytes(8, "big"))
        h.update(p)
    if powers is not None:
        for pw in powers:
            h.update(int(pw).to_bytes(8, "big", signed=True))
    return h.digest() + len(pub_bytes).to_bytes(4, "big")


# Identity memo over the content key: _cache_key walks every pubkey in
# Python (~ms at 10k validators), which used to run on EVERY flush.
# Callers that present a stable immutable key list (QuorumGroup's
# valset_pubs tuple, StreamVerifier's per-valset columns) pay it once.
# Entries pin the tuples themselves, so an id() can never alias a
# collected object — and the cache is bounded (tc.KEY_MEMO), so
# retired epochs' QuorumGroup tuples stop accumulating.
_KEY_MEMO = tc.KEY_MEMO


def _memo_cache_key(pub_bytes, powers) -> bytes:
    if type(pub_bytes) is not tuple or not (
        powers is None or type(powers) is tuple
    ):
        return _cache_key(pub_bytes, powers)  # mutable: never memoize
    with _TABLE_LOCK:
        ent = _KEY_MEMO.get(id(pub_bytes))
        if ent is not None and ent[0] is pub_bytes and ent[1] is powers:
            _TABLE_STATS["key_memo_hits"] += 1
            return ent[2]
    key = _cache_key(pub_bytes, powers)
    with _TABLE_LOCK:
        _KEY_MEMO.put(id(pub_bytes), (pub_bytes, powers, key))
    return key


def _find_incremental_base(target, padded: int):
    """Newest cached table with the same padded size and at most
    MAX_INCREMENTAL changed slots, plus the changed indices — or None.
    Callers hold _TABLE_LOCK. The delta compares FULL pubkey bytes —
    a digest here would make cache reuse collidable (round-5 advisory
    high)."""
    for cand in reversed(list(_TABLE_CACHE.values())):
        if cand.n_vals != padded or cand.pubs_host is None:
            continue
        diff = [i for i in range(padded)
                if cand.pubs_host[i] != target[i]]
        if len(diff) <= MAX_INCREMENTAL:
            return cand, diff
    return None


def _patch_from_base(cand: ValsetTable, diff, target, powers,
                     padded: int) -> Optional[ValsetTable]:
    """Patch `cand`'s delta rows into the target valset's table
    (update_table runs the SAME per-slot program build_table would, so
    the result is byte-identical to a cold full build). Returns None
    when the delta overflows update_table's slot budget — callers pay
    the full rebuild. Only CHANGED powers ride the update (the full
    map crashed update_table's slot budget for valsets > 128 and
    rewrote every power row). powers=None means ZERO powers — same as
    a cold build_table(pubs, None) — so tally semantics never depend
    on whether the lookup hit the near-miss cache (round-5 advisory
    low)."""
    changes = [(int(i), target[i]) for i in diff]
    new_ph = _powers_host(powers, padded)
    old_ph = (cand.powers_host if cand.powers_host is not None
              else np.zeros((padded,), np.int64))
    pw_map = {int(i): int(new_ph[i])
              for i in np.nonzero(new_ph != old_ph)[0]}
    try:
        t = update_table(cand, changes, pw_map)
    except ValueError:
        return None  # delta too large: full rebuild on the caller
    with _TABLE_LOCK:
        _TABLE_STATS["incremental_patches"] += 1
    return t


def table_for_pubs_info(pub_bytes: Sequence[bytes],
                        powers=None) -> Tuple[ValsetTable, bool]:
    """(table, warm): warm=True when the lookup was a straight LRU hit
    — no rekey, no build and no incremental patch. The verify plane
    stamps this into the flush ledger's `warm` column so /dump_flushes
    attributes a post-rotation stall to the cold table build it
    actually paid.

    A table the cache lacks is made by the cheapest of three paths,
    each a `table.make` stage whose `path` arg names it: "rekeyed"
    where a cached table holds the same keys, taken as a set (a power
    change, or a re-sort by power: no curve column computed; counted
    under `rekeyed`), else "patched" from a near-miss table or
    "built" in full (both counted under `misses`)."""
    key = _memo_cache_key(pub_bytes, powers)
    with _TABLE_LOCK:
        t = _TABLE_CACHE.get(key)
        if t is not None:
            _TABLE_STATS["hits"] += 1
            tc.consume_warmed(key)
            return t, True
        padded = table_pad(len(pub_bytes))
        target = _pubs_host(pub_bytes, padded)
        same, base = _bases(target, padded)
        if same is None:
            _TABLE_STATS["misses"] += 1
    # no tracer event: a table is made on whichever thread needs it
    # first (the warmer's among them), at stamps a simnet's virtual
    # clock cannot make repeat (tracing.stage_untraced)
    with tracing.stage_untraced("table.make", n=padded) as st:
        t = None
        if same is not None:
            t, path = _rekey(same, target, powers, padded), "rekeyed"
        elif base is not None:
            t = _patch_from_base(*base, target, powers, padded)
            path = "patched"
        if t is None:  # no base, or its delta overflows the patch
            t, path = build_table(pub_bytes, powers), "built"
        st.args["path"] = path
    with _TABLE_LOCK:
        _TABLE_CACHE.put(key, t)
    return t, False


def _bases(target, padded: int):
    """(same, near): the cached table the key-set index names for
    `target`'s keys, or None; and without one, the near-miss scan's
    (table, changed slots) — same padded size, few changed slots, to
    update incrementally (valset churn between epochs) — or None.
    Callers hold _TABLE_LOCK."""
    same = _TABLE_CACHE.find(tc.key_set(target))
    if same is not None:
        return same, None
    return None, _find_incremental_base(target, padded)


def warm_incremental(pub_bytes: Sequence[bytes], powers=None) -> bool:
    """The warmer's incremental fast path: a cached table of the same
    keys is rekeyed (`_rekey`), else when a cached near-miss table
    covers the change set (<= MAX_INCREMENTAL slots), its delta rows
    are patched into the cache instead of paying the full next-epoch
    build — byte-identical to the cold build either way. Returns True
    when the target table is now cached (already present, or made
    here); False means no eligible base exists and the caller decides
    whether to pay the full build. Counts neither a hit nor a miss:
    this is a warm, not a lookup."""
    key = _memo_cache_key(pub_bytes, powers)
    with _TABLE_LOCK:
        if _TABLE_CACHE.get(key) is not None:
            return True
        padded = table_pad(len(pub_bytes))
        target = _pubs_host(pub_bytes, padded)
        same, base = _bases(target, padded)
    if same is not None:
        t = _rekey(same, target, powers, padded)
    elif base is not None:
        t = _patch_from_base(*base, target, powers, padded)
        if t is None:
            return False
    else:
        return False
    with _TABLE_LOCK:
        _TABLE_CACHE.put(key, t)
    return True


def table_for_pubs(pub_bytes: Sequence[bytes],
                   powers=None) -> ValsetTable:
    return table_for_pubs_info(pub_bytes, powers)[0]


# Device-resident per-valset front cache: consensus and blocksync hold
# ONE ValidatorSet object per height window, so the (pubs, powers)
# column extraction + content-key digest hoist out of the per-flush
# path entirely — steady-state verification never re-reads the valset,
# let alone re-uploads it. Entries pin the set AND its validators list:
# update_with_change_set replaces the list wholesale, so a mutated set
# can never serve a stale table (the priority-only mutations of
# proposer rotation don't touch keys or powers) — and a ROTATED set's
# old entry becomes evictable dead weight the bounded cache drops.
_VALSET_MEMO = tc.VALSET_MEMO


def table_for_valset(vals) -> ValsetTable:
    """The device window table for a types.validator.ValidatorSet,
    memoized by set identity (mesh.py-style) over the content-keyed
    LRU. The fast path costs two dict probes, no per-validator work."""
    with _TABLE_LOCK:
        ent = _VALSET_MEMO.get(id(vals))
        if ent is not None and ent[0] is vals \
                and ent[1] is vals.validators:
            _TABLE_STATS["valset_hits"] += 1
            return ent[2]
    pubs = tuple(v.pub_key.data for v in vals.validators)
    powers = tuple(v.voting_power for v in vals.validators)
    t = table_for_pubs(pubs, powers)
    with _TABLE_LOCK:
        _TABLE_STATS["valset_misses"] += 1
        _VALSET_MEMO.put(id(vals), (vals, vals.validators, t))
    return t


# --------------------------------------------------------------------------
# sharded tables (multichip verify plane)
# --------------------------------------------------------------------------


class ShardedValsetTable:
    """One validator set's window table sharded across a device mesh.

    Device d of the mesh holds the table/ok/power columns for
    validators [d*m_shard, (d+1)*m_shard): tab/ok/power5 are GLOBAL
    jax arrays carrying the mesh NamedSharding, assembled zero-copy
    from per-device shards (make_array_from_single_device_arrays), so
    a sharded flush's jitted step does no resharding and no shard ever
    leaves its chip. m_shard is a table_pad bucket, which keeps the
    in-kernel `row mod M -> validator` map intact per device."""

    __slots__ = ("tab", "ok", "power5", "m_shard", "n_dev", "pub_raw")

    def __init__(self, tab, ok, power5, m_shard: int, n_dev: int,
                 pub_raw=None):
        self.tab = tab
        self.ok = ok
        self.power5 = power5
        self.m_shard = m_shard
        self.n_dev = n_dev
        # (n_dev*m_shard, 32) uint8 GLOBAL array, P(axis, None): device
        # d's slice holds its own validators' raw pubkeys, so the
        # sharded stamping prologue hashes A = pub_raw[row mod m_shard]
        # from purely local data. None disables delta staging.
        self.pub_raw = pub_raw


def shard_stride(n_vals: int, n_dev: int) -> int:
    """Per-device table stride M_s for an n_vals valset over n_dev
    devices: the table_pad bucket of the per-shard slice. Validator v
    lives on device v // M_s at local slot v % M_s. The ONE home of
    the sharded layout math — fused.plan_fused and the table builder
    must agree on it."""
    return table_pad(-(-max(n_vals, 1) // max(n_dev, 1)))


# (content key, mesh identity) -> ShardedValsetTable. Small and
# BOUNDED (tc.SHARDS): a node serves one live valset per mesh in the
# steady state; churn evicts the retired epochs' shard sets.
_SHARD_CACHE = tc.SHARDS


def sharded_table_for_pubs_info(pub_bytes: Sequence[bytes], powers,
                                mesh) -> Tuple[ShardedValsetTable, bool]:
    """The per-shard device-resident window table for (valset, mesh),
    memoized like table_for_pubs: the content key rides the same
    identity memo (_memo_cache_key — QuorumGroup's immutable tuples
    pay the O(valset) digest once), so a steady-state sharded flush
    uploads NOTHING. Accounting lands in table_cache_stats() under
    the shard_hits/shard_misses kinds. Returns (table, warm) like
    table_for_pubs_info (warm=True = straight cache hit)."""
    from cometbft_tpu.parallel import mesh as pm

    key = (_memo_cache_key(pub_bytes, powers), pm._mesh_key(mesh))
    with _TABLE_LOCK:
        t = _SHARD_CACHE.get(key)
        if t is not None:
            _TABLE_STATS["shard_hits"] += 1
            # the warmer marks sharded builds distinctly from plain
            # ones AND per mesh (the deck's two halves warm two
            # tables; each half's first post-rotation flush must
            # attribute its own hit)
            tc.consume_warmed((key[0], "shard", key[1]))
            return t, True
        _TABLE_STATS["shard_misses"] += 1
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = list(mesh.devices.flat)
    n_dev = len(devs)
    m_s = shard_stride(len(pub_bytes), n_dev)
    tabs, oks, p5s, prs = [], [], [], []
    for d, dev in enumerate(devs):
        lo = d * m_s
        chunk = list(pub_bytes[lo:lo + m_s])
        # pad the shard to exactly m_s slots: b"" keys decompress to
        # ok=False identity entries, power 0 — dead slots, same as the
        # single-device table's padding
        chunk.extend(b"" for _ in range(m_s - len(chunk)))
        pw = None
        if powers is not None:
            pw = list(powers[lo:lo + m_s])
            pw.extend(0 for _ in range(m_s - len(pw)))
        # build ON the target device; bypass the single-device LRU so
        # shard tables (committed to device d) never alias entries a
        # single-device lookup could serve from the wrong chip
        with jax.default_device(dev):
            st = build_table(chunk, pw)
        tabs.append(jax.device_put(st.tab, dev))
        oks.append(jax.device_put(st.ok, dev))
        p5s.append(jax.device_put(st.power5, dev))
        prs.append(jax.device_put(
            st.pub_raw if st.pub_raw is not None
            else jnp.zeros((m_s, 32), jnp.uint8), dev))
    axis = mesh.axis_names[0]
    mk = jax.make_array_from_single_device_arrays
    blocks = m_s // 128 * ENT_BLOCK
    t = ShardedValsetTable(
        mk((n_dev * blocks, 128), NamedSharding(mesh, P(axis, None)),
           tabs),
        mk((n_dev * m_s,), NamedSharding(mesh, P(axis)), oks),
        mk((n_dev * m_s, ek.POWER_LIMBS),
           NamedSharding(mesh, P(axis, None)), p5s),
        m_s, n_dev,
        mk((n_dev * m_s, 32), NamedSharding(mesh, P(axis, None)), prs),
    )
    with _TABLE_LOCK:
        _SHARD_CACHE.put(key, t)
    return t, False


def sharded_table_for_pubs(pub_bytes: Sequence[bytes], powers,
                           mesh) -> ShardedValsetTable:
    return sharded_table_for_pubs_info(pub_bytes, powers, mesh)[0]


# --------------------------------------------------------------------------
# niels-form base comb table (MXU matmul side)
# --------------------------------------------------------------------------

_BASE60_F32 = None
_BASE60_DEV = None


def base60_f32() -> np.ndarray:
    """[S]B comb table in niels form: (32*256, 60) float32, row
    (w*256 + d) = [d * 256^w]B as (y-x, y+x, 2d*t) limbs (< 2^13, so
    exact in f32)."""
    global _BASE60_F32
    if _BASE60_F32 is None:
        t = curve.base_table8_niels_np().reshape(32 * 256, NIELS_ROWS)
        _BASE60_F32 = np.ascontiguousarray(
            np.pad(t, ((0, 0), (0, ROWS_PER_ENT - NIELS_ROWS)))
        ).astype(np.float32)
    return _BASE60_F32


def base60_dev():
    global _BASE60_DEV
    if _BASE60_DEV is None:
        _BASE60_DEV = jax.device_put(base60_f32())
    return _BASE60_DEV


# the [S]B comb replicated across a mesh (the sharded fused flush's
# base argument): long-lived like base60_dev, one upload per mesh
_BASE60_REPL: dict = {}


def base60_repl(mesh):
    from cometbft_tpu.parallel import mesh as pm
    from jax.sharding import NamedSharding, PartitionSpec as P

    key = pm._mesh_key(mesh)
    dev = _BASE60_REPL.get(key)
    if dev is None:
        dev = _BASE60_REPL[key] = jax.device_put(
            base60_f32(), NamedSharding(mesh, P(None, None))
        )
    return dev


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------


def _madd_rows(p, e, b):
    """Mixed add of extended p with a niels entry (60, b) (7 muls)."""
    ym = e[0:NLIMBS]
    yp = e[NLIMBS:2 * NLIMBS]
    t2d = e[2 * NLIMBS:3 * NLIMBS]
    X1, Y1, Z1, T1 = p
    A = F.mul(F.sub(Y1, X1), ym)
    Bv = F.mul(F.add(Y1, X1), yp)
    C = F.mul(T1, t2d)
    Dv = F.mul_small(Z1, 2)
    E = F.sub(Bv, A)
    Fv = F.sub(Dv, C)
    G = F.add(Dv, C)
    H = F.add(Bv, A)
    return (F.mul(E, Fv), F.mul(G, H), F.mul(Fv, G), F.mul(E, H))


def _sel16(ref, j: int, d_row):
    """Per-lane 16-way entry select from an in-VMEM table block.

    ref rows (e*64 + r) for entries e of base j at static offsets;
    d_row (1, b) holds each lane's digit. A 4-level binary where-tree
    (15 selects on (64, b) int16) beats both the 16-term one-hot
    masked sum (31 ops) and the round-4 out-of-kernel MXU einsum
    (which cost more in HBM traffic + transposes than the curve math
    itself)."""
    base = j * NENT * ROWS_PER_ENT
    vals = [
        ref[pl.ds(base + e * ROWS_PER_ENT, ROWS_PER_ENT), :]
        for e in range(NENT)
    ]
    for k in range(4):
        m = (d_row & (1 << k)) != 0  # (1, b)
        vals = [
            jnp.where(m, vals[2 * i + 1], vals[2 * i])
            for i in range(len(vals) // 2)
        ]
    return vals[0]  # (64, b) int16


def _kernel(packed_ref, base_ref, tab_ref, valid_ref, s8_ref, h4_ref):
    b = B_TILE
    d_col = const_col(_D_T, b)
    d2_col = const_col(_D2_T, b)
    sqrt_m1_col = const_col(_SQRT_M1_T, b)

    pk = packed_ref[:, :]  # (V_KROWS, b)
    ry2 = pk[V_RY:V_RY + 10]
    ry = jnp.concatenate([ry2 & _M13, ry2 >> 13], axis=0)
    s8p = pk[V_S8:V_S8 + 8]
    s8_ref[:, :] = jnp.concatenate(
        [(s8p >> (8 * k)) & 255 for k in range(4)], axis=0
    )  # (32, b) byte digits
    h4p = pk[V_H4:V_H4 + 8]
    h4_ref[:, :] = jnp.concatenate(
        [(h4p >> (4 * k)) & 15 for k in range(8)], axis=0
    )  # (64, b) nibble digits; nibble t at row t
    flags = pk[V_FLAGS:V_FLAGS + 1]
    rsign = flags & 1
    pre = (flags >> 1) & 1

    R, ok_r = decompress(ry, rsign, d_col, sqrt_m1_col)

    # h*(-A): Horner over 8 window positions, 8 in-kernel-gathered
    # entries each. Lane l of this tile is validator (i*128 + l) mod M,
    # and tlo/thi_ref hold exactly table block (i mod M/128) via the
    # BlockSpec index_map — so the entry fetch is a static-offset
    # select, no HBM gather anywhere.
    def inner(pt, w):
        for j in range(NJ):  # nibble (8j + w) is base j's window-w digit
            d_row = h4_ref[pl.ds(NW * j + w, 1), :]
            ent = _sel16(tab_ref, j, d_row).astype(jnp.int32)
            pt = _madd_rows(pt, ent, b)
        return pt

    def win_body(i, pt):
        pt = pt_double(pt_double_p(pt_double_p(pt_double_p(pt))))
        return inner(pt, NW - 2 - i)

    acc = jax.lax.fori_loop(
        0, NW - 1, win_body, inner(pt_identity(b), NW - 1)
    )

    # [S]B comb: 32 width-8 windows, niels entries via f32 one-hot
    # matmul on the MXU (see ed25519_pallas for the precision argument).
    iota256 = jax.lax.broadcasted_iota(jnp.int32, (256, b), 0)

    def base_body(w, pt):
        d8 = s8_ref[pl.ds(w, 1), :]
        oh = (iota256 == d8).astype(jnp.float32)  # (256, b)
        t_w = base_ref[pl.ds(w * 256, 256), :]  # (256, 60) f32
        e = jax.lax.dot_general(
            t_w, oh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)  # (60, b)
        return _madd_rows(pt, e, b)

    sB = jax.lax.fori_loop(0, 32, base_body, pt_identity(b))

    W = pt_add_noT(pt_add(sB, acc, d2_col), pt_neg(R), d2_col)
    W8 = pt_double_p(pt_double_p(pt_double_p(W)))
    # identity check X8==0 ∧ Y8==Z8 with ONE canonical pass: the two
    # operands ride side-by-side on the lane axis, halving the
    # sequential carry-ripple depth.
    #
    # (A torsion-candidate design — compare W+T over E[8] against the
    # R encoding, no R decompression — was built, validated against
    # the oracle, and benchmarked in round 5: its XLA epilogue's
    # selects/canonicals/inversion cost MORE than the in-kernel sqrt
    # chain it removed, 18 ms vs 11.6 ms resident at 10k sigs, so the
    # decompress-R check stays. See git history for the variant.)
    both = F.canonical(
        jnp.concatenate([W8[0], F.sub(W8[1], W8[2])], axis=1)
    )
    z = jnp.all(both == 0, axis=0, keepdims=True)  # (1, 2b)
    eq = z[:, :b] & z[:, b:]
    valid = eq & ok_r & (pre != 0)
    valid_ref[:, :] = valid.astype(jnp.int32)


def _thresh_from_rows(rows, n_commits: int):
    """The per-commit thresholds packed into the trailing rows,
    zero-padded when the slice is short. A single-device caller always
    packs enough rows (packed_rows_shape); a LANE-SHARDED caller
    (mesh.sharded_fused_verify) packs ONE zero threshold row — its
    local slice holds B/n_dev elements, which can undercut
    n_commits*TALLY_LIMBS for many-group flushes, and real thresholds
    ride replicated out-of-band (the in-rows quorum output is
    discarded there). Without the pad, the reshape is a trace-time
    crash that would falsely trip the device breaker."""
    flat = rows[V_THRESH:].reshape(-1)
    need = n_commits * ek.TALLY_LIMBS
    if flat.size < need:
        flat = jnp.pad(flat, (0, need - flat.size))
    return flat[:need].reshape(n_commits, ek.TALLY_LIMBS)


@functools.partial(jax.jit, static_argnames=("n_commits",))
def _verify_tally_cached(rows, tab, ok, power5, base, n_commits: int):
    """Pallas verify with in-kernel table blocks + fused tally.

    Because vidx[b] == b mod M, tile i's 128 lanes are exactly the
    validators of table block (i mod M/128) — the whole block (2 MB
    int16) streams into VMEM via the BlockSpec index_map and the
    per-lane entry select happens inside the kernel. No gather, no
    materialized entry tensor (the round-4 einsum design wrote+read
    ~500 MB of HBM per 10k batch — more than the curve math cost)."""
    B = rows.shape[1]
    assert B % B_TILE == 0, f"B={B} not a multiple of {B_TILE}"
    mt = tab.shape[0] // ENT_BLOCK  # table blocks (M/128)
    M = mt * 128
    vidx = jax.lax.broadcasted_iota(jnp.int32, (B,), 0) % M

    grid = (B // B_TILE,)
    col = lambda r: pl.BlockSpec(
        (r, B_TILE), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    full = pl.BlockSpec(
        (32 * 256, ROWS_PER_ENT), lambda i: (0, 0),
        memory_space=pltpu.VMEM,
    )
    tblock = pl.BlockSpec(
        (ENT_BLOCK, 128), lambda i: (i % mt, 0),
        memory_space=pltpu.VMEM,
    )
    out = pl.pallas_call(
        _kernel,
        interpret=interpret_mode(),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        grid=grid,
        in_specs=[col(V_KROWS), full, tblock],
        out_specs=col(1),
        scratch_shapes=[
            pltpu.VMEM((32, B_TILE), jnp.int32),  # s byte digits
            pltpu.VMEM((64, B_TILE), jnp.int32),  # h nibble digits
        ],
    )(rows[:V_KROWS], base, tab)
    valid = (out[0] != 0) & jnp.take(ok, vidx, axis=0)

    # power comes from the valset table: row b is validator b mod M
    reps = -(-B // M)
    pw = jnp.tile(power5, (reps, 1))[:B]
    counted = (rows[V_FLAGS] >> 2) & 1 != 0
    commit_ids = rows[V_FLAGS] >> 3
    thresh = _thresh_from_rows(rows, n_commits)
    tally = ek.tally_core(valid, pw, counted, commit_ids, n_commits)
    return valid, tally, ek.quorum_core(tally, thresh)


# --------------------------------------------------------------------------
# host packing + entry points
# --------------------------------------------------------------------------


def packed_rows_shape(B: int, n_commits: int = 1) -> tuple:
    """Shape of the packed (R, B) array pack_rows_cached builds for a
    B-row flush carrying n_commits thresholds — the ONE home of the
    threshold-row layout math. Staging buffers handed to
    pack_rows_cached(out=...) MUST be sized through this, or the
    mismatch is silently ignored and the pooling benefit lost."""
    t_rows = max(1, -(-(n_commits * ek.TALLY_LIMBS) // B))
    return (V_THRESH + t_rows, B)


def pack_rows_cached(pb, counted=None, commit_ids=None,
                     thresh=None, out=None) -> np.ndarray:
    """PackedBatch -> one compact (R, B) int32 array for the cached path.

    Same single-transfer philosophy as ed25519_pallas.pack_rows, minus
    the 10 pubkey rows (the device table replaces them), any index row
    (row b's validator is b mod M by construction — callers MUST lay
    commits out in valset order padded to the table stride), and the
    power rows (valset data, carried by the table).

    `out` (optional) is a preallocated zeroed (R, B) int32 staging
    buffer — the pinned double-buffer path (libs/staging.py) — so a
    streaming dispatcher packs flush k+1 while the device copies/
    verifies flush k without allocator churn."""
    B = pb.ry.shape[0]
    if thresh is None:
        thresh = np.zeros((1, ek.TALLY_LIMBS), np.int32)
    tvals = np.asarray(thresh, np.int32).reshape(-1)
    t_rows = max(1, -(-tvals.size // B))
    if out is not None and out.shape == (V_THRESH + t_rows, B) \
            and out.dtype == np.int32:
        rows = out
    else:
        rows = np.zeros((V_THRESH + t_rows, B), np.int32)
    ry = np.asarray(pb.ry, np.int32)
    rows[V_RY:V_RY + 10] = (ry[:, :10] | (ry[:, 10:] << 13)).T
    s8 = (pb.sdig[:, 0::2] + 16 * pb.sdig[:, 1::2]).astype(np.int32)
    acc = np.zeros((B, 8), np.int32)
    for k in range(4):
        acc |= s8[:, 8 * k:8 * k + 8] << (8 * k)
    rows[V_S8:V_S8 + 8] = acc.T
    acc = np.zeros((B, 8), np.int32)
    h4 = np.asarray(pb.hdig, np.int32)
    for k in range(8):
        acc |= h4[:, 8 * k:8 * k + 8] << (4 * k)
    rows[V_H4:V_H4 + 8] = acc.T
    flags = (pb.rsign.astype(np.int32)
             | (pb.precheck.astype(np.int32) << 1))
    if counted is not None:
        flags = flags | (np.asarray(counted, np.int32) << 2)
    if commit_ids is not None:
        flags = flags | (np.asarray(commit_ids, np.int32) << 3)
    rows[V_FLAGS] = flags
    flat = rows[V_THRESH:].reshape(-1)
    flat[: tvals.size] = tvals
    return rows


# --------------------------------------------------------------------------
# device-side sign-bytes stamping (delta flushes)
# --------------------------------------------------------------------------
#
# A template-eligible flush ships (device-resident template, per-row
# deltas) instead of full packed rows: 64 B signature + 12 B timestamp
# words + 4 B flags per row, against the ~700 B/row the legacy host
# pack stages (scatter buffers + packed rows). The prologue below
# rebuilds the EXACT packed rows on device: LEB128-stamp the timestamp
# varints into the canonical sign-bytes (port of
# types/canonical.VoteRowTemplate.patch_rows), SHA-512 the
# R || A || msg input, reduce the digest mod L, and assemble the same
# (R, B) int32 layout pack_rows_cached builds — bit-identical by the
# differential tests in tests/test_sign_template.py. Everything is
# plain XLA (jnp), not Pallas: it is elementwise/gather work with no
# reuse to tile for, and staying XLA keeps it testable on the CPU
# tier-1 host without interpret-mode compiles.


class TemplateEntry:
    """Device-resident encoded stamp templates for one flush family: a
    row per StampSite (prefix bytes, suffix bytes, timestamp tag plus
    lengths), padded to bucketed shapes. Cached in tc.TEMPLATES under
    the sites' content key — same BoundedLRU discipline as the valset
    window tables (capacity >= 2, hits refresh recency, and a plan
    holding an entry keeps its device buffers alive across an evict,
    so the live template is never freed mid-flush)."""

    __slots__ = ("key", "pre_mat", "pre_len", "suf_mat", "suf_len",
                 "ts_tag", "n_sites", "msg_max", "nbytes")


MAX_TEMPLATE_SITES = 256  # tmpl_id rides 8 bits of the staged flags
# The site count is a SHAPE of the stamp jit (the template matrices'
# leading dimension), and one more compile of that program costs
# 10-80 s on a v5e depending on the batch (chip run, PR 21). A vote
# flush cites 1-4 sites and a streamed chunk anything up to its job
# capacity, so the matrices pad to at least this many rows, and a
# caller that knows its capacity pads to that (`pad_to`).
MIN_TEMPLATE_SITES = 8


def _bucket_up(n: int, q: int) -> int:
    return -(-max(int(n), 1) // q) * q


def _template_key(sites: tuple, pad_to: int) -> tuple:
    """(padded site count, cache key): the pad is part of the key, an
    entry padded for a vote flush is not the one a chunk compiled for."""
    t_pad = max(MIN_TEMPLATE_SITES, rows_bucket(max(len(sites), pad_to)))
    return t_pad, (t_pad,) + tuple(s.key for s in sites)


def template_entry(sites, pad_to: int = 0) -> TemplateEntry:
    """The device template matrices for a tuple of canonical.StampSite,
    via the bounded template cache (template_hits/template_misses in
    table_cache_stats()). Shapes bucket — pre/suf widths to 32 bytes,
    site count to a power of two >= max(MIN_TEMPLATE_SITES, pad_to),
    worst-case row length to 64 — so the stamp jit's compile key is
    stable across heights: heights are fixed-width sfixed64 in the
    prefix, so per-height content rides the device arrays, never the
    shapes."""
    sites = tuple(sites)
    if not 0 < len(sites) <= MAX_TEMPLATE_SITES:
        raise ValueError(
            f"{len(sites)} stamp sites (max {MAX_TEMPLATE_SITES})")
    t_pad, key = _template_key(sites, pad_to)
    with _TABLE_LOCK:
        ent = tc.TEMPLATES.get(key)
        if ent is not None:
            _TABLE_STATS["template_hits"] += 1
            tc.consume_warmed(("template",) + key)
            return ent
        _TABLE_STATS["template_misses"] += 1
    pm = _bucket_up(max(s.pre.size for s in sites), 32)
    sm = _bucket_up(max(s.suf.size for s in sites), 32)
    pre = np.zeros((t_pad, pm), np.uint8)
    suf = np.zeros((t_pad, sm), np.uint8)
    pl = np.zeros((t_pad,), np.int32)
    sl = np.zeros((t_pad,), np.int32)
    tg = np.zeros((t_pad,), np.int32)
    for i, s in enumerate(sites):
        pre[i, : s.pre.size] = s.pre
        suf[i, : s.suf.size] = s.suf
        pl[i] = s.pre.size
        sl[i] = s.suf.size
        tg[i] = s.ts_tag
    ent = TemplateEntry()
    ent.key = key
    ent.pre_mat = jax.device_put(pre)
    ent.pre_len = jax.device_put(pl)
    ent.suf_mat = jax.device_put(suf)
    ent.suf_len = jax.device_put(sl)
    ent.ts_tag = jax.device_put(tg)
    ent.n_sites = len(sites)
    ent.msg_max = _bucket_up(max(s.max_len for s in sites), 64)
    ent.nbytes = sum(int(a.nbytes) for a in
                     (ent.pre_mat, ent.pre_len, ent.suf_mat,
                      ent.suf_len, ent.ts_tag))
    with _TABLE_LOCK:
        tc.TEMPLATES.put(key, ent)
    return ent


def warm_template(sites) -> bool:
    """The warmer's template pre-build: builds AND marks only when the
    entry is absent (the PR 11 warm-attribution rules — a mark for an
    entry already cached would fake a warmed_hit). Returns True when a
    build actually happened."""
    sites = tuple(sites)
    _, key = _template_key(sites, 0)
    with _TABLE_LOCK:
        if key in tc.TEMPLATES:
            return False
    template_entry(sites)
    note_warmed(("template",) + key)
    return True


# -- 64-bit LEB128 varints from int32 words (no jax x64 anywhere) ----------


def _leb_pack(gs):
    """7-bit groups (lsb first) -> (LEB128 bytes, lengths). Length =
    last nonzero group + 1 (min 1); continuation bit on every byte
    before the last — exactly canonical._vec_uvarint's loop."""
    g = jnp.stack(gs, axis=1)  # (B, n)
    n = g.shape[1]
    idx = jnp.arange(1, n + 1, dtype=jnp.int32)
    lens = jnp.maximum(
        1, jnp.max(jnp.where(g != 0, idx[None, :], 0), axis=1))
    cont = idx[None, :] < lens[:, None]
    return g | jnp.where(cont, 0x80, 0), lens


def _dev_uvarint64(lo, hi):
    """(B,) int32 lo/hi words of a 64-bit two's-complement value ->
    ((B, 10) int32 LEB128 bytes, (B,) int32 lengths)."""
    lo = lo.astype(jnp.uint32)
    hi = hi.astype(jnp.uint32)
    gs = []
    for j in range(10):
        s = 7 * j
        if s + 7 <= 32:
            g = lo >> s
        elif s < 32:
            g = (lo >> s) | (hi << (32 - s))
        else:
            g = hi >> (s - 32)
        gs.append((g & 0x7F).astype(jnp.int32))
    return _leb_pack(gs)


def _dev_uvarint32(v):
    """(B,) small nonnegative int32 (the outer length prefix) ->
    ((B, 5) bytes, (B,) lengths)."""
    u = v.astype(jnp.uint32)
    gs = [((u >> (7 * j)) & 0x7F).astype(jnp.int32) for j in range(5)]
    return _leb_pack(gs)


# -- batched SHA-512 in (hi, lo) uint32 pairs ------------------------------

_SHA512_K = (
    0x428a2f98d728ae22, 0x7137449123ef65cd, 0xb5c0fbcfec4d3b2f,
    0xe9b5dba58189dbbc, 0x3956c25bf348b538, 0x59f111f1b605d019,
    0x923f82a4af194f9b, 0xab1c5ed5da6d8118, 0xd807aa98a3030242,
    0x12835b0145706fbe, 0x243185be4ee4b28c, 0x550c7dc3d5ffb4e2,
    0x72be5d74f27b896f, 0x80deb1fe3b1696b1, 0x9bdc06a725c71235,
    0xc19bf174cf692694, 0xe49b69c19ef14ad2, 0xefbe4786384f25e3,
    0x0fc19dc68b8cd5b5, 0x240ca1cc77ac9c65, 0x2de92c6f592b0275,
    0x4a7484aa6ea6e483, 0x5cb0a9dcbd41fbd4, 0x76f988da831153b5,
    0x983e5152ee66dfab, 0xa831c66d2db43210, 0xb00327c898fb213f,
    0xbf597fc7beef0ee4, 0xc6e00bf33da88fc2, 0xd5a79147930aa725,
    0x06ca6351e003826f, 0x142929670a0e6e70, 0x27b70a8546d22ffc,
    0x2e1b21385c26c926, 0x4d2c6dfc5ac42aed, 0x53380d139d95b3df,
    0x650a73548baf63de, 0x766a0abb3c77b2a8, 0x81c2c92e47edaee6,
    0x92722c851482353b, 0xa2bfe8a14cf10364, 0xa81a664bbc423001,
    0xc24b8b70d0f89791, 0xc76c51a30654be30, 0xd192e819d6ef5218,
    0xd69906245565a910, 0xf40e35855771202a, 0x106aa07032bbd1b8,
    0x19a4c116b8d2d0c8, 0x1e376c085141ab53, 0x2748774cdf8eeb99,
    0x34b0bcb5e19b48a8, 0x391c0cb3c5c95a63, 0x4ed8aa4ae3418acb,
    0x5b9cca4f7763e373, 0x682e6ff3d6b2b8a3, 0x748f82ee5defb2fc,
    0x78a5636f43172f60, 0x84c87814a1f0ab72, 0x8cc702081a6439ec,
    0x90befffa23631e28, 0xa4506cebde82bde9, 0xbef9a3f7b2c67915,
    0xc67178f2e372532b, 0xca273eceea26619c, 0xd186b8c721c0c207,
    0xeada7dd6cde0eb1e, 0xf57d4f7fee6ed178, 0x06f067aa72176fba,
    0x0a637dc5a2c898a6, 0x113f9804bef90dae, 0x1b710b35131c471b,
    0x28db77f523047d84, 0x32caab7b40c72493, 0x3c9ebe0a15c9bebc,
    0x431d67c49c100d4c, 0x4cc5d4becb3e42b6, 0x597f299cfc657e2a,
    0x5fcb6fab3ad6faec, 0x6c44198c4a475817,
)
_SHA512_H0 = (
    0x6a09e667f3bcc908, 0xbb67ae8584caa73b, 0x3c6ef372fe94f82b,
    0xa54ff53a5f1d36f1, 0x510e527fade682d1, 0x9b05688c2b3e6c1f,
    0x1f83d9abfb41bd6b, 0x5be0cd19137e2179,
)


def _pair_const(vals):
    a = np.asarray(vals, np.uint64)
    return (np.asarray(a >> np.uint64(32), np.uint32),
            np.asarray(a & np.uint64(0xFFFFFFFF), np.uint32))


_SHA_K_HI, _SHA_K_LO = _pair_const(_SHA512_K)
_SHA_H_HI, _SHA_H_LO = _pair_const(_SHA512_H0)


def _rotr_p(h, l, n: int):
    if n < 32:
        return ((h >> n) | (l << (32 - n)), (l >> n) | (h << (32 - n)))
    if n == 32:
        return l, h
    m = n - 32
    return ((l >> m) | (h << (32 - m)), (h >> m) | (l << (32 - m)))


def _shr_p(h, l, n: int):
    if n < 32:
        return h >> n, (l >> n) | (h << (32 - n))
    return jnp.zeros_like(h), h >> (n - 32)


def _xor3_p(a, b, c):
    return a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1]


def _add_p(a, b):
    lo = a[1] + b[1]
    hi = a[0] + b[0] + (lo < a[1]).astype(jnp.uint32)
    return hi, lo


def _sha512_blocks(data, nblk_row, nblk: int):
    """Batched SHA-512 over (B, nblk*128) int32 byte lanes. Rows stop
    absorbing after their own nblk_row blocks (per-row active mask) —
    padding and bit-length bytes are already in `data`. Returns the 8
    state words as (hi, lo) uint32 pairs. W extension and the 80
    rounds run as fori_loops so the traced graph stays small on the
    CPU tier-1 host."""
    B = data.shape[0]
    state = [(jnp.full((B,), _SHA_H_HI[i], jnp.uint32),
              jnp.full((B,), _SHA_H_LO[i], jnp.uint32))
             for i in range(8)]
    k_hi = jnp.asarray(_SHA_K_HI)
    k_lo = jnp.asarray(_SHA_K_LO)
    for j in range(nblk):
        blk = data[:, j * 128:(j + 1) * 128].astype(jnp.uint32)
        wh = jnp.zeros((80, B), jnp.uint32)
        wl = jnp.zeros((80, B), jnp.uint32)
        for t in range(16):
            hi = ((blk[:, 8 * t] << 24) | (blk[:, 8 * t + 1] << 16)
                  | (blk[:, 8 * t + 2] << 8) | blk[:, 8 * t + 3])
            lo = ((blk[:, 8 * t + 4] << 24) | (blk[:, 8 * t + 5] << 16)
                  | (blk[:, 8 * t + 6] << 8) | blk[:, 8 * t + 7])
            wh = wh.at[t].set(hi)
            wl = wl.at[t].set(lo)

        def w_ext(t, wp):
            wh, wl = wp
            x15 = (wh[t - 15], wl[t - 15])
            x2 = (wh[t - 2], wl[t - 2])
            s0 = _xor3_p(_rotr_p(*x15, 1), _rotr_p(*x15, 8),
                         _shr_p(*x15, 7))
            s1 = _xor3_p(_rotr_p(*x2, 19), _rotr_p(*x2, 61),
                         _shr_p(*x2, 6))
            nw = _add_p(_add_p((wh[t - 16], wl[t - 16]), s0),
                        _add_p((wh[t - 7], wl[t - 7]), s1))
            return wh.at[t].set(nw[0]), wl.at[t].set(nw[1])

        wh, wl = jax.lax.fori_loop(16, 80, w_ext, (wh, wl))

        def round_body(t, st):
            a = (st[0], st[1])
            b = (st[2], st[3])
            c = (st[4], st[5])
            d = (st[6], st[7])
            e = (st[8], st[9])
            f = (st[10], st[11])
            g = (st[12], st[13])
            h = (st[14], st[15])
            s1 = _xor3_p(_rotr_p(*e, 14), _rotr_p(*e, 18),
                         _rotr_p(*e, 41))
            ch = ((e[0] & f[0]) ^ (~e[0] & g[0]),
                  (e[1] & f[1]) ^ (~e[1] & g[1]))
            t1 = _add_p(_add_p(_add_p(h, s1), ch),
                        _add_p((k_hi[t], k_lo[t]), (wh[t], wl[t])))
            s0 = _xor3_p(_rotr_p(*a, 28), _rotr_p(*a, 34),
                         _rotr_p(*a, 39))
            maj = ((a[0] & b[0]) ^ (a[0] & c[0]) ^ (b[0] & c[0]),
                   (a[1] & b[1]) ^ (a[1] & c[1]) ^ (b[1] & c[1]))
            t2 = _add_p(s0, maj)
            ne = _add_p(d, t1)
            na = _add_p(t1, t2)
            return (na[0], na[1], a[0], a[1], b[0], b[1], c[0], c[1],
                    ne[0], ne[1], e[0], e[1], f[0], f[1], g[0], g[1])

        init = tuple(x for p in state for x in p)
        fin = jax.lax.fori_loop(0, 80, round_body, init)
        act = nblk_row > j
        nxt = []
        for i in range(8):
            s = _add_p(state[i], (fin[2 * i], fin[2 * i + 1]))
            nxt.append((jnp.where(act, s[0], state[i][0]),
                        jnp.where(act, s[1], state[i][1])))
        state = nxt
    return state


def _digest_le_bytes(state):
    """SHA-512 state -> the 64 digest bytes as (B,) int32 lanes, in
    LITTLE-ENDIAN integer order (byte 0 = LSB of the 512-bit value the
    mod-L reduction consumes). The stream itself is big-endian per
    64-bit word, which is exactly this ordering read front to back."""
    out = []
    for i in range(8):
        for w in state[i]:
            for k in range(4):
                out.append(((w >> (24 - 8 * k)) & 0xFF)
                           .astype(jnp.int32))
    return out


# -- digest mod L in 13-bit int32 limbs ------------------------------------

_SC_L = ref.L
_SC_C = _SC_L - (1 << 252)          # L = 2^252 + c
_SC_C1 = _SC_C << 8                 # 2^260 === -c1 (mod L): limb-aligned


def _limbs13_int(v: int, n: int):
    return tuple((v >> (13 * i)) & 0x1FFF for i in range(n))


_C_LIMBS = _limbs13_int(_SC_C, 10)      # c  < 2^125
_C1_LIMBS = _limbs13_int(_SC_C1, 11)    # c1 < 2^133
_L_LIMBS13 = _limbs13_int(_SC_L, 20)
_L_U32 = tuple(int(w) for w in np.frombuffer(
    _SC_L.to_bytes(32, "little"), "<u4"))


def _fold_offset(n_conv: int):
    """A multiple of L, represented with per-limb headroom 2^30 over
    the first n_conv limbs, so `lo + offset - conv(hi, c1)` never goes
    negative in any lane (conv lanes are < 11 * 2^26 < 2^30). Keeps
    the whole fold chain in nonnegative int32 limbs."""
    s = sum(1 << (13 * k) for k in range(n_conv))
    r = (-(1 << 30) * s) % _SC_L
    m = max(n_conv, 20)
    v = [0] * m
    for k in range(n_conv):
        v[k] += 1 << 30
    for k, rl in enumerate(_limbs13_int(r, 20)):
        v[k] += rl
    return tuple(v)


_FOLD_OFFS = (_fold_offset(30), _fold_offset(22), _fold_offset(14))


def _carry13(y, extra: int):
    """Sequential carry propagation to canonical 13-bit limbs (int32
    arithmetic shift = floor semantics, so the same loop serves the
    signed 252-bit fold). `extra` top limbs absorb the final carry."""
    out = []
    carry = None
    for t in y:
        if carry is not None:
            t = t + carry
        out.append(t & 0x1FFF)
        carry = t >> 13
    for _ in range(extra):
        out.append(carry & 0x1FFF)
        carry = carry >> 13
    return out


def _fold_limbs(limbs, off):
    """One fold at the 2^260 limb boundary: x = lo + 2^260*hi ===
    lo - c1*hi (mod L), plus the nonneg offset. Canonical 13-bit limbs
    in, canonical out (len(off) + 2 limbs)."""
    lo, hi = limbs[:20], limbs[20:]
    n_conv = len(hi) + len(_C1_LIMBS) - 1
    zero = jnp.zeros_like(limbs[0])
    y = []
    for k in range(len(off)):
        t = (lo[k] if k < 20 else zero) + off[k]
        if k < n_conv:
            s = zero
            for i in range(len(hi)):
                j = k - i
                if 0 <= j < len(_C1_LIMBS):
                    s = s + hi[i] * _C1_LIMBS[j]
            t = t - s
        y.append(t)
    return _carry13(y, extra=2)


def _mod_l_nibbles(dig_bytes):
    """64 little-endian digest byte lanes -> the 64 base-16 digits of
    (digest mod L) — hdig, exactly `nibbles(digest % L as 32 LE
    bytes)` from the host pack. Three limb-aligned folds take 512 ->
    ~260 bits, a 252-bit fold lands in [0, 2L), and one conditional
    subtract canonicalizes."""
    zero = jnp.zeros_like(dig_bytes[0])
    pad = list(dig_bytes) + [zero] * 3
    limbs = []
    for i in range(40):
        j, r = divmod(13 * i, 8)
        win = pad[j] | (pad[j + 1] << 8) | (pad[j + 2] << 16)
        limbs.append((win >> r) & 0x1FFF)
    for off in _FOLD_OFFS:
        limbs = _fold_limbs(limbs, off)
    # 252-bit fold: x = q*2^252 + r === r + (L - q*c) in [0, 2L)
    q = (limbs[19] >> 5) | (limbs[20] << 8) | (limbs[21] << 21)
    y = []
    for k in range(20):
        t = (limbs[k] if k < 19 else (limbs[19] & 0x1F)) + _L_LIMBS13[k]
        if k < len(_C_LIMBS):
            t = t - q * _C_LIMBS[k]
        y.append(t)
    res = _carry13(y, extra=0)
    # conditional subtract: borrow-free z means res >= L, take z
    z = []
    carry = zero
    for k in range(20):
        t = res[k] - _L_LIMBS13[k] + carry
        z.append(t & 0x1FFF)
        carry = t >> 13
    ge = carry == 0
    res = [jnp.where(ge, z[k], res[k]) for k in range(20)]
    nibs = []
    for t_i in range(64):
        i, r = divmod(4 * t_i, 13)
        v = res[i] >> r
        if r > 9 and i + 1 < 20:
            v = v | (res[i + 1] << (13 - r))
        nibs.append(v & 15)
    return nibs


# -- the stamping prologue --------------------------------------------------


def _stamp_rows_core(sig, ts, flags, pre_mat, pre_len, suf_mat,
                     suf_len, ts_tag, pub_raw, thr,
                     msg_max: int, t_rows: int):
    """(per-row deltas, device template, valset pubkeys) -> the packed
    (V_THRESH + t_rows, B) rows — bit-identical to pack_rows_cached
    over a host pack_batch of the expanded batch.

    sig (B, 64) uint8 raw signatures; ts (B, 3) int32 [secs_lo,
    secs_hi, nanos]; flags (B,) int32 with bit0=live, bit1=counted,
    bits 2..9 = template row, bits 10.. = commit id. Dead lanes
    (live=0, the pool's zero fill) produce all-zero columns exactly
    like the legacy zero-filled padding rows. thr is the tiny
    (n_commits, TALLY_LIMBS) threshold matrix, expanded into the
    trailing rows on device (staging it pre-expanded would ship
    t_rows*B words for n_commits*6 of content)."""
    B = sig.shape[0]
    pm = pre_mat.shape[1]
    live = (flags & 1).astype(jnp.int32)
    counted = (flags >> 1) & 1
    tmpl = (flags >> 2) & 0xFF
    cid = flags >> 10
    sig32 = sig.astype(jnp.int32)

    # timestamp varints + proto3 zero-skip lengths (patch_rows math)
    sb, sl = _dev_uvarint64(ts[:, 0], ts[:, 1])
    nb, nl = _dev_uvarint64(ts[:, 2], ts[:, 2] >> 31)
    s_nz = ((ts[:, 0] | ts[:, 1]) != 0).astype(jnp.int32)
    n_nz = (ts[:, 2] != 0).astype(jnp.int32)
    sfl = jnp.where(s_nz != 0, sl + 1, 0)
    nfl = jnp.where(n_nz != 0, nl + 1, 0)
    ts_len = sfl + nfl
    p_row = pre_len[tmpl]
    s_row = suf_len[tmpl]
    body_len = p_row + 2 + ts_len + s_row
    ob, ol = _dev_uvarint32(body_len)
    total = ol + body_len

    # one gather assembles every row from a per-row source vector via
    # piecewise-iota boundaries (the segment layout of patch_rows)
    src = jnp.concatenate([
        ob,                                   # +0        outer varint
        pre_mat[tmpl].astype(jnp.int32),      # +5
        ts_tag[tmpl][:, None],                # +5+pm
        ts_len[:, None],                      # +6+pm
        jnp.full((B, 1), 0x08, jnp.int32),    # +7+pm     seconds tag
        sb,                                   # +8+pm
        jnp.full((B, 1), 0x10, jnp.int32),    # +18+pm    nanos tag
        nb,                                   # +19+pm
        suf_mat[tmpl].astype(jnp.int32),      # +20+pm
        jnp.zeros((B, 1), jnp.int32),         # +20+pm+sm dead lane
    ], axis=1)
    o_pre, o_tag = 5, 5 + pm
    o_tsl, o_t08, o_sb = o_tag + 1, o_tag + 2, o_tag + 3
    o_t10, o_nb = o_sb + 10, o_sb + 11
    o_suf = o_nb + 10
    o_z = o_suf + suf_mat.shape[1]
    col = lambda x: x[:, None]  # noqa: E731
    b0 = col(ol)
    b1 = b0 + col(p_row)
    b2 = b1 + 1
    b3 = b2 + 1
    b4 = b3 + col(s_nz)
    b5 = b4 + col(sl * s_nz)
    b6 = b5 + col(n_nz)
    b7 = b6 + col(nl * n_nz)
    b8 = b7 + col(s_row)
    p = jnp.arange(msg_max, dtype=jnp.int32)[None, :]
    idx = jnp.where(p < b0, p,
          jnp.where(p < b1, o_pre + (p - b0),
          jnp.where(p < b2, o_tag,
          jnp.where(p < b3, o_tsl,
          jnp.where(p < b4, o_t08,
          jnp.where(p < b5, o_sb + (p - b4),
          jnp.where(p < b6, o_t10,
          jnp.where(p < b7, o_nb + (p - b6),
          jnp.where(p < b8, o_suf + (p - b7), o_z)))))))))
    msg = jnp.take_along_axis(src, idx, axis=1)

    # full padded SHA-512 input: R || A || msg || 0x80 || 0* || bitlen
    # (the length field is 128-bit — 17 pad bytes minimum, not 9; our
    # bit counts fit 24 bits so only the low 4 length bytes are ever
    # nonzero)
    nblk = (64 + msg_max + 17 + 127) // 128
    width = nblk * 128
    vidx = jnp.arange(B, dtype=jnp.int32) % pub_raw.shape[0]
    a_row = pub_raw[vidx].astype(jnp.int32)
    data = jnp.concatenate(
        [sig32[:, :32], a_row, msg,
         jnp.zeros((B, width - 64 - msg_max), jnp.int32)], axis=1)
    pos = jnp.arange(width, dtype=jnp.int32)[None, :]
    tm = col(64 + total)
    data = data | jnp.where(pos == tm, 0x80, 0)
    nblk_row = (tm + 17 + 127) // 128
    bits = tm * 8
    rel = pos - (nblk_row * 128 - 8)
    sh = jnp.clip((7 - rel) * 8, 0, 24)
    data = data | jnp.where((rel >= 4) & (rel < 8),
                            (bits >> sh) & 0xFF, 0)
    st = _sha512_blocks(data, nblk_row[:, 0], nblk)
    nibs = _mod_l_nibbles(_digest_le_bytes(st))

    # packed-row assembly (pack_rows_cached's exact layout)
    h4_rows = [sum(nibs[8 * k + j] << (4 * k) for k in range(8)) * live
               for j in range(8)]
    s8_rows = [sum(sig32[:, 32 + 8 * k + j] << (8 * k)
                   for k in range(4)) * live for j in range(8)]
    zero = jnp.zeros_like(live)
    rb = [sig32[:, k] for k in range(32)] + [zero] * 3
    rb[31] = rb[31] & 0x7F
    rl = []
    for i in range(NLIMBS):
        j, r = divmod(13 * i, 8)
        win = rb[j] | (rb[j + 1] << 8) | (rb[j + 2] << 16)
        rl.append((win >> r) & 0x1FFF)
    ry_rows = [(rl[i] | (rl[i + 10] << 13)) * live for i in range(10)]
    rsign = (sig32[:, 31] >> 7) * live
    lt = jnp.zeros((B,), jnp.bool_)
    dec = jnp.zeros((B,), jnp.bool_)
    for k in range(7, -1, -1):
        wk = (sig[:, 32 + 4 * k].astype(jnp.uint32)
              | (sig[:, 33 + 4 * k].astype(jnp.uint32) << 8)
              | (sig[:, 34 + 4 * k].astype(jnp.uint32) << 16)
              | (sig[:, 35 + 4 * k].astype(jnp.uint32) << 24))
        mw = jnp.uint32(_L_U32[k])
        lt = lt | (~dec & (wk < mw))
        dec = dec | (wk != mw)
    precheck = lt.astype(jnp.int32) * live
    f_row = (rsign | (precheck << 1) | ((counted * live) << 2)
             | ((cid * live) << 3))
    flat = thr.reshape(-1).astype(jnp.int32)
    flat = jnp.pad(flat, (0, t_rows * B - flat.shape[0]))
    head = jnp.stack(ry_rows + s8_rows + h4_rows + [f_row], axis=0)
    return jnp.concatenate([head, flat.reshape(t_rows, B)], axis=0)


_stamp_rows_jit = jax.jit(_stamp_rows_core,
                          static_argnames=("msg_max", "t_rows"))


def stamp_rows_cached(sig, ts, flags, ent: TemplateEntry,
                      table: ValsetTable, n_commits: int = 1,
                      thresh=None):
    """Device-stamped packed rows for a delta flush — what
    pack_rows_cached would build from the expanded batch, assembled on
    device (differential-tested bit-identical). Requires a
    stamping-aware table (pub_raw present)."""
    if table.pub_raw is None:
        raise ValueError(
            "delta flush needs a table built with pub_raw")
    B = int(sig.shape[0])
    t_rows = packed_rows_shape(B, n_commits)[0] - V_THRESH
    if thresh is None:
        thresh = np.zeros((1, ek.TALLY_LIMBS), np.int32)
    return _stamp_rows_jit(
        jnp.asarray(sig), jnp.asarray(ts), jnp.asarray(flags),
        ent.pre_mat, ent.pre_len, ent.suf_mat, ent.suf_len,
        ent.ts_tag, table.pub_raw,
        jnp.asarray(np.asarray(thresh, np.int32)),
        msg_max=ent.msg_max, t_rows=t_rows)


def verify_tally_delta_cached(sig, ts, flags, ent: TemplateEntry,
                              table: ValsetTable, n_commits: int,
                              thresh=None):
    """Fused verify+tally for a delta-staged flush: the stamping
    prologue expands (template, deltas) into the packed rows ON
    DEVICE, then the cached verify kernel consumes them — the rows
    never exist host-side. Two dispatches by design: keeping
    _verify_tally_cached a separately-jitted module attribute
    preserves the kernel-stub seam the shardplane prog patches, and
    the rows stay device-resident between the two."""
    rows = stamp_rows_cached(sig, ts, flags, ent, table, n_commits,
                             thresh)
    return _verify_tally_cached(rows, table.tab, table.ok,
                                table.power5, base60_dev(), n_commits)


def verify_tally_rows_cached(rows, table: ValsetTable, n_commits: int):
    """Fused verify+tally from one packed (R, B) array.

    Buffer-lifetime note (README "Zero-copy hot path"): the per-flush
    rows buffer is dead once the kernel has consumed it — XLA buffer
    donation was evaluated here but does nothing for this signature
    (no output aval matches the (R, B) rows input, so XLA cannot alias
    it and merely warns), so the staging turnover is handled host-side
    by the pool rotation instead. The valset table / ok / power5 /
    base comb arguments are long-lived device-resident caches and must
    NEVER be donated or staged through the rotating pool."""
    return _verify_tally_cached(rows, table.tab, table.ok,
                                table.power5, base60_dev(), n_commits)


def pad_rows(n: int) -> int:
    """Batch padding for the cached path: fine-grained buckets (multiples
    of 2048 above 4096) — the coarse power-of-4 buckets waste up to 1.6x
    device work (10k -> 16384), and the cached path is fast enough that
    the waste dominates. Always >= B_TILE and a multiple of it."""
    n = max(n, 1)
    for b in (128, 256, 512, 1024, 2048, 4096):
        if n <= b:
            return b
    if n > 65536:
        raise ValueError(f"batch of {n} exceeds max bucket 65536")
    return -(-n // 2048) * 2048


def verify_rows_cached(rows, table: ValsetTable) -> np.ndarray:
    valid, _, _ = verify_tally_rows_cached(rows, table, 1)
    return valid


def verify_batch_cached(pub_bytes, msgs, sigs,
                        table: Optional[ValsetTable] = None) -> np.ndarray:
    """Drop-in verify_batch where row i's key is pub_bytes[i]; builds (or
    LRU-reuses) the valset table for the key list."""
    n = len(pub_bytes)
    if table is None:
        table = table_for_pubs(pub_bytes)
    pad = pad_rows(n)
    pb = ek.pack_batch(pub_bytes, msgs, sigs, pad_to=pad)
    rows = pack_rows_cached(pb)
    return np.asarray(verify_rows_cached(rows, table))[:n]
