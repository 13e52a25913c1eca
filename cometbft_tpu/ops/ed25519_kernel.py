"""Batched ed25519 ZIP-215 verification with fused voting-power quorum tally.

This is the north-star device kernel (BASELINE.json): thousands of
(pubkey, msg, sig) triples verified in one data-parallel pass, with the
2/3-of-total-voting-power tally computed in the same compiled program.

Replaces, behind one seam:
  - crypto/ed25519/ed25519.go:208-241  BatchVerifier (curve25519-voi batch)
  - types/validation.go:153-257        verifyCommitBatch sign-bytes + tally
  - libs/bits/bit_array.go             the quorum bitset bookkeeping

Host/device split: SHA-512 challenge hashing (h = H(R||A||M) mod L) and
byte unpacking happen on host (cheap relative to curve ops — SURVEY.md §7
stage 1 explicitly blesses this split); all curve arithmetic (two 253-bit
scalar multiplications + decompression sqrt per signature) runs on device.

Voting powers ride as 5x13-bit int32 limbs so the tally stays int32-pure on
TPU (no emulated int64): power < 2^63 and MaxTotalVotingPower = MaxInt64/8
(types/validator_set.go:25) bound every per-limb partial sum below 2^31 for
batches up to 2^17 signatures.
"""
from __future__ import annotations

import hashlib
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import curve25519 as curve
from cometbft_tpu.ops.field import F25519, NLIMBS
from cometbft_tpu.types import canonical

F = F25519

POWER_LIMBS = 5
POWER_LIMB_BITS = 13
POWER_MASK = (1 << POWER_LIMB_BITS) - 1
# tally needs ceil(64/13) + headroom for carries
TALLY_LIMBS = 6

BUCKETS = (64, 256, 1024, 4096, 16384, 32768, 65536)


def bucket_size(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"batch of {n} exceeds max bucket {BUCKETS[-1]}")


# --------------------------------------------------------------------------
# Host-side packing
# --------------------------------------------------------------------------


def scalar_digits(v: int) -> np.ndarray:
    """256-bit int -> 64 base-16 digits, little-endian."""
    b = np.frombuffer(int.to_bytes(v, 32, "little"), dtype=np.uint8)
    lo = b & 0xF
    hi = b >> 4
    return np.stack([lo, hi], axis=1).reshape(64).astype(np.int32)


def nibbles(b: np.ndarray) -> np.ndarray:
    """(..., 32) uint8 -> (..., 64) int32 base-16 digits, little-endian.

    Batched scalar_digits — one numpy pass for the whole batch."""
    lo = (b & 0xF).astype(np.int32)
    hi = (b >> 4).astype(np.int32)
    return np.stack([lo, hi], axis=-1).reshape(b.shape[:-1] + (64,))


_L_WORDS = np.frombuffer(int.to_bytes(ref.L, 32, "little"), np.uint8).view(
    "<u8"
)


def below_words(b: np.ndarray, mod_words: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 LE -> (B,) bool value < modulus, vectorized as a
    lexicographic compare over 4 little-endian uint64 words."""
    w = np.ascontiguousarray(b).view("<u8")  # (B, 4)
    lt = np.zeros(b.shape[0], np.bool_)
    decided = np.zeros(b.shape[0], np.bool_)
    for i in range(3, -1, -1):
        mw = mod_words[i]
        lt |= ~decided & (w[:, i] < mw)
        decided |= w[:, i] != mw
    return lt


def s_below_l(s_bytes: np.ndarray) -> np.ndarray:
    """The malleability precheck of crypto/ed25519/ed25519.go:189
    (S < order)."""
    return below_words(s_bytes, _L_WORDS)


def power_limbs(powers: np.ndarray) -> np.ndarray:
    """(B,) int64 voting powers -> (B, POWER_LIMBS) int32 13-bit limbs."""
    p = np.asarray(powers, dtype=np.int64)
    out = np.empty(p.shape + (POWER_LIMBS,), dtype=np.int32)
    for i in range(POWER_LIMBS):
        out[..., i] = (p >> (POWER_LIMB_BITS * i)) & POWER_MASK
    return out


def threshold_limbs(v: int, n_commits: int = 1) -> np.ndarray:
    """Quorum threshold int -> (n_commits, TALLY_LIMBS) int32 limbs."""
    out = np.zeros((n_commits, TALLY_LIMBS), np.int32)
    for i in range(TALLY_LIMBS):
        out[:, i] = (v >> (POWER_LIMB_BITS * i)) & POWER_MASK
    return out


def tally_to_int(t: np.ndarray):
    """(.., TALLY_LIMBS) int32 -> Python int/object array."""
    t = np.asarray(t).astype(object)
    out = 0
    for i in range(t.shape[-1]):
        out = out + (t[..., i] << (POWER_LIMB_BITS * i))
    return out


class PackedBatch(NamedTuple):
    """Device-ready arrays for one verification batch (padded to a bucket)."""

    n: int
    padded: int
    ay: np.ndarray
    asign: np.ndarray
    ry: np.ndarray
    rsign: np.ndarray
    sdig: np.ndarray
    hdig: np.ndarray
    precheck: np.ndarray


def pack_batch(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    pad_to: Optional[int] = None,
) -> PackedBatch:
    """Stage (pubkey, msg, sig) triples into device-ready arrays.

    Malformed rows (bad lengths, S >= L) get precheck=False and zeroed
    payloads; they verify as invalid without poisoning the batch. The batch
    is padded to a fixed bucket size to avoid XLA recompiles
    (types/validation.go's variable commit sizes -> static shapes).
    """
    n = len(pubkeys)
    assert len(msgs) == n and len(sigs) == n
    padded = pad_to if pad_to is not None else bucket_size(max(n, 1))
    assert padded >= n

    # Length screen first; malformed rows keep zeroed payloads and
    # precheck=False (they verify invalid without poisoning the batch).
    lenok = [
        len(p) == 32 and len(s) == 64 for p, s in zip(pubkeys, sigs)
    ]

    a_raw = np.zeros((padded, 32), np.uint8)
    r_raw = np.zeros((padded, 32), np.uint8)
    s_raw = np.zeros((padded, 32), np.uint8)
    sha512 = hashlib.sha512
    if all(lenok):
        from cometbft_tpu import native

        pub_cat, sig_cat_b = b"".join(pubkeys), b"".join(sigs)
        # fully-native pack: digest + mod-L + limb/nibble decomposition
        # + S<L precheck in ONE call (cometbft_tpu/native hostaccel);
        # numpy+hashlib pipeline below is the fallback and the
        # differential reference (tests/test_native.py pack parity)
        packed = native.ed25519_pack(pub_cat, sig_cat_b, msgs, padded)
        if packed is not None:
            ay, asign, ry, rsign, sdig, hdig, precheck = packed
            return PackedBatch(n, padded, ay, asign, ry, rsign, sdig,
                               hdig, precheck)
        # fast numpy path: single join + frombuffer per array
        a_raw[:n] = np.frombuffer(pub_cat, np.uint8).reshape(n, 32)
        sig_cat = np.frombuffer(sig_cat_b, np.uint8).reshape(n, 64)
        r_raw[:n] = sig_cat[:, :32]
        s_raw[:n] = sig_cat[:, 32:]
        digests = [
            sha512(sig[:32] + pk + msg).digest()
            for pk, msg, sig in zip(pubkeys, msgs, sigs)
        ]
        lenok_np = np.ones(n, np.bool_)
    else:
        digests = [b"\x00" * 64] * n
        for i, (pk, msg, sig) in enumerate(zip(pubkeys, msgs, sigs)):
            if not lenok[i]:
                continue
            a_raw[i] = np.frombuffer(pk, np.uint8)
            r_raw[i] = np.frombuffer(sig[:32], np.uint8)
            s_raw[i] = np.frombuffer(sig[32:], np.uint8)
            digests[i] = sha512(sig[:32] + pk + msg).digest()
        lenok_np = np.asarray(lenok, np.bool_)

    # h = digest mod L: C-bigint per row (the native path returned
    # above), then one vectorized nibble split for the batch
    h_bytes = np.zeros((padded, 32), np.uint8)
    if n:
        from_b, to_b = int.from_bytes, int.to_bytes
        h_bytes[:n] = np.frombuffer(
            b"".join(
                to_b(from_b(d, "little") % ref.L, 32, "little")
                for d in digests
            ),
            np.uint8,
        ).reshape(n, 32)

    precheck = np.zeros((padded,), np.bool_)
    precheck[:n] = lenok_np & s_below_l(s_raw[:n])
    sdig = nibbles(s_raw)
    hdig = nibbles(h_bytes)
    asign = (a_raw[:, 31] >> 7).astype(np.int32)
    rsign = (r_raw[:, 31] >> 7).astype(np.int32)
    ay = F.from_bytes_le(a_raw, nbits=255)
    ry = F.from_bytes_le(r_raw, nbits=255)
    return PackedBatch(n, padded, ay, asign, ry, rsign, sdig, hdig, precheck)


def pack_templated(pubkeys: Sequence[bytes], msgs: Sequence[bytes],
                   sigs: Sequence[bytes], pad_to: Optional[int] = None):
    """pack_batch for messages that may be a canonical.TemplateRows
    (a commit's sign-bytes as templates and a timestamp a row):
    returns (PackedBatch, templated).

    Where they are, the native library loads and every key is 32 and
    every signature 64 bytes long, the rows' sign-bytes are built
    inside the SHA-512 that hashes them (native.ed25519_pack_commits)
    and never exist as Python objects: templated is True. Anything
    else is pack_batch over the list of bytes, with its screens and
    its numpy fallback, and templated False. The arrays are the same
    either way (tests/test_sign_template.py)."""
    from cometbft_tpu import native

    n = len(pubkeys)
    padded = pad_to if pad_to is not None else bucket_size(max(n, 1))
    if isinstance(msgs, canonical.TemplateRows):
        if (n and set(map(len, pubkeys)) == {32}
                and set(map(len, sigs)) == {64}):
            packed = native.ed25519_pack_commits(
                b"".join(pubkeys), b"".join(sigs),
                [t.template for t in msgs.templates],
                msgs.tmpl, msgs.secs, msgs.nanos, padded)
            if packed is not None:
                return PackedBatch(n, padded, *packed), True
        msgs = list(msgs)
    return pack_batch(pubkeys, msgs, sigs, pad_to=padded), False


# --------------------------------------------------------------------------
# Device kernel
# --------------------------------------------------------------------------


def verify_core(ay, asign, ry, rsign, sdig, hdig, precheck):
    """(B,)-batched ZIP-215 check: [8][S]B == [8]R + [8][h]A.

    Computed as [8]([S]B + [h](-A) + (-R)) == identity with complete
    edwards formulas, so one branch-free circuit covers every signature.
    Returns (B,) bool validity.
    """
    A, ok_a = curve.decompress(ay, asign)
    R, ok_r = curve.decompress(ry, rsign)
    h_negA = curve.scalar_mul_windowed(hdig, curve.neg(A))
    sB = curve.base_scalar_mul(sdig)
    W = curve.add(curve.add(sB, h_negA), curve.neg(R))
    eq = curve.is_identity(curve.mul_by_cofactor(W))
    return eq & ok_a & ok_r & precheck


def tally_core(valid, power5, counted, commit_ids, n_commits: int):
    """Fused voting-power tally: per-commit sum of power over valid,
    counted signatures, in 13-bit limbs (int32-pure).

    Mirrors the tally loop at types/validation.go:217-231 but data-parallel:
    instead of an early break at 2/3, every signature is verified and the
    segmented sum is one one-hot matmul (MXU-friendly).
    """
    mask = (valid & counted).astype(jnp.int32)  # (B,)
    contrib = power5 * mask[:, None]  # (B, 5)
    onehot = (commit_ids[:, None] == jnp.arange(n_commits)[None, :]).astype(
        jnp.int32
    )  # (B, C)
    # (C, 5): per-limb partial sums; B <= 2^17 and limb < 2^13 -> < 2^30
    t = jnp.einsum("bc,bl->cl", onehot, contrib)
    t = jnp.pad(t, [(0, 0), (0, TALLY_LIMBS - POWER_LIMBS)])
    # carry-propagate so each limb is canonical 13-bit
    for i in range(TALLY_LIMBS - 1):
        c = t[:, i] >> POWER_LIMB_BITS
        t = t.at[:, i].add(-(c << POWER_LIMB_BITS)).at[:, i + 1].add(c)
    return t


def quorum_core(tally, threshold):
    """tally > threshold on multi-limb numbers (both canonical 13-bit)."""
    # lexicographic compare from the top limb down
    gt = jnp.zeros(tally.shape[:-1], dtype=bool)
    eq = jnp.ones(tally.shape[:-1], dtype=bool)
    for i in range(TALLY_LIMBS - 1, -1, -1):
        gt = gt | (eq & (tally[..., i] > threshold[..., i]))
        eq = eq & (tally[..., i] == threshold[..., i])
    return gt


@partial(jax.jit, static_argnames=("n_commits",))
def verify_tally_kernel(
    ay,
    asign,
    ry,
    rsign,
    sdig,
    hdig,
    precheck,
    power5,
    counted,
    commit_ids,
    threshold,
    n_commits: int,
):
    """The fused kernel: batched ZIP-215 verify + per-commit quorum tally.

    Returns (valid (B,), tally (C, TALLY_LIMBS), quorum (C,)).
    """
    valid = verify_core(ay, asign, ry, rsign, sdig, hdig, precheck)
    tally = tally_core(valid, power5, counted, commit_ids, n_commits)
    return valid, tally, quorum_core(tally, threshold)


@jax.jit
def verify_kernel(ay, asign, ry, rsign, sdig, hdig, precheck):
    """Verification only (no tally) — the plain BatchVerifier.Verify path."""
    return verify_core(ay, asign, ry, rsign, sdig, hdig, precheck)


# --------------------------------------------------------------------------
# High-level entry points
# --------------------------------------------------------------------------


def verify_batch(pubkeys, msgs, sigs) -> np.ndarray:
    """Verify a batch; returns (n,) bool numpy array of per-sig validity.

    The device-side analog of crypto/ed25519/ed25519.go:236 Verify()'s
    per-signature valid slice (the blame path of types/validation.go:243
    needs exactly this)."""
    pb, _ = pack_templated(pubkeys, msgs, sigs)
    valid = verify_kernel(
        pb.ay, pb.asign, pb.ry, pb.rsign, pb.sdig, pb.hdig, pb.precheck
    )
    return np.asarray(valid)[: pb.n]
