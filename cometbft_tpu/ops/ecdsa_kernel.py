"""Batched secp256k1 ECDSA verification for TPU.

Per signature (r, s) over msg with compressed pubkey Q:
  host:   z = SHA256(msg); w = s^-1 mod N; u1 = z*w; u2 = r*w  (C bigint)
  device: R = [u1]G + [u2]Q;  valid iff R != inf and x(R) ≡ r (mod N)

The x ≡ r (mod N) check is projective: x = X/Z, and since N < P there are
at most two candidate representatives r and r+N, so validity is
X == r*Z or X == (r+N)*Z (the second only when r+N < P) — no device
inversion needed.

This capability has NO reference counterpart: CometBFT's secp256k1 has no
batch verifier (crypto/batch/batch.go:12-21); its single verify is
btcec's ecdsa.Verify with high-S rejection (crypto/secp256k1/
secp256k1.go:192-220), whose semantics (incl. the low-S rule) this kernel
reproduces in the precheck + device pass.
"""
from __future__ import annotations

import hashlib
from typing import NamedTuple, Optional, Sequence

import jax
import numpy as np

from cometbft_tpu import native
from cometbft_tpu.crypto import secp256k1_ref as ref
from cometbft_tpu.ops import secp256k1 as curve
from cometbft_tpu.ops.ed25519_kernel import bucket_size, nibbles
from cometbft_tpu.ops.field import FSECP
from cometbft_tpu.types import canonical

F = FSECP


class PackedEcdsaBatch(NamedTuple):
    n: int
    padded: int
    qx: np.ndarray        # (B, NLIMBS) pubkey x
    qparity: np.ndarray   # (B,) prefix low bit
    u1dig: np.ndarray     # (B, 64) base-16 digits of u1
    u2dig: np.ndarray     # (B, 64)
    xr1: np.ndarray       # (B, NLIMBS) candidate x = r
    xr2: np.ndarray       # (B, NLIMBS) candidate x = r + N (or r again)
    precheck: np.ndarray  # (B,) host-side validity screen
    native: bool = False  # packed by the one C call, not the loop below


def pack_batch(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    pad_to: Optional[int] = None,
) -> PackedEcdsaBatch:
    """Stage (pubkey33, msg, sig64) triples into device-ready arrays,
    padded to `pad_to` rows (a served commit's chunk shape) or the
    bucket ladder's rung for n.

    Malformed rows (bad lengths/prefix, x >= P, r/s out of range, high-S)
    get precheck=False and zeroed payloads. `msgs` may be a commit's
    lazy rows (canonical.TemplateRows) or the matrix they expand to
    (canonical.SignRows, or a run of it): SHA-256 then reads each row
    where it lies in the matrix, and no bytes object a row is made.

    Where the native library loads and every key is 33 and every
    signature 64 bytes long, the whole chunk is ONE C call
    (native.secp256k1_pack) and the result's `native` is True; anything
    else is the Python loop below. The arrays are the same either way
    (tests/test_native.py)."""
    n = len(pubkeys)
    assert len(msgs) == n and len(sigs) == n
    if isinstance(msgs, canonical.TemplateRows):
        msgs = msgs.expand()
    padded = pad_to if pad_to is not None else bucket_size(max(n, 1))
    assert padded >= n
    if (n and set(map(len, pubkeys)) == {33}
            and set(map(len, sigs)) == {64}):
        packed = native.secp256k1_pack(b"".join(pubkeys), b"".join(sigs),
                                       msgs, padded)
        if packed is not None:
            return PackedEcdsaBatch(n, padded, *packed, native=True)
    if isinstance(msgs, canonical.SignRows):
        msgs = [row[:ln] for row, ln in zip(msgs.mat, msgs.lens.tolist())]

    x_raw = np.zeros((padded, 32), np.uint8)
    parity = np.zeros((padded,), np.int32)
    u1b = np.zeros((padded, 32), np.uint8)
    u2b = np.zeros((padded, 32), np.uint8)
    xr1 = np.zeros((padded, 32), np.uint8)
    xr2 = np.zeros((padded, 32), np.uint8)
    precheck = np.zeros((padded,), np.bool_)

    from_b, to_b = int.from_bytes, int.to_bytes
    N_, P_, HALF = ref.N, ref.P, ref.HALF_N
    sha256 = hashlib.sha256
    # row screen (cheap python) — collect per-row ints, then do the
    # expensive modular work vectorized below
    ok_idx, xs, rs, ss, zs = [], [], [], [], []
    for i, (pk, msg, sig) in enumerate(zip(pubkeys, msgs, sigs)):
        if len(pk) != 33 or pk[0] not in (2, 3) or len(sig) != 64:
            continue
        x = from_b(pk[1:], "big")
        r = from_b(sig[:32], "big")
        s = from_b(sig[32:], "big")
        if x >= P_ or not (1 <= r < N_ and 1 <= s <= HALF):
            continue
        ok_idx.append(i)
        xs.append(x)
        rs.append(r)
        ss.append(s)
        zs.append(from_b(sha256(msg).digest(), "big"))
        parity[i] = pk[0] & 1
        precheck[i] = True
    if ok_idx:
        # batched modular inverse (Montgomery's trick): one pow + 3k muls
        # instead of k pows — the pack was the ECDSA pipeline bottleneck
        # (1.7 s/10k with per-row pow)
        m = len(ok_idx)
        pref = [1] * (m + 1)
        for j in range(m):
            pref[j + 1] = pref[j] * ss[j] % N_
        inv_all = pow(pref[m], N_ - 2, N_)
        ws = [0] * m
        for j in range(m - 1, -1, -1):
            ws[j] = pref[j] * inv_all % N_
            inv_all = inv_all * ss[j] % N_
        xb, u1l, u2l, r1l, r2l = [], [], [], [], []
        for j in range(m):
            w = ws[j]
            r = rs[j]
            xb.append(to_b(xs[j], 32, "little"))
            u1l.append(to_b(zs[j] * w % N_, 32, "little"))
            u2l.append(to_b(r * w % N_, 32, "little"))
            r1l.append(to_b(r, 32, "little"))
            r2l.append(to_b(r + N_ if r + N_ < P_ else r, 32, "little"))
        rows = np.asarray(ok_idx)
        x_raw[rows] = np.frombuffer(b"".join(xb), np.uint8).reshape(m, 32)
        u1b[rows] = np.frombuffer(b"".join(u1l), np.uint8).reshape(m, 32)
        u2b[rows] = np.frombuffer(b"".join(u2l), np.uint8).reshape(m, 32)
        xr1[rows] = np.frombuffer(b"".join(r1l), np.uint8).reshape(m, 32)
        xr2[rows] = np.frombuffer(b"".join(r2l), np.uint8).reshape(m, 32)

    return PackedEcdsaBatch(
        n, padded,
        F.from_bytes_le(x_raw), parity,
        nibbles(u1b), nibbles(u2b),
        F.from_bytes_le(xr1), F.from_bytes_le(xr2),
        precheck,
    )


def verify_core(qx, qparity, u1dig, u2dig, xr1, xr2, precheck):
    """(B,)-batched ECDSA check. Returns (B,) bool validity."""
    Q, ok_q = curve.decompress(qx, qparity)
    R = curve.add(curve.base_scalar_mul(u1dig),
                  curve.scalar_mul_windowed(u2dig, Q))
    X, _, Z = curve.unstack(R)
    not_inf = ~F.is_zero(Z)
    xr_match = F.eq(X, F.mul(xr1, Z)) | F.eq(X, F.mul(xr2, Z))
    return ok_q & not_inf & xr_match & precheck


verify_kernel = jax.jit(verify_core)


def verify_batch(pubkeys, msgs, sigs) -> np.ndarray:
    """Verify a batch; returns (n,) bool per-signature validity — the
    BatchVerifier surface the reference never grew for secp256k1."""
    pb = pack_batch(pubkeys, msgs, sigs)
    valid = verify_kernel(
        pb.qx, pb.qparity, pb.u1dig, pb.u2dig, pb.xr1, pb.xr2, pb.precheck
    )
    return np.asarray(valid)[: pb.n]
