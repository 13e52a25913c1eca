"""Pallas TPU kernel: fully fused batched ed25519 ZIP-215 verification.

The XLA-composed kernel (ops.ed25519_kernel) is HBM-bound: every field op
materializes (B, 39) int32 intermediates in HBM. This kernel keeps the
entire per-signature computation — point decompression (sqrt chain), the
per-signature 16-entry table, 63 window iterations of the double-and-add
loop, the base-point comb, cofactor clearing and the identity check —
VMEM-resident per 128-lane tile, with the limb axis on sublanes (see
ops.field_lf for the layout rationale).

Mosaic constraints shape the design:
  * no captured array constants — field constants are materialized
    in-trace from Python ints (field_lf.const_col), and the base-point
    comb table is an explicit kernel input;
  * the per-signature table (entries [d](-A), d<16) is built with a
    statically unrolled loop and kept as a loop-invariant VMEM value;
    lookups are one-hot masked sums (tables differ per lane);
  * the base comb ([S]B) lookup is a float32 one-hot matmul on the MXU —
    table limbs are < 2^13 so f32 is exact, and each output column is a
    single table entry (no accumulation).

Reference seam (same as ops.ed25519_kernel): crypto/ed25519/ed25519.go:
208-241 BatchVerifier + types/validation.go:153 verifyCommitBatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import curve25519 as curve_hl
from cometbft_tpu.ops.field import F25519, NLIMBS
from cometbft_tpu.ops.field_lf import FieldLF, const_col, interpret_mode

F = FieldLF(F25519)
B_TILE = 128

# field constants as Python limb tuples (materialized in-trace, never captured)
_D_T = F.const_limbs(ref.D)
_D2_T = F.const_limbs(2 * ref.D % ref.P)
_SQRT_M1_T = F.const_limbs(ref.SQRT_M1)
_ONE_T = (1,) + (0,) * (NLIMBS - 1)


# --------------------------------------------------------------------------
# limbs-first point ops (points are 4-tuples of (NLIMBS, B) arrays)
# --------------------------------------------------------------------------


def pt_add(p, q, d2_col):
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    A = F.mul(F.sub(Y1, X1), F.sub(Y2, X2))
    B = F.mul(F.add(Y1, X1), F.add(Y2, X2))
    C = F.mul(F.mul(T1, T2), d2_col)
    Dv = F.mul_small(F.mul(Z1, Z2), 2)
    E = F.sub(B, A)
    Fv = F.sub(Dv, C)
    G = F.add(Dv, C)
    H = F.add(B, A)
    return (F.mul(E, Fv), F.mul(G, H), F.mul(Fv, G), F.mul(E, H))


def pt_double(p):
    X1, Y1, Z1 = p[0], p[1], p[2]
    A = F.square(X1)
    B = F.square(Y1)
    C = F.mul_small(F.square(Z1), 2)
    H = F.add(A, B)
    E = F.sub(H, F.square(F.add(X1, Y1)))
    G = F.sub(A, B)
    Fv = F.add(C, G)
    return (F.mul(E, Fv), F.mul(G, H), F.mul(Fv, G), F.mul(E, H))


def pt_double_p(p):
    """Projective doubling, T dropped (3M+4S vs 4M+4S).

    Legal whenever the next op is another doubling — only an add consumes
    T. Returns a 3-tuple (X, Y, Z); feed pt_double (which ignores T) to
    re-extend on the last doubling before an add."""
    X1, Y1, Z1 = p[0], p[1], p[2]
    A = F.square(X1)
    B = F.square(Y1)
    C = F.mul_small(F.square(Z1), 2)
    H = F.add(A, B)
    E = F.sub(H, F.square(F.add(X1, Y1)))
    G = F.sub(A, B)
    Fv = F.add(C, G)
    return (F.mul(E, Fv), F.mul(G, H), F.mul(Fv, G))


def pt_add_noT(p, q, d2_col):
    """Unified add with the T output dropped (8M) — for results that are
    never re-added (the final accumulation before the identity check)."""
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    A = F.mul(F.sub(Y1, X1), F.sub(Y2, X2))
    B = F.mul(F.add(Y1, X1), F.add(Y2, X2))
    C = F.mul(F.mul(T1, T2), d2_col)
    Dv = F.mul_small(F.mul(Z1, Z2), 2)
    E = F.sub(B, A)
    Fv = F.sub(Dv, C)
    G = F.add(Dv, C)
    H = F.add(B, A)
    return (F.mul(E, Fv), F.mul(G, H), F.mul(Fv, G))


def pt_neg(p):
    X, Y, Z, T = p
    return (-X, Y, Z, -T)


def pt_identity(b):
    one = const_col(_ONE_T, b)
    zero = jnp.zeros((NLIMBS, b), jnp.int32)
    return (zero, one, one, zero)


def decompress(y, sign_row, d_col, sqrt_m1_col):
    """ZIP-215 decompression; y (NLIMBS, B), sign_row (1, B) -> (pt, ok).

    ok is (1, B) bool; on ok=False the point contents are garbage and the
    caller must mask. Mirrors ed25519_ref.pt_decompress (zip215=True).
    """
    b = y.shape[1]
    yy = F.square(y)
    one = const_col(_ONE_T, b)
    u = F.sub(yy, one)
    v = F.add(F.mul(yy, d_col), one)
    v3 = F.mul(F.square(v), v)
    v7 = F.mul(F.square(v3), v)
    r = F.mul(F.mul(u, v3), F.pow_p58(F.mul(u, v7)))
    check = F.mul(v, F.square(r))
    is_pos = F.eq(check, u)  # (1, B)
    is_neg = F.is_zero(check + u)  # check == -u
    ok = is_pos | is_neg
    r = jnp.where(is_neg, F.mul(r, sqrt_m1_col), r)
    flip = F.parity(r) != sign_row
    x = jnp.where(flip, -r, r)
    return (x, y, one, F.mul(x, y)), ok


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------


# Compact packed-row layout. Every per-signature device input rides ONE
# int32 array (rows, B): limbs are packed two-per-word, scalar digits
# byte/nibble-packed, flags bit-packed. 42 rows = 168 B/signature, vs 179
# unpacked rows (716 B/sig) — every byte is per-commit H2D traffic, and
# unpacking is a handful of VPU shifts.
C_AY = 0        # 10 rows: pubkey y limb pairs, word = l[i] | l[i+10] << 13
C_RY = 10       # 10 rows: sig R y limb pairs
C_S8 = 20       # 8 rows: byte digits of s (comb), digit d at row d%8
C_H4 = 28       # 8 rows: nibble digits of h, digit d at row d%8
C_FLAGS = 36    # asign | rsign<<1 | precheck<<2 | counted<<3
C_KROWS = 37    # kernel block height (rows below are tally-side only)
C_POW = 37      # 3 rows: p0|p1<<13, p2|p3<<13, p4
C_CID = 40      # commit id per signature row
C_THRESH = 41   # flattened (n_commits, TALLY_LIMBS) thresholds
_M13 = (1 << 13) - 1


def _kernel(packed_ref, base_ref, valid_ref, s8_ref, h4_ref):
    b = B_TILE
    d_col = const_col(_D_T, b)
    d2_col = const_col(_D2_T, b)
    sqrt_m1_col = const_col(_SQRT_M1_T, b)

    pk = packed_ref[:, :]  # (C_KROWS, b)
    ay2 = pk[C_AY:C_AY + 10]
    ay = jnp.concatenate([ay2 & _M13, ay2 >> 13], axis=0)
    ry2 = pk[C_RY:C_RY + 10]
    ry = jnp.concatenate([ry2 & _M13, ry2 >> 13], axis=0)
    # digits go to VMEM scratch: the window loops index them with a
    # dynamic pl.ds, which Mosaic supports on refs but not on values
    s8p = pk[C_S8:C_S8 + 8]
    s8_ref[:, :] = jnp.concatenate(
        [(s8p >> (8 * k)) & 255 for k in range(4)], axis=0
    )  # (32, b) byte digits
    h4p = pk[C_H4:C_H4 + 8]
    h4_ref[:, :] = jnp.concatenate(
        [(h4p >> (4 * k)) & 15 for k in range(8)], axis=0
    )  # (64, b) nibble digits
    flags = pk[C_FLAGS:C_FLAGS + 1]
    asign = flags & 1
    rsign = (flags >> 1) & 1
    pre = (flags >> 2) & 1

    A, ok_a = decompress(ay, asign, d_col, sqrt_m1_col)
    R, ok_r = decompress(ry, rsign, d_col, sqrt_m1_col)
    negA = pt_neg(A)

    # per-signature table entries [d](-A), d in 0..15 — statically unrolled,
    # kept as one loop-invariant VMEM value (16, 4, NLIMBS, B)
    entries = []
    pt = pt_identity(b)
    for d in range(16):
        entries.append(jnp.stack(pt))
        if d < 15:
            pt = pt_add(pt, negA, d2_col)
    tbl = jnp.stack(entries)

    def lookup(d_row):
        """d_row (1, B) -> table entry per lane, one-hot masked sum."""
        ent = jnp.zeros((4, NLIMBS, b), jnp.int32)
        for dv in range(16):
            m = (d_row == dv)[None]  # (1, 1, B)
            ent = ent + jnp.where(m, tbl[dv], 0)
        return (ent[0], ent[1], ent[2], ent[3])

    # h * (-A): 63 windows of 4 doublings + 1 table add (Horner, base 16);
    # doublings 1-3 stay projective (3M+4S), the 4th re-extends T for the add
    def win_body(i, pt):
        w = 62 - i
        pt = pt_double(pt_double_p(pt_double_p(pt_double_p(pt))))
        d_row = h4_ref[pl.ds(w, 1), :]
        return pt_add(pt, lookup(d_row), d2_col)

    h_negA = jax.lax.fori_loop(0, 63, win_body, lookup(h4_ref[63:64, :]))

    # [S]B comb: 32 width-8 windows, each an f32 one-hot matmul on the MXU.
    # base_ref rows are (window*256 + digit) -> flattened point (4*NLIMBS,)
    iota256 = jax.lax.broadcasted_iota(jnp.int32, (256, b), 0)

    def base_body(w, pt):
        d8 = s8_ref[pl.ds(w, 1), :]
        oh = (iota256 == d8).astype(jnp.float32)  # (256, B)
        t_w = base_ref[pl.ds(w * 256, 256), :]  # (256, 80) f32
        ent = jax.lax.dot_general(
            t_w, oh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            # HIGHEST forces exact f32 (multi-pass bf16) — the v5e MXU's
            # default single-pass bf16 rounds 13-bit limbs (8-bit mantissa)
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)  # (80, B), exact: one-hot selects single values
        e = ent.reshape(4, NLIMBS, b)
        return pt_add(pt, (e[0], e[1], e[2], e[3]), d2_col)

    sB = jax.lax.fori_loop(0, 32, base_body, pt_identity(b))

    W = pt_add_noT(pt_add(sB, h_negA, d2_col), pt_neg(R), d2_col)
    W8 = pt_double_p(pt_double_p(pt_double_p(W)))
    eq = F.is_zero(W8[0]) & F.eq(W8[1], W8[2])  # (1, B)
    valid = eq & ok_a & ok_r & (pre != 0)
    valid_ref[:, :] = valid.astype(jnp.int32)


_BASE_F32 = None
_BASE_DEV = None


def base_dev():
    """Device-resident base comb table, uploaded once per process.

    jnp.asarray(base_f32()) at every call site re-transferred the 2.6 MB
    table per verify; the table is immutable, so pin it once.
    """
    global _BASE_DEV
    if _BASE_DEV is None:
        import jax as _jax

        _BASE_DEV = _jax.device_put(base_f32())
    return _BASE_DEV


def base_f32() -> np.ndarray:
    """Base comb table as (32*256, 4*NLIMBS) float32; rows indexed by
    window*256 + digit. Built eagerly from the numpy table — never inside
    a trace (round-1 bug: jnp base_table() under jit raised
    TracerArrayConversionError)."""
    global _BASE_F32
    if _BASE_F32 is None:
        t = curve_hl.base_table8_np()  # numpy (32, 256, 4, NLIMBS)
        _BASE_F32 = np.ascontiguousarray(
            t.reshape(32 * 256, 4 * NLIMBS)
        ).astype(np.float32)
    return _BASE_F32


@jax.jit
def _verify_rows(rows, base):
    """Fused verify over a compact packed array (>= C_KROWS rows, B).

    B must be a multiple of B_TILE. Returns (B,) bool.
    """
    B = rows.shape[1]
    assert B % B_TILE == 0, f"B={B} not a multiple of {B_TILE}"
    grid = (B // B_TILE,)
    col = lambda r: pl.BlockSpec(
        (r, B_TILE), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    full = pl.BlockSpec(
        (32 * 256, 4 * NLIMBS), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        _kernel,
        interpret=interpret_mode(),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        grid=grid,
        in_specs=[col(C_KROWS), full],
        out_specs=col(1),
        scratch_shapes=[
            pltpu.VMEM((32, B_TILE), jnp.int32),  # s8 byte digits
            pltpu.VMEM((64, B_TILE), jnp.int32),  # h4 nibble digits
        ],
    )(rows[:C_KROWS], base)
    return out[0] != 0


@functools.partial(jax.jit, static_argnums=(2,))
def _verify_tally_rows(rows, base, n_commits: int):
    """Pallas verify + fused XLA tally/quorum in one compiled program.

    The tally is one one-hot einsum + carry chain (ed25519_kernel.tally_core)
    — negligible next to the curve work, so it rides the XLA side of the
    same jit rather than the Mosaic kernel."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    valid = _verify_rows.__wrapped__(rows, base)
    pw = rows[C_POW:C_POW + 3]
    power5 = jnp.stack(
        [pw[0] & _M13, pw[0] >> 13, pw[1] & _M13, pw[1] >> 13, pw[2]],
        axis=1,
    )  # (B, POWER_LIMBS)
    counted = (rows[C_FLAGS] >> 3) & 1 != 0
    commit_ids = rows[C_CID]
    thresh = rows[C_THRESH:].reshape(-1)[
        : n_commits * ek.TALLY_LIMBS
    ].reshape(n_commits, ek.TALLY_LIMBS)
    tally = ek.tally_core(valid, power5, counted, commit_ids, n_commits)
    return valid, tally, ek.quorum_core(tally, thresh)


def pack_rows(pb, power5=None, counted=None, commit_ids=None,
              thresh=None) -> np.ndarray:
    """Pack a PackedBatch (+ optional tally metadata) into one compact
    (R, B) int32 array — exactly one H2D transfer per batch (11
    separate device_puts before): 42 rows = 168 B/signature.
    """
    from cometbft_tpu.ops import ed25519_kernel as ek

    B = pb.ay.shape[0]
    if thresh is None:
        thresh = np.zeros((1, ek.TALLY_LIMBS), np.int32)
    tvals = np.asarray(thresh, np.int32).reshape(-1)
    t_rows = max(1, -(-tvals.size // B))
    rows = np.zeros((C_THRESH + t_rows, B), np.int32)
    ay = np.asarray(pb.ay, np.int32)
    ry = np.asarray(pb.ry, np.int32)
    rows[C_AY:C_AY + 10] = (ay[:, :10] | (ay[:, 10:] << 13)).T
    rows[C_RY:C_RY + 10] = (ry[:, :10] | (ry[:, 10:] << 13)).T
    s8 = (pb.sdig[:, 0::2] + 16 * pb.sdig[:, 1::2]).astype(np.int32)  # (B,32)
    acc = np.zeros((B, 8), np.int32)
    for k in range(4):
        acc |= s8[:, 8 * k:8 * k + 8] << (8 * k)
    rows[C_S8:C_S8 + 8] = acc.T
    acc = np.zeros((B, 8), np.int32)
    h4 = np.asarray(pb.hdig, np.int32)
    for k in range(8):
        acc |= h4[:, 8 * k:8 * k + 8] << (4 * k)
    rows[C_H4:C_H4 + 8] = acc.T
    flags = (pb.asign.astype(np.int32)
             | (pb.rsign.astype(np.int32) << 1)
             | (pb.precheck.astype(np.int32) << 2))
    if counted is not None:
        flags = flags | (np.asarray(counted, np.int32) << 3)
    rows[C_FLAGS] = flags
    if power5 is not None:
        p = np.asarray(power5, np.int32)
        rows[C_POW] = p[:, 0] | (p[:, 1] << 13)
        rows[C_POW + 1] = p[:, 2] | (p[:, 3] << 13)
        rows[C_POW + 2] = p[:, 4]
    if commit_ids is not None:
        rows[C_CID] = np.asarray(commit_ids, np.int32)
    flat = rows[C_THRESH:].reshape(-1)
    flat[: tvals.size] = tvals
    return rows


def verify_rows(rows):
    """(R, B) packed array (host or device) -> (B,) bool validity."""
    return _verify_rows(rows, base_dev())


def verify_tally_rows(rows, n_commits: int):
    """Fused verify+tally from one packed (R, B) int32 array (host or
    device). One upload, one compiled program, three outputs."""
    return _verify_tally_rows(rows, base_dev(), n_commits)


class _PB:
    """Duck-typed PackedBatch view over pre-split arrays (used by
    ops.sr25519_kernel to reuse pack_rows for schnorrkel rows)."""

    def __init__(self, ay, asign, ry, rsign, sdig, hdig, precheck):
        self.ay, self.asign, self.ry, self.rsign = ay, asign, ry, rsign
        self.sdig, self.hdig, self.precheck = sdig, hdig, precheck


def pad_to_tile(n: int) -> int:
    """Bucket size for the Pallas path: >= B_TILE and a multiple of it."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    b = ek.bucket_size(max(n, 1))
    return max(b, B_TILE)


def verify_batch(pubkeys, msgs, sigs) -> np.ndarray:
    """Drop-in equivalent of ed25519_kernel.verify_batch via Pallas."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    pb = ek.pack_batch(pubkeys, msgs, sigs, pad_to=pad_to_tile(len(pubkeys)))
    return np.asarray(verify_rows(pack_rows(pb)))[: pb.n]
