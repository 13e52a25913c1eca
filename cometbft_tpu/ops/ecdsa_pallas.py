"""Pallas TPU kernel: batched secp256k1 ECDSA verification.

The XLA-composed kernel (ops.ecdsa_kernel) materializes every field op
in HBM and ran ~4 s for a 10k batch — slower than a single-core OpenSSL
loop, which made BASELINE config #5 a loss. This kernel applies the
ed25519_pallas design (limbs-first VMEM-resident tiles, w8 base comb via
MXU one-hot matmul, per-signature window table) to the Renes–Costello–
Batina complete a=0 short-Weierstrass formulas (eprint 2015/1060, algs
7/9 — branch-free, so identity/doubling cases need no masks).

Per signature (host pack shared with ops.ecdsa_kernel.pack_batch):
  host:   z = SHA256(msg); w = s^-1 mod N; u1 = z*w; u2 = r*w
  device: decompress Q (sqrt via x^((p+1)/4), p ≡ 3 mod 4);
          R = [u1]G + [u2]Q;
          valid iff Z != 0 and (X == r*Z or X == (r+N)*Z)   (no inversion)

Reference: crypto/secp256k1/secp256k1.go:192-220 single verify; the
batch capability itself has NO reference counterpart
(crypto/batch/batch.go:12-21).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cometbft_tpu.crypto import secp256k1_ref as ref
from cometbft_tpu.ops import ecdsa_kernel as ek
from cometbft_tpu.ops.field import FSECP, NLIMBS
from cometbft_tpu.ops.field_lf import FieldLF, const_col, interpret_mode

FS = FieldLF(FSECP)
B_TILE = 128
_M13 = (1 << 13) - 1
_B7_T = FS.const_limbs(ref.B)  # curve b = 7
_ONE_T = (1,) + (0,) * (NLIMBS - 1)

# compact row layout (all int32, lanes = signatures)
E_QX = 0       # 10 rows: pubkey x, limb pairs l[i] | l[i+10] << 13
E_XR1 = 10     # 10 rows: r as a field element
E_XR2 = 20     # 10 rows: r + N if < p else r
E_U1 = 30      # 8 rows: u1 byte digits (4 per word) for the base comb
E_U2 = 38      # 8 rows: u2 nibble digits (8 per word) for the window loop
E_FLAGS = 46   # parity | precheck << 2
E_KROWS = 47


def s_add(p, q, b=None):
    """RCB complete addition (alg 7, a=0, b3=21), limbs-first 3-tuples."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    t0 = FS.mul(X1, X2)
    t1 = FS.mul(Y1, Y2)
    t2 = FS.mul(Z1, Z2)
    t3 = FS.mul(FS.add(X1, Y1), FS.add(X2, Y2))
    t3 = FS.sub(t3, FS.add(t0, t1))
    t4 = FS.mul(FS.add(Y1, Z1), FS.add(Y2, Z2))
    t4 = FS.sub(t4, FS.add(t1, t2))
    X3 = FS.mul(FS.add(X1, Z1), FS.add(X2, Z2))
    Y3 = FS.sub(X3, FS.add(t0, t2))
    t0 = FS.mul_small(t0, 3)
    t2 = FS.mul_small(t2, 3 * ref.B)
    Z3 = FS.add(t1, t2)
    t1 = FS.sub(t1, t2)
    Y3 = FS.mul_small(Y3, 3 * ref.B)
    X3 = FS.sub(FS.mul(t3, t1), FS.mul(t4, Y3))
    Y3 = FS.add(FS.mul(t1, Z3), FS.mul(Y3, t0))
    Z3 = FS.add(FS.mul(Z3, t4), FS.mul(t0, t3))
    return (X3, Y3, Z3)


def s_double(p):
    """RCB complete doubling (alg 9, a=0): 6M + 2S."""
    X, Y, Z = p
    t0 = FS.square(Y)
    Z3 = FS.mul_small(t0, 8)
    t1 = FS.mul(Y, Z)
    t2 = FS.mul_small(FS.square(Z), 3 * ref.B)
    X3 = FS.mul(t2, Z3)
    Y3 = FS.add(t0, t2)
    Z3 = FS.mul(t1, Z3)
    t2 = FS.mul_small(t2, 3)
    t0 = FS.sub(t0, t2)
    Y3 = FS.add(X3, FS.mul(t0, Y3))
    X3 = FS.mul_small(FS.mul(FS.mul(X, Y), t0), 2)
    return (X3, Y3, Z3)


def s_identity(b):
    one = const_col(_ONE_T, b)
    zero = jnp.zeros((NLIMBS, b), jnp.int32)
    return (zero, one, zero)


def powc(x, e: int):
    """x^e for a host-constant exponent: width-4 windows, squaring runs
    compressed through fori_loop (FS.pow2k) to keep the trace small."""
    digs = []
    while e:
        digs.append(e & 15)
        e >>= 4
    digs.reverse()
    tbl = [None, x]
    for i in range(2, 16):
        tbl.append(FS.mul(tbl[i - 1], x))
    acc = tbl[digs[0]]
    for d in digs[1:]:
        acc = FS.pow2k(acc, 4)
        if d:
            acc = FS.mul(acc, tbl[d])
    return acc


def s_decompress(x, parity_row):
    """Compressed-point sqrt: y = (x^3+7)^((p+1)/4); ok iff y^2 matches."""
    b = x.shape[1]
    yy = FS.add(FS.mul(FS.square(x), x), const_col(_B7_T, b))
    y = powc(yy, (ref.P + 1) // 4)
    ok = FS.eq(FS.square(y), yy)
    flip = FS.parity(y) != parity_row
    y = jnp.where(flip, -y, y)
    return (x, y, const_col(_ONE_T, b)), ok


def _kernel(packed_ref, base_ref, valid_ref, u1_ref, u2_ref):
    b = B_TILE
    pk = packed_ref[:, :]
    qx2 = pk[E_QX:E_QX + 10]
    qx = jnp.concatenate([qx2 & _M13, qx2 >> 13], axis=0)
    xr1p = pk[E_XR1:E_XR1 + 10]
    xr1 = jnp.concatenate([xr1p & _M13, xr1p >> 13], axis=0)
    xr2p = pk[E_XR2:E_XR2 + 10]
    xr2 = jnp.concatenate([xr2p & _M13, xr2p >> 13], axis=0)
    u1p = pk[E_U1:E_U1 + 8]
    u1_ref[:, :] = jnp.concatenate(
        [(u1p >> (8 * k)) & 255 for k in range(4)], axis=0
    )  # (32, b) byte digits
    u2p = pk[E_U2:E_U2 + 8]
    u2_ref[:, :] = jnp.concatenate(
        [(u2p >> (4 * k)) & 15 for k in range(8)], axis=0
    )  # (64, b) nibble digits
    flags = pk[E_FLAGS:E_FLAGS + 1]
    parity = flags & 1
    pre = (flags >> 2) & 1

    Q, ok_q = s_decompress(qx, parity)

    # per-signature window table [d]Q, d in 0..15
    entries = []
    pt = s_identity(b)
    for d in range(16):
        entries.append(jnp.stack(pt))
        if d < 15:
            pt = s_add(pt, Q)
    tbl = jnp.stack(entries)

    def lookup(d_row):
        ent = jnp.zeros((3, NLIMBS, b), jnp.int32)
        for dv in range(16):
            m = (d_row == dv)[None]
            ent = ent + jnp.where(m, tbl[dv], 0)
        return (ent[0], ent[1], ent[2])

    def win_body(i, pt):
        w = 62 - i
        pt = s_double(s_double(s_double(s_double(pt))))
        d_row = u2_ref[pl.ds(w, 1), :]
        return s_add(pt, lookup(d_row))

    u2Q = jax.lax.fori_loop(0, 63, win_body, lookup(u2_ref[63:64, :]))

    # [u1]G comb: 32 width-8 windows over the shared G table (f32 matmul)
    iota256 = jax.lax.broadcasted_iota(jnp.int32, (256, b), 0)

    def base_body(w, pt):
        d8 = u1_ref[pl.ds(w, 1), :]
        oh = (iota256 == d8).astype(jnp.float32)
        t_w = base_ref[pl.ds(w * 256, 256), :]  # (256, 60) f32
        ent = jax.lax.dot_general(
            t_w, oh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)
        e = ent.reshape(3, NLIMBS, b)
        return s_add(pt, (e[0], e[1], e[2]))

    u1G = jax.lax.fori_loop(0, 32, base_body, s_identity(b))

    X, Y, Z = s_add(u1G, u2Q)
    not_inf = ~FS.is_zero(Z)
    match = FS.eq(X, FS.mul(xr1, Z)) | FS.eq(X, FS.mul(xr2, Z))
    valid = ok_q & not_inf & match & (pre != 0)
    valid_ref[:, :] = valid.astype(jnp.int32)


_T8 = None
_BASE_DEV = None


def base_table8_np() -> np.ndarray:
    """(32*256, 3*NLIMBS) f32 comb table: row w*256+d = [d*256^w]G.

    Identity rows encode as (0, 1, 0) — the complete formulas absorb
    them with no special case."""
    global _T8
    if _T8 is None:
        from cometbft_tpu.ops import secp256k1 as curve

        inf = np.stack(
            [FSECP.from_int(0), FSECP.from_int(1), FSECP.from_int(0)]
        )
        rows = []
        g_w = (ref.GX, ref.GY)  # [256^w]G affine
        for w in range(32):
            row = [inf]
            acc = None
            for _ in range(255):
                acc = ref.pt_add(acc, g_w)
                row.append(curve.from_affine_int(acc[0], acc[1]))
            rows.append(np.stack(row))
            for _ in range(8):  # g_{w+1} = [256]g_w
                g_w = ref.pt_add(g_w, g_w)
        _T8 = np.stack(rows).reshape(32 * 256, 3 * NLIMBS).astype(np.float32)
    return _T8


def base_dev():
    global _BASE_DEV
    if _BASE_DEV is None:
        _BASE_DEV = jax.device_put(base_table8_np())
    return _BASE_DEV


@jax.jit
def _verify_rows_secp(rows, base):
    # a name of its own: a device trace names the Mosaic program after
    # it, apart from ed25519_pallas's `_verify_rows` and the sr25519
    # kernel's `_verify_rows_sr`
    B = rows.shape[1]
    assert B % B_TILE == 0
    grid = (B // B_TILE,)
    col = lambda r: pl.BlockSpec(
        (r, B_TILE), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    full = pl.BlockSpec(
        (32 * 256, 3 * NLIMBS), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        _kernel,
        interpret=interpret_mode(),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        grid=grid,
        in_specs=[col(E_KROWS), full],
        out_specs=col(1),
        scratch_shapes=[
            pltpu.VMEM((32, B_TILE), jnp.int32),  # u1 byte digits
            pltpu.VMEM((64, B_TILE), jnp.int32),  # u2 nibble digits
        ],
    )(rows[:E_KROWS], base)
    return out[0] != 0


def verify_rows(rows):
    return _verify_rows_secp(rows, base_dev())


def pack_rows(pb: ek.PackedEcdsaBatch) -> np.ndarray:
    """PackedEcdsaBatch -> compact (E_KROWS, B) int32 array."""
    B = pb.qx.shape[0]
    rows = np.zeros((E_KROWS, B), np.int32)
    qx = np.asarray(pb.qx, np.int32)
    rows[E_QX:E_QX + 10] = (qx[:, :10] | (qx[:, 10:] << 13)).T
    x1 = np.asarray(pb.xr1, np.int32)
    rows[E_XR1:E_XR1 + 10] = (x1[:, :10] | (x1[:, 10:] << 13)).T
    x2 = np.asarray(pb.xr2, np.int32)
    rows[E_XR2:E_XR2 + 10] = (x2[:, :10] | (x2[:, 10:] << 13)).T
    u1_8 = (pb.u1dig[:, 0::2] + 16 * pb.u1dig[:, 1::2]).astype(np.int32)
    acc = np.zeros((B, 8), np.int32)
    for k in range(4):
        acc |= u1_8[:, 8 * k:8 * k + 8] << (8 * k)
    rows[E_U1:E_U1 + 8] = acc.T
    acc = np.zeros((B, 8), np.int32)
    u2 = np.asarray(pb.u2dig, np.int32)
    for k in range(8):
        acc |= u2[:, 8 * k:8 * k + 8] << (4 * k)
    rows[E_U2:E_U2 + 8] = acc.T
    rows[E_FLAGS] = (np.asarray(pb.qparity, np.int32)
                     | (np.asarray(pb.precheck, np.int32) << 2))
    return rows


def pad_to_tile(n: int) -> int:
    b = ek.bucket_size(max(n, 1))
    return max(b, B_TILE)


def verify_batch(pubkeys, msgs, sigs) -> np.ndarray:
    """Drop-in replacement for ecdsa_kernel.verify_batch via Pallas."""
    pb = ek.pack_batch(pubkeys, msgs, sigs,
                       pad_to=pad_to_tile(len(pubkeys)))
    return np.asarray(verify_rows(pack_rows(pb)))[: pb.n]
