"""Pallas TPU kernel: batched sr25519 (schnorrkel) verification.

Device side of the sr25519 batch verifier (reference seam:
crypto/sr25519/batch.go:44-77 — voi's merlin-transcript batch verify).
The merlin challenge k = H(transcript) is computed HOST-side with the
numpy-batched STROBE (crypto/merlin.BatchTranscript) — the same division
of labor as ed25519's host SHA-512 — and the curve work rides the same
limbs-first Pallas machinery as ops/ed25519_pallas:

  decode_ristretto(A), decode_ristretto(R)        (RFC 9496 §4.3.1)
  P1 = [s]B + [k](-A)      (w8 comb on the shared base table + 63-window
                            double-and-add on the per-sig table)
  valid = EQUALS(P1, R)    (coset equality X1Y2==Y1X2 | Y1Y2==X1X2 —
                            no cofactor clearing needed; cheaper than
                            ed25519's 8*W identity check)

Scalar canonicality (s < L, schnorrkel marker bit) and encoding
canonicality (s_enc < p, even) are host prechecks folded into the
precheck flag, mirroring how the ed25519 pack handles non-canonical
encodings.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cometbft_tpu.crypto import ed25519_ref as ed
from cometbft_tpu.crypto import merlin
from cometbft_tpu.crypto import sr25519_ref as sr
from cometbft_tpu.ops import ed25519_pallas as kp
from cometbft_tpu.ops.ed25519_pallas import (
    _D_T,
    _D2_T,
    _M13,
    _ONE_T,
    _SQRT_M1_T,
    B_TILE,
    C_AY,
    C_CID,
    C_FLAGS,
    C_H4,
    C_KROWS,
    C_POW,
    C_RY,
    C_S8,
    C_THRESH,
    F,
    pt_add,
    pt_identity,
    pt_neg,
)
from cometbft_tpu.ops.field import NLIMBS, F25519
from cometbft_tpu.ops.field_lf import const_col, interpret_mode
from cometbft_tpu.types import canonical


def rist_decode(s, d_col, sqrt_m1_col):
    """ristretto255 DECODE, limbs-first; s (NLIMBS, B) assumed canonical
    even < p (host precheck). Returns (pt, ok)."""
    b = s.shape[1]
    one = const_col(_ONE_T, b)
    ss = F.square(s)
    u1 = F.sub(one, ss)
    u2 = F.add(one, ss)
    u2s = F.square(u2)
    v = -(F.add(F.mul(d_col, F.square(u1)), u2s))
    w = F.mul(v, u2s)
    w3 = F.mul(F.square(w), w)
    w7 = F.mul(F.square(w3), w)
    r = F.mul(w3, F.pow_p58(w7))
    check = F.mul(w, F.square(r))
    correct = F.eq(check, one)
    flipped = F.is_zero(check + one)          # check == -1
    flipped_i = F.is_zero(check + sqrt_m1_col)  # check == -sqrt(-1)
    r = jnp.where(flipped | flipped_i, F.mul(r, sqrt_m1_col), r)
    r = jnp.where(F.parity(r) != 0, -r, r)    # CT_ABS
    was_square = correct | flipped
    den_x = F.mul(r, u2)
    den_y = F.mul(F.mul(r, den_x), v)
    x = F.mul_small(F.mul(s, den_x), 2)
    x = jnp.where(F.parity(x) != 0, -x, x)    # CT_ABS
    y = F.mul(u1, den_y)
    t = F.mul(x, y)
    ok = was_square & (F.parity(t) == 0) & (~F.is_zero(y))
    return (x, y, one, t), ok


def _kernel_sr(packed_ref, base_ref, valid_ref, s8_ref, h4_ref):
    b = B_TILE
    d_col = const_col(_D_T, b)
    d2_col = const_col(_D2_T, b)
    sqrt_m1_col = const_col(_SQRT_M1_T, b)

    pk = packed_ref[:, :]
    a_enc = pk[C_AY:C_AY + 10]
    a_s = jnp.concatenate([a_enc & _M13, a_enc >> 13], axis=0)
    r_enc = pk[C_RY:C_RY + 10]
    r_s = jnp.concatenate([r_enc & _M13, r_enc >> 13], axis=0)
    s8p = pk[C_S8:C_S8 + 8]
    s8_ref[:, :] = jnp.concatenate(
        [(s8p >> (8 * k)) & 255 for k in range(4)], axis=0
    )
    h4p = pk[C_H4:C_H4 + 8]
    h4_ref[:, :] = jnp.concatenate(
        [(h4p >> (4 * k)) & 15 for k in range(8)], axis=0
    )
    pre = (pk[C_FLAGS:C_FLAGS + 1] >> 2) & 1

    A, ok_a = rist_decode(a_s, d_col, sqrt_m1_col)
    R, ok_r = rist_decode(r_s, d_col, sqrt_m1_col)
    negA = pt_neg(A)

    entries = []
    pt = pt_identity(b)
    for d in range(16):
        entries.append(jnp.stack(pt))
        if d < 15:
            pt = pt_add(pt, negA, d2_col)
    tbl = jnp.stack(entries)

    def lookup(d_row):
        ent = jnp.zeros((4, NLIMBS, b), jnp.int32)
        for dv in range(16):
            m = (d_row == dv)[None]
            ent = ent + jnp.where(m, tbl[dv], 0)
        return (ent[0], ent[1], ent[2], ent[3])

    from cometbft_tpu.ops.ed25519_pallas import pt_double, pt_double_p

    def win_body(i, pt):
        w = 62 - i
        pt = pt_double(pt_double_p(pt_double_p(pt_double_p(pt))))
        d_row = h4_ref[pl.ds(w, 1), :]
        return pt_add(pt, lookup(d_row), d2_col)

    k_negA = jax.lax.fori_loop(0, 63, win_body, lookup(h4_ref[63:64, :]))

    iota256 = jax.lax.broadcasted_iota(jnp.int32, (256, b), 0)

    def base_body(w, pt):
        d8 = s8_ref[pl.ds(w, 1), :]
        oh = (iota256 == d8).astype(jnp.float32)
        t_w = base_ref[pl.ds(w * 256, 256), :]
        ent = jax.lax.dot_general(
            t_w, oh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)
        e = ent.reshape(4, NLIMBS, b)
        return pt_add(pt, (e[0], e[1], e[2], e[3]), d2_col)

    sB = jax.lax.fori_loop(0, 32, base_body, pt_identity(b))

    P1 = pt_add(sB, k_negA, d2_col)  # s*B - k*A, extended
    # ristretto coset equality vs R: X1Y2 == Y1X2  |  Y1Y2 == X1X2
    eq = F.eq(F.mul(P1[0], R[1]), F.mul(P1[1], R[0])) | F.eq(
        F.mul(P1[1], R[1]), F.mul(P1[0], R[0])
    )
    valid = eq & ok_a & ok_r & (pre != 0)
    valid_ref[:, :] = valid.astype(jnp.int32)


@jax.jit
def _verify_rows_sr(rows, base):
    B = rows.shape[1]
    assert B % B_TILE == 0
    grid = (B // B_TILE,)
    col = lambda r: pl.BlockSpec(
        (r, B_TILE), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    full = pl.BlockSpec(
        (32 * 256, 4 * NLIMBS), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        _kernel_sr,
        interpret=interpret_mode(),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        grid=grid,
        in_specs=[col(C_KROWS), full],
        out_specs=col(1),
        scratch_shapes=[
            pltpu.VMEM((32, B_TILE), jnp.int32),
            pltpu.VMEM((64, B_TILE), jnp.int32),
        ],
    )(rows[:C_KROWS], base)
    return out[0] != 0


@functools.partial(jax.jit, static_argnums=(2,))
def _verify_tally_rows_sr(rows, base, n_commits: int):
    from cometbft_tpu.ops import ed25519_kernel as ek

    valid = _verify_rows_sr.__wrapped__(rows, base)
    pw = rows[C_POW:C_POW + 3]
    power5 = jnp.stack(
        [pw[0] & _M13, pw[0] >> 13, pw[1] & _M13, pw[1] >> 13, pw[2]],
        axis=1,
    )
    counted = (rows[C_FLAGS] >> 3) & 1 != 0
    commit_ids = rows[C_CID]
    thresh = rows[C_THRESH:].reshape(-1)[
        : n_commits * ek.TALLY_LIMBS
    ].reshape(n_commits, ek.TALLY_LIMBS)
    tally = ek.tally_core(valid, power5, counted, commit_ids, n_commits)
    return valid, tally, ek.quorum_core(tally, thresh)


def verify_rows(rows):
    return _verify_rows_sr(rows, kp.base_dev())


def verify_tally_rows(rows, n_commits: int):
    return _verify_tally_rows_sr(rows, kp.base_dev(), n_commits)


# --------------------------------------------------------------------------
# host packing
# --------------------------------------------------------------------------


_P_WORDS = np.frombuffer(
    int.to_bytes(F25519.p, 32, "little"), np.uint8
).view("<u8")


def _below_p(b: np.ndarray) -> np.ndarray:
    """value < 2^255-19, via the shared word-compare helper."""
    from cometbft_tpu.ops import ed25519_kernel as _ek

    return _ek.below_words(b, _P_WORDS)



def _length_groups(msgs):
    """(row indices, their messages as one (k, ln) uint8 matrix) for
    each message length `ln`: rows of a canonical.SignRows matrix are
    selected by length, a list of bytes is joined a group."""
    if isinstance(msgs, canonical.SignRows):
        for ln in np.unique(msgs.lens):
            idxs = np.flatnonzero(msgs.lens == ln)
            yield idxs, msgs.mat[idxs, :ln]
        return
    groups = {}
    for i, m in enumerate(msgs):
        groups.setdefault(len(m), []).append(i)
    for ln, idxs in groups.items():
        yield idxs, np.frombuffer(
            b"".join(msgs[i] for i in idxs), np.uint8
        ).reshape(len(idxs), ln)


def batch_challenges(msgs, pubs, r_encs) -> np.ndarray:
    """Merlin challenge scalars for a batch, vectorized by message length.

    `msgs` is n byte strings or a canonical.SignRows matrix of n rows;
    `pubs` and `r_encs` are n 32-byte strings each, or (n, 32) uint8.
    Returns (n, 64) uint8 of raw challenge bytes (reduce mod L happens in
    the nibble pack). Groups rows by len(msg): within a group the
    transcript op sequence is identical, so the batched STROBE applies.
    """
    from cometbft_tpu import native

    n = len(msgs)
    out = np.zeros((n, 64), np.uint8)
    prefix = sr._signing_prefix()
    pk_all, r_all = (
        a if isinstance(a, np.ndarray) else
        np.frombuffer(b"".join(a), np.uint8).reshape(n, 32)
        for a in (pubs, r_encs))
    use_native = native.available()
    for idxs, marr in _length_groups(msgs):
        parr, rarr = pk_all[idxs], r_all[idxs]
        ch = None
        if use_native and marr.shape[1] > 0:
            # whole transcripts in one C call (the numpy BatchStrobe
            # below paid ~70 ms of python/numpy op dispatch per 5k-row
            # commit — the round-4 mixed-commit host bottleneck); BatchStrobe
            # stays as the differential reference (tests/test_native)
            s = prefix.strobe
            ch = native.sr25519_batch_challenges(
                bytes(s.st), s.pos, s.pos_begin, s.cur_flags,
                marr, parr, rarr,
            )
        if ch is None:
            bt = merlin.BatchTranscript(len(idxs), prefix)
            bt.append_message_batch(b"sign-bytes", marr)
            bt.append_message_shared(b"proto-name", b"Schnorr-sig")
            bt.append_message_batch(b"sign:pk", parr)
            bt.append_message_batch(b"sign:R", rarr)
            ch = bt.challenge_bytes_batch(b"sign:c", 64)
        out[np.asarray(idxs)] = ch
    return out


_ZERO32 = b"\x00" * 32


def pack_batch_sr(pubkeys, msgs, sigs, pad_to=None,
                  power5=None, counted=None, commit_ids=None, thresh=None):
    """sr25519 rows -> compact packed array (ed25519_pallas layout),
    padded to `pad_to` rows (a served commit's chunk shape) or the
    bucket ladder's rung for n.

    C_AY carries the pubkey's ristretto s-encoding limbs, C_RY the
    signature R's, C_S8 the s-scalar byte digits, C_H4 the merlin
    challenge k's nibble digits. A row that fails a host precheck (a
    length, the marker bit, a canonicality rule) is packed as zeros
    with its precheck flag down.
    """
    from cometbft_tpu import native
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.ops import ed25519_kernel as ek

    n = len(pubkeys)
    pad = pad_to or kp.pad_to_tile(n)
    a_l = np.zeros((pad, NLIMBS), np.int32)
    r_l = np.zeros((pad, NLIMBS), np.int32)
    sdig = np.zeros((pad, 64), np.int32)
    hdig = np.zeros((pad, 64), np.int32)
    precheck = np.zeros((pad,), np.int32)

    if n:
        pubkeys = [bytes(p) for p in pubkeys]
        sigs = [bytes(s) for s in sigs]
        lenok = np.ones((n,), np.bool_)
        if not (all(len(p) == 32 for p in pubkeys)
                and all(len(s) == 64 for s in sigs)):
            # malformed rows: zeros in their place, rejected by `lenok`
            lenok = np.array([len(p) == 32 and len(s) == 64
                              for p, s in zip(pubkeys, sigs)], np.bool_)
            pubkeys = [p if len(p) == 32 else _ZERO32 for p in pubkeys]
            sigs = [s if len(s) == 64 else _ZERO32 * 2 for s in sigs]
        pk_arr = np.frombuffer(b"".join(pubkeys), np.uint8).reshape(
            n, 32).copy()
        sig_arr = np.frombuffer(b"".join(sigs), np.uint8).reshape(n, 64)
        r_arr = sig_arr[:, :32].copy()
        s_arr = sig_arr[:, 32:].copy()
        lenok &= (s_arr[:, 31] & 0x80) != 0  # schnorrkel's marker bit
        s_arr[:, 31] &= 0x7F
        # merlin hashes the message bytes: a commit's lazy rows become
        # one matrix (no bytes object a row) unless the caller expanded
        # them already, anything else a list
        if isinstance(msgs, canonical.TemplateRows):
            msgs = msgs.expand()
        elif not isinstance(msgs, canonical.SignRows):
            msgs = [bytes(m) for m in msgs]
        # the merlin / STROBE / keccak transcripts, one native call
        with tracing.stage("sr25519.challenge", rows=n):
            chal = batch_challenges(msgs, pubkeys, r_arr)
        # canonicality prechecks, vectorized: encodings < p and even,
        # s < L (same semantics as the reference's decode rejections)
        ok = (lenok & _below_p(pk_arr) & _below_p(r_arr)
              & ((pk_arr[:, 0] & 1) == 0) & ((r_arr[:, 0] & 1) == 0)
              & ek.s_below_l(s_arr))
        # k = challenge mod L: native batch reduce, bigint fallback
        k_red = native.batch_reduce_mod_l(chal)
        if k_red is None:
            k_red = np.zeros((n, 32), np.uint8)
            for i in range(n):
                k_red[i] = np.frombuffer(
                    (int.from_bytes(bytes(chal[i]), "little")
                     % ed.L).to_bytes(32, "little"), np.uint8
                )
        # zeroing the inputs of failed rows zeroes every derived output
        # (from_bytes_le(0) == 0, nibbles(0) == 0) — one mask layer
        bad = ~ok
        for arr in (pk_arr, r_arr, s_arr, k_red):
            arr[bad] = 0
        a_l[:n] = F25519.from_bytes_le(pk_arr)
        r_l[:n] = F25519.from_bytes_le(r_arr)
        sdig[:n] = ek.nibbles(s_arr)
        hdig[:n] = ek.nibbles(k_red)
        precheck[:n] = ok.astype(np.int32)

    pb = kp._PB(a_l, np.zeros((pad,), np.int32), r_l,
                np.zeros((pad,), np.int32), sdig, hdig, precheck)
    pb.n = n
    return kp.pack_rows(pb, power5, counted, commit_ids, thresh)


def verify_batch(pubkeys, msgs, sigs) -> np.ndarray:
    """Batch verify in ONE pass padded by the bucket ladder; (n,) bool.
    What crypto/batch reaches where no kernel was installed for
    sr25519 (the verify plane's dispatcher, a bare verify_batch call);
    the served commit check installs its own, which feeds the same
    pack_batch_sr / verify_rows as chunks of one shape
    (types/validation.device_batch_fn)."""
    n = len(pubkeys)
    rows = pack_batch_sr(pubkeys, msgs, sigs)
    return np.asarray(verify_rows(rows))[:n]
