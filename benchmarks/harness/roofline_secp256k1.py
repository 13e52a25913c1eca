"""The operations and bytes secp256k1 ECDSA verification needs, per
LIVE signature, for `secp256k1_roofline`.

Counted from the algorithm `ops/ecdsa_pallas.py`'s docstring states
(R = u1 G + u2 Q, valid iff R is finite and x(R) = r mod N, checked
projectively), not from its instruction stream or its padded rows, so
the number is the same whatever implements it. Per signature:

  Q decompressed: x^3 + 7 (a squaring and a multiplication), its
      square root as ((x^3 + 7)^((P+1)/4): the chain over the exponent's
      three runs of ones, 254 squarings + 13 multiplications), and the
      root's check (a squaring);
  u2 Q by a 16-entry table of the signature's own key and 64 windows of
      4 bits: 14 complete additions for the table (Renes-Costello-
      Batina, a = 0: 12 multiplications an addition), then 63 x (4
      doublings + 1 addition), a doubling 6 multiplications + 2
      squarings; the top window is a look-up alone;
  u1 G by an 8-bit comb on the shared base table: 32 mixed additions
      (11 multiplications each);
  R = u1 G + u2 Q: one addition; X == r Z or X == (r + N) Z: 2
      multiplications.

A field element is 20 limbs of 13 bits in int32, a multiplication 400
multiply-adds, a squaring 210 (harness/roofline.py, whose constants
these are); carries, reductions, the multiplications by the small
constants 3 and 21, selects and the table look-ups are not counted,
and SHA-256, the inverse of s and u1, u2 run on the host, so the least
time is a floor. Bytes per signature: its packed row in (47 int32) and
its verdict out; the 1.9 MB base table is read once a pass and shared
with the padding, so it is left out.
"""
from __future__ import annotations

from harness.roofline import MUL, SQR

ADD = 12 * MUL
MIXED_ADD = 11 * MUL
DOUBLING = 6 * MUL + 2 * SQR
DECOMPRESS = (SQR + MUL) + (254 * SQR + 13 * MUL) + SQR
PACKED_ROW_BYTES = 47 * 4
VERDICT_BYTES = 4


def ecdsa_verify(sigs: int) -> dict:
    """{"int32_mac": ..., "bytes": ...} for `sigs` live signatures."""
    per_sig = (DECOMPRESS                          # Q
               + 14 * ADD                          # table of Q
               + 63 * (4 * DOUBLING + ADD)         # u2 Q
               + 32 * MIXED_ADD                    # u1 G
               + ADD + 2 * MUL)                    # R, x(R) == r
    return {"int32_mac": sigs * per_sig,
            "bytes": sigs * (PACKED_ROW_BYTES + VERDICT_BYTES)}
