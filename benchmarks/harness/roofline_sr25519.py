"""The operations and bytes sr25519 (schnorrkel) verification needs,
per LIVE signature, for `sr25519_roofline`.

Counted from the algorithm `ops/sr25519_kernel.py`'s docstring states,
not from its instruction stream or its padded rows, so the number is
the same whatever implements it. Per signature:

  decode_ristretto(A) and decode_ristretto(R) (RFC 9496 section 4.3.1):
      each ONE inverse-square-root chain (254 squarings + 12
      multiplications, as harness/roofline.py counts a square root) and
      4 squarings + 11 multiplications around it (s^2, u2^2, u1^2,
      d u1^2, v u2^2, w^3 and w^7, the candidate root, its check,
      den_x, den_y, x, y, t);
  k (-A) by a 16-entry table of the signature's own key and 63 windows:
      14 additions for the table, then 63 x (4 doublings + 1 addition)
      of full extended points (9 multiplications an addition);
  s B by an 8-bit comb on the shared base table: 32 mixed additions
      (7 multiplications each);
  P1 = s B + k (-A): one addition; the coset equality X1 Y2 == Y1 X2
      or Y1 Y2 == X1 X2: 4 multiplications.

A field element is 20 limbs of 13 bits in int32, a multiplication 400
multiply-adds, a squaring 210, a doubling 4 squarings + 4
multiplications (harness/roofline.py, whose constants these are);
carries, reductions, selects and the table look-ups are not counted,
and the merlin transcripts run on the host, so the least time is a
floor. Bytes per signature: its packed row in (42 int32) and its
verdict out; the 2.6 MB base table is read once a pass and shared with
the padding, so it is left out.
"""
from __future__ import annotations

from harness.roofline import DOUBLING, MIXED_ADD, MUL, SQR

FULL_ADD = 9 * MUL
DECODE = (254 * SQR + 12 * MUL) + (4 * SQR + 11 * MUL)
PACKED_ROW_BYTES = 42 * 4
VERDICT_BYTES = 4


def sr25519_verify(sigs: int) -> dict:
    """{"int32_mac": ..., "bytes": ...} for `sigs` live signatures."""
    per_sig = (2 * DECODE                               # A and R
               + 14 * FULL_ADD                          # table of -A
               + 63 * (4 * DOUBLING + FULL_ADD)         # k (-A)
               + 32 * MIXED_ADD                         # s B
               + FULL_ADD + 4 * MUL)                    # P1, equality
    return {"int32_mac": sigs * per_sig,
            "bytes": sigs * (PACKED_ROW_BYTES + VERDICT_BYTES)}
