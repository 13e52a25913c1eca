"""The operations and bytes the verification algorithm needs, as
functions of its shapes, and the least time a chip could take for them
by the table of peaks (peaks.json, keyed by device_kind; a device that
is not in the table is an error).

The counts follow the algorithm the cached-table kernel states
(`ops/ed25519_cached.py`, module docstring), not its instruction
stream: per signature
  h * (-A) from the validator's window table: 28 doublings + 64 mixed
      additions (7 field multiplications each);
  s * B by an 8-bit comb: 32 mixed additions, the doublings shared;
  R decompressed: one square-root chain, 254 squarings + 12 multiplications;
  8 W == identity: 3 doublings and a comparison (4 multiplications).
A doubling is 4 squarings + 4 multiplications. A field element is 20
limbs of 13 bits in int32 (`ops/field_lf.py`): a multiplication is
20 x 20 = 400 multiply-adds, a squaring 20 * 21 / 2 = 210, carries and
the reduction not counted. SHA-512 of the stamped rows and the scalar
reduction mod L are not multiply-adds and are left out, so the least
time is a floor.
Bytes per signature: the staged delta row (80 B: signature, timestamp
words, flags) and the validator's table block, 8 bases x 16 entries x
64 rows of int16 = 16 KB read once per signature.
"""
from __future__ import annotations

import json
import os

from harness import catalog

NLIMBS = 20
MUL = NLIMBS * NLIMBS
SQR = NLIMBS * (NLIMBS + 1) // 2
DOUBLING = 4 * SQR + 4 * MUL
MIXED_ADD = 7 * MUL
TABLE_BYTES_PER_VALIDATOR = 8 * 16 * 64 * 2
DELTA_ROW_BYTES = 80


def cached_verify(sigs: int) -> dict:
    """{"int32_mac": ..., "bytes": ...} for `sigs` signatures verified
    against a cached validator-set table."""
    per_sig = (28 * DOUBLING + 64 * MIXED_ADD   # h * (-A)
               + 32 * MIXED_ADD                 # s * B
               + 254 * SQR + 12 * MUL           # decompress R
               + 3 * DOUBLING + 4 * MUL)        # 8 W == identity
    return {"int32_mac": sigs * per_sig,
            "bytes": sigs * (TABLE_BYTES_PER_VALIDATOR + DELTA_ROW_BYTES)}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(catalog.BENCH_DIR, "peaks.json"),
              encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json "
                       f"(has {sorted(table)})")
    return table[device_kind]


def least_seconds(device_kind: str, need: dict):
    """(seconds, which bound applies) for `need` on one chip; None
    where the table's measured row has no value yet."""
    pk = peaks(device_kind)
    if pk["int32_mac_per_s"]["value"] is None:
        return None
    compute = need["int32_mac"] / pk["int32_mac_per_s"]["value"]
    memory = need["bytes"] / pk["hbm_bytes_per_s"]["value"]
    return (compute, "int32_mac") if compute >= memory else (memory, "hbm")
