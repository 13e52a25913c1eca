"""On-TPU differential: cached-valset kernel vs the ZIP-215 oracle.

The authoritative correctness check for ops.ed25519_cached on real
hardware (the Pallas-interpret CPU path is compile-prohibitive for
this kernel — see tests/test_ed25519_cached.py). `edge_cases()` is the
vector set: valid rows, tampered sig/msg, S>=L malleability, bad
pubkey, small-order identity, the -0 sign encoding, non-canonical y in
R and in A, and an off-curve R. chip_smoke.py sends the same vectors
through every ed25519 kernel as its kernel leg.

Run on the chip: python tools/tpu_differential.py (about a minute of
cold compile). Refuses to run on the CPU backend.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import numpy as np  # noqa: E402

from cometbft_tpu.crypto import ed25519_ref as ed  # noqa: E402


def edge_cases():
    """[(pubkey, msg, sig)] with the oracle's verdicts mixed: the
    ZIP-215 corner every kernel must decide exactly like ed25519_ref."""
    cases = []
    for i in range(8):
        seed = bytes([i + 1]) * 32
        m = b"msg-%d" % i
        cases.append((ed.pubkey_from_seed(seed), m, ed.sign(seed, m)))
    # tampered sig / msg / malleable S / bad pubkey
    pk, m, s = cases[2]
    cases[2] = (pk, m, s[:10] + bytes([s[10] ^ 1]) + s[11:])
    pk, m, s = cases[5]
    cases[5] = (pk, m + b"t", s)
    pk, m, s = cases[6]
    cases[6] = (pk, m, s[:32] + int.to_bytes(
        int.from_bytes(s[32:], "little") + ed.L, 32, "little"))
    cases[7] = (b"\xff" * 32, b"m", cases[7][2])
    # small-order identity, its -0 encoding, zero s
    ident = ed.pt_compress(ed.IDENT)
    cases.append((ident, b"m", ident + b"\x00" * 32))
    ident_neg = ident[:31] + bytes([ident[31] | 0x80])
    cases.append((ident, b"m", ident_neg + b"\x00" * 32))
    neg_zero = int.to_bytes(1 | (1 << 255), 32, "little")
    cases.append((neg_zero, b"m", neg_zero + b"\x00" * 32))
    # non-canonical y (y + p) as R and as A, and an off-curve R
    for y in range(2, 60):
        u, v = (y * y - 1) % ed.P, (ed.D * y * y + 1) % ed.P
        ok, x = ed._sqrt_ratio(u, v)
        if ok:
            enc_nc = int.to_bytes((y + ed.P) | ((x & 1) << 255), 32,
                                  "little")
            break
    seed0 = bytes(32)
    pk0 = ed.pubkey_from_seed(seed0)
    s0 = ed.sign(seed0, b"x")
    cases.append((pk0, b"x", enc_nc + s0[32:]))
    cases.append((enc_nc, b"x", s0))
    cases.append((pk0, b"x", int.to_bytes(2, 32, "little") + s0[32:]))
    return cases


def main() -> int:
    from cometbft_tpu.libs import deviceledger
    from cometbft_tpu.libs.jax_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    print("device:", deviceledger.require_accelerator())
    from cometbft_tpu.ops import ed25519_cached as ec

    cases = edge_cases()
    pubs, msgs, sigs = (list(z) for z in zip(*cases))
    got = ec.verify_batch_cached(pubs, msgs, sigs)
    exp = np.asarray([ed.verify(p, m, s) for p, m, s in cases])
    print("got:", got.astype(int))
    print("exp:", exp.astype(int))
    assert (got == exp).all(), np.nonzero(got != exp)
    print("CACHED KERNEL: all", len(cases), "cases match oracle")
    return 0


if __name__ == "__main__":
    sys.exit(main())
