"""The plain reference of the `light-secp-10k` deployment: secp256k1
ECDSA as upstream checks it, and one step of a skipping light client.

It imports nothing of the program under test. A signature is checked by
OpenSSL (`verify_sig`: `cryptography`'s ECDSA over SHA-256, given the
DER of r and s) after btcec's rules, written out in `parse`, which
OpenSSL does not apply: a 33-byte compressed key with prefix 2 or 3, a
64-byte `r || s` big-endian signature, r and s in [1, N-1], and
s <= N/2 (upstream `crypto/secp256k1/secp256k1.go:192-220` refuses a
high-S signature). `verify_sig_ints` is the same check over plain
Python integers (affine secp256k1, some 20 ms a signature): the third
voice the tier-1 tests hear beside OpenSSL and the program's own
`secp256k1_ref`.

The light step follows upstream `light/verifier.go:32-91`
(`VerifyNonAdjacent`) and `types/validation.go:60-257`: the header
checks in their order, then MORE than `trust_level` of the OLD set's
power among the commit's rows, looked up by ADDRESS (rows of
validators the old set does not know are passed over, a second row of
one validator is a double vote), then MORE than 2/3 of the NEW set's
power, by index. In each check the rows are collected in the commit's
order until the power is reached and no further, the power is checked
BEFORE any signature, and the first bad signature among those
collected takes the blame by its index in the COMMIT. It takes plain
data: keys, powers, the signed bytes and the signatures. The signed
bytes are made by the program's `types/canonical` encoder, which made
them for signing too, and header and set hashes by the program's
types: this reference does not test those (tier-1's golden vectors
do), so `validate_basic` and the validator-set hash comparison are not
in it.

An outcome is `("ok",)`, or the error a step ends with and what it
names: `("cant_be_trusted", needed)` (bisect),
`("invalid_header", "invalid_signature", commit index)`,
`("invalid_header", "not_enough_power", needed)`,
`("invalid_header", "double_vote", the validator's address in hex)`,
`("invalid_header", <which header check>)`, `("expired",)`,
`("adjacent",)`.
"""
from __future__ import annotations

import hashlib
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    encode_dss_signature,
)

OK = ("ok",)

# y^2 = x^3 + 7 over F_P; G of prime order N (SEC 2, section 2.4.1)
P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8


# --------------------------------------------------------------------------
# one signature
# --------------------------------------------------------------------------


def parse(pub: bytes, sig: bytes) -> Optional[Tuple[int, int]]:
    """btcec's rules on the encodings: (r, s), or None where the key
    or the signature is refused before any curve arithmetic."""
    if len(pub) != 33 or pub[0] not in (2, 3) or len(sig) != 64:
        return None
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:], "big")
    if not (1 <= r < N and 1 <= s < N) or s > N // 2:
        return None
    return r, s


@lru_cache(maxsize=32768)
def _openssl_key(pub: bytes):
    """A validator's key signs every commit: decoded once. None where
    OpenSSL refuses the point (x >= P, or not on the curve)."""
    try:
        return ec.EllipticCurvePublicKey.from_encoded_point(
            ec.SECP256K1(), pub)
    except ValueError:
        return None


def verify_sig(pub: bytes, msg: bytes, sig: bytes) -> bool:
    rs = parse(pub, sig)
    key = _openssl_key(bytes(pub)) if rs else None
    if key is None:
        return False
    try:
        key.verify(encode_dss_signature(*rs), bytes(msg),
                   ec.ECDSA(hashes.SHA256()))
    except InvalidSignature:
        return False
    return True


def _add(a, b):
    """Affine addition on the curve; None is the point at infinity."""
    if a is None or b is None:
        return a if b is None else b
    (x1, y1), (x2, y2) = a, b
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return x3, (lam * (x1 - x3) - y1) % P


def _mul(k: int, pt):
    acc = None
    while k:
        if k & 1:
            acc = _add(acc, pt)
        pt = _add(pt, pt)
        k >>= 1
    return acc


def verify_sig_ints(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """`verify_sig` with no library: SEC 1 section 4.1.4 after the
    same rules, the key decompressed by y = (x^3 + 7)^((P+1)/4)."""
    rs = parse(pub, sig)
    if rs is None:
        return False
    r, s = rs
    x = int.from_bytes(pub[1:], "big")
    yy = (x * x * x + 7) % P
    y = pow(yy, (P + 1) // 4, P)
    if x >= P or y * y % P != yy:
        return False
    if y & 1 != pub[0] & 1:
        y = P - y
    z = int.from_bytes(hashlib.sha256(msg).digest(), "big")
    w = pow(s, -1, N)
    at = _add(_mul(z * w % N, (GX, GY)), _mul(r * w % N, (x, y)))
    return at is not None and at[0] % N == r


def address(pub: bytes) -> bytes:
    """RIPEMD160(SHA256(compressed key)): upstream secp256k1.go:131."""
    return hashlib.new("ripemd160", hashlib.sha256(pub).digest()).digest()


# --------------------------------------------------------------------------
# the two commit checks of a step
# --------------------------------------------------------------------------


def light_rows(powers: Sequence[int], sigs: Sequence[Optional[bytes]]):
    """The rows `verify_commit_light` collects: (commit indexes, their
    power, the power that has to be passed)."""
    needed = sum(powers) * 2 // 3
    rows: List[int] = []
    tallied = 0
    for i, sig in enumerate(sigs):
        if sig is None:
            continue
        rows.append(i)
        tallied += powers[i]
        if tallied > needed:
            break
    return rows, tallied, needed


def trusting_rows(old: Dict[bytes, Tuple[bytes, int]],
                  addresses: Sequence[bytes],
                  sigs: Sequence[Optional[bytes]],
                  trust_level: Tuple[int, int] = (1, 3)):
    """The rows `verify_commit_light_trusting` collects of a commit
    whose row i was signed by `addresses[i]`: (commit indexes, their
    power in the OLD set, the power to pass, the address of a double
    vote met while collecting, or None). `old` is the trusted set:
    address -> (key, power)."""
    num, den = trust_level
    needed = sum(p for _, p in old.values()) * num // den
    rows: List[int] = []
    seen = set()
    tallied = 0
    for i, (addr, sig) in enumerate(zip(addresses, sigs)):
        if sig is None or addr not in old:
            continue
        if addr in seen:
            return rows, tallied, needed, addr
        seen.add(addr)
        rows.append(i)
        tallied += old[addr][1]
        if tallied > needed:
            break
    return rows, tallied, needed, None


def _first_bad(rows, pubs, msgs, sigs, verify=verify_sig) -> Optional[int]:
    return next((i for i in rows
                 if not verify(pubs[i], msgs[i], sigs[i])), None)


def verify_commit_light(pubs, powers, msgs, sigs, verify=verify_sig):
    """("ok",) | ("invalid_signature", idx) | ("not_enough_power",
    needed): `plain.verify_commit_light` over unequal powers and
    secp256k1 keys."""
    rows, tallied, needed = light_rows(powers, sigs)
    if tallied <= needed:
        return ("not_enough_power", needed)
    bad = _first_bad(rows, pubs, msgs, sigs, verify)
    return OK if bad is None else ("invalid_signature", bad)


def verify_commit_light_trusting(old, addresses, msgs, sigs,
                                 trust_level=(1, 3), verify=verify_sig):
    """("ok",) | ("double_vote", address in hex) |
    ("not_enough_power", needed) |
    ("invalid_signature", idx). A row is checked against the key the
    OLD set holds for its address."""
    rows, tallied, needed, twice = trusting_rows(old, addresses, sigs,
                                                 trust_level)
    if twice is not None:
        return ("double_vote", twice.hex())
    if tallied <= needed:
        return ("not_enough_power", needed)
    keys = {i: old[addresses[i]][0] for i in rows}
    bad = _first_bad(rows, keys, msgs, sigs, verify)
    return OK if bad is None else ("invalid_signature", bad)


# --------------------------------------------------------------------------
# one step of the skipping client
# --------------------------------------------------------------------------


def verify_non_adjacent(trusted: dict, new: dict, now_ns: int,
                        trusting_period_s: float = 14 * 24 * 3600.0,
                        max_clock_drift_s: float = 10.0,
                        trust_level: Tuple[int, int] = (1, 3),
                        verify=verify_sig) -> Tuple:
    """One `VerifyNonAdjacent`. `trusted` holds the trusted block's
    "height", "time_ns", and its set as "pubs" and "powers"; `new`
    those four of the block to verify and its commit as "msgs" and
    "sigs" (None = absent), row i signed by validator i of ITS set."""
    if new["height"] == trusted["height"] + 1:
        return ("adjacent",)
    if now_ns >= trusted["time_ns"] + int(trusting_period_s * 1e9):
        return ("expired",)
    if new["height"] <= trusted["height"]:
        return ("invalid_header", "height")
    if new["time_ns"] <= trusted["time_ns"]:
        return ("invalid_header", "time")
    if new["time_ns"] > now_ns + int(max_clock_drift_s * 1e9):
        return ("invalid_header", "from_the_future")
    old = {address(k): (k, p)
           for k, p in zip(trusted["pubs"], trusted["powers"])}
    got = verify_commit_light_trusting(
        old, [address(k) for k in new["pubs"]], new["msgs"], new["sigs"],
        trust_level, verify)
    if got[0] == "not_enough_power":
        return ("cant_be_trusted", got[1])
    if got != OK:
        return ("invalid_header",) + got
    got = verify_commit_light(new["pubs"], new["powers"], new["msgs"],
                              new["sigs"], verify)
    return OK if got == OK else ("invalid_header",) + got
