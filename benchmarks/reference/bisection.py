"""The plain reference of the `light-ed-10k` deployment: upstream's
skipping light client, bisection and all, over a chain of ed25519
validators of unequal power.

It imports nothing of the program under test. `verify_skipping` is
upstream `light/client.go:706` (`verifySkipping`) line for line over
plain data: a cache of upper bounds (the target first) and a depth;
on `ErrNewValSetCantBeTrusted` a pivot is fetched at `verified +
(upper - verified) * 1/2` unless one is cached; after EVERY verified
block the depth goes back to 0, the target, and the pivots already
fetched above that block are kept. A pivot the provider lacks ends the
verification with the error that asked for it (upstream's benign
`ErrLightBlockNotFound`).

Each attempt is one `Verify` (`light/verifier.go:139`):
`verify_non_adjacent`, written out as `reference/ecdsa.verify_non_
adjacent` is, or `verify_adjacent`. Their commit checks are
`ecdsa.verify_commit_light_trusting` and `ecdsa.verify_commit_light`,
called with `plain.verify_sig` (OpenSSL's Ed25519) and ed25519
addresses: the first 20 bytes of the key's SHA-256
(`crypto/ed25519/ed25519.go` `Address`). Every block fetched has its
validator set hashed here (`validators_root`: SimpleValidator leaves,
RFC 6962 prefixes and split, `hashlib`) and compared with its header,
as `LightBlock.ValidateBasic` does.

A block is a dict: "height", "time_ns", "validators_hash",
"next_validators_hash", its set as "pubs" and "powers" (in the set's
order), and its commit as "msgs" and "sigs" (None = absent), row i
signed by validator i of its own set. `fetch(height)` gives one, or
None where the provider has none.

`verify_skipping` returns (verdict, trace, attempts):

- verdict: `("trusted",)`, or `("refused", height) + outcome` for the
  block that ended it: an attempt's outcome in `ecdsa`'s words
  (`("cant_be_trusted", needed)`, `("invalid_header",
  "invalid_signature", commit index)`, ...), `("no_such_block",)` for
  a target the provider lacks, or `("invalid_header",
  "validators_hash")` for a set that does not hash to its header;
- trace: the heights verified, in order, the trusted one first
  (upstream's `trace`);
- attempts: `(trusted height, candidate height, outcome)` of every
  `Verify`, in order.
"""
from __future__ import annotations

import hashlib
from typing import Callable, List, Optional, Sequence, Tuple

from reference import ecdsa, plain

TRUSTED = ("trusted",)


# --------------------------------------------------------------------------
# a validator set's root
# --------------------------------------------------------------------------


def _uvarint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append(v & 0x7F | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def simple_validator(pub: bytes, power: int) -> bytes:
    """types/validator.go:119 `Bytes`: the proto of a SimpleValidator
    whose PublicKey is ed25519 (oneof field 1) and its voting power
    (field 2, left out where 0)."""
    key = b"\x0a" + _uvarint(len(pub)) + pub
    out = b"\x0a" + _uvarint(len(key)) + key
    return out + (b"\x10" + _uvarint(power) if power else b"")


def _root(hashes: Sequence[bytes]) -> bytes:
    if len(hashes) == 1:
        return hashes[0]
    k = 1
    while 2 * k < len(hashes):
        k *= 2  # the largest power of two below the count
    return hashlib.sha256(b"\x01" + _root(hashes[:k])
                          + _root(hashes[k:])).digest()


def validators_root(pubs: Sequence[bytes], powers: Sequence[int]) -> bytes:
    """`ValidatorSet.Hash` (validator_set.go:347): the RFC 6962 merkle
    root (crypto/merkle/tree.go) of the members' SimpleValidator
    leaves, leaf prefix 0x00, inner prefix 0x01."""
    if not pubs:
        return hashlib.sha256(b"").digest()
    return _root([hashlib.sha256(b"\x00" + simple_validator(k, p)).digest()
                  for k, p in zip(pubs, powers)])


def address(pub: bytes) -> bytes:
    """An ed25519 key's address: SHA-256 of the key, first 20 bytes."""
    return hashlib.sha256(pub).digest()[:20]


# --------------------------------------------------------------------------
# one attempt
# --------------------------------------------------------------------------


def _header_checks(trusted: dict, new: dict, now_ns: int,
                   trusting_period_s: float,
                   max_clock_drift_s: float) -> Optional[Tuple]:
    if now_ns >= trusted["time_ns"] + int(trusting_period_s * 1e9):
        return ("expired",)
    if new["height"] <= trusted["height"]:
        return ("invalid_header", "height")
    if new["time_ns"] <= trusted["time_ns"]:
        return ("invalid_header", "time")
    if new["time_ns"] > now_ns + int(max_clock_drift_s * 1e9):
        return ("invalid_header", "from_the_future")
    return None


def verify_non_adjacent(trusted: dict, new: dict, now_ns: int,
                        trusting_period_s: float, max_clock_drift_s: float,
                        trust_level: Tuple[int, int],
                        verify=plain.verify_sig) -> Tuple:
    """`VerifyNonAdjacent` (light/verifier.go:32): the header checks,
    MORE than `trust_level` of the OLD set's power by address, then
    MORE than 2/3 of the NEW set's by index."""
    bad = _header_checks(trusted, new, now_ns, trusting_period_s,
                         max_clock_drift_s)
    if bad is not None:
        return bad
    old = {address(k): (k, p)
           for k, p in zip(trusted["pubs"], trusted["powers"])}
    got = ecdsa.verify_commit_light_trusting(
        old, [address(k) for k in new["pubs"]], new["msgs"], new["sigs"],
        trust_level, verify)
    if got[0] == "not_enough_power":
        return ("cant_be_trusted", got[1])
    if got != ecdsa.OK:
        return ("invalid_header",) + got
    got = ecdsa.verify_commit_light(new["pubs"], new["powers"], new["msgs"],
                                    new["sigs"], verify)
    return ecdsa.OK if got == ecdsa.OK else ("invalid_header",) + got


def verify_adjacent(trusted: dict, new: dict, now_ns: int,
                    trusting_period_s: float, max_clock_drift_s: float,
                    verify=plain.verify_sig) -> Tuple:
    """`VerifyAdjacent` (light/verifier.go:93): the header checks, the
    new set named by the trusted header's next validators hash, MORE
    than 2/3 of the new set's power."""
    bad = _header_checks(trusted, new, now_ns, trusting_period_s,
                         max_clock_drift_s)
    if bad is not None:
        return bad
    if new["validators_hash"] != trusted["next_validators_hash"]:
        return ("invalid_header", "next_validators_hash")
    got = ecdsa.verify_commit_light(new["pubs"], new["powers"], new["msgs"],
                                    new["sigs"], verify)
    return ecdsa.OK if got == ecdsa.OK else ("invalid_header",) + got


# --------------------------------------------------------------------------
# the skipping loop
# --------------------------------------------------------------------------


def verify_skipping(trusted: dict, height: int,
                    fetch: Callable[[int], Optional[dict]], now_ns: int,
                    trusting_period_s: float = 14 * 24 * 3600.0,
                    max_clock_drift_s: float = 10.0,
                    trust_level: Tuple[int, int] = (1, 3),
                    verify=plain.verify_sig):
    """`VerifyLightBlockAtHeight(height)` from the trusted block
    `trusted` (light/client.go:474): the target's fetch and its set's
    root, then `verifySkipping` (:706). (verdict, trace, attempts)."""
    attempts: List[Tuple] = []
    trace = [trusted["height"]]

    def fetched(h: int):
        blk = fetch(h)
        if blk is None:
            return None, ("no_such_block",)
        if validators_root(blk["pubs"], blk["powers"]) != \
                blk["validators_hash"]:
            return None, ("invalid_header", "validators_hash")
        return blk, None

    def attempt(old: dict, new: dict) -> Tuple:
        if new["height"] == old["height"] + 1:
            got = verify_adjacent(old, new, now_ns, trusting_period_s,
                                  max_clock_drift_s, verify)
        else:
            got = verify_non_adjacent(old, new, now_ns, trusting_period_s,
                                      max_clock_drift_s, trust_level,
                                      verify)
        attempts.append((old["height"], new["height"], got))
        return got

    target, bad = fetched(height)
    if bad is not None:
        return ("refused", height) + bad, trace, attempts
    cache, depth, verified = [target], 0, trusted
    while True:
        got = attempt(verified, cache[depth])
        if got == ecdsa.OK:
            trace.append(cache[depth]["height"])
            if depth == 0:
                return TRUSTED, trace, attempts
            verified = cache[depth]
            del cache[depth:]
            depth = 0
        elif got[0] == "cant_be_trusted":
            if depth == len(cache) - 1:
                # verifySkippingNumerator / verifySkippingDenominator
                pivot_h = verified["height"] + (
                    cache[depth]["height"] - verified["height"]) * 1 // 2
                pivot, bad = fetched(pivot_h)
                if bad == ("no_such_block",):
                    return (("refused", cache[depth]["height"]) + got,
                            trace, attempts)
                if bad is not None:
                    return ("refused", pivot_h) + bad, trace, attempts
                cache.append(pivot)
            depth += 1
        else:
            return ("refused", cache[depth]["height"]) + got, trace, attempts
