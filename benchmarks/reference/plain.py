"""The plain reference that decides `correct` for every cell.

It imports nothing of the program under test: a signature is checked
by OpenSSL's Ed25519 verify (the `cryptography` package), and the
tally, the 2/3 rule, the order of light verification and the blame on
the first bad index are written out below. It takes plain data: public
keys, powers, the signed bytes and the signatures. The signed bytes are
made by the program's `types/canonical` encoder, the same one that made
them for signing, so this reference does not test that encoding
(tier-1's golden vectors do).

OpenSSL's verify is cofactorless and strict on encodings; the program
promises ZIP-215. The two agree on honest signatures and on honest
signatures with one bit flipped, which is all the benchmark's seeded
data holds. The ZIP-215 edge vectors, where they differ, stay with
`chip_smoke.py` and tier-1 (`tools/tpu_differential.edge_cases`).
"""
from __future__ import annotations

from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey,
)

OK = ("ok",)


@lru_cache(maxsize=16384)
def _pub(raw: bytes) -> Ed25519PublicKey:
    return Ed25519PublicKey.from_public_bytes(raw)


def verify_sig(pub: bytes, msg: bytes, sig: bytes) -> bool:
    try:
        _pub(pub).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return False
    return True


def needed_power(powers: Sequence[int]) -> int:
    """A commit or vote set is decided by MORE than this much power."""
    return sum(powers) * 2 // 3


def verify_commit_light(pubs: Sequence[bytes], powers: Sequence[int],
                        msgs: Sequence[bytes],
                        sigs: Sequence[Optional[bytes]]) -> Tuple:
    """Light verification of one commit, validator i signing msgs[i]
    (sigs[i] None = absent, skipped). Signatures are examined in order
    and only until more than 2/3 of the power has signed; the first bad
    one among those examined takes the blame:
    ("ok",) | ("invalid_signature", idx) | ("not_enough_power", needed).
    """
    needed = needed_power(powers)
    # who would be examined: the prefix that first crosses 2/3
    examined: List[int] = []
    tallied = 0
    for i, sig in enumerate(sigs):
        if sig is None:
            continue
        examined.append(i)
        tallied += powers[i]
        if tallied > needed:
            break
    if tallied <= needed:
        return ("not_enough_power", needed)
    for i in examined:
        if not verify_sig(pubs[i], msgs[i], sigs[i]):
            return ("invalid_signature", i)
    return OK


def vote_set_state(pubs: Sequence[bytes], powers: Sequence[int],
                   votes: Sequence[Tuple[int, bytes, bytes, bytes]]):
    """The final state of one vote set fed `votes` in order, each
    (validator index, block key, signed bytes, signature): per vote
    whether it was admitted, then the power admitted, the indexes that
    voted and the block key with more than 2/3 of the power (or None).
    A second vote from one validator is not admitted (the benchmark's
    traffic holds none)."""
    needed = needed_power(powers)
    admitted: List[bool] = []
    voted = set()
    by_block: dict = {}
    total = 0
    maj = None
    for idx, block_key, msg, sig in votes:
        ok = idx not in voted and verify_sig(pubs[idx], msg, sig)
        admitted.append(ok)
        if not ok:
            continue
        voted.add(idx)
        total += powers[idx]
        by_block[block_key] = by_block.get(block_key, 0) + powers[idx]
        if maj is None and by_block[block_key] > needed:
            maj = block_key
    return admitted, total, sorted(voted), maj
