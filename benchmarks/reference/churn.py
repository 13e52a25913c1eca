"""The plain reference of the `pos175-churn` deployment: a chain whose
validator powers move at almost every height, caught up block by block.

It imports nothing of the program under test. Each height's validator
set follows from the chain's updates alone, as upstream's `updateState`
(`state/execution.go`) and `ValidatorSet.UpdateWithChangeSet` make it:

- a validator is keyed by its address (the first 20 bytes of its
  ed25519 key's SHA-256); an update of power 0 removes it, any other
  power sets it, and a new key joins;
- the updates a block's FinalizeBlock returns take effect two heights
  later: the sets of heights 1 and 2 are the genesis set, and the set
  of height h + 2 is that of h + 1 with block h's updates applied;
- a set is ordered by voting power descending, then address ascending
  (`ValidatorsByVotingPower`), and its root is `bisection.validators_root`.

A chain is checked block by block (`block_outcome`): the header must
name the root of its own set and of the next one, and its commit must
carry MORE than 2/3 of its OWN set's power in signatures that OpenSSL's
Ed25519 accepts (`plain.verify_commit_light`: row i signed by member i
of that set, examined in order up to the 2/3 point, the first bad one
blamed). A block is a dict of plain data: "chain_id", "height",
"validators_hash", "next_validators_hash", and its commit as "round",
"block_id" (hash, part set total, part set hash), "timestamps" ((seconds,
nanos) by row) and "sigs" (None = absent). The bytes each row signs are
encoded here (`precommit_bytes`, upstream's `VoteSignBytes` written out
from `canonical.proto`), not taken from the program.

`verdict` takes the outcomes of a chain's blocks and gives `("ok",
tip)` or `("refused", height) + outcome` for the first block refused.
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Mapping, Sequence, Tuple

from reference import bisection, plain

Member = Tuple[bytes, int]  # (ed25519 public key, voting power)


def _uvarint(v: int) -> bytes:
    out = bytearray()
    while v > 0x7F:
        out.append(v & 0x7F | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _field(number: int, wire: int, payload: bytes) -> bytes:
    return _uvarint(number << 3 | wire) + payload


def _int(number: int, v: int) -> bytes:
    """A varint field (int64, int32, uint32, enum), absent at zero; a
    negative value as its 64-bit two's complement."""
    return _field(number, 0, _uvarint(v % 2**64)) if v else b""


def _fixed64(number: int, v: int) -> bytes:
    return _field(number, 1, (v % 2**64).to_bytes(8, "little")) if v else b""


def _len(number: int, body: bytes, keep_empty: bool = False) -> bytes:
    if not body and not keep_empty:
        return b""
    return _field(number, 2, _uvarint(len(body)) + body)


def precommit_bytes(chain_id: str, height: int, round_: int,
                    block_id: Tuple[bytes, int, bytes],
                    ts: Tuple[int, int]) -> bytes:
    """The bytes a validator signs for a precommit of `block_id` (hash,
    part set total, part set hash): upstream's `CanonicalVote`
    (proto/tendermint/types/canonical.proto) with proto3's zero
    skipping, the part set header and the timestamp always present,
    prefixed by its length (`VoteSignBytes`, types/vote.go)."""
    bhash, total, phash = block_id
    part_set = _int(1, total) + _len(2, phash)
    body = (_int(1, 2)  # SIGNED_MSG_TYPE_PRECOMMIT
            + _fixed64(2, height) + _fixed64(3, round_)
            + _len(4, _len(1, bhash) + _len(2, part_set, keep_empty=True))
            + _len(5, _int(1, ts[0]) + _int(2, ts[1]), keep_empty=True)
            + _len(6, chain_id.encode()))
    return _uvarint(len(body)) + body


def address(pub: bytes) -> bytes:
    return hashlib.sha256(pub).digest()[:20]


def ordered(members: Mapping[bytes, Member]) -> List[Member]:
    """address -> (key, power), as the set orders it: power descending,
    then address ascending."""
    return [members[a] for a in sorted(
        members, key=lambda a: (-members[a][1], a))]


def sets(genesis: Sequence[Member],
         updates: Mapping[int, Sequence[Member]],
         last: int) -> Dict[int, List[Member]]:
    """The ordered set of every height 1..last + 2 (a header names the
    next height's set, the tip's commit its own)."""
    cur = {address(k): (k, p) for k, p in genesis}
    out = {1: ordered(cur), 2: ordered(cur)}
    for h in range(1, last + 1):
        nxt = dict(cur)
        for k, p in updates.get(h, ()):
            if p == 0:
                nxt.pop(address(k), None)
            else:
                nxt[address(k)] = (k, p)
        out[h + 2] = ordered(nxt)
        cur = nxt
    return out


def root(members: Sequence[Member]) -> bytes:
    return bisection.validators_root([k for k, _ in members],
                                     [p for _, p in members])


def tally(members: Sequence[Member], signers: Sequence[int]) -> int:
    """The power the rows `signers` carry under `members`."""
    return sum(members[i][1] for i in signers)


def block_outcome(block: dict, own: Sequence[Member],
                  own_root: bytes, next_root: bytes) -> Tuple:
    """`("ok",)`, `("invalid_header", field)`, or the commit check's
    outcome in `plain`'s words, for one block under its own set."""
    if block["validators_hash"] != own_root:
        return ("invalid_header", "validators_hash")
    if block["next_validators_hash"] != next_root:
        return ("invalid_header", "next_validators_hash")
    if len(block["sigs"]) != len(own):
        return ("invalid_commit", "size")
    msgs = [None if ts is None else precommit_bytes(
        block["chain_id"], block["height"], block["round"],
        block["block_id"], ts) for ts in block["timestamps"]]
    return plain.verify_commit_light(
        [k for k, _ in own], [p for _, p in own], msgs, block["sigs"])


def verdict(outcomes: Mapping[int, Tuple]) -> Tuple:
    """The chain's verdict from its blocks' outcomes, by height."""
    for h in sorted(outcomes):
        if outcomes[h] != plain.OK:
            return ("refused", h) + tuple(outcomes[h])
    return ("ok", max(outcomes))
