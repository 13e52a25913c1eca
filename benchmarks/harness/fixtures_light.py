"""Seeded data for cells in which a light client skips along a chain of
secp256k1 validators with unequal powers: the keys, each light
block's validator set in its own order, headers that name those sets,
signed commits, and the plain reference's outcome of every step.

`harness/fixtures.py` is ed25519 and one set; this file is its twin
for a chain and reuses what is generic there (key seeds, `flip`,
timestamps, `build_commit`, the worker pool, the cache's store). What
differs:

- a seat is (32-byte key seed, voting power). Block 0's seats and
  every later block's changes (which seats leave, the newcomers' key
  seeds and powers) follow from `--seed`; a validator set puts them in
  its own order, by power and address;
- a secret is a scalar in [1, N-1] drawn from the key seed, a
  signature OpenSSL's deterministic ECDSA (RFC 6979) over SHA-256,
  low-S as upstream signs: the same seed gives the same bytes. A key's
  derivation is a scalar multiplication (0.7 ms), so the public keys,
  which the sets and headers need before anything can be signed, come
  from the worker pool too;
- one worker signs one light block's commit and, where asked, runs
  `reference/ecdsa.verify_non_adjacent` over the step that ends at it
  (some 7,900 OpenSSL checks): the expected outcomes are cached with
  the signatures;
- the tampered row of a step is drawn after the trusting check's last
  collected row and before the new-set check's, both found by the
  reference's own collection rules (`trusting_rows`, `light_rows`);
- the cache key also digests this file and `reference/ecdsa.py`.

Fixture DATA may come from any fast code: every VERDICT the cell is
held to is the plain reference's own. Workers never import JAX.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import random
from typing import List, Optional, Sequence, Tuple

from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature,
)
from cryptography.hazmat.primitives.serialization import (
    Encoding,
    PublicFormat,
)

from harness import fixtures
from reference import ecdsa

SECP256K1 = "secp256k1"
HEIGHT0 = 12_345
Seat = Tuple[bytes, int]  # (key seed, voting power)


# --------------------------------------------------------------------------
# keys and signatures (worker side and parent side)
# --------------------------------------------------------------------------


def _key(seed32: bytes):
    d = int.from_bytes(hashlib.sha256(
        b"tpu-bft-bench/secp256k1/" + seed32).digest(), "big")
    return ec.derive_private_key(d % (ecdsa.N - 1) + 1, ec.SECP256K1())


def pubs_of(seeds: Sequence[bytes]) -> List[bytes]:
    """The 33-byte compressed key of each key seed."""
    return [_key(s).public_key().public_bytes(
        Encoding.X962, PublicFormat.CompressedPoint) for s in seeds]


def sign(key, msg: bytes) -> bytes:
    """64 bytes r || s, big-endian, s <= N/2."""
    r, s = decode_dss_signature(key.sign(msg, ec.ECDSA(
        hashes.SHA256(), deterministic_signing=True)))
    return (r.to_bytes(32, "big")
            + min(s, ecdsa.N - s).to_bytes(32, "big"))


def sign_block(task: dict) -> list:
    """Sign one light block's commit: every validator of `seeds` (the
    block's set, in its order) signs its precommit for (height, block
    id); `tamper` is the row that gets one bit flipped, or None.
    `refer` holds what `ecdsa.verify_non_adjacent` needs of the step
    that ends at this block but the commit itself, or is None.
    Returns [{"sigs": n*64 bytes, "expected": outcome or None}]: one
    commit a task, in `fixtures.sign_commits`'s form."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.timestamp import Timestamp

    height = task["height"]
    bh, total, ph = task["bid"]
    enc = canonical.CanonicalVoteEncoder(
        task["chain"], canonical.PRECOMMIT_TYPE, height, 0,
        BlockID(bh, PartSetHeader(total, ph)))
    msgs = [enc.bytes_for(Timestamp(*fixtures.commit_ts(height, i)))
            for i in range(len(task["seeds"]))]
    sigs = [sign(_key(s), m) for s, m in zip(task["seeds"], msgs)]
    if task["tamper"] is not None:
        sigs[task["tamper"]] = fixtures.flip(sigs[task["tamper"]])
    expected = None
    if task["refer"] is not None:
        step = dict(task["refer"])
        new = dict(step.pop("new"), msgs=msgs, sigs=sigs)
        expected = ecdsa.verify_non_adjacent(new=new, **step)
    return [{"sigs": b"".join(sigs), "expected": expected}]


# --------------------------------------------------------------------------
# the chain's plan (parent side, no program types)
# --------------------------------------------------------------------------


def seat_plan(seed: int, n: int, blocks: int, changed: int,
              low: int, high: int) -> List[List[Seat]]:
    """The seats of each light block, in no order: block 0's `n` from
    the seed, and from block to block `changed` of them (drawn from the
    seed) handed to newcomers with keys and powers of their own."""
    rnd = random.Random(f"light-seats/{seed}")
    seats = [(s, rnd.randint(low, high))
             for s in fixtures.key_seeds(seed, "light/0", n)]
    out = [seats]
    for k in range(1, blocks):
        seats = list(seats)
        new = fixtures.key_seeds(seed, f"light/{k}", changed)
        for at, s in zip(rnd.sample(range(n), changed), new):
            seats[at] = (s, rnd.randint(low, high))
        out.append(seats)
    return out


def tamper_at(rnd, old_pubs, old_powers, new_pubs, new_powers) -> int:
    """A commit index after the last row the trusting check collects
    and before the last the new-set check does: the first check cannot
    see a bad signature there and the second must."""
    old = {ecdsa.address(k): (k, p) for k, p in zip(old_pubs, old_powers)}
    signed = [b""] * len(new_pubs)  # every validator signs
    trusting = ecdsa.trusting_rows(
        old, [ecdsa.address(k) for k in new_pubs], signed)[0]
    light = ecdsa.light_rows(new_powers, signed)[0]
    return rnd.randrange(trusting[-1] + 1, light[-1])


# --------------------------------------------------------------------------
# parent side
# --------------------------------------------------------------------------


def valset(seats: Sequence[Seat], pub_of: dict):
    """(ValidatorSet, the seats' key seeds in the set's own order)."""
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    by_pub = {pub_of[s]: s for s, _ in seats}
    vs = ValidatorSet([Validator(PubKey(pub_of[s], SECP256K1), p)
                       for s, p in seats])
    return vs, [by_pub[v.pub_key.data] for v in vs.validators]


def header_time(height: int):
    from cometbft_tpu.types.timestamp import Timestamp

    return Timestamp(fixtures.TS_BASE + 7 * height, 0)


def header_for(chain: str, height: int, vs):
    """(Header, BlockID) of the light block at `height` whose set is
    `vs`: what `validate_basic` and the header checks read is true (the
    chain id, the height, a time that rises with it, the set's root);
    the rest follows from the height."""
    from cometbft_tpu.types.block import Header
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader

    root = vs.hash()
    header = Header(
        chain_id=chain, height=height, time=header_time(height),
        last_block_id=fixtures.block_id(b"light/%d" % (height - 1)),
        validators_hash=root, next_validators_hash=root,
        proposer_address=vs.validators[0].address,
        app_hash=hashlib.sha256(b"app/%d" % height).digest())
    h = header.hash()
    return header, BlockID(h, PartSetHeader(1, hashlib.sha256(h).digest()))


def plain_set(vs) -> dict:
    """A validator set as the plain reference takes it."""
    return {"pubs": [v.pub_key.data for v in vs.validators],
            "powers": [v.voting_power for v in vs.validators]}


class Signed(fixtures.Signed):
    """`fixtures.Signed` for a chain of light blocks: one pool derives
    the public keys (`pubs`, at once) and signs the blocks (`submit`,
    gathered by `result()`); cached under a key that also digests this
    file and the ECDSA reference."""

    def __init__(self, cell, ctx, workers: int):
        super().__init__(cell, ctx)
        h = hashlib.sha256(os.path.basename(self.path).encode())
        for path in (os.path.abspath(__file__), ecdsa.__file__):
            with open(path, "rb") as f:
                h.update(f.read())
        self.path = os.path.join(
            fixtures.CACHE_DIR,
            f"{self.prefix}seed{ctx.seed}.{h.hexdigest()[:12]}.pkl")
        try:
            with open(self.path, "rb") as f:
                self.commits = pickle.load(f)  # written by _store only
        except (OSError, pickle.UnpicklingError, EOFError):
            self.commits = None
        ctx.info["fixtures"] = "signed" if self.commits is None else "cache"
        self.workers = workers
        self.pool = fixtures.pool(workers)

    def pubs(self, seeds: Sequence[bytes]) -> dict:
        """key seed -> public key, derived by the pool's workers."""
        step = -(-len(seeds) // (4 * self.workers))
        runs = [seeds[i:i + step] for i in range(0, len(seeds), step)]
        found = self.pool.map(pubs_of, runs)
        return {s: p for run, got in zip(runs, found)
                for s, p in zip(run, got)}

    def submit(self, tasks: Sequence[dict], workers: Optional[int] = None):
        self.futures = [self.pool.submit(sign_block, t) for t in tasks]
