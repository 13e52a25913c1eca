"""Seeded data for cells in which a light client bisects along chains of
ed25519 validators with unequal powers whose set slides: the seats,
each light block's validator set, headers that name those sets, signed
commits, and the plain reference's verdict, trace and attempts of the
whole verification of each chain.

What is generic comes from `harness/fixtures.py` (key seeds and
ed25519 keys, `flip`, timestamps, `build_commit`, the worker pool, the
cache's store); the driver takes heights, header times and headers
from `harness/fixtures_light.py`. What differs:

- a seat is (32-byte key seed, voting power); seats join in order, and
  light block k holds seats [slide k, slide k + n): between
  consecutive light blocks the `slide` seats that joined earliest leave
  and `slide` newcomers join (upstream's `genMockNode` shape). Every
  chain has keys and powers of its own from `--seed`;
- one worker a chain signs its light blocks' commits (every validator
  signs; a tampered chain's one block carries one flipped signature)
  and runs `reference/bisection.verify_skipping` from the first light
  block to the target at full width: the expected (verdict, trace,
  attempts) are cached with the signatures;
- the tampered row is one the new-set check collects (before its 2/3
  point) of a seat the block named by `tampered_unknown_to` does not
  hold, so the trusting check from that block passes it over;
- the cache key also digests this file and the references it calls.

Fixture DATA may come from any fast code: every VERDICT the cell is
held to is the plain reference's own. Workers never import JAX.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import random
from typing import List, Sequence, Tuple

from harness import fixtures
from reference import bisection, ecdsa, plain

Seat = Tuple[bytes, int]  # (key seed, voting power)


# --------------------------------------------------------------------------
# the chains' plan (parent side, no program types)
# --------------------------------------------------------------------------


def seats(seed: int, chain: int, n: int, blocks: int, slide: int,
          low: int, high: int) -> List[Seat]:
    """Every seat of one chain in the order it joined: block k holds
    `seats[slide * k: slide * k + n]`."""
    rnd = random.Random(f"light-bisect/{seed}/{chain}")
    return [(s, rnd.randint(low, high)) for s in fixtures.key_seeds(
        seed, f"bisect/{chain}", n + slide * (blocks - 1))]


def members(plan: Sequence[Seat], k: int, n: int, slide: int):
    return plan[slide * k: slide * k + n]


def tamper_at(rnd, old_pubs, new_pubs, new_powers) -> int:
    """A commit index the new-set check collects whose key the old set
    does not hold: the trusting check from the old set passes it over
    as unknown, and the new-set check must refuse it."""
    known = set(old_pubs)
    light = ecdsa.light_rows(new_powers, [b""] * len(new_pubs))[0]
    return rnd.choice([i for i in light if new_pubs[i] not in known])


# --------------------------------------------------------------------------
# worker side (module top level: pickled by import path)
# --------------------------------------------------------------------------


def sign_chain(task: dict) -> list:
    """Sign every light block of one chain: every validator of a
    block's set (`seeds`, in the set's order) signs its precommit for
    (height, block id); `tamper` = (block, row) gets one bit flipped,
    or is None. `refer` holds what `bisection.verify_skipping` needs
    but the keys and the commits, or is None. Returns [{"sigs": one
    n*64-byte blob a block, "expected": (verdict, trace, attempts) or
    None}]."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.timestamp import Timestamp

    blobs, blocks = [], {}
    for k, blk in enumerate(task["blocks"]):
        height = blk["height"]
        bh, total, ph = blk["bid"]
        enc = canonical.CanonicalVoteEncoder(
            task["chain"], canonical.PRECOMMIT_TYPE, height, 0,
            BlockID(bh, PartSetHeader(total, ph)))
        msgs = [enc.bytes_for(Timestamp(*fixtures.commit_ts(height, i)))
                for i in range(len(blk["seeds"]))]
        sigs = [fixtures._key(s).sign(m) for s, m in zip(blk["seeds"], msgs)]
        if task["tamper"] is not None and task["tamper"][0] == k:
            row = task["tamper"][1]
            sigs[row] = fixtures.flip(sigs[row])
        blobs.append(b"".join(sigs))
        blocks[height] = dict(
            blk["refer"], height=height, msgs=msgs, sigs=sigs,
            pubs=[fixtures.pub_of(s) for s in blk["seeds"]],
            powers=blk["powers"])
    expected = None
    if task["refer"] is not None:
        first = task["blocks"][0]["height"]
        expected = bisection.verify_skipping(
            blocks[first], task["target"], blocks.get, **task["refer"])
    return [{"sigs": blobs, "expected": expected}]


# --------------------------------------------------------------------------
# parent side
# --------------------------------------------------------------------------


def valset(block: Sequence[Seat], pub_of: dict):
    """(ValidatorSet, the seats' key seeds in the set's own order)."""
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    by_pub = {pub_of[s]: s for s, _ in block}
    vs = ValidatorSet([Validator(PubKey(pub_of[s]), p) for s, p in block])
    return vs, [by_pub[v.pub_key.data] for v in vs.validators]


def _pubs_of(seeds: Sequence[bytes]) -> List[bytes]:
    return [fixtures.pub_of(s) for s in seeds]


class Signed(fixtures.Signed):
    """`fixtures.Signed` for chains of light blocks: one pool derives
    the public keys (`pubs`, at once) and signs the chains (`submit`,
    gathered by `result()`); cached under a key that also digests this
    file and the references the workers run."""

    def __init__(self, cell, ctx, workers: int):
        super().__init__(cell, ctx)
        h = hashlib.sha256(os.path.basename(self.path).encode())
        for path in (os.path.abspath(__file__), bisection.__file__,
                     ecdsa.__file__, plain.__file__):
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
        """key seed -> ed25519 public key, derived by the pool's
        workers."""
        step = -(-len(seeds) // (4 * self.workers))
        runs = [seeds[i:i + step] for i in range(0, len(seeds), step)]
        found = self.pool.map(_pubs_of, runs)
        return {s: p for run, got in zip(runs, found)
                for s, p in zip(run, got)}

    def submit(self, tasks: Sequence[dict], workers=None):
        self.futures = [self.pool.submit(sign_chain, t) for t in tasks]
