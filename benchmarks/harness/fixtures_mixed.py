"""Seeded data for cells whose validators hold ed25519 AND sr25519
keys: the keys, a validator set in its own order with each row's key
type, signed commits, and the plain reference's expected outcomes.

`harness/fixtures.py` is ed25519 only; this file is its twin for a
mixed set and reuses what is generic there (block ids, timestamps,
`build_commit`, the worker pool, the cache's store). What differs:

- a validator is (key type, 32-byte key seed); the types are a seeded
  shuffle of the configuration's counts, so the set's order (by
  address) interleaves them;
- an sr25519 secret is a scalar and a nonce drawn from the key seed.
  Any scalar is a schnorrkel secret; how a wallet expands a
  mini-secret into one is the signer's business, not the verifier's;
- sr25519 signatures are made in bulk, a commit at a time: R = r B
  and the public keys by `reference/schnorrkel.py`'s fixed-base table,
  the merlin challenges by the program's native batch routine (plain
  Python takes 0.8 ms each), s = k a + r. Fixture DATA may come from
  any fast code: every VERDICT the cell is held to is the plain
  reference's own, so a wrong signer shows as `correct: false`;
- the worker that signs a commit runs `schnorrkel.verify_commit_light`
  over it (2.7 ms a schnorrkel check: why it is done there, in
  parallel, and cached with the fixtures);
- the cache key also digests this file and `reference/schnorrkel.py`.

Workers never import JAX.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import random
from typing import List, Optional, Sequence, Tuple

import numpy as np

from harness import fixtures
from reference import schnorrkel

ED25519, SR25519 = "ed25519", "sr25519"
KeyRow = Tuple[str, bytes]  # (key type, key seed)


# --------------------------------------------------------------------------
# keys and validator sets
# --------------------------------------------------------------------------


def key_rows(seed: int, counts: dict) -> List[KeyRow]:
    """One (key type, key seed) a validator: the configuration's count
    of each type, dealt to the key seeds by a shuffle from `seed`."""
    types = [kt for kt in sorted(counts) for _ in range(counts[kt])]
    random.Random(f"key-types/{seed}").shuffle(types)
    return list(zip(types, fixtures.key_seeds(seed, "valset", len(types))))


def sr_secret(seed32: bytes) -> Tuple[int, bytes]:
    """(scalar, nonce) of the sr25519 key that `seed32` names."""
    h = hashlib.sha512(b"tpu-bft-bench/sr25519/" + seed32).digest()
    return int.from_bytes(h[:32], "little") % schnorrkel.L or 1, h[32:]


def pub_of(row: KeyRow) -> bytes:
    kt, seed32 = row
    if kt == ED25519:
        return fixtures.pub_of(seed32)
    return schnorrkel.encode(schnorrkel.base_mul(sr_secret(seed32)[0]))


def valset(rows: Sequence[KeyRow], power: int):
    """(ValidatorSet, the key rows in the set's own order, their public
    keys in that order)."""
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    by_pub = {pub_of(r): r for r in rows}
    vs = ValidatorSet([Validator(PubKey(p, r[0]), power)
                       for p, r in by_pub.items()])
    pubs = [v.pub_key.data for v in vs.validators]
    return vs, [by_pub[p] for p in pubs], pubs


# --------------------------------------------------------------------------
# worker side (module top level: pickled by import path)
# --------------------------------------------------------------------------


def _stack(rows: Sequence[bytes], idxs: Sequence[int], width: int):
    return np.frombuffer(b"".join(rows[i] for i in idxs),
                         np.uint8).reshape(len(idxs), width)


def _challenges(msgs: Sequence[bytes], pubs: Sequence[bytes],
                r_encs: Sequence[bytes]) -> List[int]:
    """The merlin challenge scalar of each row: whole transcripts in
    the program's native routine, a message length at a time, or the
    plain reference's where the native library is missing."""
    from cometbft_tpu import native

    out: List[Optional[int]] = [None] * len(msgs)
    if native.available():
        ctx = schnorrkel._signing_context().strobe
        by_len: dict = {}
        for i, m in enumerate(msgs):
            by_len.setdefault(len(m), []).append(i)
        for ln, idxs in by_len.items():
            ch = native.sr25519_batch_challenges(
                bytes(ctx.st), ctx.pos, ctx.pos_begin, ctx.cur_flags,
                _stack(msgs, idxs, ln), _stack(pubs, idxs, 32),
                _stack(r_encs, idxs, 32))
            for i, c in zip(idxs, ch):
                out[i] = int.from_bytes(c.tobytes(), "little") % schnorrkel.L
        return out
    return [schnorrkel.challenge(m, p, r)
            for m, p, r in zip(msgs, pubs, r_encs)]


def sign_sr25519(secrets: Sequence[Tuple[int, bytes]],
                 pubs: Sequence[bytes], msgs: Sequence[bytes]) -> List[bytes]:
    """One schnorrkel signature a row (R, s with the marker bit), the
    witness r drawn from the key's nonce and the message."""
    rs = [int.from_bytes(hashlib.sha512(nonce + m).digest(), "little")
          % schnorrkel.L for (_, nonce), m in zip(secrets, msgs)]
    r_encs = [schnorrkel.encode(schnorrkel.base_mul(r)) for r in rs]
    ks = _challenges(msgs, pubs, r_encs)
    out = []
    for (a, _), r, r_enc, k in zip(secrets, rs, r_encs, ks):
        s = (k * a + r) % schnorrkel.L
        out.append(r_enc + (s | 1 << 255).to_bytes(32, "little"))
    return out


def sign_commits(task: dict) -> list:
    """`fixtures.sign_commits` for a mixed set. `task["rows"]` are the
    key rows and `task["pubs"]` the public keys, in the set's order;
    the rest is as there. Returns one {"sigs", "expected"} a commit."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.timestamp import Timestamp

    rows, pubs = task["rows"], task["pubs"]
    types = [kt for kt, _ in rows]
    sr_at = [i for i, kt in enumerate(types) if kt == SR25519]
    ed_keys = {i: fixtures._key(s) for i, (kt, s) in enumerate(rows)
               if kt == ED25519}
    sr_secrets = [sr_secret(rows[i][1]) for i in sr_at]
    powers = [task["power"]] * len(rows)
    out = []
    for height, (bh, total, ph) in task["blocks"]:
        enc = canonical.CanonicalVoteEncoder(
            task["chain"], canonical.PRECOMMIT_TYPE, height, 0,
            BlockID(bh, PartSetHeader(total, ph)))
        msgs = [enc.bytes_for(Timestamp(*fixtures.commit_ts(height, i)))
                for i in range(len(rows))]
        sigs: List[Optional[bytes]] = [None] * len(rows)
        for i, key in ed_keys.items():
            sigs[i] = key.sign(msgs[i])
        for i, sig in zip(sr_at, sign_sr25519(
                sr_secrets, [pubs[i] for i in sr_at],
                [msgs[i] for i in sr_at])):
            sigs[i] = sig
        for i in task["tamper"].get(height, ()):
            sigs[i] = fixtures.flip(sigs[i])
        expected = None
        if height in task["refer"]:
            expected = schnorrkel.verify_commit_light(
                pubs, types, powers, msgs, sigs)
        out.append({"sigs": b"".join(sigs), "expected": expected})
    return out


# --------------------------------------------------------------------------
# parent side
# --------------------------------------------------------------------------


class Signed(fixtures.Signed):
    """`fixtures.Signed` for a mixed set: signed by `sign_commits`
    above, and cached under a key that also digests this file and the
    schnorrkel reference (a change to either makes new fixtures)."""

    def __init__(self, cell, ctx):
        super().__init__(cell, ctx)
        h = hashlib.sha256(os.path.basename(self.path).encode())
        for path in (os.path.abspath(__file__), schnorrkel.__file__):
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

    def submit(self, tasks: Sequence[dict], workers: Optional[int] = None):
        self.pool = fixtures.pool(workers)
        self.futures = [self.pool.submit(sign_commits, t) for t in tasks]
