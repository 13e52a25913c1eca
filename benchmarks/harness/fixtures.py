"""Seeded data for the cells: validator keys, signed commits, and the
plain reference's expected outcomes, made together.

The makers follow `chip_smoke.py`'s (`_privs`, `_valset`, `_commit`,
`_tampered`) and `bench._catchup_history`, with two changes: every byte
follows from `--seed`, and commits are signed in worker processes that
never import JAX (a million signatures is 40 s on one core), each
worker keeping its OpenSSL key objects. The worker that signs a commit
also runs the plain reference over it where asked, so the expected
outcomes are cached with the fixtures.

The cache is `benchmarks/.cache/fixtures/`, keyed by cell, seed and a
digest of the cell's files and of this code; the newest few seeds are
kept.
"""
from __future__ import annotations

import hashlib
import multiprocessing
import os
import pickle
import sys
from concurrent.futures import ProcessPoolExecutor
from functools import lru_cache
from typing import List, Optional, Sequence

from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
)
from cryptography.hazmat.primitives.serialization import (
    Encoding,
    PublicFormat,
)

from harness import catalog

CACHE_DIR = os.path.join(catalog.BENCH_DIR, ".cache", "fixtures")
KEEP_SEEDS = 2  # cached fixture files kept per cell (a replay file is 66 MB)
TS_BASE = 1_700_000_000


# --------------------------------------------------------------------------
# keys and validator sets
# --------------------------------------------------------------------------


def key_seeds(seed: int, tag: str, n: int) -> List[bytes]:
    base = hashlib.sha256(f"tpu-bft-bench/{seed}/{tag}".encode()).digest()
    return [hashlib.sha256(base + i.to_bytes(4, "big")).digest()
            for i in range(n)]


@lru_cache(maxsize=16384)
def _key(seed32: bytes) -> Ed25519PrivateKey:
    return Ed25519PrivateKey.from_private_bytes(seed32)


def pub_of(seed32: bytes) -> bytes:
    return _key(seed32).public_key().public_bytes(
        Encoding.Raw, PublicFormat.Raw)


def valset(seeds: Sequence[bytes], power: int):
    """(ValidatorSet, key seeds in the set's own order). The set sorts
    its validators, so the order is the set's, not the seeds'."""
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    by_pub = {pub_of(s): s for s in seeds}
    vs = ValidatorSet([Validator(PubKey(p), power) for p in by_pub])
    return vs, [by_pub[v.pub_key.data] for v in vs.validators]


def block_id(tag: bytes):
    """A BlockID whose hashes follow from `tag`."""
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader

    h = hashlib.sha256(b"block/" + tag).digest()
    return BlockID(h, PartSetHeader(2, hashlib.sha256(h).digest()))


def flip(sig: bytes, at: int = 5) -> bytes:
    return sig[:at] + bytes([sig[at] ^ 1]) + sig[at + 1:]


def commit_ts(height: int, idx: int):
    """(seconds, nanos) of validator idx's precommit at `height`: only
    the timestamp differs between the rows of one commit."""
    return TS_BASE + 7 * height + idx % 5, idx


# --------------------------------------------------------------------------
# worker side (module top level: pickled by import path)
# --------------------------------------------------------------------------


def _worker_init(sys_path):
    os.environ["JAX_PLATFORMS"] = "cpu"  # a worker never takes the chip
    sys.path[:] = sys_path


def sign_commits(task: dict) -> list:
    """Sign every validator's precommit for each (height, block id) of
    the task; returns one {"sigs": n*64 bytes, "expected": outcome or
    None} per commit, in order. `tamper` maps a height to the validator
    indexes whose signature gets one bit flipped; `refer` holds the
    heights the plain reference verifies."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.timestamp import Timestamp
    from reference import plain

    keys = [_key(s) for s in task["seeds"]]
    if task["refer"]:
        pubs = [pub_of(s) for s in task["seeds"]]
        powers = [task["power"]] * len(keys)
    out = []
    for height, (bh, total, ph) in task["blocks"]:
        enc = canonical.CanonicalVoteEncoder(
            task["chain"], canonical.PRECOMMIT_TYPE, height, 0,
            BlockID(bh, PartSetHeader(total, ph)))
        msgs = [enc.bytes_for(Timestamp(*commit_ts(height, i)))
                for i in range(len(keys))]
        sigs = [k.sign(m) for k, m in zip(keys, msgs)]
        for i in task["tamper"].get(height, ()):
            sigs[i] = flip(sigs[i])
        expected = None
        if height in task["refer"]:
            expected = plain.verify_commit_light(pubs, powers, msgs, sigs)
        out.append({"sigs": b"".join(sigs), "expected": expected})
    return out


# --------------------------------------------------------------------------
# parent side
# --------------------------------------------------------------------------


def pool(workers: Optional[int] = None) -> ProcessPoolExecutor:
    """Spawned workers (the parent may already hold threads), given the
    parent's import path."""
    n = workers or max(2, min(12, (os.cpu_count() or 4) - 1))
    return ProcessPoolExecutor(
        max_workers=n, mp_context=multiprocessing.get_context("spawn"),
        initializer=_worker_init, initargs=(list(sys.path),))


def bid_tuple(bid) -> tuple:
    return (bid.hash, bid.part_set_header.total, bid.part_set_header.hash)


def build_commit(vs, height: int, bid, sigs_blob: bytes):
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp

    return Commit(height, 0, bid, [
        CommitSig(BLOCK_ID_FLAG_COMMIT, v.address,
                  Timestamp(*commit_ts(height, i)),
                  sigs_blob[64 * i:64 * i + 64])
        for i, v in enumerate(vs.validators)])


class Signed:
    """The signed commits of one cell and seed: read from the cache, or
    being signed by a pool of workers (`submit`) until `result()`
    gathers them, stores them and stops the pool."""

    def __init__(self, cell, ctx):
        h = hashlib.sha256()
        for path in (cell.config_path, cell.traffic_path,
                     os.path.abspath(__file__), cell.driver.__file__,
                     os.path.join(catalog.BENCH_DIR, "reference",
                                  "plain.py")):
            with open(path, "rb") as f:
                h.update(f.read())
        kind = "rehearsal" if ctx.rehearse else "full"
        self.prefix = f"{cell.name}.{kind}."
        self.path = os.path.join(
            CACHE_DIR,
            f"{self.prefix}seed{ctx.seed}.{h.hexdigest()[:12]}.pkl")
        self.pool = None
        self.futures = []
        try:
            with open(self.path, "rb") as f:
                self.commits = pickle.load(f)  # written by _store only
        except (OSError, pickle.UnpicklingError, EOFError):
            self.commits = None
        ctx.info["fixtures"] = "signed" if self.commits is None else "cache"

    @property
    def cached(self) -> bool:
        return self.commits is not None

    def submit(self, tasks: Sequence[dict], workers: Optional[int] = None):
        """Start signing `tasks` (see sign_commits), in order."""
        self.pool = pool(workers)
        self.futures = [self.pool.submit(sign_commits, t) for t in tasks]

    def abandon(self) -> None:
        if self.pool is not None:
            self.pool.shutdown(wait=True, cancel_futures=True)
            self.pool = None

    def result(self) -> list:
        """Every task's commits, concatenated in order."""
        if self.commits is None:
            try:
                self.commits = [c for f in self.futures for c in f.result()]
            finally:
                self.abandon()
            self._store()
        return self.commits

    def _store(self) -> None:
        os.makedirs(CACHE_DIR, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "wb") as f:
            pickle.dump(self.commits, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, self.path)
        mine = sorted((os.path.join(CACHE_DIR, n)
                       for n in os.listdir(CACHE_DIR)
                       if n.startswith(self.prefix) and n.endswith(".pkl")),
                      key=os.path.getmtime)
        for old in mine[:-KEEP_SEEDS]:
            os.remove(old)
