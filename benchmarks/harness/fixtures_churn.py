"""Seeded data for cells that catch up on a chain whose validator powers
move at almost every height: the chain's keys and powers, the `val:`
updates each block carries, the blocks themselves as the program's own
`BlockExecutor` makes them over the kvstore app, their commits, and the
plain reference's outcome for every block.

What is generic comes from `harness/fixtures.py` (key seeds, ed25519
keys, `flip`, timestamps, the worker pool, the cache's store). What
differs:

- `plan` draws, from `--seed` alone and with no program types: the
  genesis powers (a rank law with seeded jitter, heavy-tailed), the
  power updates of every block (1-8 seats, each moved by 0.01-1 % up or
  down), a seat wall every `seat_change_every` blocks (one seat leaves
  with power 0, a new key joins at the median power) and the absent
  rows of every commit;
- `reference/churn.sets` turns that plan into each height's ordered set
  with the +2 delay; every commit is signed by the set the REFERENCE
  says signs that height, in its order, so a program whose sets drift
  from upstream's cannot verify its own chain;
- `build_chain` (one worker) runs the updates as kvstore `val:` txs
  through the program's `BlockExecutor` (proposal, FinalizeBlock,
  updateState, the state store), so headers carry real app hashes,
  results hashes, BFT times and the +2 delay, and signs each block's
  commit before the next block is proposed over it. It also signs the
  two refused commits and keeps the states the set-up checks start
  from;
- `refer` (the pool) gives `reference/churn.block_outcome` of every
  block and of the two refused commits.

Fixture DATA may come from any fast code: every VERDICT the cell is
held to is the plain reference's own. Workers never import JAX.
"""
from __future__ import annotations

import base64
import bisect
import hashlib
import os
import pickle
import random
import statistics
from typing import Dict, List, Sequence, Tuple

from harness import catalog, fixtures
from reference import bisection, churn, plain

Member = churn.Member

# the genesis powers' rank law: member k (from 0) holds about
# POWER_SCALE * (k + 1) ** -POWER_EXPONENT, so that the 7 largest of 175
# hold one third of the power
POWER_SCALE = 50_000_000
POWER_EXPONENT = 0.82
POWER_JITTER = 0.05


# --------------------------------------------------------------------------
# the chain's plan (parent side, no program types)
# --------------------------------------------------------------------------


def plan(seed: int, cfg: dict) -> dict:
    """Keys, genesis set and every block's updates and absent rows.
    Returns {"seeds": key seed by public key, "genesis": [(key,
    power)], "updates": {height: [(key, power)]}, "absent":
    {height: row indexes}}."""
    n, blocks = cfg["validators"], cfg["history_blocks"]
    every = cfg["seat_change_every"]
    rnd = random.Random(f"churn/{seed}")
    seeds = fixtures.key_seeds(seed, "churn", n)
    joiners = fixtures.key_seeds(seed, "churn-join", blocks // every + 1)
    pub_seed = {fixtures.pub_of(s): s for s in seeds + joiners}
    pubs = [fixtures.pub_of(s) for s in seeds]
    genesis = [(k, max(1, round(POWER_SCALE * (i + 1) ** -POWER_EXPONENT
                                * rnd.uniform(1 - POWER_JITTER,
                                              1 + POWER_JITTER))))
               for i, k in enumerate(pubs)]
    latest = dict(genesis)  # the power each key was LAST given
    lo, hi = cfg["updates_per_block"]
    pct_lo, pct_hi = cfg["update_pct"]
    n_absent = round((1 - cfg["signing_share"]) * n)
    updates, absent = {}, {}
    joined = 0
    for h in range(1, blocks + 1):
        members = sorted(latest)
        ups: List[Member] = []
        if h % every == 0:
            leaver = rnd.choice(members)
            newcomer = fixtures.pub_of(joiners[joined])
            joined += 1
            median = round(statistics.median(latest.values()))
            ups += [(leaver, 0), (newcomer, median)]
            members.remove(leaver)
            del latest[leaver]
            latest[newcomer] = median
        for k in rnd.sample(members, rnd.randint(lo, hi)):
            step = max(1, round(latest[k] * rnd.uniform(pct_lo, pct_hi)
                                / 100))
            latest[k] = max(1, latest[k] + rnd.choice((-step, step)))
            ups.append((k, latest[k]))
        updates[h] = ups
        absent[h] = rnd.sample(range(n), n_absent)
    return {"seeds": pub_seed, "genesis": genesis, "updates": updates,
            "absent": absent}


def first_signer(members: Sequence[Member], absent: Sequence[int]) -> int:
    """The commit row examined first: the tampered one."""
    gone = set(absent)
    return next(i for i in range(len(members)) if i not in gone)


def stale_signers(prev: Sequence[Member], cur: Sequence[Member],
                  rnd: random.Random):
    """Rows of `cur` whose members hold MORE than 2/3 of the power
    under `prev` (the set one height before, same keys) and NOT more
    than 2/3 under `cur`, or None where no such rows were found. Seats
    whose power fell sign, seats whose power rose do not; the rest is
    filled largest first to just under `cur`'s 2/3 point and then
    evened by swaps until it lies above `prev`'s."""
    was = dict(prev)
    if set(was) != {k for k, _ in cur}:
        return None
    need_cur = sum(p for _, p in cur) * 2 // 3
    need_prev = sum(p for _, p in prev) * 2 // 3
    fell = [i for i, (k, p) in enumerate(cur) if was[k] > p]
    rose = {i for i, (k, p) in enumerate(cur) if was[k] < p}
    free = [i for i in range(len(cur)) if i not in rose and i not in fell]
    base_cur = sum(cur[i][1] for i in fell)
    base_prev = sum(was[cur[i][0]] for i in fell)
    # free rows count the same under both sets: their sum s must give
    # base_prev + s > need_prev and base_cur + s <= need_cur
    lo, hi = need_prev + 1 - base_prev, need_cur - base_cur
    if lo > hi:
        return None

    def power(i):
        return cur[i][1]

    for attempt in range(64):
        order = (sorted(free, key=power, reverse=True) if attempt == 0
                 else rnd.sample(free, len(free)))
        chosen, s = [], 0
        for i in order:
            if s + power(i) <= hi:
                chosen.append(i)
                s += power(i)
        if s >= lo:
            return sorted(fell + chosen)
        # one swap: x out, y in, with lo - s <= p(y) - p(x) <= hi - s
        out = sorted(set(free) - set(chosen), key=power)
        powers = [power(i) for i in out]
        for x in chosen:
            a = bisect.bisect_left(powers, power(x) + lo - s)
            if a < len(out) and powers[a] <= power(x) + hi - s:
                return sorted(fell + [i for i in chosen if i != x]
                              + [out[a]])
    return None


def val_tx(key: bytes, power: int) -> bytes:
    """The kvstore's validator-update tx (`val:base64pubkey!power`)."""
    return b"val:" + base64.b64encode(key) + b"!" + str(power).encode()


# --------------------------------------------------------------------------
# worker side (module top level: pickled by import path)
# --------------------------------------------------------------------------


def _signed_commit(chain: str, height: int, bid, members, seeds,
                   signers):
    """Commit for `bid` at `height`: row i is member i of the set;
    the rows in `signers` sign, the rest are absent."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp

    enc = canonical.CanonicalVoteEncoder(
        chain, canonical.PRECOMMIT_TYPE, height, 0, bid)
    rows = []
    signing = set(signers)
    for i, (key, _) in enumerate(members):
        if i not in signing:
            rows.append(CommitSig.absent())
            continue
        ts = Timestamp(*fixtures.commit_ts(height, i))
        rows.append(CommitSig(BLOCK_ID_FLAG_COMMIT, churn.address(key), ts,
                              fixtures._key(seeds[key]).sign(
                                  enc.bytes_for(ts))))
    return Commit(height, 0, bid, rows)


def build_chain(task: dict) -> dict:
    """The chain of `task["blocks"]` blocks, proposed and applied by the
    program's BlockExecutor over a fresh kvstore app from genesis, each
    commit signed by the reference's set of its height (`sets`, row
    order included) with `absent` rows left out. Returns {"genesis":
    State, "blocks": {h: Block}, "commits": {h: Commit}, "states": {h:
    State after h, for h in `keep`}, "bad": {h: Commit} of the `bad`
    commits}."""
    from cometbft_tpu.abci import types as abci
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import State, StateStore
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    chain, seeds, sets = task["chain"], task["seeds"], task["sets"]
    app = KVStoreApplication()
    app_hash = app.init_chain(abci.RequestInitChain(chain_id=chain)).app_hash
    genesis = State.make_genesis(
        chain, ValidatorSet([Validator(PubKey(k), p)
                             for k, p in task["genesis"]]),
        app_hash=app_hash, genesis_time=Timestamp(fixtures.TS_BASE, 0))
    ex = BlockExecutor(app, StateStore(":memory:"))
    state, last_commit = genesis.copy(), None
    blocks, bids, commits, states = {}, {}, {}, {}
    for h in range(1, task["blocks"] + 1):
        blk = ex.create_proposal_block(
            h, state, last_commit,
            state.validators.get_proposer().address,
            txs=[val_tx(k, p) for k, p in task["updates"].get(h, ())])
        bid = bids[h] = blk.block_id()
        members = sets[h]
        signers = [i for i in range(len(members))
                   if i not in set(task["absent"].get(h, ()))]
        commits[h] = _signed_commit(chain, h, bid, members, seeds, signers)
        state = ex.apply_block(state, bid, blk, validate=False)
        # the part set holds a lock: the engine splits the block anew
        blk.__dict__.pop("_part_set", None)
        blocks[h] = blk
        last_commit = commits[h]
        if h in task["keep"]:
            states[h] = state.copy()
    bad = {}
    for h, how in task["bad"].items():
        bid = bids[h]
        if how[0] == "tamper":
            c = _signed_commit(chain, h, bid, sets[h], seeds,
                               [i for i in range(len(sets[h]))
                                if i not in set(task["absent"][h])])
            row = how[1]
            c.signatures[row].signature = fixtures.flip(
                c.signatures[row].signature)
            bad[h] = c
        else:  # ("signers", rows)
            bad[h] = _signed_commit(chain, h, bid, sets[h], seeds, how[1])
    return {"genesis": genesis, "blocks": blocks, "commits": commits,
            "states": states, "bad": bad}


def refer(task: dict) -> Dict[int, Tuple]:
    """`reference/churn.block_outcome` of each block of the task."""
    return {b["height"]: churn.block_outcome(b, own, own_root, next_root)
            for b, own, own_root, next_root in task["blocks"]}


def reference_block(blk, commit) -> dict:
    """A block as the reference reads it: plain data only, the fields
    each row signs included (the reference encodes the bytes itself)."""
    bid = commit.block_id
    rows = [cs if cs.for_block() else None for cs in commit.signatures]
    return {"chain_id": blk.header.chain_id, "height": commit.height,
            "validators_hash": blk.header.validators_hash,
            "next_validators_hash": blk.header.next_validators_hash,
            "round": commit.round,
            "block_id": (bid.hash, bid.part_set_header.total,
                         bid.part_set_header.hash),
            "timestamps": [None if cs is None else
                           (cs.timestamp.seconds, cs.timestamp.nanos)
                           for cs in rows],
            "sigs": [None if cs is None else cs.signature for cs in rows]}


def _program_files() -> List[str]:
    """Every Python module of the program, in a fixed order."""
    root = os.path.join(catalog.REPO_ROOT, "cometbft_tpu")
    return sorted(os.path.join(d, n) for d, _, names in os.walk(root)
                  for n in names if n.endswith(".py"))


class Chain(fixtures.Signed):
    """`fixtures.Signed` for a chain built by the program: read from the
    cache, or built by one worker of the pool and refereed by the rest
    (`result()` gathers, stores and stops the pool); cached under a key
    that also digests this file, the references the workers run and
    every module of the program, whose objects (blocks, commits, states)
    the cache holds and whose code built them."""

    def __init__(self, cell, ctx, workers: int):
        super().__init__(cell, ctx)
        h = hashlib.sha256(os.path.basename(self.path).encode())
        for path in (__file__, churn.__file__, plain.__file__,
                     bisection.__file__, *_program_files()):
            path = os.path.abspath(path)
            h.update(os.path.relpath(path, catalog.REPO_ROOT).encode())
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

    def submit(self, task: dict, roots: dict) -> None:
        """Start building the chain of `task` (see build_chain); `roots`
        holds the reference's root of every height's set."""
        if self.cached:
            return
        self.task, self.roots = task, roots
        self.pool = fixtures.pool(self.workers)
        self.futures = [self.pool.submit(build_chain, task)]

    def result(self) -> dict:
        """{"chain": build_chain's result, "outcomes": {h: outcome},
        "bad_outcomes": {h: outcome of the bad commit}}."""
        if self.commits is None:
            try:
                chain = self.futures[0].result()
                self.commits = {"chain": chain, **self._refer(chain)}
            finally:
                self.abandon()
            self._store()
        return self.commits

    def _refer(self, chain: dict) -> dict:
        sets, roots = self.task["sets"], self.roots

        def row(h, commit):
            return (reference_block(chain["blocks"][h], commit), sets[h],
                    roots[h], roots[h + 1])

        hs = sorted(chain["blocks"])
        step = -(-len(hs) // (2 * self.workers))
        tasks = [{"blocks": [row(h, chain["commits"][h])
                             for h in hs[i:i + step]]}
                 for i in range(0, len(hs), step)]
        tasks.append({"blocks": [row(h, c)
                                 for h, c in chain["bad"].items()]})
        found = list(self.pool.map(refer, tasks))
        outcomes = {h: o for got in found[:-1] for h, o in got.items()}
        return {"outcomes": outcomes, "bad_outcomes": found[-1]}
