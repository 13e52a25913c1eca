"""Driver `replay_catchup`: a node catching up, through the program's
own loop.

Each lap is one `blocksync.catchup.CatchupEngine.run()` over an
in-memory history of distinct signed blocks, with the default
`make_stream_verifier()`, the engine's `read_ahead` and `max_run`, a
real cursor file and an `apply_fn` that only advances the state: run
length, overlap and cursor are the program's. A fresh engine and cursor
per lap; the window's last lap is cut where the window ends. Driving the
engine from memory follows `bench._catchup_history`, `_HistorySource`,
`_ReplayState` and `_catchup_drive`.

All blocks of the window are valid, because the engine stops at the
first bad commit. The tampered case runs in set-up through the same
engine: the history's last `tampered_tail` blocks with one flipped
signature, on which `run` must raise at exactly that height with the
cursor behind it. That pass also warms the one chunk shape the window
uses, and the laps' first runs do not find its templates cached,
because the tail is what a lap reaches last.

Traffic parameters (the mix's file): read_ahead, max_run, tampered_tail,
 tampered_at, tampered_sig, reference_sample.
"""
from __future__ import annotations

import os
import random
import re
import time
from types import SimpleNamespace

from harness import fixtures

BLOCKS_PER_TASK = 16


class _Source:
    def __init__(self, items):
        self.items = items

    def base(self):
        return min(self.items)

    def tip(self):
        return max(self.items)

    def load(self, h):
        return self.items[h]


class _ReplayState:
    """The slice of State the catch-up engine reads."""

    __slots__ = ("chain_id", "last_block_height", "validators",
                 "next_validators")

    def __init__(self, chain_id, h, vals):
        self.chain_id = chain_id
        self.last_block_height = h
        self.validators = vals
        self.next_validators = vals


class _WindowOver(Exception):
    pass


def _chain(chain: str, vs, n_blocks: int, seed: int):
    """Real Block objects, each header naming its predecessor, whose
    block_id()s the commits sign."""
    from cometbft_tpu.types.block import Block, Data, Header
    from cometbft_tpu.types.timestamp import Timestamp

    vhash = vs.hash()
    blocks, last_bid = {}, None
    for h in range(1, n_blocks + 1):
        hdr = Header(chain_id=chain, height=h,
                     time=Timestamp(fixtures.TS_BASE + h, seed),
                     validators_hash=vhash, next_validators_hash=vhash,
                     proposer_address=vs.validators[h % len(vs)].address)
        if last_bid is not None:
            hdr.last_block_id = last_bid
        blk = Block(hdr, Data())
        blk.fill_header()
        last_bid = blk.block_id()
        blocks[h] = (blk, last_bid)
    return blocks


def prepare(ctx):
    tr = ctx.traffic
    n_blocks = ctx.config["history_blocks"]
    power = ctx.config["voting_power"]
    chain = ctx.config["chain_id"]
    vs, seeds = fixtures.valset(fixtures.key_seeds(
        ctx.seed, "valset", ctx.config["validators"]), power)
    blocks = _chain(chain, vs, n_blocks, ctx.seed)
    rnd = random.Random(f"replay/{ctx.seed}")
    bad_h = n_blocks - tr["tampered_tail"] + tr["tampered_at"]
    # the plain reference checks a seeded sample of the blocks, the
    # tampered one as signed and as tampered
    sample = set(rnd.sample(range(1, n_blocks + 1), tr["reference_sample"]))
    sample.add(bad_h)
    signed = fixtures.Signed(ctx.cell, ctx)
    if not signed.cached:
        base = {"chain": chain, "seeds": seeds, "power": power}
        hs = list(range(1, n_blocks + 1))
        tasks = [{**base, "tamper": {}, "refer": sample,
                  "blocks": [(h, fixtures.bid_tuple(blocks[h][1]))
                             for h in hs[k:k + BLOCKS_PER_TASK]]}
                 for k in range(0, n_blocks, BLOCKS_PER_TASK)]
        # last: the tampered copy of one block
        tasks.append({**base, "tamper": {bad_h: [tr["tampered_sig"]]},
                      "refer": {bad_h},
                      "blocks": [(bad_h,
                                  fixtures.bid_tuple(blocks[bad_h][1]))]})
        signed.submit(tasks)
    return {"vs": vs, "blocks": blocks, "chain": chain, "bad_h": bad_h,
            "sample": sample, "signed": signed}


def abandon(fx) -> None:
    fx["signed"].abandon()


def close(st) -> None:
    pass


class _TimedVerifier:
    """The verifier the engine is handed: the program's, with the
    benchmark's span around each call."""

    def __init__(self, inner, ctx):
        self.inner, self.ctx = inner, ctx
        self.calls = []  # (t_start, t_end, jobs) on time.monotonic()

    def verify(self, jobs):
        t = time.monotonic()
        with self.ctx.span("verify"):
            out = self.inner.verify(jobs)
        self.calls.append((t, time.monotonic(), len(jobs)))
        return out


def _engine(ctx, st, items, start: int, apply_fn, tag: str):
    from cometbft_tpu.blocksync.catchup import CatchupEngine

    return CatchupEngine(
        _Source(items), _ReplayState(st.chain, start, st.vs),
        apply_fn=apply_fn, verifier=st.verifier,
        cursor_path=os.path.join(ctx.tmpdir, f"cursor-{tag}.json"),
        read_ahead=ctx.traffic["read_ahead"],
        max_run=ctx.traffic["max_run"])


def warm(ctx, fx):
    from cometbft_tpu.blocksync.catchup import CatchupError
    from cometbft_tpu.blocksync.pipeline import make_stream_verifier
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.ops import ed25519_cached as ec

    tr = ctx.traffic
    st = SimpleNamespace()
    st.vs, st.chain = fx["vs"], fx["chain"]
    signed = fx["signed"].result()
    n_blocks = ctx.config["history_blocks"]
    st.items = {h: (fx["blocks"][h][0], fixtures.build_commit(
        st.vs, h, fx["blocks"][h][1], signed[h - 1]["sigs"]))
        for h in range(1, n_blocks + 1)}
    st.sigs_per_block = len(st.vs)
    ctx.mark("fixtures_built")
    st.sample_ok = all(tuple(signed[h - 1]["expected"]) == ("ok",)
                       for h in fx["sample"])
    st.sample = len(fx["sample"])
    st.stream = make_stream_verifier()  # the engine's own default
    st.verifier = _TimedVerifier(st.stream, ctx)
    st.breaker = cbatch.device_breaker()

    # the tampered case, in set-up: the tail of the history with one
    # flipped signature; the engine must stop at exactly that height
    bad_h, tail = fx["bad_h"], tr["tampered_tail"]
    bad = signed[-1]
    items = dict(st.items)
    items[bad_h] = (fx["blocks"][bad_h][0], fixtures.build_commit(
        st.vs, bad_h, fx["blocks"][bad_h][1], bad["sigs"]))
    eng = _engine(ctx, st, items, n_blocks - tail,
                  lambda s, blk, c: _ReplayState(
                      s.chain_id, blk.header.height, st.vs), "tampered")
    got = None
    try:
        eng.run()
    except CatchupError as e:
        m = re.search(r"failed at height (\d+): (.*)$", str(e))
        got = (int(m.group(1)), m.group(2)) if m else ("?", str(e))
    exp = tuple(bad["expected"])
    st.tampered = {
        "height": bad_h, "reference": exp, "engine": got,
        "cursor": eng.cursor.as_dict(),
        "ok": (exp == ("invalid_signature", tr["tampered_sig"])
               and got is not None and got[0] == bad_h
               and re.search(rf"\b{tr['tampered_sig']}\b", got[1]) is not None
               and eng.cursor.verified < bad_h
               and eng.cursor.applied == eng.cursor.verified
               and eng.state.last_block_height == eng.cursor.applied),
    }
    ctx.info["tampered_setup"] = st.tampered
    ctx.mark("tampered_pass")
    st.stats0 = ec.table_cache_stats()
    st.chunks0 = dict(st.stream.chunks)
    st.faults0 = st.breaker.faults
    del st.verifier.calls[:]
    return st


def window(ctx, st):
    from cometbft_tpu.blocksync.catchup import CatchupError
    from cometbft_tpu.ops import ed25519_cached as ec

    applied = []  # (t, height) of every block applied
    laps, error = [], None
    t0 = time.monotonic()
    deadline = t0 + ctx.seconds

    def apply_fn(s, blk, commit):
        now = time.monotonic()
        if now > deadline:
            raise _WindowOver()
        with ctx.span("apply"):
            applied.append((now, blk.header.height))
            return _ReplayState(s.chain_id, blk.header.height, st.vs)

    lap = 0
    while time.monotonic() < deadline and error is None:
        misses0 = ec.table_cache_stats()["template_misses"]
        chunks0 = sum(st.stream.chunks.values())
        n0 = len(applied)
        with ctx.span("lap"):
            eng = _engine(ctx, st, st.items, 0, apply_fn, f"lap{lap}")
            try:
                eng.run()
            except _WindowOver:
                pass
            except CatchupError as e:  # a valid block refused
                error = str(e)
        laps.append({
            "blocks": len(applied) - n0,
            "chunks": sum(st.stream.chunks.values()) - chunks0,
            "template_misses":
                ec.table_cache_stats()["template_misses"] - misses0})
        lap += 1
    stats = ec.table_cache_stats()
    calls = [c for c in st.verifier.calls if c[1] <= deadline]
    t_last = applied[-1][0] if applied else t0
    return {
        "t0": t0, "t1": deadline, "t_last": t_last,
        "samples": {"verify_ms": [(b - a) * 1e3 for a, b, _ in calls]},
        "work": [(t, st.sigs_per_block) for t, _ in applied],
        "verify_s": sum(b - a for a, b, _ in calls if b <= t_last),
        "engine_s": t_last - t0,
        "blocks_applied": len(applied),
        "sigs_applied": len(applied) * st.sigs_per_block,
        "error": error,
        "counters": {
            "laps": laps,
            "chunks": {k: st.stream.chunks[k] - st.chunks0[k]
                       for k in st.chunks0},
            "table_builds": stats["misses"] - st.stats0["misses"],
            "breaker_faults": st.breaker.faults - st.faults0,
        },
    }


def verify(ctx, st, obs):
    blocks = obs["blocks_applied"]
    chunks = obs["counters"]["chunks"]
    # blocks of a chunk that left the device-stamped path, or of a
    # batch re-verified on the host after a device fault
    off_path = (chunks["host_packed"] + chunks["dense"]
                + obs["counters"]["breaker_faults"]) * ctx.traffic["max_run"]
    refused = 0 if obs["error"] is None else 1
    # a lap must cost what a first pass costs: every chunk of every lap
    # builds its templates anew
    full = [lp for lp in obs["counters"]["laps"] if lp["chunks"]]
    first_pass = (not chunks["stamped"] or all(
        lp["template_misses"] >= lp["chunks"] for lp in full))
    return {
        "attempted": blocks + refused,
        "failed": min(blocks + refused, off_path + refused),
        # every sampled block is valid by the plain reference and was
        # applied by the engine; the tampered one was refused where the
        # reference puts the blame
        "correct": (st.sample_ok and st.tampered["ok"]
                    and obs["error"] is None and first_pass
                    and blocks >= ctx.traffic["max_run"]),
    }
