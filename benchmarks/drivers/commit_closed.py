"""Driver `commit_closed`: one caller, closed loop, over the served
commit check.

Each operation is `validation.verify_commit_light(chain, vals,
block_id, height, commit, Config().crypto.batch_fn())`: the call and
the `batch_fn` that `cmd/cli.build_node` wires a node with. The caller
waits for each answer before it asks again, as blocksync's
single-commit check and a light client's step do. The commits come from
a ring of distinct seeded commits at different heights, so nothing
keyed by a commit can serve a repeat; `tampered` of them carry one
flipped signature before the quorum point and must be refused with that
index.

Traffic parameters (the mix's file): ring, tampered, power.
"""
from __future__ import annotations

import time
from types import SimpleNamespace

from harness import fixtures

HEIGHT0 = 12_345


def _ring_plan(ctx, n_vals: int):
    """[(height, tampered index or None)] from the seed."""
    import random

    rnd = random.Random(f"commit-ring/{ctx.seed}")
    ring, bad = ctx.traffic["ring"], ctx.traffic["tampered"]
    bad_slots = set(rnd.sample(range(ring), bad))
    # light verification examines validators in order until more than
    # 2/3 of the (equal) power has signed: blame must fall inside that
    examined = n_vals * 2 // 3
    return [(HEIGHT0 + 17 * k,
             rnd.randrange(examined) if k in bad_slots else None)
            for k in range(ring)]


def prepare(ctx):
    n = ctx.config["validators"]
    power = ctx.config["voting_power"]
    chain = ctx.config["chain_id"]
    vs, seeds = fixtures.valset(
        fixtures.key_seeds(ctx.seed, "valset", n), power)
    plan = _ring_plan(ctx, n)
    bids = [fixtures.block_id(b"commit/%d/%d" % (ctx.seed, h))
            for h, _ in plan]
    signed = fixtures.Signed(ctx.cell, ctx)
    if not signed.cached:
        signed.submit([{
            "chain": chain, "seeds": seeds, "power": power,
            "blocks": [(h, fixtures.bid_tuple(bid))],
            "tamper": {h: [bad]} if bad is not None else {},
            "refer": {h}} for (h, bad), bid in zip(plan, bids)],
            workers=min(len(plan), 8))
    return {"vs": vs, "plan": plan, "bids": bids, "chain": chain,
            "signed": signed}


def abandon(fx) -> None:
    fx["signed"].abandon()


def close(st) -> None:
    pass


def _outcome(err):
    """The program's answer as the plain reference words it."""
    from cometbft_tpu.types import validation as tv

    if err is None:
        return ("ok",)
    if isinstance(err, tv.InvalidSignatureError):
        return ("invalid_signature", err.idx)
    if isinstance(err, tv.NotEnoughPowerError):
        return ("not_enough_power", err.needed)
    return ("error", type(err).__name__, str(err))


def warm(ctx, fx):
    from cometbft_tpu.config.config import Config
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.types import validation as tv

    st = SimpleNamespace()
    st.vs, st.chain = fx["vs"], fx["chain"]
    signed = fx["signed"].result()
    st.ring = [(h, bid, fixtures.build_commit(st.vs, h, bid, s["sigs"]),
                tuple(s["expected"]))
               for (h, _), bid, s in zip(fx["plan"], fx["bids"], signed)]
    ctx.mark("fixtures_built")
    inner = Config().crypto.batch_fn()  # what cli.build_node passes
    if inner is None:
        raise RuntimeError("default [crypto] verifier is not the device")
    st.batch_ms, st.batch_rows = [], []

    def batch_fn(pubs, msgs, sigs):
        # the benchmark's span around the batch_fn it hands in
        t = time.perf_counter()
        with ctx.span("batch_fn"):
            out = inner(pubs, msgs, sigs)
        st.batch_ms.append((time.perf_counter() - t) * 1e3)
        st.batch_rows.append(len(sigs))
        return out

    def call(k):
        h, bid, commit, _ = st.ring[k]
        try:
            with ctx.span("commit_call"):
                tv.verify_commit_light(st.chain, st.vs, bid, h, commit,
                                       batch_fn)
        except tv.VerificationError as e:
            return _outcome(e)
        return _outcome(None)

    st.call = call
    st.breaker = cbatch.device_breaker()
    st.faults0 = st.breaker.faults
    # every commit of the ring once: compiles the one padded shape and
    # proves each expected outcome before the window opens
    st.warm_outcomes = [call(k) for k in range(len(st.ring))]
    return st


def window(ctx, st):
    del st.batch_ms[:], st.batch_rows[:]
    call_ms, outcomes, work = [], [], []
    n = len(st.ring)
    t0 = time.monotonic()
    deadline = t0 + ctx.seconds
    k = 0
    while True:
        t = time.perf_counter()
        out = st.call(k % n)
        dt = (time.perf_counter() - t) * 1e3
        now = time.monotonic()
        if now > deadline:
            break  # the call that straddles the end is not a reading
        call_ms.append(dt)
        outcomes.append((k % n, out))
        work.append((now, st.batch_rows[-1]))
        k += 1
    batch_ms = st.batch_ms[:len(call_ms)]
    return {
        "t0": t0, "t1": deadline,
        "samples": {"commit_ms": call_ms, "batchfn_ms": batch_ms,
                    "host_ms": [c - b for c, b in zip(call_ms, batch_ms)]},
        "work": work, "outcomes": outcomes,
        "counters": {"signatures_per_call": st.batch_rows[0],
                     "breaker_faults": st.breaker.faults - st.faults0},
    }


def verify(ctx, st, obs):
    expected = [e for _, _, _, e in st.ring]
    wrong = sum(1 for k, out in obs["outcomes"] if out != expected[k])
    warm_wrong = sum(1 for out, e in zip(st.warm_outcomes, expected)
                     if out != e)
    refused = sum(1 for e in expected if e[0] == "invalid_signature")
    faults = obs["counters"]["breaker_faults"]
    return {
        "attempted": len(obs["outcomes"]),
        # a wrong verdict, or a batch re-verified on the host after a
        # device fault (no silent fallback)
        "failed": min(len(obs["outcomes"]), wrong + faults),
        "correct": (wrong == 0 and warm_wrong == 0
                    and refused == ctx.traffic["tampered"]),
    }
