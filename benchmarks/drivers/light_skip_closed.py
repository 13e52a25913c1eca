"""Driver `light_skip_closed`: one light client, closed loop, skipping
from light block to light block over a chain of secp256k1 validators.

Each operation is ONE `light.client.Client.verify_light_block_at_height
(H_k, now)` on a `Client(chain, primary=Provider(...), witnesses=[],
skipping=True, trust_level=..., batch_fn=Config().crypto.batch_fn())`
with the default in-memory `TrustedStore`: the provider's block, its
`validate_basic`, one `verify_non_adjacent` (the trusting check of the
OLD set by address, then the 2/3 check of the NEW set), the store. The
caller waits for each answer before it asks for the next header, k =
1..ring in order from a trusted H0. After the last the driver builds a
new `Client`, trusts H0 again and starts the next lap, outside every
timed operation. `tampered` of the blocks carry one flipped signature
where only the second check can see it (`fixtures_light.tamper_at`):
that step must end in `ErrInvalidHeader` with the commit's index, trust
stays where it was, and the next operation skips from there.

The observation carries `commit_closed`'s keys, so its readers read it
unchanged: `commit_ms` is one whole step (BOTH checks), `batchfn_ms`
the step's two `batch_fn` calls together, `host_ms` the rest; `work`
the live signatures of both. `work_secp256k1` is `work` again (every
row is an ECDSA row), for the readers of that kernel's device time.

Traffic parameters (the mix's file): ring, tampered.
"""
from __future__ import annotations

import random
import time
from types import SimpleNamespace

from harness import fixtures, fixtures_light
from reference import ecdsa

NOW_AFTER_S = 60  # `now`: this long after the last header's time


def _heights(ctx):
    gap = ctx.config["height_gap"]
    return [fixtures_light.HEIGHT0 + gap * k
            for k in range(ctx.traffic["ring"] + 1)]


def _steps(ring: int, bad: set):
    """[(trusted block, target block)] of one lap: a refused target
    leaves trust where it was."""
    out, trusted = [], 0
    for k in range(1, ring + 1):
        out.append((trusted, k))
        if k not in bad:
            trusted = k
    return out


def prepare(ctx):
    from cometbft_tpu.types.timestamp import Timestamp

    cfg, ring = ctx.config, ctx.traffic["ring"]
    n, chain = cfg["validators"], cfg["chain_id"]
    power = cfg["voting_power"]
    heights = _heights(ctx)
    if len(heights) > cfg["light_blocks"]:
        raise ValueError("the ring is longer than the chain")
    seats = fixtures_light.seat_plan(
        ctx.seed, n, len(heights), cfg["seats_changed"], power["low"],
        power["high"])
    signed = fixtures_light.Signed(ctx.cell, ctx,
                                   workers=min(len(heights), 12))
    try:
        pub_of = signed.pubs(sorted({s for blk in seats for s, _ in blk}))
        sets, blocks = [], []
        for h, blk in zip(heights, seats):
            vs, seeds = fixtures_light.valset(blk, pub_of)
            header, bid = fixtures_light.header_for(chain, h, vs)
            sets.append(fixtures_light.plain_set(vs))
            blocks.append({"vs": vs, "seeds": seeds, "header": header,
                           "bid": bid})
        rnd = random.Random(f"light-ring/{ctx.seed}")
        bad = set(rnd.sample(range(1, ring + 1), ctx.traffic["tampered"]))
        steps = _steps(ring, bad)
        last = fixtures_light.header_time(heights[-1])
        now = Timestamp(last.seconds + NOW_AFTER_S, 0)
        tamper = {k: fixtures_light.tamper_at(
            rnd, sets[t]["pubs"], sets[t]["powers"], sets[k]["pubs"],
            sets[k]["powers"]) for t, k in steps if k in bad}
        if not signed.cached:
            def block(k):
                return dict(sets[k], height=heights[k],
                            time_ns=blocks[k]["header"].time.to_ns())

            refer = {k: {"trusted": block(t), "new": block(k),
                         "now_ns": now.to_ns(),
                         "trusting_period_s": cfg["trusting_period_s"],
                         "max_clock_drift_s": cfg["max_clock_drift_s"],
                         "trust_level": tuple(cfg["trust_level"])}
                     for t, k in steps}
            signed.submit([{
                "chain": chain, "height": h, "seeds": b["seeds"],
                "bid": fixtures.bid_tuple(b["bid"]),
                "tamper": tamper.get(k), "refer": refer.get(k)}
                for k, (h, b) in enumerate(zip(heights, blocks))])
        else:
            signed.abandon()
    except BaseException:
        signed.abandon()
        raise
    return {"blocks": blocks, "heights": heights, "steps": steps,
            "tamper": tamper, "now": now, "chain": chain, "signed": signed}


def abandon(fx) -> None:
    fx["signed"].abandon()


def close(st) -> None:
    pass


def _outcome(err):
    """The program's answer as the plain reference words it."""
    from cometbft_tpu.light import verifier as lv
    from cometbft_tpu.types import validation as tv

    if err is None:
        return ecdsa.OK
    cause = err.__cause__
    if isinstance(err, lv.ErrNewValSetCantBeTrusted) and isinstance(
            cause, tv.NotEnoughPowerError):
        return ("cant_be_trusted", cause.needed)
    if isinstance(err, lv.ErrInvalidHeader):
        if isinstance(cause, tv.InvalidSignatureError):
            return ("invalid_header", "invalid_signature", cause.idx)
        if isinstance(cause, tv.NotEnoughPowerError):
            return ("invalid_header", "not_enough_power", cause.needed)
        if str(cause).startswith("double vote from "):
            return ("invalid_header", "double_vote", str(cause)[17:])
    return ("error", type(err).__name__, str(err))


def warm(ctx, fx):
    from cometbft_tpu.config.config import Config
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.libs import deviceledger
    from cometbft_tpu.light import client as lc
    from cometbft_tpu.light import verifier as lv
    from cometbft_tpu.types.validator import ValidatorSet

    st = SimpleNamespace()
    st.chain, st.now, st.heights = fx["chain"], fx["now"], fx["heights"]
    st.steps = fx["steps"]
    signed = fx["signed"].result()
    st.expected = {k: tuple(s["expected"])
                   for k, s in enumerate(signed) if s["expected"]}
    st.tamper = fx["tamper"]
    st.blocks = {}
    for h, b, s in zip(st.heights, fx["blocks"], signed):
        commit = fixtures.build_commit(b["vs"], h, b["bid"], s["sigs"])
        st.blocks[h] = lv.LightBlock(lv.SignedHeader(b["header"], commit),
                                     b["vs"])
    ctx.mark("fixtures_built")
    # a first-contact skip pays one merkle root of the new set more than
    # a step of this ring, whose sets remember theirs: what one costs
    # here, once, on this host's clock (PERF.md section 5)
    fresh = ValidatorSet(list(fx["blocks"][-1]["vs"].validators))
    t = time.perf_counter()
    fresh.hash()
    ctx.info["valset_root_first_contact_ms"] = (
        time.perf_counter() - t) * 1e3
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

    provider = lc.Provider(st.chain, st.blocks.get)

    def new_lap():
        st.client = lc.Client(
            st.chain, primary=provider, witnesses=[], skipping=True,
            trust_level=tuple(ctx.config["trust_level"]),
            trusting_period=float(ctx.config["trusting_period_s"]),
            max_clock_drift=float(ctx.config["max_clock_drift_s"]),
            batch_fn=batch_fn)
        st.client.trust_light_block(st.blocks[st.heights[0]])

    def call(k):
        try:
            with ctx.span("commit_call"):
                st.client.verify_light_block_at_height(st.heights[k],
                                                       now=st.now)
        except lv.LightClientError as e:
            return _outcome(e)
        return _outcome(None)

    st.new_lap, st.call = new_lap, call
    st.breaker = cbatch.device_breaker()
    st.faults0 = st.breaker.faults
    st.compiles = lambda: sum(deviceledger.counters()[k] for k in (
        "compiles", "pcache_hits"))
    # one whole lap: compiles the one chunk shape and proves every
    # step's expected outcome before the window opens
    new_lap()
    st.warm_outcomes = [call(k) for _, k in st.steps]
    return st


def window(ctx, st):
    del st.batch_ms[:], st.batch_rows[:]
    call_ms, batch_ms, outcomes, work = [], [], [], []
    ring = len(st.steps)
    compiles0 = st.compiles()
    t0 = time.monotonic()
    deadline = t0 + ctx.seconds
    n = 0
    while True:
        if n % ring == 0:
            st.new_lap()  # outside every timed operation
        k = st.steps[n % ring][1]
        at = len(st.batch_ms)
        t = time.perf_counter()
        out = st.call(k)
        dt = (time.perf_counter() - t) * 1e3
        now = time.monotonic()
        if now > deadline:
            break  # the step that straddles the end is not a reading
        call_ms.append(dt)
        batch_ms.append(sum(st.batch_ms[at:]))
        outcomes.append((k, out))
        work.append((now, sum(st.batch_rows[at:])))
        n += 1
    lap_rows = st.batch_rows[:2 * ring]
    return {
        "t0": t0, "t1": deadline,
        "samples": {"commit_ms": call_ms, "batchfn_ms": batch_ms,
                    "host_ms": [c - b for c, b in zip(call_ms, batch_ms)]},
        "work": work, "work_secp256k1": work, "outcomes": outcomes,
        "counters": {
            "signatures_per_lap": sum(lap_rows),
            "batch_rows_first_lap": lap_rows,
            "breaker_faults": st.breaker.faults - st.faults0,
            "compiles_in_window": st.compiles() - compiles0},
    }


def verify(ctx, st, obs):
    wrong = sum(1 for k, out in obs["outcomes"] if out != st.expected[k])
    warm_wrong = sum(1 for (_, k), out in zip(st.steps, st.warm_outcomes)
                     if out != st.expected[k])
    # each tampered block is refused by the second check, with the
    # index the fixtures put the flipped signature at
    refused = sum(1 for k, at in st.tamper.items() if st.expected[k] == (
        "invalid_header", "invalid_signature", at))
    accepted = sum(1 for e in st.expected.values() if e == ecdsa.OK)
    faults = obs["counters"]["breaker_faults"]
    compiles = obs["counters"]["compiles_in_window"]
    return {
        "attempted": len(obs["outcomes"]),
        # a wrong outcome, a group re-verified on the host after a
        # device fault (no silent fallback), a compile in the window
        "failed": min(len(obs["outcomes"]), wrong + faults + compiles),
        "correct": (wrong == 0 and warm_wrong == 0
                    and refused == ctx.traffic["tampered"]
                    and accepted == len(st.steps) - refused),
    }
