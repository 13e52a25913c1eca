"""Driver `light_bisect_closed`: one light client, closed loop, coming
back to chains of ed25519 validators whose set slid past 2/3 since the
block it trusts, so that every verification bisects.

Each operation is ONE `light.client.Client.verify_light_block_at_height
(H8, now)` on a new `Client(chain, primary=Provider(...), witnesses=[],
skipping=True, trust_level=..., batch_fn=Config().crypto.batch_fn())`
that trusts H0: the client and its store are built, and H0 trusted,
outside the timed operation, so every operation bisects from H0. The
provider builds a new `ValidatorSet` from the block's members at every
fetch, as the program's RPC provider does (`rpc/client.light_provider`),
so each fetch pays the set's build and its first-contact root inside
the operation. The ring is `ring` chains taken in turn, each with keys
of its own; `tampered` of them carry one flipped signature in one
pivot's commit (`fixtures_bisect.tamper_at`).

The outcome of an operation is what the plain reference
(`reference/bisection.verify_skipping`) gives: the verdict, the heights
verified (the client's store) and every attempt (trusted height,
candidate height, result). The attempts are recorded by wrapping the
two `Verify` functions the client calls (`light.client.verify_non_
adjacent`, `verify_adjacent`), as the driver wraps the `batch_fn` it
hands in: the program's code runs as it is.

The observation carries `commit_closed`'s keys, so its readers read it
unchanged: `commit_ms` is one whole operation, `batchfn_ms` its
`batch_fn` calls together, `host_ms` the rest, `work` the live
signatures; `op_spans` bounds each operation on `time.monotonic()` for
`light_attempts_per_op`.

Traffic parameters (the mix's file): ring, target_block, tampered,
tampered_block, tampered_unknown_to.
"""
from __future__ import annotations

import random
import time
from types import SimpleNamespace

from drivers import light_skip_closed as skip
from harness import fixtures, fixtures_bisect, fixtures_light
from reference import bisection, ecdsa


def prepare(ctx):
    from cometbft_tpu.types.timestamp import Timestamp

    cfg, tr = ctx.config, ctx.traffic
    n, slide, ring = cfg["validators"], cfg["seats_slid"], tr["ring"]
    power = cfg["voting_power"]
    heights = [fixtures_light.HEIGHT0 + cfg["height_gap"] * k
               for k in range(cfg["light_blocks"])]
    plans = [fixtures_bisect.seats(ctx.seed, c, n, len(heights), slide,
                                   power["low"], power["high"])
             for c in range(ring)]
    signed = fixtures_bisect.Signed(ctx.cell, ctx, workers=12)
    try:
        pub_of = signed.pubs([s for plan in plans for s, _ in plan])
        rnd = random.Random(f"light-bisect-ring/{ctx.seed}")
        bad = set(rnd.sample(range(ring), tr["tampered"]))
        chains = []
        for c, plan in enumerate(plans):
            chain_id = f"{cfg['chain_id']}-{c}"
            blocks = []
            for k, h in enumerate(heights):
                vs, seeds = fixtures_bisect.valset(
                    fixtures_bisect.members(plan, k, n, slide), pub_of)
                header, bid = fixtures_light.header_for(chain_id, h, vs)
                blocks.append({"vs": vs, "seeds": seeds, "header": header,
                               "bid": bid})
            tamper = None
            if c in bad:
                t = tr["tampered_block"]
                new = blocks[t]["vs"].validators
                tamper = (t, fixtures_bisect.tamper_at(
                    rnd, [v.pub_key.data for v in
                          blocks[tr["tampered_unknown_to"]]["vs"].validators],
                    [v.pub_key.data for v in new],
                    [v.voting_power for v in new]))
            chains.append({"chain": chain_id, "blocks": blocks,
                           "tamper": tamper})
        last = fixtures_light.header_time(heights[-1])
        now = Timestamp(last.seconds + skip.NOW_AFTER_S, 0)
        if not signed.cached:
            refer = {"now_ns": now.to_ns(),
                     "trusting_period_s": cfg["trusting_period_s"],
                     "max_clock_drift_s": cfg["max_clock_drift_s"],
                     "trust_level": tuple(cfg["trust_level"])}
            signed.submit([{
                "chain": ch["chain"], "target": heights[tr["target_block"]],
                "tamper": ch["tamper"], "refer": refer,
                "blocks": [{
                    "height": h, "seeds": b["seeds"],
                    "bid": fixtures.bid_tuple(b["bid"]),
                    "powers": [v.voting_power for v in b["vs"].validators],
                    "refer": {
                        "time_ns": b["header"].time.to_ns(),
                        "validators_hash": b["header"].validators_hash,
                        "next_validators_hash":
                            b["header"].next_validators_hash}}
                    for h, b in zip(heights, ch["blocks"])]}
                for ch in chains])
        else:
            signed.abandon()
    except BaseException:
        signed.abandon()
        raise
    return {"chains": chains, "heights": heights, "now": now,
            "signed": signed}


def abandon(fx) -> None:
    fx["signed"].abandon()


def close(st) -> None:
    st.unwrap()


def _verdict(err, attempts):
    """The program's answer in the plain reference's words: trusted, or
    refused at the candidate of the attempt that ended the verification
    (every block of this traffic is there and names its own set, so
    no fetch ends one)."""
    if err is None:
        return bisection.TRUSTED
    return ("refused", attempts[-1][1]) + skip._outcome(err)


def warm(ctx, fx):
    from cometbft_tpu.config.config import Config
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.libs import deviceledger, tracing
    from cometbft_tpu.light import client as lc
    from cometbft_tpu.light import verifier as lv
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    st = SimpleNamespace(now=fx["now"], heights=fx["heights"], attempts=[])
    st.target = st.heights[ctx.traffic["target_block"]]
    signed = fx["signed"].result()
    st.chains = []
    for ch, got in zip(fx["chains"], signed):
        blocks = {}
        for h, b, blob in zip(st.heights, ch["blocks"], got["sigs"]):
            vals = b["vs"].validators
            blocks[h] = (b["header"],
                         fixtures.build_commit(b["vs"], h, b["bid"], blob),
                         [(v.pub_key.data, v.voting_power) for v in vals])
        verdict, trace, attempts = got["expected"]
        st.chains.append({"chain": ch["chain"], "blocks": blocks,
                          "tamper": ch["tamper"],
                          "expected": (tuple(verdict), list(trace),
                                       [tuple(a) for a in attempts])})
    ctx.mark("fixtures_built")

    def provider(chain):
        def fetch(h):
            if h not in chain["blocks"]:
                return None
            header, commit, members = chain["blocks"][h]
            # a new set at every fetch, as the RPC provider builds one
            vals = ValidatorSet([Validator(PubKey(pub), p)
                                 for pub, p in members])
            return lv.LightBlock(lv.SignedHeader(header, commit), vals)

        return lc.Provider(chain["chain"], fetch)

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

    def recorded(verify, new_at):
        def attempt(*args, **kw):
            trusted, new = args[1].height, args[new_at].height
            try:
                verify(*args, **kw)
            except lv.LightClientError as e:
                st.attempts.append((trusted, new, skip._outcome(e)))
                raise
            st.attempts.append((trusted, new, ecdsa.OK))

        return attempt

    wrapped = (lc.verify_non_adjacent, lc.verify_adjacent)
    lc.verify_non_adjacent = recorded(lv.verify_non_adjacent, 3)
    lc.verify_adjacent = recorded(lv.verify_adjacent, 2)

    def unwrap():
        lc.verify_non_adjacent, lc.verify_adjacent = wrapped

    providers = [provider(ch) for ch in st.chains]

    def begin(c):
        """A new client trusting H0 of chain c (outside every timed
        operation)."""
        st.client = lc.Client(
            st.chains[c]["chain"], primary=providers[c], witnesses=[],
            skipping=True, trust_level=tuple(ctx.config["trust_level"]),
            trusting_period=float(ctx.config["trusting_period_s"]),
            max_clock_drift=float(ctx.config["max_clock_drift_s"]),
            batch_fn=batch_fn)
        st.client.trust_light_block(providers[c].light_block(st.heights[0]))
        st.attempts = []

    def call():
        err = None
        try:
            with ctx.span("commit_call"):
                st.client.verify_light_block_at_height(st.target, now=st.now)
        except lv.LightClientError as e:
            err = e
        return (_verdict(err, st.attempts), st.client.store.heights(),
                st.attempts)

    st.begin, st.call, st.unwrap = begin, call, unwrap
    st.breaker = cbatch.device_breaker()
    st.faults0 = st.breaker.faults
    st.compiles = lambda: sum(deviceledger.counters()[k] for k in (
        "compiles", "pcache_hits"))
    st.dropped = tracing.stages_dropped
    # one whole lap, every chain once: compiles the one chunk shape and
    # proves every chain's expected outcome before the window opens;
    # the roots each operation built (a set met for the first time)
    st.warm_outcomes, st.warm_roots = [], []
    for c in range(len(st.chains)):
        begin(c)
        t0 = tracing.monotonic_ns()
        st.warm_outcomes.append(call())
        st.warm_roots.append(sum(1 for r in tracing.stages()
                                 if r[0] == "valset.hash" and r[1] >= t0))
    return st


def window(ctx, st):
    del st.batch_ms[:], st.batch_rows[:]
    call_ms, batch_ms, outcomes, work, spans = [], [], [], [], []
    ring = len(st.chains)
    compiles0, dropped0 = st.compiles(), st.dropped()
    t0 = time.monotonic()
    deadline = t0 + ctx.seconds
    n = 0
    while True:
        c = n % ring
        st.begin(c)  # outside every timed operation
        at = len(st.batch_ms)
        start = time.monotonic()
        t = time.perf_counter()
        out = st.call()
        dt = (time.perf_counter() - t) * 1e3
        now = time.monotonic()
        if now > deadline:
            break  # the operation that straddles the end is not a reading
        call_ms.append(dt)
        batch_ms.append(sum(st.batch_ms[at:]))
        outcomes.append((c, out))
        work.append((now, sum(st.batch_rows[at:])))
        spans.append((start, now))
        n += 1
    return {
        "t0": t0, "t1": deadline,
        "samples": {"commit_ms": call_ms, "batchfn_ms": batch_ms,
                    "host_ms": [c - b for c, b in zip(call_ms, batch_ms)]},
        "work": work, "outcomes": outcomes, "op_spans": spans,
        "counters": {
            "signatures_per_op": [w for _, w in work[:ring]],
            "attempts_first_lap": [len(o[2]) for o in st.warm_outcomes],
            "roots_first_lap": st.warm_roots,
            "breaker_faults": st.breaker.faults - st.faults0,
            "compiles_in_window": st.compiles() - compiles0,
            "stages_dropped": st.dropped() - dropped0},
    }


def verify(ctx, st, obs):
    wrong = sum(1 for c, out in obs["outcomes"]
                if out != st.chains[c]["expected"])
    warm_wrong = sum(1 for ch, out in zip(st.chains, st.warm_outcomes)
                     if out != ch["expected"])
    # the tampered chains end at the tampered block, refused by the
    # new-set check with the index the fixtures flipped; the others
    # are trusted
    tb = st.heights[ctx.traffic["tampered_block"]]
    shaped = sum(1 for ch in st.chains if ch["expected"][0] == (
        ("refused", tb, "invalid_header", "invalid_signature",
         ch["tamper"][1]) if ch["tamper"] else bisection.TRUSTED))
    faults = obs["counters"]["breaker_faults"]
    compiles = obs["counters"]["compiles_in_window"]
    return {
        "attempted": len(obs["outcomes"]),
        # a wrong outcome, a group re-verified on the host after a
        # device fault (no silent fallback), a compile in the window
        "failed": min(len(obs["outcomes"]), wrong + faults + compiles),
        "correct": (wrong == 0 and warm_wrong == 0
                    and shaped == len(st.chains)),
    }
