"""Driver `votes_serial`: votes as a node feeds them to its vote sets.

The plane is built, started, made global and primed as `Node.on_start`
does (`VerifyPlaneConfig(enable=True).build()`, `set_global_plane`,
`prime(vals, chain)`, then the table warmer). Votes go to
`HeightVoteSet.add_vote`, the call `consensus/state.py`'s receive
routine makes (`_receive_routine` -> `_handle` -> `_try_add_vote`): one
vote at a time from `consumers` threads. A node has one such thread,
so `consumers` is 1 where a cell stands for a node of today.

Open loop. Vote k is due at t0 + (k + jitter_k) * gap, gap = 1 / rate,
jitter uniform in +-jitter_gap_share / 2 of a gap, from the seed: an
even schedule, as votes of many independent validators arrive. A vote
waits in a queue from its due time until a consumer takes it; a
consumer that finds the next vote not yet due sleeps until it is. That
is a FIFO queue served by `consumers` threads, without a producer
thread to share the interpreter with. Latency runs from the due time
to the verdict, so a stall delays every vote due behind it.
`generator_lag` is how late a sleeping consumer woke for a vote.

Votes form successive (height, type) sets of one vote per validator in
seeded order; `bad_share` of them carry a flipped signature and must be
rejected. All votes due in the window are served (the drain after the
window's end is bounded); a vote not served counts as failed.

Traffic parameters (the mix's file): rate_per_s, consumers,
jitter_gap_share, bad_share, warm_votes, drain_s.
"""
from __future__ import annotations

import random
import threading
import time
from types import SimpleNamespace

from harness import fixtures, stats

HEIGHT0 = 1_000


def _schedule(ctx, n_vals: int):
    """[(set number, validator index, bad, due offset s)] for the
    window, from the seed."""
    tr = ctx.traffic
    rnd = random.Random(f"votes/{ctx.seed}")
    gap = 1.0 / tr["rate_per_s"]
    n = int(tr["rate_per_s"] * ctx.seconds)
    out = []
    order = []
    for k in range(n):
        if k % n_vals == 0:
            order = list(range(n_vals))
            rnd.shuffle(order)
        jitter = (rnd.random() - 0.5) * tr["jitter_gap_share"]
        out.append((k // n_vals, order[k % n_vals],
                    rnd.random() < tr["bad_share"],
                    max(0.0, (k + 0.5 + jitter) * gap)))
    return out


def _set_key(s: int):
    """Set number -> (height, vote type): prevotes, then precommits."""
    from cometbft_tpu.types import canonical

    return (HEIGHT0 + s // 2, canonical.PREVOTE_TYPE if s % 2 == 0
            else canonical.PRECOMMIT_TYPE)


def _make_votes(chain, vs, seeds, plan, tag: bytes):
    """Signed Vote objects for `plan` rows (set, idx, bad, ...)."""
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.vote import Vote

    votes = []
    for row in plan:
        s, idx, bad = row[0], row[1], row[2]
        height, vtype = _set_key(s)
        bid = fixtures.block_id(tag + b"/%d" % height)
        v = Vote(vote_type=vtype, height=height, round=0, block_id=bid,
                 timestamp=Timestamp(*fixtures.commit_ts(height, idx)),
                 validator_address=vs.validators[idx].address,
                 validator_index=idx)
        sig = fixtures._key(seeds[idx]).sign(v.sign_bytes(chain))
        v.signature = fixtures.flip(sig) if bad else sig
        votes.append(v)
    return votes


def prepare(ctx):
    n = ctx.config["validators"]
    chain = ctx.config["chain_id"]
    vs, seeds = fixtures.valset(
        fixtures.key_seeds(ctx.seed, "valset", n),
        ctx.config["voting_power"])
    plan = _schedule(ctx, n)
    tag = b"votes/%d" % ctx.seed
    votes = _make_votes(chain, vs, seeds, plan, tag)
    # warm-up votes: a set of their own (set -2: the height below)
    w = min(ctx.traffic["warm_votes"], n)
    warm_plan = [(-2, i, i % 7 == 3, 0.0) for i in range(w)]
    warm_votes = _make_votes(chain, vs, seeds, warm_plan, tag)
    ctx.info["fixtures"] = "signed"  # thousands of votes: no cache
    return {"vs": vs, "chain": chain, "plan": plan, "votes": votes,
            "warm_plan": warm_plan, "warm_votes": warm_votes}


def abandon(fx) -> None:
    pass


def _add(hvs, vote):
    """One served call; its verdict as the reference words it."""
    from cometbft_tpu.types.vote_set import VoteSetError

    try:
        return bool(hvs.add_vote(vote))
    except VoteSetError:
        return False  # the designed rejection of a bad signature


def warm(ctx, fx):
    from cometbft_tpu import verifyplane
    from cometbft_tpu.config.config import VerifyPlaneConfig
    from cometbft_tpu.consensus.height_vote_set import HeightVoteSet
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.verifyplane import plane as vplane
    from cometbft_tpu.verifyplane import warmer as vwarmer

    st = SimpleNamespace()
    st.vs, st.chain = fx["vs"], fx["chain"]
    st.plan, st.votes = fx["plan"], fx["votes"]
    cfg = VerifyPlaneConfig(enable=True)
    st.plane = cfg.build()
    # the program's own ledger, wide enough to keep the whole window
    st.plane.ledger = type(st.plane.ledger)(capacity=1 << 17)
    st.plane.start()
    verifyplane.set_global_plane(st.plane)
    st.warmer = cfg.build_warmer()
    try:
        primed = st.plane.prime(st.vs, st.chain)  # as Node.on_start does
        if primed is None and not ctx.rehearse:
            raise RuntimeError("the plane verifies on the host")
        ctx.info["primed_s"] = primed
        ctx.mark("primed")
        if st.warmer is not None:
            st.warmer.start()
            vwarmer.set_global_warmer(st.warmer)
        heights = sorted({_set_key(s)[0] for s, *_ in st.plan} | {
            _set_key(-2)[0]})
        st.hvs = {h: HeightVoteSet(st.chain, h, st.vs) for h in heights}
        hw = st.hvs[_set_key(-2)[0]]
        st.warm_got = [_add(hw, v) for v in fx["warm_votes"]]
        st.warm_exp = [not bad for _, _, bad, _ in fx["warm_plan"]]
    except BaseException:
        close(st)
        raise
    st.breaker = cbatch.device_breaker()
    st.faults0 = st.breaker.faults
    st.timeouts0 = vplane.result_timeouts()
    st.tables0 = ec.table_cache_stats()
    recs = st.plane.ledger.records()
    st.seq0 = recs[-1]["seq"] + 1 if recs else 0
    return st


def close(st) -> None:
    from cometbft_tpu import verifyplane
    from cometbft_tpu.verifyplane import warmer as vwarmer

    if st.warmer is not None:
        vwarmer.clear_global_warmer(st.warmer)
        st.warmer.stop()
        st.warmer = None
    if st.plane is not None:
        verifyplane.clear_global_plane(st.plane)
        st.plane.stop()
        st.plane = None


def window(ctx, st):
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.verifyplane import plane as vplane

    n = len(st.votes)
    due = [row[3] for row in st.plan]
    hvs_of = [st.hvs[_set_key(row[0])[0]] for row in st.plan]
    done_at = [None] * n
    verdict = [None] * n
    lag = [0.0] * n
    nxt = [0]
    lock = threading.Lock()
    raised = []
    t0 = time.monotonic() + 0.05
    stop_at = t0 + ctx.seconds + ctx.traffic["drain_s"]

    def consume():
        try:
            while True:
                with lock:
                    k = nxt[0]
                    nxt[0] += 1
                if k >= n:
                    return
                t_due = t0 + due[k]
                now = time.monotonic()
                if now < t_due:
                    with ctx.span("wait_vote"):
                        time.sleep(t_due - now)
                    lag[k] = time.monotonic() - t_due
                elif now > stop_at:
                    return  # not served: counted as failed
                with ctx.span("add_vote"):
                    verdict[k] = _add(hvs_of[k], st.votes[k])
                done_at[k] = time.monotonic()
        except BaseException as e:  # re-raised below, on the main thread
            raised.append(e)

    threads = [threading.Thread(target=consume, name=f"consumer-{i}")
               for i in range(ctx.traffic["consumers"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if raised:
        raise raised[0]
    recs = [r for r in st.plane.ledger.records() if r["seq"] >= st.seq0]
    tables = ec.table_cache_stats()
    timeouts = vplane.result_timeouts() - st.timeouts0
    faults = st.breaker.faults - st.faults0
    served = [k for k in range(n) if done_at[k] is not None]
    t1 = t0 + ctx.seconds
    # backlog at the window's end: votes due by then, not yet served
    backlog = sum(1 for k in range(n) if t0 + due[k] <= t1
                  and (done_at[k] is None or done_at[k] > t1))
    paths = {}
    for r in recs:
        paths[r["path"]] = paths.get(r["path"], 0) + 1
    vote_ms = [(done_at[k] - t0 - due[k]) * 1e3 for k in served]
    half = len(served) // 2
    return {
        "t0": t0, "t1": t1,
        "samples": {
            "vote_ms": vote_ms,
            "generator_lag_ms": [lag[k] * 1e3 for k in served],
            "flush_rows": [r["rows"] for r in recs],
            "flush_queued_ms": [r["queued_ms"] for r in recs],
            "flush_fill": [r["util"] for r in recs
                           if r["path"] == "fused" and r["util"]],
        },
        "work": [(done_at[k], 1) for k in served],
        "verdicts": verdict,
        "counters": {
            "votes_due": n, "votes_served": len(served),
            "backlog_at_end": backlog,
            "drain_s": max((done_at[k] for k in served), default=t1) - t1,
            "vote_ms_percentiles": {
                str(q): stats.percentile(vote_ms, q / 100) if vote_ms
                else None for q in (50, 75, 90, 95, 99, 100)},
            # the slowest votes, as (vote number, ms): where stalls fall
            "slowest": sorted(((k, ms) for k, ms in zip(served, vote_ms)),
                              key=lambda x: -x[1])[:8],
            "vote_ms_p50_first_half": _p50(vote_ms[:half]),
            "vote_ms_p50_second_half": _p50(vote_ms[half:]),
            "flushes": len(recs), "paths": paths,
            "flush_stage_ms_p50": {
                k: _p50([r[k + "_ms"] for r in recs])
                for k in ("queued", "pack", "flight", "collect", "settle",
                          "h2d", "dev")},
            "stamp_host": sum(1 for r in recs if r["stamp"] == "host"),
            "shed": sum(r["shed"] for r in recs),
            "result_timeouts": timeouts, "breaker_faults": faults,
            "table_builds": tables["misses"] - st.tables0["misses"],
        },
    }


def _p50(xs):
    return stats.median(xs) if xs else None


def verify(ctx, st, obs):
    """Every vote set's final state against the plain reference's."""
    from cometbft_tpu.types import canonical
    from reference import plain

    pubs = [v.pub_key.data for v in st.vs.validators]
    powers = [v.voting_power for v in st.vs.validators]
    verdicts = obs["verdicts"]
    by_set = {}
    for k, row in enumerate(st.plan):
        if verdicts[k] is not None:
            by_set.setdefault(row[0], []).append(k)
    wrong, states_ok = 0, True
    for s, ks in sorted(by_set.items()):
        height, vtype = _set_key(s)
        fed = [(st.plan[k][1], st.votes[k].block_id.key(),
                st.votes[k].sign_bytes(st.chain), st.votes[k].signature)
               for k in ks]
        admitted, total, voted, maj = plain.vote_set_state(
            pubs, powers, fed)
        wrong += sum(1 for k, a in zip(ks, admitted) if verdicts[k] != a)
        hvs = st.hvs[height]
        vset = (hvs.prevotes(0) if vtype == canonical.PREVOTE_TYPE
                else hvs.precommits(0))
        got_maj = vset.two_thirds_majority()
        bits = vset.bit_array()
        got_voted = bits.true_indices()
        if (vset.sum != total or got_voted != voted
                or (None if got_maj is None else got_maj.key()) != maj):
            states_ok = False
    c = obs["counters"]
    unserved = c["votes_due"] - c["votes_served"]
    off_path = (sum(n for p, n in c["paths"].items() if p != "fused")
                + c["stamp_host"] + c["shed"] + c["result_timeouts"]
                + c["breaker_faults"])
    if ctx.rehearse:
        off_path = 0  # the CPU backend has no fused path to stay on
    warm_ok = st.warm_got == st.warm_exp
    rejected = sum(1 for v in verdicts if v is False)
    return {
        "attempted": c["votes_due"],
        "failed": min(c["votes_due"], wrong + unserved + off_path),
        "correct": (wrong == 0 and states_ok and warm_ok
                    and (rejected > 0 or not any(r[2] for r in st.plan))),
    }
