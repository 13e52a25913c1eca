"""Driver `votes_bursts`: a live chain's votes as one of its nodes
receives them, burst by burst.

Every height every validator sends a prevote and, `precommit_offset_s`
later, a precommit; the votes of one such set reach a node within
`burst_ms`. So a height of `block_period_s` holds two bursts of
`votes_per_burst` votes (one a validator, seeded order, each due time
drawn uniformly from the burst, from the seed: votes clump and leave
gaps as independent senders' do) and is idle for the rest: successive
(height, type) sets, as `votes_serial` numbers them.
`bad_share` of the votes carry a flipped signature and must be
rejected.

The plane is built, started, made global, primed and warmed as
`votes_serial.warm` does it (as `Node.on_start` does). Votes go to
`consensus.vote_intake.intake`, the function `consensus/state.py`'s
receive routine hands its waiting votes to: the ONE consumer takes
every vote that is due and not yet taken, in due order (what
`msg_queue` would hold; at most the plane's `max_batch`, as there),
hands them over with `HeightVoteSet.add_vote` as the handler, and
sleeps until the next vote is due when none is.

Open loop. Latency runs from a vote's DUE time to its verdict. A
verdict later than `late_after_s` after the due time counts as failed:
the chain has moved on. After every vote it handled the driver asks the
vote's set for `two_thirds_majority()` and keeps the first moment and
the vote it came with; the plain reference (`reference/quorum.py`) says
which vote that must be.

Traffic parameters (the mix's file): block_period_s, burst_ms,
votes_per_burst, precommit_offset_s, consumers, bad_share,
late_after_s, warm_votes, drain_s.
"""
from __future__ import annotations

import random
import threading
import time

from drivers import votes_serial as serial
from harness import fixtures, stages, stats


def _schedule(ctx, n_vals: int):
    """[(set number, validator index, bad, due offset s)] in due order:
    the bursts that end inside the window."""
    tr = ctx.traffic
    rnd = random.Random(f"bursts/{ctx.seed}")
    per, burst = tr["votes_per_burst"], tr["burst_ms"] / 1e3
    if per > n_vals or tr["consumers"] != 1 \
            or tr["block_period_s"] != ctx.config["block_period_s"]:
        raise ValueError("votes-bursts: one vote a validator at most, one "
                         "consumer, the configuration's block period")
    out = []
    s = 0
    while True:
        start = ((s // 2) * tr["block_period_s"]
                 + (s % 2) * tr["precommit_offset_s"])
        if start + burst > ctx.seconds:
            break
        order = list(range(n_vals))
        rnd.shuffle(order)
        dues = [start + rnd.random() * burst for _ in range(per)]
        out += [(s, idx, rnd.random() < tr["bad_share"], due)
                for idx, due in zip(order, dues)]
        s += 1
    return sorted(out, key=lambda row: row[3])


def prepare(ctx):
    # first of all: a program without the vote intake cannot run this
    # cell, and says so before anything is signed or started
    from cometbft_tpu.consensus import vote_intake  # noqa: F401

    n = ctx.config["validators"]
    chain = ctx.config["chain_id"]
    vs, seeds = fixtures.valset(
        fixtures.key_seeds(ctx.seed, "valset", n),
        ctx.config["voting_power"])
    plan = _schedule(ctx, n)
    tag = b"bursts/%d" % ctx.seed
    votes = serial._make_votes(chain, vs, seeds, plan, tag)
    # the warm-up, in the calls it is handed over in: one vote, then a
    # burst of the set below the window's first. Both are flushes of
    # the one program `prime` compiled (one stride, whatever rows are
    # live; the intake keeps a validator's two votes out of one flush)
    w = min(ctx.traffic["warm_votes"], n)
    warm_plan = [(-2, i, i % 7 == 3, 0.0) for i in range(w)]
    warm_calls = [warm_plan[:1], warm_plan[1:]]
    warm_votes = serial._make_votes(chain, vs, seeds, warm_plan, tag)
    ctx.info["fixtures"] = "signed"
    return {"vs": vs, "chain": chain, "plan": plan, "votes": votes,
            "warm_plan": warm_plan, "warm_votes": warm_votes,
            "warm_calls": [len(call) for call in warm_calls]}


def abandon(fx) -> None:
    pass


def _vote_set(st, s: int):
    from cometbft_tpu.types import canonical

    height, vtype = serial._set_key(s)
    hvs = st.hvs[height]
    return (hvs.prevotes(0) if vtype == canonical.PREVOTE_TYPE
            else hvs.precommits(0))


def _hand_over(st, items, vote_of, handle):
    """One call of the program's vote intake, as the receive routine
    makes it: what is waiting, the vote each item carries, the set it
    would reach, the serial handler."""
    from cometbft_tpu.consensus import vote_intake

    return vote_intake.intake(items, vote_of,
                              lambda v: st.hvs[v.height], handle)


def warm(ctx, fx):
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.verifyplane import plane as vplane

    # plane, prime, warmer, vote sets: votes_serial's, with no warm-up
    # votes of its own kind (one at a time); the burst below is ours
    st = serial.warm(ctx, {**fx, "warm_votes": [], "warm_plan": []})
    try:
        hw = st.hvs[serial._set_key(-2)[0]]
        st.warm_got, k = [], 0
        for n_call in fx["warm_calls"]:
            if n_call:
                st.warm_got += _hand_over(
                    st, fx["warm_votes"][k:k + n_call], lambda v: v,
                    lambda v: serial._add(hw, v))
            k += n_call
        st.warm_exp = [not bad for _, _, bad, _ in fx["warm_plan"]]
    except BaseException:
        serial.close(st)
        raise
    # the window's baselines, taken after the warm-up
    st.faults0 = st.breaker.faults
    st.timeouts0 = vplane.result_timeouts()
    st.tables0 = ec.table_cache_stats()
    recs = st.plane.ledger.records()
    st.seq0 = recs[-1]["seq"] + 1 if recs else 0
    return st


close = serial.close


def window(ctx, st):
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.verifyplane import plane as vplane

    n = len(st.votes)
    due = [row[3] for row in st.plan]
    set_of = [row[0] for row in st.plan]
    hvs_of = [st.hvs[serial._set_key(s)[0]] for s in set_of]
    sets = sorted(set(set_of))
    vset = {s: _vote_set(st, s) for s in sets}
    quorum = {}  # set -> (moment it first reported +2/3, vote number)
    done_at = [None] * n
    verdict = [None] * n
    lag = [0.0] * n
    calls = []  # per intake call: (s into the window, votes, ms it took)
    raised = []
    limit = st.plane.max_batch
    t0 = time.monotonic() + 0.05
    stop_at = t0 + ctx.seconds + ctx.traffic["drain_s"]

    def consume():
        k = 0

        def handle(i):
            verdict[i] = serial._add(hvs_of[i], st.votes[i])
            done_at[i] = now = time.monotonic()
            s = set_of[i]
            if s not in quorum and vset[s].two_thirds_majority() is not None:
                quorum[s] = (now, i)

        try:
            while k < n:
                now = time.monotonic()
                t_due = t0 + due[k]
                if now < t_due:
                    with ctx.span("wait_vote"):
                        time.sleep(t_due - now)
                    now = time.monotonic()
                    lag[k] = now - t_due
                elif now > stop_at:
                    return  # not served: counted as failed
                j = k + 1
                while j < n and j - k < limit and t0 + due[j] <= now:
                    j += 1
                with ctx.span("intake"):
                    _hand_over(st, range(k, j), st.votes.__getitem__,
                               handle)
                calls.append((now - t0, j - k,
                              (time.monotonic() - now) * 1e3))
                k = j
        except BaseException as e:  # re-raised below, on the main thread
            raised.append(e)

    consumer = threading.Thread(target=consume, name="consumer-0")
    consumer.start()
    consumer.join()
    if raised:
        raise raised[0]
    recs = [r for r in st.plane.ledger.records() if r["seq"] >= st.seq0]
    tables = ec.table_cache_stats()
    timeouts = vplane.result_timeouts() - st.timeouts0
    faults = st.breaker.faults - st.faults0
    served = [k for k in range(n) if done_at[k] is not None]
    t1 = t0 + ctx.seconds
    backlog = sum(1 for k in range(n) if t0 + due[k] <= t1
                  and (done_at[k] is None or done_at[k] > t1))
    paths = {}
    for r in recs:
        paths[r["path"]] = paths.get(r["path"], 0) + 1
    vote_ms = [(done_at[k] - t0 - due[k]) * 1e3 for k in served]
    late_ms = ctx.traffic["late_after_s"] * 1e3
    by_set = {s: [k for k in served if set_of[k] == s] for s in sets}
    span = {"t0": t0, "t1": t1}
    return {
        **span,
        "samples": {
            "vote_ms": vote_ms,
            "generator_lag_ms": [lag[k] * 1e3 for k in served],
            "flush_rows": [r["rows"] for r in recs],
            "flush_queued_ms": [r["queued_ms"] for r in recs],
            "flush_fill": [r["util"] for r in recs
                           if r["path"] == "fused" and r["util"]],
            "intake_rows": [c[1] for c in calls],
        },
        "work": [(done_at[k], 1) for k in served],
        "verdicts": verdict,
        "quorum": quorum,
        "counters": {
            "votes_due": n, "votes_served": len(served),
            "late": sum(1 for ms in vote_ms if ms > late_ms),
            "bursts": len(sets), "intake_calls": len(calls),
            "backlog_at_end": backlog,
            "drain_s": max((done_at[k] for k in served), default=t1) - t1,
            "vote_ms_percentiles": {
                str(q): stats.percentile(vote_ms, q / 100) if vote_ms
                else None for q in (50, 75, 90, 95, 99, 100)},
            "slowest": sorted(((k, ms) for k, ms in zip(served, vote_ms)),
                              key=lambda x: -x[1])[:8],
            # calls that took over 50 ms and the latest wake-up: where
            # stalls fall
            "slow_calls": [c for c in calls if c[2] > 50.0][:16],
            "generator_lag_max_ms": max(lag) * 1e3,
            # per burst: its last verdict after its last due vote
            "burst_tail_ms": [
                (max(done_at[k] for k in ks) - t0 - max(due[k] for k in ks))
                * 1e3 for ks in by_set.values() if ks],
            # the program's own stages around a call, medians in ms
            "intake_stage_ms_p50": {
                name: stages.median_ms(span, "votes." + name)
                for name in ("intake", "stage", "settle")},
            "flushes": len(recs), "paths": paths,
            "flush_rows_max": max((r["rows"] for r in recs), default=0),
            "flush_stage_ms_p50": {
                k: serial._p50([r[k + "_ms"] for r in recs])
                for k in ("queued", "pack", "flight", "collect", "settle",
                          "h2d", "dev")},
            "stamp_host": sum(1 for r in recs if r["stamp"] == "host"),
            "shed": sum(r["shed"] for r in recs),
            "result_timeouts": timeouts, "breaker_faults": faults,
            "table_builds": tables["misses"] - st.tables0["misses"],
        },
    }


def verify(ctx, st, obs):
    """`votes_serial.verify` (every verdict and every set's final state
    against the plain reference), and besides: each set reported its
    majority with the very vote the reference names, a late verdict
    counts as failed, and `burst_quorum_ms` gets its readings."""
    from reference import quorum as qref

    out = serial.verify(ctx, st, obs)
    pubs = [v.pub_key.data for v in st.vs.validators]
    powers = [v.voting_power for v in st.vs.validators]
    verdicts = obs["verdicts"]
    by_set = {}
    for k, row in enumerate(st.plan):
        if verdicts[k] is not None:
            by_set.setdefault(row[0], []).append(k)
    in_order, waits = True, []
    for s, ks in sorted(by_set.items()):
        fed = [(st.plan[k][1], st.votes[k].block_id.key(),
                st.votes[k].sign_bytes(st.chain), st.votes[k].signature)
               for k in ks]
        at = qref.first_quorum_index(pubs, powers, fed)
        seen = obs["quorum"].get(s)
        if (at is None) != (seen is None) \
                or (at is not None and ks[at] != seen[1]):
            in_order = False
        elif at is not None:
            waits.append((seen[0] - obs["t0"] - st.plan[ks[at]][3]) * 1e3)
    obs["samples"]["burst_quorum_ms"] = waits
    del obs["quorum"]  # moments on the host clock: not for the line
    out["failed"] = min(out["attempted"],
                        out["failed"] + obs["counters"]["late"])
    out["correct"] = out["correct"] and in_order
    return out
