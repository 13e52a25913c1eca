"""Driver `commit_closed_mixed`: `commit_closed` over a validator set
that holds ed25519 AND sr25519 keys.

The operation, the loop, the warm-up, the window and the check are
`commit_closed`'s own functions (one caller, closed loop,
`validation.verify_commit_light(chain, vals, block_id, height, commit,
Config().crypto.batch_fn())` over a ring of distinct seeded commits), so
the observation carries the same keys and the ten `commit_*` readers
read it unchanged. What differs is the data: the set's key types are
the configuration's `key_types`, dealt from the seed; commits are signed
and refereed by `harness/fixtures_mixed.py` (the plain reference is
`reference/schnorrkel.verify_commit_light`); and each tampered commit
carries its flipped signature on a row of `tampered_key_type` before
the quorum point, so the refusal has to name the COMMIT's index across
the seam between the two key types' groups.

The window also notes the live sr25519 signatures of every call
(`work_sr25519`, as `work` holds all of them), for the readers of the
sr25519 kernel's device time.

Traffic parameters (the mix's file): ring, tampered, tampered_key_type.
"""
from __future__ import annotations

import random

from drivers import commit_closed
from harness import fixtures, fixtures_mixed

abandon, close = commit_closed.abandon, commit_closed.close
warm, verify = commit_closed.warm, commit_closed.verify


def _examined(n_vals: int) -> int:
    """Rows a light check of equal powers, all signing, examines: the
    prefix whose power first passes 2/3 of the total."""
    return n_vals * 2 // 3 + 1


def _ring_plan(ctx, types):
    """[(height, tampered index or None)] from the seed; a tampered
    index is a row of `tampered_key_type` strictly inside the examined
    prefix, as `commit_closed` draws it."""
    rnd = random.Random(f"commit-ring/{ctx.seed}")
    ring, bad = ctx.traffic["ring"], ctx.traffic["tampered"]
    bad_slots = set(rnd.sample(range(ring), bad))
    eligible = [i for i in range(_examined(len(types)) - 1)
                if types[i] == ctx.traffic["tampered_key_type"]]
    return [(commit_closed.HEIGHT0 + 17 * k,
             rnd.choice(eligible) if k in bad_slots else None)
            for k in range(ring)]


def prepare(ctx):
    from cometbft_tpu import native

    power = ctx.config["voting_power"]
    chain = ctx.config["chain_id"]
    counts = ctx.config["key_types"]
    if sum(counts.values()) != ctx.config["validators"]:
        raise ValueError("key_types do not add up to validators")
    native.available()  # built once, here, before eight workers want it
    vs, rows, pubs = fixtures_mixed.valset(
        fixtures_mixed.key_rows(ctx.seed, counts), power)
    types = [kt for kt, _ in rows]
    plan = _ring_plan(ctx, types)
    bids = [fixtures.block_id(b"commit/%d/%d" % (ctx.seed, h))
            for h, _ in plan]
    signed = fixtures_mixed.Signed(ctx.cell, ctx)
    if not signed.cached:
        signed.submit([{
            "chain": chain, "rows": rows, "pubs": pubs, "power": power,
            "blocks": [(h, fixtures.bid_tuple(bid))],
            "tamper": {h: [bad]} if bad is not None else {},
            "refer": {h}} for (h, bad), bid in zip(plan, bids)],
            workers=min(len(plan), 8))
    return {"vs": vs, "plan": plan, "bids": bids, "chain": chain,
            "signed": signed}


def window(ctx, st):
    obs = commit_closed.window(ctx, st)
    # every commit of the ring is signed by all, so every call hands
    # the batch_fn the same prefix of the set: its sr25519 rows
    live = obs["counters"]["signatures_per_call"]
    n_sr = sum(v.pub_key.key_type == fixtures_mixed.SR25519
               for v in st.vs.validators[:live])
    obs["work_sr25519"] = [(t, n_sr) for t, _ in obs["work"]]
    obs["counters"]["sr25519_signatures_per_call"] = n_sr
    return obs
