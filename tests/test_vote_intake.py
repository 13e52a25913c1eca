"""The vote intake (consensus/vote_intake.py): a burst's signature
checks are in flight together, and everything else is as if the votes
had come one at a time.

Seeded bursts (bad signatures, a duplicate, a conflicting vote, nil
votes, votes for other rounds, a burst that crosses the +2/3 point and
one that crosses a height) go through the intake on a host plane and
are compared, vote by vote, with

  (a) `PlainHeight` below: the vote sets of one height written out
      from upstream's vote_set.go and height_vote_set.go over
      `cryptography`'s ed25519, sharing no code with
      types/vote_set.py, and
  (b) the same votes through today's `add_vote`, one at a time:

verdicts, exceptions, final sets, the fused groups' tallies, and on a
`ConsensusState` the WAL's records, the evidence, `on_vote_added` and
the step transitions. One test runs the receive routine's thread and
finds the burst's rows in one flush of the plane's ledger.
"""
import json
import queue
import random

import pytest
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey,
)

from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.consensus import vote_intake
from cometbft_tpu.consensus import wal as walmod
from cometbft_tpu.consensus.height_vote_set import HeightVoteSet
from cometbft_tpu.consensus.state import (
    ConsensusState,
    ProposalMsg,
    VoteMsg,
)
from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto.keys import PrivKey
from cometbft_tpu.libs import tracing
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.state.state import State, StateStore
from cometbft_tpu.store.blockstore import BlockStore
from cometbft_tpu.types import canonical, serde
from cometbft_tpu.types.block_id import BlockID, PartSetHeader
from cometbft_tpu.types.commit import Commit
from cometbft_tpu.types.proposal import Proposal
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.validator import Validator, ValidatorSet
from cometbft_tpu.types.vote import Vote
from cometbft_tpu.types.vote_set import ConflictingVoteError, VoteSetError
from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

CHAIN = "intake-chain"
N_VALS, POWER = 7, 10  # 70 in all: the fifth vote for a block decides
PREVOTE, PRECOMMIT = canonical.PREVOTE_TYPE, canonical.PRECOMMIT_TYPE
BLOCK_A = BlockID(b"\xaa" * 32, PartSetHeader(1, b"\xa1" * 32))
BLOCK_B = BlockID(b"\xbb" * 32, PartSetHeader(1, b"\xb1" * 32))
NIL = BlockID()

PRIVS = [PrivKey.generate(bytes([i + 90]) * 32) for i in range(N_VALS)]
VALS = ValidatorSet([Validator(p.pub_key(), POWER) for p in PRIVS])
PRIV_AT = {VALS.get_by_address(p.pub_key().address())[0]: p for p in PRIVS}


@pytest.fixture(autouse=True)
def plane():
    """A host plane as the process's global one: it coalesces what is
    submitted together and verifies on the host."""
    set_global_plane(None)
    cbatch.device_breaker().reset()
    p = VerifyPlane(window_ms=5.0, max_batch=64, max_queue=1024)
    p.start()
    set_global_plane(p)
    yield p
    set_global_plane(None)
    p.stop()


def mk(idx, vtype=PREVOTE, bid=BLOCK_A, round_=0, height=1, bad=False,
       nanos=0):
    v = Vote(vote_type=vtype, height=height, round=round_, block_id=bid,
             timestamp=Timestamp(1_700_000_100 + idx, nanos),
             validator_address=VALS.validators[idx].address,
             validator_index=idx)
    sig = PRIV_AT[idx].sign(v.sign_bytes(CHAIN))
    v.signature = sig[:7] + bytes([sig[7] ^ 4]) + sig[8:] if bad else sig
    return v


# --------------------------------------------------------------------------
# (a) the plain reference
# --------------------------------------------------------------------------


class PlainHeight:
    """Every (round, type) vote set of one height. A vote is refused
    for a round beyond the next; a second vote of one validator for
    the same block is a duplicate whatever it is signed with; then the
    signature decides; then a vote against the validator's first one
    is a conflict and changes nothing; a block is decided when MORE
    than 2/3 of all power voted for it, once."""

    def __init__(self, pubs, powers, round_=0):
        self.pubs, self.powers, self.round = pubs, powers, round_
        self.needed = sum(powers) * 2 // 3
        self.sets = {}

    def add(self, idx, round_, vtype, block_key, msg, sig) -> str:
        if round_ > self.round + 1:
            return "refused"
        s = self.sets.setdefault((round_, vtype), {
            "first": {}, "sum": 0, "by_block": {}, "maj": None})
        if s["first"].get(idx) == block_key:
            return "refused"
        try:
            Ed25519PublicKey.from_public_bytes(self.pubs[idx]).verify(
                sig, msg)
        except InvalidSignature:
            return "invalid"
        if idx in s["first"]:
            return "conflict"
        s["first"][idx] = block_key
        s["sum"] += self.powers[idx]
        s["by_block"][block_key] = (s["by_block"].get(block_key, 0)
                                    + self.powers[idx])
        if s["maj"] is None and s["by_block"][block_key] > self.needed:
            s["maj"] = block_key
        return "added"

    def state(self):
        return {k: (s["sum"], s["maj"], dict(s["first"]),
                    dict(s["by_block"]))
                for k, s in self.sets.items() if s["first"]}


def plain_run(votes):
    ref = PlainHeight([v.pub_key.data for v in VALS.validators],
                      [v.voting_power for v in VALS.validators])
    got = [ref.add(v.validator_index, v.round, v.vote_type,
                   v.block_id.key(), v.sign_bytes(CHAIN), v.signature)
           for v in votes]
    return got, ref.state()


# --------------------------------------------------------------------------
# the program, both ways
# --------------------------------------------------------------------------


def outcome(hvs, vote) -> str:
    try:
        return "added" if hvs.add_vote(vote) else "refused"
    except ConflictingVoteError:
        return "conflict"
    except VoteSetError:
        return "invalid"


def snapshot(hvs):
    """What a height's vote sets hold, in the plain reference's terms,
    and beside it the fused groups' tallies by block."""
    sets, tallies = {}, {}
    for round_, by_type in hvs._rounds.items():
        for vtype, vs in by_type.items():
            if not vs.sum and not vs._plane_groups:
                continue
            maj = vs.two_thirds_majority()
            sets[(round_, vtype)] = (
                vs.sum, None if maj is None else maj.key(),
                {i: v.block_id.key() for i, v in enumerate(vs.votes)
                 if v is not None},
                {k: bv.sum for k, bv in vs.votes_by_block.items()
                 if bv.sum})
            tallies[(round_, vtype)] = {
                k: g.tally for k, g in vs._plane_groups.items() if g.tally}
            assert not vs._staged  # nothing staged outlives its call
    return sets, tallies


def through_intake(bursts):
    hvs = HeightVoteSet(CHAIN, 1, VALS)
    got = []
    for burst in bursts:
        got += vote_intake.intake(burst, lambda v: v, lambda v: hvs,
                                  lambda v: outcome(hvs, v))
    return got, snapshot(hvs)


def one_at_a_time(bursts):
    hvs = HeightVoteSet(CHAIN, 1, VALS)
    return [outcome(hvs, v) for b in bursts for v in b], snapshot(hvs)


# --------------------------------------------------------------------------
# the bursts
# --------------------------------------------------------------------------


def _shuffled(rnd, votes):
    votes = list(votes)
    rnd.shuffle(votes)
    return votes


def bad_signatures(rnd):
    bad = set(rnd.sample(range(N_VALS), 2))
    return [_shuffled(rnd, [mk(i, bad=i in bad) for i in range(N_VALS)])]


def duplicate(rnd):
    v2 = mk(2)
    burst = _shuffled(rnd, [mk(i) for i in range(5) if i != 2])
    # the same vote as a second object, the same object again, and a
    # duplicate that is signed wrongly (a duplicate all the same)
    return [[v2] + burst[:2] + [mk(2), v2] + burst[2:] + [mk(2, bad=True)]]


def conflicting(rnd):
    return [[mk(0), mk(3), mk(3, bid=BLOCK_B), mk(1, bid=BLOCK_B), mk(2),
             mk(3, bid=BLOCK_B, bad=True), mk(4), mk(5), mk(3, bid=NIL)]]


def nil_votes(rnd):
    return [_shuffled(rnd, [mk(i, bid=NIL if i < 3 else BLOCK_A)
                            for i in range(N_VALS)])]


def other_rounds(rnd):
    return [_shuffled(rnd, [mk(0), mk(1), mk(2, round_=1), mk(3, round_=2),
                            mk(4, round_=1, vtype=PRECOMMIT), mk(5),
                            mk(6, round_=3, bad=True)])]


def crosses_quorum(rnd):
    # prevotes and precommits of one height in one burst: two groups
    # in one flush, each crossing its +2/3 point inside it
    return [_shuffled(rnd, [mk(i, vtype=t) for i in range(N_VALS)
                            for t in (PREVOTE, PRECOMMIT)])]


def two_bursts(rnd):
    return [_shuffled(rnd, [mk(i, bad=i == 1) for i in range(N_VALS)]),
            _shuffled(rnd, [mk(i, vtype=PRECOMMIT,
                               bid=BLOCK_B if i == 6 else BLOCK_A)
                            for i in range(N_VALS)])]


def mixed(rnd):
    """Anything at all: some 20 votes over two bursts."""
    votes = []
    for _ in range(20):
        votes.append(mk(
            rnd.randrange(N_VALS), vtype=rnd.choice((PREVOTE, PRECOMMIT)),
            bid=rnd.choice((BLOCK_A, BLOCK_A, BLOCK_A, BLOCK_B, NIL)),
            round_=rnd.choice((0, 0, 0, 0, 1, 2)),
            bad=rnd.random() < 0.15, nanos=rnd.randrange(3)))
    return [votes[:12], votes[12:]]


SCENARIOS = [(fn, seed) for fn in (bad_signatures, duplicate, conflicting,
                                   nil_votes, other_rounds, crosses_quorum,
                                   two_bursts) for seed in (1,)]
SCENARIOS += [(mixed, seed) for seed in (2, 3, 5, 8, 13)]
SCENARIO_IDS = [f"{fn.__name__}-{seed}" for fn, seed in SCENARIOS]


@pytest.mark.parametrize("make,seed", SCENARIOS, ids=SCENARIO_IDS)
def test_burst_is_what_the_plain_sets_and_one_at_a_time_give(
        plane, make, seed):
    bursts = make(random.Random(seed))
    flat = [v for b in bursts for v in b]
    want, want_sets = plain_run(flat)
    serial, (serial_sets, serial_tallies) = one_at_a_time(bursts)
    rows0 = plane.rows_verified
    got, (sets, tallies) = through_intake(bursts)
    assert got == want == serial
    assert sets == want_sets == serial_sets
    # the fused tally of every block stands at the power admitted
    assert tallies == serial_tallies == {
        k: s[3] for k, s in want_sets.items()}
    # a burst's rows met: fewer flushes than rows
    log = list(plane.dispatch_log)
    assert plane.rows_verified > rows0
    assert max(d["rows"] for d in log) > 1


def test_one_vote_is_todays_add_vote(plane):
    """One vote handed over: nothing is staged, one row, one flush."""
    hvs = HeightVoteSet(CHAIN, 1, VALS)
    v = mk(0)
    staged = []
    real = hvs.stage_vote
    hvs.stage_vote = lambda vote: staged.append(vote) or real(vote)
    assert vote_intake.intake([v], lambda v: v, lambda _: hvs,
                              lambda vote: hvs.add_vote(vote)) == [True]
    assert staged == []
    assert [d["rows"] for d in plane.dispatch_log] == [1]
    names = [r[0] for r in tracing.stage_records()
             if r[0].startswith("votes.")]
    assert names[-3:] == ["votes.stage", "votes.settle", "votes.intake"]
    assert tracing.stage_records()[-1][4] == {"n": 1}


def test_one_validators_two_votes_never_share_a_flush(plane):
    """What a node that fell half a period behind finds waiting: a
    set's prevotes, then its precommits. The run is cut where a
    validator's second vote comes, so no flush holds two rows of one
    validator (on the chip a two-stride flush, a program the start-up
    compile does not cover), and the outcome is the serial one."""
    burst = ([mk(i, bad=i == 2) for i in range(N_VALS)]
             + [mk(i, vtype=PRECOMMIT) for i in range(N_VALS)])
    want, want_sets = plain_run(burst)
    staged = []
    hvs = HeightVoteSet(CHAIN, 1, VALS)
    real = hvs.stage_vote
    hvs.stage_vote = lambda v: staged.append(v.validator_index) or real(v)
    flushed = []
    got = vote_intake.intake(
        burst, lambda v: v, lambda v: hvs,
        lambda v: (outcome(hvs, v),
                   flushed.append(len(plane.dispatch_log)))[0])
    assert got == want and snapshot(hvs)[0] == want_sets
    assert staged == list(range(N_VALS)) * 2
    # the first run was settled before the second was staged: one flush
    # a run, N_VALS rows each, the second after the 7th vote was handled
    assert [d["rows"] for d in plane.dispatch_log] == [N_VALS, N_VALS]
    assert flushed == [1] * N_VALS + [2] * N_VALS
    settles = [r for r in tracing.stage_records()
               if r[0].startswith("votes.")][-5:]
    assert [r[0] for r in settles] == ["votes.stage", "votes.settle"] * 2 \
        + ["votes.intake"]
    assert settles[-1][4] == {"n": 2 * N_VALS}


def test_no_plane_stages_nothing():
    set_global_plane(None)
    hvs = HeightVoteSet(CHAIN, 1, VALS)
    burst = [mk(i, bad=i == 2) for i in range(4)]
    assert all(hvs.stage_vote(v) is None for v in burst)
    got = vote_intake.intake(burst, lambda v: v, lambda _: hvs,
                             lambda v: outcome(hvs, v))
    assert got == ["added", "added", "invalid", "added"]


@pytest.mark.parametrize("how", ["never-added", "handler-raises",
                                 "no-target"])
def test_a_staged_check_nobody_takes_up_is_unwound(plane, how):
    """The fused tally holds only what was admitted, also where a vote
    was staged and its add_vote never came (the caller dropped it, the
    handler raised before its turn), and a vote with no target set is
    not staged at all."""
    hvs = HeightVoteSet(CHAIN, 1, VALS)
    burst = [mk(i) for i in range(4)]

    def handle(v):
        if how == "never-added" and v.validator_index == 2:
            return None  # as a prefilter drop would
        if how == "handler-raises" and v.validator_index == 2:
            raise RuntimeError("boom")
        return hvs.add_vote(v)

    target = (lambda v: None) if how == "no-target" else (lambda v: hvs)
    if how == "handler-raises":
        with pytest.raises(RuntimeError):
            vote_intake.intake(burst, lambda v: v, target, handle)
        admitted = 2
    else:
        got = vote_intake.intake(burst, lambda v: v, target, handle)
        admitted = sum(1 for g in got if g)
        assert admitted == (4 if how == "no-target" else 3)
    vs = hvs.prevotes(0)
    assert vs.sum == admitted * POWER and not vs._staged
    assert vs._plane_groups[BLOCK_A.key()].tally == admitted * POWER
    if how == "no-target":
        assert all(d["rows"] == 1 for d in plane.dispatch_log)


# --------------------------------------------------------------------------
# on a ConsensusState: WAL, evidence, hooks, steps
# --------------------------------------------------------------------------


class Pool:
    def __init__(self):
        self.seen = []

    def add_evidence(self, ev):
        self.seen.append((ev.vote_a.signature, ev.vote_b.signature))
        return True


def make_cs(tmp_path, name):
    """A full node (no key of its own, so nothing it writes carries a
    wall-clock reading) of the 7-validator chain, driven by hand."""
    state = State.make_genesis(CHAIN, VALS,
                               genesis_time=Timestamp(1_700_000_000, 0))
    exec_ = BlockExecutor(KVStoreApplication(), StateStore(":memory:"))
    cs = ConsensusState(state, exec_, BlockStore(":memory:"),
                        wal_path=str(tmp_path / name / "wal"),
                        manual_ticker=True)
    cs._started = True
    cs.evidence_pool = Pool()
    cs.seen = {"added": [], "steps": []}
    cs.on_vote_added = lambda v: cs.seen["added"].append(v.signature)
    cs.on_step_change = lambda: cs.seen["steps"].append(
        (cs.height, cs.round, cs.step))
    cs._enter_new_round(1, 0)
    return cs, exec_


def serve(cs, item):
    """One turn of the receive routine."""
    if item[0] == "vote":
        cs._intake_votes(item)
    else:
        cs._handle_logged(item)


def run_queues(cs, serve):
    """The receive routine's loop until both queues are empty, each
    message it takes given to `serve(cs, item)`."""
    while True:
        item = cs._next_msg(timeout=0)
        if item is None:
            return
        serve(cs, item)


def feed(cs, bursts, batched: bool):
    """Each burst is on msg_queue before the loop looks; `batched`
    serves it as the receive routine does, else one message a turn as
    before the intake."""
    for burst in bursts:
        for item in burst:
            cs.msg_queue.put(item if isinstance(item, tuple)
                             else ("vote", VoteMsg(item)))
        run_queues(cs, serve if batched
                   else lambda cs, item: cs._handle_logged(item))


def wal_records(cs):
    cs.wal.flush_and_sync()
    return [(r.kind, r.data) for r in walmod.WAL.iter_records(cs._wal_path)]


def left_behind(cs, heights):
    return {
        "wal": wal_records(cs),
        "evidence": cs.evidence_pool.seen,
        "added": cs.seen["added"],
        "steps": cs.seen["steps"],
        "at": (cs.height, cs.round, cs.step),
        "sets": [snapshot(h) for h in heights],
        "drops": cs.prefilter_drops,
    }


@pytest.mark.parametrize("make,seed", SCENARIOS, ids=SCENARIO_IDS)
def test_state_burst_leaves_what_one_at_a_time_leaves(
        plane, tmp_path, make, seed):
    bursts = make(random.Random(seed))
    flat = [v for b in bursts for v in b]
    a, _ = make_cs(tmp_path, "batched")
    b, _ = make_cs(tmp_path, "serial")
    hvs_a, hvs_b = a.votes, b.votes
    log0 = len(plane.dispatch_log)
    feed(a, bursts, batched=True)
    met = max(d["rows"] for d in list(plane.dispatch_log)[log0:])
    feed(b, bursts, batched=False)
    got, want = left_behind(a, [hvs_a]), left_behind(b, [hvs_b])
    assert got == want
    assert met > 1
    # every vote is in the WAL once, in arrival order, before anything
    # was decided about it (all pass the prefilter here)
    logged = [json.loads(d.decode())["v"]
              for k, d in got["wal"] if k == walmod.MSG_INFO]
    assert logged == [serde.vote_to_j(v) for v in flat]
    # and the sets are the plain reference's
    _, want_sets = plain_run(flat)
    assert got["sets"][0][0] == want_sets
    assert got["sets"][0][1] == {k: s[3] for k, s in want_sets.items()}


def _proposal(cs, exec_):
    proposer = cs.proposer_for_round(0)
    block = exec_.create_proposal_block(
        1, cs.state, Commit(0, 0, BlockID(), []), proposer.address,
        txs=[b"k=v"])
    idx, _ = VALS.get_by_address(proposer.address)
    prop = Proposal(1, 0, -1, block.block_id(), Timestamp(1_700_000_050, 0))
    prop.signature = PRIV_AT[idx].sign(prop.sign_bytes(CHAIN))
    return ("proposal", ProposalMsg(prop, block)), block.block_id()


def test_state_burst_that_crosses_a_height(plane, tmp_path):
    """One run of votes on the queue: height 1's precommits, of which
    the fifth decides the block (the node finalizes and moves to height
    2 in the middle of the run), then the rest of them, now stragglers,
    and prevotes of height 2 that were staged against no set."""
    a, exec_a = make_cs(tmp_path, "batched")
    b, _ = make_cs(tmp_path, "serial")
    proposal, bid = _proposal(a, exec_a)
    bursts = [
        [proposal] + [mk(i, bid=bid) for i in range(N_VALS)],
        [mk(i, vtype=PRECOMMIT, bid=bid) for i in range(N_VALS)]
        + [mk(i, bid=BLOCK_B, height=2, bad=i == 1) for i in range(4)]
        + [mk(0, vtype=PRECOMMIT, bid=bid)],
    ]
    hvs_a, hvs_b = a.votes, b.votes
    feed(a, bursts, batched=True)
    feed(b, bursts, batched=False)
    assert a.height == b.height == 2 and a.votes is not hvs_a
    got = left_behind(a, [hvs_a, a.votes])
    want = left_behind(b, [hvs_b, b.votes])
    assert got == want
    assert got["drops"] == 3  # 2 precommits behind the decision + 1 late
    assert a.votes.prevotes(0).sum == 3 * POWER  # 4 less the bad one
    # the decision took 5 precommits; the 2 behind it were staged, never
    # added, and are not in the old set's fused tally either
    assert got["sets"][0][1][(0, PRECOMMIT)] == {bid.key(): 5 * POWER}
    assert a.block_store.height() == b.block_store.height() == 1
    kinds = [k for k, _ in got["wal"]]
    assert kinds.count(walmod.END_HEIGHT) == 1


def test_a_message_of_another_kind_ends_the_run_and_keeps_its_turn(
        plane, tmp_path):
    cs, exec_ = make_cs(tmp_path, "held")
    proposal, bid = _proposal(cs, exec_)
    order = []
    real = cs._handle
    cs._handle = lambda item, write_wal: (order.append(item[0]),
                                          real(item, write_wal))[1]
    for item in [mk(0, bid=bid), mk(1, bid=bid), proposal, mk(2, bid=bid)]:
        cs.msg_queue.put(item if isinstance(item, tuple)
                         else ("vote", VoteMsg(item)))
    serve(cs, cs._next_msg(timeout=0))
    assert order == ["vote", "vote"] and cs._held_msg is proposal
    assert cs.msg_queue.qsize() == 1
    run_queues(cs, serve)
    assert order == ["vote", "vote", "proposal", "vote"]
    assert cs._held_msg is None and cs.votes.prevotes(0).sum == 3 * POWER


def test_running_state_puts_a_burst_in_one_flush(plane, tmp_path):
    """The receive routine's own thread: a burst that is on msg_queue
    when it looks gives a flush of more than one row."""
    state = State.make_genesis(CHAIN, VALS)
    exec_ = BlockExecutor(KVStoreApplication(), StateStore(":memory:"))
    cs = ConsensusState(state, exec_, BlockStore(":memory:"),
                        wal_path=str(tmp_path / "wal"), manual_ticker=True)
    burst = [mk(i, bad=i == 3) for i in range(N_VALS)]
    for v in burst:
        cs.receive_vote(v)
    seq0 = len(plane.ledger.records())
    cs.start()
    try:
        deadline = 200
        while cs.votes.prevotes(0).sum < 6 * POWER and deadline:
            deadline -= 1
            cs.wait(0.05)
        assert cs.votes.prevotes(0).sum == 6 * POWER
    finally:
        cs.stop()
    rows = [r["rows"] for r in plane.ledger.records()[seq0:]]
    assert rows and max(rows) > 1 and sum(rows) == N_VALS
    logged = [json.loads(r.data.decode())["v"]
              for r in walmod.WAL.iter_records(str(tmp_path / "wal"))
              if r.kind == walmod.MSG_INFO]
    assert logged == [serde.vote_to_j(v) for v in burst]
    with pytest.raises(queue.Empty):
        cs.msg_queue.get_nowait()
