"""Catch-up on a chain whose validator powers move at every height,
through the node's apply path (`CatchupEngine(block_exec=...)`), held to
the benchmark's plain reference (benchmarks/reference/churn.py: each
height's set from the updates alone, the +2 delay, upstream's order and
root, OpenSSL's Ed25519 and a tally under the block's OWN set).

The chain is the `pos175-churn` cell's at a small size: 7 validators,
40 blocks, 1-3 power updates a block and a seat wall every 16 blocks,
made by harness/fixtures_churn.py (the program's BlockExecutor over the
kvstore app, commits signed by the reference's sets). It pins every
height's set root, the +2 delay, the key wall, the tampered and the
stale-quorum refusals by the engine and the reference alike, what
closed each segment (`catchup.scan`'s `closed_by`), and that the
apply path's stage (`exec.apply`) writes one record a block.
"""
import random
import re

import pytest

from cometbft_tpu.abci import types as abci
from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.blocksync.catchup import (
    CatchupEngine, CatchupError, HostCommitVerifier)
from cometbft_tpu.libs import tracing
from cometbft_tpu.state import execution
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.state.state import StateStore
from cometbft_tpu.types import canonical
from cometbft_tpu.types.block_id import BlockID, PartSetHeader
from cometbft_tpu.types.timestamp import Timestamp

# every engine run of this module checks each remembered validator-set
# root against a fresh one (conftest.checked_valset_roots)
pytestmark = pytest.mark.usefixtures("checked_valset_roots")

SEED = 2147042042
CHAIN = "churn-chain"
CFG = {"validators": 7, "history_blocks": 40, "seat_change_every": 16,
       "updates_per_block": [1, 3], "update_pct": [0.01, 1.0],
       "signing_share": 0.98}
TAMPERED_AT = 12
STALE_FROM = 20
LEAD = 3
# a 20 s window at 400 blocks/s: the apply path's records a block
# must leave the stage ring room for the catch-up's own
RING_BLOCKS = 400 * 20
# what may close a segment (`catchup.scan`'s `closed_by`) and the
# phases an `exec.apply` record times
SCAN_CLOSED_BY = ("keys", "powers", "max_run", "buffer")
APPLY_PHASES = ("validate_ms", "finalize_ms", "update_state_ms", "save_ms")


@pytest.fixture(scope="module")
def churn_chain(plain_reference):
    from harness import fixtures_churn
    from reference import churn

    pl = fixtures_churn.plan(SEED, CFG)
    n = CFG["history_blocks"]
    sets = churn.sets(pl["genesis"], pl["updates"], n)
    roots = {h: churn.root(m) for h, m in sets.items()}
    row = fixtures_churn.first_signer(sets[TAMPERED_AT],
                                      pl["absent"][TAMPERED_AT])
    rnd = random.Random(SEED)
    stale = next((h, rows) for h in range(STALE_FROM, n + 1)
                 for rows in [fixtures_churn.stale_signers(
                     sets[h - 1], sets[h], rnd)] if rows is not None)
    built = fixtures_churn.build_chain({
        "chain": CHAIN, "seeds": pl["seeds"], "sets": sets,
        "genesis": pl["genesis"], "updates": pl["updates"],
        "absent": pl["absent"], "blocks": n,
        "keep": {TAMPERED_AT - LEAD, stale[0] - LEAD},
        "bad": {TAMPERED_AT: ("tamper", row), stale[0]: ("signers",
                                                         stale[1])}})
    items = {h: (built["blocks"][h], built["commits"][h])
             for h in range(1, n + 1)}

    def outcome(h, commit):
        blk = fixtures_churn.reference_block(built["blocks"][h], commit)
        return churn.block_outcome(blk, sets[h], roots[h], roots[h + 1])

    outcomes = {h: outcome(h, built["commits"][h]) for h in items}
    bad = {h: (c, outcome(h, c)) for h, c in built["bad"].items()}
    return {"plan": pl, "sets": sets, "roots": roots, "built": built,
            "items": items, "outcomes": outcomes, "bad": bad, "row": row,
            "stale": stale, "churn": churn}


class _Source:
    def __init__(self, items):
        self.items = items

    def tip(self):
        return max(self.items)

    def load(self, h):
        return self.items[h]


def _engine(items, state, tmp_path, tag, applied=None, max_run=8):
    class _Exec(BlockExecutor):
        def apply_block(self, *a, **kw):
            out = super().apply_block(*a, **kw)
            if applied is not None:
                applied.append(out)
            return out

    app = KVStoreApplication()
    app.init_chain(abci.RequestInitChain(chain_id=CHAIN))
    ex = _Exec(app, StateStore(str(tmp_path / f"state-{tag}.db")))
    return CatchupEngine(_Source(items), state.copy(), block_exec=ex,
                         verifier=HostCommitVerifier(),
                         cursor_path=str(tmp_path / f"cursor-{tag}.json"),
                         read_ahead=16, max_run=max_run)


@pytest.fixture(scope="module")
def full_run(churn_chain, tmp_path_factory):
    """The engine from genesis to the tip; the states it applied and
    the stage records it left."""
    tracing.set_clock(None)  # an empty stage ring
    applied = []
    eng = _engine(churn_chain["items"], churn_chain["built"]["genesis"],
                  tmp_path_factory.mktemp("churn"), "full", applied)
    eng.run()
    return {"engine": eng, "applied": applied,
            "records": tracing.stage_records()}


def test_the_reference_accepts_the_chain_the_program_built(churn_chain):
    assert churn_chain["churn"].verdict(churn_chain["outcomes"]) == (
        "ok", CFG["history_blocks"])


@pytest.mark.parametrize("height,round_,bid,ts", [
    (1, 0, (b"\x11" * 32, 1, b"\x22" * 32), (1_700_000_000, 0)),
    (300, 0, (b"\x33" * 32, 4, b"\x44" * 32), (1_700_000_123, 456_789)),
    (2**40 + 3, 7, (b"\x55" * 32, 0, b""), (0, 1)),
    (5, 2**31, (b"", 200, b"\x66" * 32), (-62_135_596_800, 999_999_999)),
])
def test_the_reference_encodes_the_bytes_a_precommit_signs(
        plain_reference, height, round_, bid, ts):
    """The reference's own CanonicalVote encoding (its commits are
    checked over it, not over bytes the program made) is the program's
    byte for byte: zero fields left out, negative seconds, a part set
    header without a total or hash."""
    from reference import churn

    block_id = BlockID(bid[0], PartSetHeader(bid[1], bid[2]))
    want = canonical.canonical_vote_bytes(
        CHAIN, canonical.PRECOMMIT_TYPE, height, round_, block_id,
        Timestamp(*ts))
    assert churn.precommit_bytes(CHAIN, height, round_, bid, ts) == want


def test_every_height_set_hash_is_the_references(churn_chain, full_run):
    roots = churn_chain["roots"]
    assert [s.last_block_height for s in full_run["applied"]] == list(
        range(1, CFG["history_blocks"] + 1))
    for s in full_run["applied"]:
        h = s.last_block_height
        assert s.last_validators.hash() == roots[h]
        assert s.validators.hash() == roots[h + 1]
        assert s.next_validators.hash() == roots[h + 2]
        hdr = churn_chain["items"][h][0].header
        assert (hdr.validators_hash, hdr.next_validators_hash) == (
            roots[h], roots[h + 1])


def test_updates_take_effect_two_heights_later(churn_chain, full_run):
    """A block's `val:` updates are in the state's NEXT set once it is
    applied (the set of h + 2), and not in the set of h + 1."""
    sets, moved = churn_chain["sets"], 0
    for s in full_run["applied"]:
        h = s.last_block_height
        for key, power in churn_chain["plan"]["updates"][h]:
            nxt = dict(sets[h + 2])
            assert nxt.get(key, 0) == power
            i, v = s.next_validators.get_by_address(
                churn_chain["churn"].address(key))
            assert (v.voting_power if v else 0) == power
            if dict(sets[h + 1]).get(key, 0) != power:
                _, cur = s.validators.get_by_address(
                    churn_chain["churn"].address(key))
                assert (cur.voting_power if cur else 0) != power
                moved += 1
    assert moved >= CFG["history_blocks"]


@pytest.mark.parametrize("wall", [16, 32])
def test_a_seat_wall_changes_keys_at_its_second_height(churn_chain, full_run,
                                                       wall):
    """The wall block's leave (power 0) and join take effect at wall +
    2, by the reference and in the engine's state: the keys of wall + 1
    are those before, the newcomer joins at the median power."""
    churn, sets = churn_chain["churn"], churn_chain["sets"]
    (gone, zero), (new, power) = churn_chain["plan"]["updates"][wall][:2]
    keys = {h: {k for k, _ in sets[h]} for h in (wall + 1, wall + 2)}
    assert zero == 0 and gone in keys[wall + 1] and new not in keys[wall + 1]
    assert gone not in keys[wall + 2] and dict(sets[wall + 2])[new] == power
    assert len(keys[wall + 2]) == CFG["validators"]
    applied = {s.last_block_height: s for s in full_run["applied"]}
    cur = applied[wall].validators  # the set of wall + 1
    nxt = applied[wall + 1].validators  # the set of wall + 2
    assert cur.has_address(churn.address(gone))
    assert not cur.has_address(churn.address(new))
    assert not nxt.has_address(churn.address(gone))
    assert nxt.get_by_address(churn.address(new))[1].voting_power == power


@pytest.mark.parametrize("case", ["tampered", "stale"])
def test_the_engine_and_the_reference_refuse_at_the_same_height(
        churn_chain, tmp_path, case):
    """From a state LEAD heights before it, the engine raises at exactly
    the bad height with the cursor behind it, and the reference refuses
    the same commit: a flipped signature on the first examined row, or
    signers with more than 2/3 of the power under the PREVIOUS height's
    set and not under their own."""
    churn = churn_chain["churn"]
    sets = churn_chain["sets"]
    if case == "tampered":
        h = TAMPERED_AT
        want = ("invalid_signature", churn_chain["row"])
        words = f"#{churn_chain['row']})"
    else:
        h, rows = churn_chain["stale"]
        before = dict(sets[h - 1])
        prev = sum(before[sets[h][i][0]] for i in rows)
        assert prev > sum(before.values()) * 2 // 3
        assert {k for k, _ in sets[h]} == set(before)  # powers alone moved
        want = ("not_enough_power", sum(p for _, p in sets[h]) * 2 // 3)
        words = "insufficient voting power"
    commit, got = churn_chain["bad"][h]
    assert got == want
    outcomes = {**churn_chain["outcomes"], h: got}
    assert churn.verdict(outcomes) == ("refused", h) + want
    items = dict(churn_chain["items"])
    items[h] = (items[h][0], commit)
    eng = _engine(items, churn_chain["built"]["states"][h - LEAD], tmp_path,
                  case)
    with pytest.raises(CatchupError,
                       match=rf"failed at height {h}: .*{re.escape(words)}"):
        eng.run()
    assert eng.cursor.verified == eng.cursor.applied == h - 1
    assert eng.state.last_block_height == h - 1


def _scans(records):
    return [r[4] for r in records if r[0] == "catchup.scan"]


@pytest.mark.parametrize("closed_by", ["powers", "keys", "buffer"])
def test_scan_says_what_closed_each_segment(full_run, closed_by):
    """Every set but the genesis one differs from the one before, so
    every segment but the first is one block, closed by the next
    block's set: "keys" where a seat wall takes effect (two heights
    after its block), "powers" elsewhere; the tip's by the end of the
    buffer. The first (blocks 1 and 2 share the genesis set) is closed
    at block 3, whose set is two heights ahead of the genesis state and
    so not known to it: "keys"."""
    assert closed_by in SCAN_CLOSED_BY
    n, every = CFG["history_blocks"], CFG["seat_change_every"]
    walls = {w + 2 for w in range(every, n + 1, every)}
    want = [(2, "keys")] + [
        (1, "buffer" if b == n else "keys" if b + 1 in walls else "powers")
        for b in range(3, n + 1)]
    got = [(s["blocks"], s["closed_by"]) for s in _scans(full_run["records"])]
    assert got == want
    assert any(kind == closed_by for _, kind in got)


def test_scan_closes_at_max_run_under_one_set(churn_chain, tmp_path):
    """Blocks 1 and 2 share the genesis set: with max_run 2 the first
    segment is closed by max_run, the next by the set change after it,
    the last by the end of the buffer."""
    tracing.set_clock(None)
    items = {h: churn_chain["items"][h] for h in (1, 2, 3, 4)}
    _engine(items, churn_chain["built"]["genesis"], tmp_path, "max",
            max_run=2).run()
    scans = _scans(tracing.stage_records())
    assert [(s["blocks"], s["closed_by"]) for s in scans] == [
        (2, "max_run"), (1, "powers"), (1, "buffer")]


def test_apply_path_writes_one_stage_record_a_block(full_run):
    """`exec.apply`: one record a block, carrying the four phases, the
    catch-up's two validations (the engine's and apply_block's own)
    inside validate_ms; within the stage ring's budget at 400 blocks/s
    for 20 s."""
    recs = [r for r in full_run["records"]
            if r[0] == execution.APPLY_STAGE]
    n = CFG["history_blocks"]
    assert [r[4]["height"] for r in recs] == list(range(1, n + 1))
    for r in recs:
        args = r[4]
        assert args["validations"] == 2
        assert all(args[k] >= 0 for k in APPLY_PHASES)
        # validate_ms holds the engine's validation, made before the
        # stage began; the other phases lie inside it
        assert sum(args[k] for k in APPLY_PHASES[1:]) <= r[2] / 1e6
    assert len(recs) / n * RING_BLOCKS < tracing.STAGE_CAPACITY
