"""VerifyCommit family: differential tests (oracle vs XLA device path) and
reference-semantics cases (blame path, quorum math, trusting mode).

Mirrors types/validation_test.go's case structure.
"""
import numpy as np
import pytest

from cometbft_tpu.crypto.keys import PrivKey
from cometbft_tpu.types import canonical, validation
from cometbft_tpu.types.block_id import BlockID, PartSetHeader
from cometbft_tpu.types.commit import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    Commit,
    CommitSig,
)
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.validator import Validator, ValidatorSet
from cometbft_tpu.types.vote import Vote

CHAIN_ID = "test_chain"
HEIGHT = 10


def make_commit(n_vals=6, power=100, invalid=(), absent=(), nil=(),
                height=HEIGHT, round_=2):
    """Build a valset + commit with n_vals validators, each signing a real
    precommit; indices in `invalid` get corrupted sigs, `absent` no sig,
    `nil` a nil-vote."""
    privs = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(n_vals)]
    vals = [Validator(p.pub_key(), power) for p in privs]
    vs = ValidatorSet(vals)
    # sort privs to match the sorted set
    addr_to_priv = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\xab" * 32, PartSetHeader(2, b"\xcd" * 32))
    sigs = []
    for idx, v in enumerate(vs.validators):
        p = addr_to_priv[v.address]
        if idx in absent:
            sigs.append(CommitSig.absent())
            continue
        flag = BLOCK_ID_FLAG_NIL if idx in nil else BLOCK_ID_FLAG_COMMIT
        ts = Timestamp(1700000000 + idx, idx)
        vote_bid = BlockID() if idx in nil else bid
        sb = canonical.canonical_vote_bytes(
            CHAIN_ID, canonical.PRECOMMIT_TYPE, height, round_, vote_bid, ts
        )
        sig = p.sign(sb)
        if idx in invalid:
            sig = sig[:10] + bytes([sig[10] ^ 1]) + sig[11:]
        sigs.append(CommitSig(flag, v.address, ts, sig))
    return vs, Commit(height, round_, bid, sigs), bid


BATCH_FNS = [
    ("oracle", validation.oracle_batch_fn),
    ("device-xla", lambda: validation.device_batch_fn(use_pallas=False)),
]


@pytest.mark.parametrize("name,mk_fn", BATCH_FNS)
def test_verify_commit_all_good(name, mk_fn):
    vs, commit, bid = make_commit()
    validation.verify_commit(CHAIN_ID, vs, bid, HEIGHT, commit, mk_fn())
    validation.verify_commit_light(
        CHAIN_ID, vs, bid, HEIGHT, commit, mk_fn()
    )
    validation.verify_commit_light_trusting(
        CHAIN_ID, vs, commit, (1, 3), mk_fn()
    )


@pytest.mark.parametrize("name,mk_fn", BATCH_FNS)
def test_verify_commit_blame_path(name, mk_fn):
    vs, commit, bid = make_commit(invalid=(3,))
    with pytest.raises(validation.InvalidSignatureError) as ei:
        validation.verify_commit(CHAIN_ID, vs, bid, HEIGHT, commit, mk_fn())
    assert ei.value.idx == 3


@pytest.mark.parametrize("name,mk_fn", BATCH_FNS)
def test_verify_commit_insufficient_power(name, mk_fn):
    # 3 of 6 absent -> exactly 50% < 2/3
    vs, commit, bid = make_commit(absent=(0, 1, 2))
    with pytest.raises(validation.NotEnoughPowerError):
        validation.verify_commit(CHAIN_ID, vs, bid, HEIGHT, commit, mk_fn())


@pytest.mark.parametrize("name,mk_fn", BATCH_FNS)
def test_nil_votes_not_counted_but_verified(name, mk_fn):
    # VerifyCommit (full): nil votes ARE verified but NOT counted.
    # 5 commit + 1 nil of 6 -> 5/6 > 2/3 passes (4/6 would be exactly
    # 2/3, which the strict > rejects)
    vs, commit, bid = make_commit(nil=(5,))
    validation.verify_commit(CHAIN_ID, vs, bid, HEIGHT, commit, mk_fn())
    # but an invalid nil-vote signature fails full verification
    vs2, commit2, bid2 = make_commit(nil=(5,), invalid=(5,))
    with pytest.raises(validation.InvalidSignatureError):
        validation.verify_commit(
            CHAIN_ID, vs2, bid2, HEIGHT, commit2, mk_fn()
        )
    # ...while light verification ignores non-commit sigs entirely
    validation.verify_commit_light(
        CHAIN_ID, vs2, bid2, HEIGHT, commit2, mk_fn()
    )


def test_verify_commit_wrong_height_block_id():
    vs, commit, bid = make_commit()
    with pytest.raises(validation.VerificationError):
        validation.verify_commit(CHAIN_ID, vs, bid, HEIGHT + 1, commit)
    other = BlockID(b"\x11" * 32, PartSetHeader(2, b"\xcd" * 32))
    with pytest.raises(validation.VerificationError):
        validation.verify_commit(CHAIN_ID, vs, other, HEIGHT, commit)


def test_trusting_mode_by_address_subset():
    """Old set = subset of signers: lookups by address, 1/3 threshold."""
    vs, commit, bid = make_commit(n_vals=9)
    # old set = 4 of the 9 validators -> all 4 signed -> 4/4 > 1/3
    old = ValidatorSet(vs.validators[:4])
    validation.verify_commit_light_trusting(
        CHAIN_ID, old, commit, (1, 3), validation.oracle_batch_fn()
    )
    # trust 1/1 (100%): 4/4 power still passes only if > total*1//1...
    with pytest.raises(validation.NotEnoughPowerError):
        validation.verify_commit_light_trusting(
            CHAIN_ID, old, commit, (1, 1), validation.oracle_batch_fn()
        )


@pytest.mark.parametrize("name,mk_fn", BATCH_FNS)
def test_light_early_break_skips_trailing_invalid(name, mk_fn):
    """VerifyCommitLight stops collecting at 2/3 (validation.go:223-225):
    an invalid signature AFTER quorum is never examined — but full
    VerifyCommit (count_all) must reject it."""
    vs, commit, bid = make_commit(n_vals=6, invalid=(5,))
    validation.verify_commit_light(
        CHAIN_ID, vs, bid, HEIGHT, commit, mk_fn()
    )  # quorum from sigs 0-4 (5/6); sig 5 never touched
    with pytest.raises(validation.InvalidSignatureError):
        validation.verify_commit(CHAIN_ID, vs, bid, HEIGHT, commit, mk_fn())
    # and an invalid signature BEFORE quorum still fails light verify
    vs2, commit2, bid2 = make_commit(n_vals=6, invalid=(0,))
    with pytest.raises(validation.InvalidSignatureError):
        validation.verify_commit_light(
            CHAIN_ID, vs2, bid2, HEIGHT, commit2, mk_fn()
        )


def test_power_precheck_before_verification():
    """Underpowered commits fail on power BEFORE signatures are verified
    (validation.go:230-233) — even when signatures are also invalid."""
    vs, commit, bid = make_commit(absent=(0, 1, 2), invalid=(3,))
    calls = []

    def spy_fn(pubs, msgs, sigs):
        calls.append(len(pubs))
        return np.ones(len(pubs), bool)

    with pytest.raises(validation.NotEnoughPowerError):
        validation.verify_commit(CHAIN_ID, vs, bid, HEIGHT, commit, spy_fn)
    assert calls == []  # batch_fn never invoked


@pytest.mark.parametrize("case,kw,raises,closed", [
    ("accepted", {}, None,
     ["commit.collect", "commit.sign_bytes", "commit.batch_fn",
      "commit.verify"]),
    ("tampered", {"invalid": (1,)}, validation.InvalidSignatureError,
     ["commit.collect", "commit.sign_bytes", "commit.batch_fn",
      "commit.verify"]),
    ("short_of_power", {"absent": (0, 1, 2)},
     validation.NotEnoughPowerError,
     ["commit.collect", "commit.verify"]),
])
def test_commit_stages_close_on_every_exit(case, kw, raises, closed):
    """verify_commit_light's always-on stages (libs/tracing.stage):
    the children close in order inside commit.verify, whether the call
    returns, blames a signature or stops short of the power."""
    from cometbft_tpu.libs import tracing

    vs, commit, bid = make_commit(**kw)
    tracing.set_clock(None)  # an empty stage ring
    fn = validation.oracle_batch_fn()
    if raises is None:
        validation.verify_commit_light(CHAIN_ID, vs, bid, HEIGHT,
                                       commit, fn)
    else:
        with pytest.raises(raises):
            validation.verify_commit_light(CHAIN_ID, vs, bid, HEIGHT,
                                           commit, fn)
    recs = tracing.stages()
    assert [r[0] for r in recs] == closed
    _, v0, vdur, _ = recs[-1]
    ends = [v0]
    for _, c0, cdur, _ in recs[:-1]:  # in order, inside, no overlap
        assert ends[-1] <= c0 and c0 + cdur <= v0 + vdur
        ends.append(c0 + cdur)


def test_single_path_matches_batch():
    """No batch_fn -> single-verify loop; same outcomes."""
    vs, commit, bid = make_commit()
    validation.verify_commit(CHAIN_ID, vs, bid, HEIGHT, commit, None)
    vs2, commit2, _ = make_commit(invalid=(2,))
    with pytest.raises(validation.InvalidSignatureError) as ei:
        validation.verify_commit(CHAIN_ID, vs2, commit2.block_id, HEIGHT,
                                 commit2, None)
    assert ei.value.idx == 2


def test_vote_verify_roundtrip():
    priv = PrivKey.generate(b"\x07" * 32)
    bid = BlockID(b"\xab" * 32, PartSetHeader(1, b"\xcd" * 32))
    v = Vote(
        vote_type=canonical.PRECOMMIT_TYPE,
        height=3, round=0, block_id=bid,
        timestamp=Timestamp(1700000001, 42),
        validator_address=priv.pub_key().address(),
        validator_index=0,
    )
    v.signature = priv.sign(v.sign_bytes(CHAIN_ID))
    v.verify(CHAIN_ID, priv.pub_key())
    v.validate_basic()
    other = PrivKey.generate(b"\x08" * 32)
    with pytest.raises(Exception):
        v.verify(CHAIN_ID, other.pub_key())


# --------------------------------------------------------------------------
# device_batch_fn over a batch larger than one chunk (COMMIT_CHUNK_ROWS)
# --------------------------------------------------------------------------

T = 64  # the XLA kernel's smallest bucket: the shape every test above
#         compiles, so three chunks cost three small batches


@pytest.fixture
def chunked(monkeypatch):
    monkeypatch.setattr(validation, "COMMIT_CHUNK_ROWS", T)


@pytest.fixture
def kernel_calls(monkeypatch):
    """Stands in for the XLA kernel: a row passes its precheck. Returns
    the row count of every batch the kernel was handed."""
    import jax.numpy as jnp

    from cometbft_tpu.ops import ed25519_kernel as ek

    seen = []

    def fake(ay, asign, ry, rsign, sdig, hdig, precheck):
        seen.append(len(precheck))
        return jnp.asarray(precheck)

    monkeypatch.setattr(ek, "verify_kernel", fake)
    return seen


def make_rows(n, bad=()):
    privs = [PrivKey.generate(i.to_bytes(2, "big") * 16) for i in range(n)]
    msgs = [b"chunked-%d" % i for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    for i in bad:
        sigs[i] = sigs[i][:10] + bytes([sigs[i][10] ^ 1]) + sigs[i][11:]
    return [p.pub_key() for p in privs], msgs, sigs


def test_chunked_verdicts_match_the_oracle(chunked):
    bad = (0, T - 1, T, 2 * T + 4)  # both ends of a chunk, and the tail's
    pubs, msgs, sigs = make_rows(2 * T + 5, bad)
    got = validation.device_batch_fn(use_pallas=False)(pubs, msgs, sigs)
    want = validation.oracle_batch_fn()(pubs, msgs, sigs)
    np.testing.assert_array_equal(got, want)
    assert tuple(np.flatnonzero(~want)) == bad


@pytest.mark.parametrize("name,mk_fn", BATCH_FNS)
def test_chunked_blame_is_the_first_bad_collected_index(chunked, name,
                                                        mk_fn):
    """Two chunks hold one bad signature each: the earlier is blamed,
    as the one-by-one fallback of the reference would stop there."""
    vs, commit, bid = make_commit(n_vals=3 * T, invalid=(T + 6, 2 * T + 2))
    with pytest.raises(validation.InvalidSignatureError) as ei:
        validation.verify_commit_light(CHAIN_ID, vs, bid, HEIGHT, commit,
                                       mk_fn())
    assert ei.value.idx == T + 6


@pytest.mark.parametrize("n,chunks", [
    (T - 1, 1), (T, 1),          # the ladder's one padded batch
    (T + 1, 2), (2 * T + 5, 3),  # every chunk T rows, the tail too
    (5 * T, 5),   # the ladder would say 1,024
    (1024 * T + 1, 1025),  # past the ladder's last rung (65,536)
], ids=["below", "at", "one-over", "tail", "multiple", "past-the-ladder"])
def test_every_chunk_has_one_shape_and_the_result_n_rows(
        chunked, kernel_calls, n, chunks):
    from cometbft_tpu.libs import tracing

    pubs, msgs, sigs = make_rows(1)
    tracing.set_clock(None)  # an empty stage ring
    got = validation.device_batch_fn(use_pallas=False)(
        pubs * n, msgs * n, sigs * n)
    assert got.shape == (n,) and got.all()
    assert kernel_calls == [T] * chunks
    packs = [r[4] for r in tracing.stage_records()
             if r[0] == "ed25519.pack"]
    assert [p["padded"] for p in packs] == [T] * chunks
    assert sum(p["rows"] for p in packs) == n


def test_chunk_stages_pack_and_dispatch_in_turn_then_one_fetch(
        chunked, kernel_calls):
    from cometbft_tpu.libs import tracing

    pubs, msgs, sigs = make_rows(1)
    n = 2 * T + 5
    tracing.set_clock(None)  # an empty stage ring
    validation.device_batch_fn(use_pallas=False)(
        pubs * n, msgs * n, sigs * n)
    recs = tracing.stage_records()
    assert [r[0] for r in recs] == (
        ["ed25519.pack", "ed25519.dispatch"] * 3 + ["ed25519.fetch"])
    for k, (p, d) in enumerate(zip(recs[0:6:2], recs[1:6:2])):
        for args in (p[4], d[4]):
            assert (args["chunk"], args["chunks"]) == (k, 3)
            assert 0 <= args["flying"] <= k
        assert p[4]["rows"] == d[4]["rows"] == (T if k < 2 else 5)
        assert p[1] + p[2] <= d[1]  # packed, then dispatched
    assert recs[0][4]["flying"] == 0  # nothing flies before the first
    assert recs[-1][4] == {} and recs[5][1] + recs[5][2] <= recs[-1][1]
