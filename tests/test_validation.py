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


# --------------------------------------------------------------------------
# device_batch_fn over a commit of two key types: one queue for both
# --------------------------------------------------------------------------


class LazyVerdicts:
    """What a kernel's dispatch hands back, as the chunk loop sees a
    jax.Array: not ready until fetched. `log` gets one entry a fetch."""

    def __init__(self, valid, log, tag):
        self.valid, self.log, self.tag = np.asarray(valid), log, tag
        self.fetched = False

    def is_ready(self):
        return self.fetched

    def __array__(self, dtype=None, copy=None):
        self.fetched = True
        self.log.append(("fetch", self.tag))
        return self.valid


@pytest.fixture
def sr_standin(monkeypatch):
    """A host stand-in for the sr25519 kernel (the interpreted Pallas
    kernel compiles for 85 s): the true pack runs, and `verify_rows`
    answers for the rows that pack was given with sr25519_ref's
    verdicts, padded to the packed width and not ready until fetched.
    Returns the log: ("dispatch", "sr25519", padded rows) and
    ("fetch", "sr25519"), in order."""
    from cometbft_tpu.crypto import sr25519_ref as sr
    from cometbft_tpu.ops import sr25519_kernel as srk

    log, packed, memo = [], {}, {}
    real_pack = srk.pack_batch_sr

    def verdict(*row):
        if row not in memo:
            memo[row] = sr.verify(*row)
        return memo[row]

    def pack(pubs, msgs, sigs, pad_to=None):
        rows = real_pack(pubs, msgs, sigs, pad_to=pad_to)
        packed[id(rows)] = (rows, [verdict(*r)
                                   for r in zip(pubs, msgs, sigs)])
        return rows

    def verify_rows(rows):
        kept, valid = packed.pop(id(rows))
        assert kept is rows
        width = rows.shape[1]
        log.append(("dispatch", "sr25519", width))
        return LazyVerdicts(valid + [False] * (width - len(valid)), log,
                            "sr25519")

    monkeypatch.setattr(srk, "pack_batch_sr", pack)
    monkeypatch.setattr(srk, "verify_rows", verify_rows)
    return log


@pytest.fixture
def ed_standin(monkeypatch, sr_standin):
    """The XLA ed25519 kernel's stand-in in the same log: a row passes
    its precheck, not ready until fetched."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    def fake(ay, asign, ry, rsign, sdig, hdig, precheck):
        sr_standin.append(("dispatch", "ed25519", len(precheck)))
        return LazyVerdicts(np.asarray(precheck) != 0, sr_standin,
                            "ed25519")

    monkeypatch.setattr(ek, "verify_kernel", fake)
    return sr_standin


def make_mixed_commit(n_vals=48, invalid=(), absent=(), nil=()):
    """make_commit over a set whose validators hold ed25519 and sr25519
    keys in turn (by seed; the set's own order interleaves them). Also
    returns what the benchmark's plain reference is given: pubs, key
    types, powers, sign-bytes and signatures (None = absent) by index."""
    from cometbft_tpu.crypto.keys import Sr25519PrivKey

    privs = [(Sr25519PrivKey if i % 2 else PrivKey).generate(
        bytes([i + 1]) * 32) for i in range(n_vals)]
    vs = ValidatorSet([Validator(p.pub_key(), 100) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\xab" * 32, PartSetHeader(2, b"\xcd" * 32))
    sigs, plain = [], []
    for idx, v in enumerate(vs.validators):
        ts = Timestamp(1700000000 + idx, idx)
        sb = canonical.canonical_vote_bytes(
            CHAIN_ID, canonical.PRECOMMIT_TYPE, HEIGHT, 2,
            None if idx in nil else bid, ts)
        sig = by_addr[v.address].sign(sb)
        if idx in invalid:
            sig = sig[:10] + bytes([sig[10] ^ 1]) + sig[11:]
        if idx in absent:
            sigs.append(CommitSig.absent())
        else:
            sigs.append(CommitSig(
                BLOCK_ID_FLAG_NIL if idx in nil else BLOCK_ID_FLAG_COMMIT,
                v.address, ts, sig))
        plain.append((v.pub_key.data, v.pub_key.key_type, 100, sb,
                      None if idx in absent else sig))
    return vs, Commit(HEIGHT, 2, bid, sigs), bid, plain


def first_of(plain, key_type, after=0):
    return next(i for i, row in enumerate(plain)
                if row[1] == key_type and i >= after)


@pytest.mark.parametrize("case", ["ok", "bad-ed25519", "bad-sr25519",
                                  "both-bad", "not-enough-power"])
def test_mixed_commit_outcome_is_the_plain_references(
        sr_standin, plain_reference, case):
    """A 48-validator commit of both key types through
    verify_commit_light and device_batch_fn, against
    benchmarks/reference/schnorrkel.verify_commit_light: the blame is
    the COMMIT's index, whichever group holds the bad row."""
    _, _, _, plain = make_mixed_commit()
    ed_at, sr_at = first_of(plain, "ed25519"), first_of(plain, "sr25519")
    kw = {"ok": {}, "bad-ed25519": {"invalid": (ed_at,)},
          "bad-sr25519": {"invalid": (sr_at,)},
          # the sr25519 row comes first in the commit, second in the
          # batch's groups: the commit's order decides
          "both-bad": {"invalid": (first_of(plain, "ed25519", sr_at + 1),
                                   sr_at)},
          "not-enough-power": {"absent": tuple(range(0, 48, 3))}}[case]
    vs, commit, bid, plain = make_mixed_commit(**kw)
    want = plain_reference.schnorrkel.verify_commit_light(*zip(*plain))
    try:
        validation.verify_commit_light(
            CHAIN_ID, vs, bid, HEIGHT, commit,
            validation.device_batch_fn(use_pallas=False))
        got = ("ok",)
    except validation.InvalidSignatureError as e:
        got = ("invalid_signature", e.idx)
    except validation.NotEnoughPowerError as e:
        got = ("not_enough_power", e.needed)
    assert got == want
    assert got[0] == {"ok": "ok", "not-enough-power": "not_enough_power"
                      }.get(case, "invalid_signature")
    if case == "both-bad":
        assert got[1] == sr_at
    # one pass a key type (33 rows examined, 16 or 17 of each type),
    # each padded by the ladder as before; no pass where power lacked
    assert [e for e in sr_standin if e[0] == "dispatch"] == (
        [] if case == "not-enough-power" else [("dispatch", "sr25519", 128)])


def test_mixed_chunks_share_one_queue(chunked, ed_standin):
    """Both groups are cut into chunks of the one shape; every chunk of
    both is dispatched before the first verdict is fetched; `flying`
    counts the chunks of either key type."""
    from cometbft_tpu.crypto.keys import Sr25519PrivKey
    from cometbft_tpu.libs import tracing

    ed_pub, ed_msg, ed_sig = (x[0] for x in make_rows(1))
    sk = Sr25519PrivKey.generate(b"\x21" * 32)
    n_ed, n_sr = 2 * T + 5, T + 3
    pubs = [ed_pub] * n_ed + [sk.pub_key()] * n_sr
    msgs = [ed_msg] * n_ed + [b"queue"] * n_sr
    sigs = [ed_sig] * n_ed + [sk.sign(b"queue")] * n_sr
    # interleaved as a commit's rows are; groups keep the batch's order
    order = np.random.RandomState(5).permutation(n_ed + n_sr)
    tracing.set_clock(None)  # an empty stage ring
    got = validation.device_batch_fn(use_pallas=False)(
        [pubs[i] for i in order], [msgs[i] for i in order],
        [sigs[i] for i in order])
    assert got.shape == (n_ed + n_sr,) and got.all()
    first = pubs[order[0]].key_type  # groups go in order of appearance
    second = "sr25519" if first == "ed25519" else "ed25519"
    chunks = {"ed25519": 3, "sr25519": 2}
    assert ed_standin == (
        [("dispatch", first, T)] * chunks[first]
        + [("dispatch", second, T)] * chunks[second]
        + [("fetch", first)] * chunks[first]
        + [("fetch", second)] * chunks[second])
    recs = [r for r in tracing.stage_records()
            if r[0] != "sr25519.challenge"]
    names = [r[0] for r in recs]
    assert names == (
        [first + ".pack", first + ".dispatch"] * chunks[first]
        + [second + ".pack", second + ".dispatch"] * chunks[second]
        + [first + ".fetch", second + ".fetch"])
    packs = [r[4] for r in recs if r[0].endswith(".pack")]
    assert [p["flying"] for p in packs] == [0, 1, 2, 3, 4]
    assert [p["padded"] for p in packs] == [T] * 5
    assert [(p["chunk"], p["chunks"]) for p in packs] == (
        [(k, chunks[first]) for k in range(chunks[first])]
        + [(k, chunks[second]) for k in range(chunks[second])])
    rows = {"ed25519": [T, T, 5], "sr25519": [T, 3]}
    assert [p["rows"] for p in packs] == rows[first] + rows[second]
    # the merlin transcripts: a stage inside each sr25519 pack
    chal = [r for r in tracing.stage_records()
            if r[0] == "sr25519.challenge"]
    sr_packs = [r for r in recs if r[0] == "sr25519.pack"]
    assert len(chal) == len(sr_packs) == 2
    for c, p in zip(chal, sr_packs):
        assert p[1] <= c[1] and c[1] + c[2] <= p[1] + p[2]
        assert c[4]["rows"] == p[4]["rows"]


def test_an_ed25519_commit_records_the_stages_it_always_did(kernel_calls):
    from cometbft_tpu.libs import tracing

    vs, commit, bid = make_commit(n_vals=6)
    tracing.set_clock(None)  # an empty stage ring
    validation.verify_commit_light(
        CHAIN_ID, vs, bid, HEIGHT, commit,
        validation.device_batch_fn(use_pallas=False))
    assert [r[0] for r in tracing.stage_records()] == [
        "commit.collect", "commit.sign_bytes", "ed25519.pack",
        "ed25519.dispatch", "ed25519.fetch", "commit.batch_fn",
        "commit.verify"]


# --------------------------------------------------------------------------
# The served call hands its batch_fn lazy rows (canonical.TemplateRows):
# every chunk's pack builds the sign-bytes where it hashes them
# --------------------------------------------------------------------------

VARIANTS = {
    "full": lambda vs, bid, commit, fn: validation.verify_commit(
        CHAIN_ID, vs, bid, HEIGHT, commit, fn),
    "light": lambda vs, bid, commit, fn: validation.verify_commit_light(
        CHAIN_ID, vs, bid, HEIGHT, commit, fn),
    "trusting": lambda vs, bid, commit, fn:
        validation.verify_commit_light_trusting(CHAIN_ID, vs, commit,
                                                (2, 3), fn),
}
_COMMITS = {}  # the fixtures of the test below, signed once


def outcome_of(call):
    try:
        call()
    except validation.InvalidSignatureError as e:
        return ("invalid_signature", e.idx)
    except validation.NotEnoughPowerError as e:
        return ("not_enough_power", e.needed)
    return ("ok",)


def chunked_commit(keys, case):
    """A commit of 2 T + 8 ed25519 rows (a light check examines 2 T - 37:
    two chunks; a full one all: three), or of some 2 T rows of each key
    type, with nil rows (verify_commit's second template) and, in the
    "bad" case, a flipped signature in a later chunk of each group."""
    if (keys, case) not in _COMMITS:
        nil = (3, T + 2)
        if keys == "ed25519":
            bad = (T, T + 6) if case == "bad" else ()
            vs, commit, bid = make_commit(n_vals=2 * T + 8, invalid=bad,
                                          nil=nil)
        else:
            _, _, _, plain = make_mixed_commit(n_vals=250)
            by_type = {kt: [i for i, row in enumerate(plain)
                            if row[1] == kt and i not in nil]
                       for kt in ("ed25519", "sr25519")}
            # a row of each group's SECOND chunk as a light check cuts it
            bad = ((by_type["sr25519"][T + 3], by_type["ed25519"][T + 1])
                   if case == "bad" else ())
            vs, commit, bid, _ = make_mixed_commit(n_vals=250, invalid=bad,
                                                   nil=nil)
        _COMMITS[keys, case] = (vs, commit, bid, bad)
    return _COMMITS[keys, case]


@pytest.mark.parametrize("case", ["ok", "bad"])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("keys", ["ed25519", "mixed"])
def test_chunked_outcome_is_the_oracles_and_every_pack_is_templated(
        chunked, sr_standin, keys, variant, case):
    """verify_commit, _light and _light_trusting over a batch of several
    chunks a key type give the oracle's outcome and blame index, and
    every chunk's pack records `templated` 1: its sign-bytes were never
    Python objects."""
    from cometbft_tpu import native
    from cometbft_tpu.libs import tracing

    vs, commit, bid, bad = chunked_commit(keys, case)
    want = outcome_of(lambda: VARIANTS[variant](
        vs, bid, commit, validation.oracle_batch_fn()))
    seen = []
    inner = validation.device_batch_fn(use_pallas=False)

    def batch_fn(pubs, msgs, sigs):
        seen.append(msgs)
        return inner(pubs, msgs, sigs)

    tracing.set_clock(None)  # an empty stage ring
    got = outcome_of(lambda: VARIANTS[variant](vs, bid, commit, batch_fn))
    assert got == want
    assert got == (("invalid_signature", min(bad)) if bad else ("ok",))
    assert [type(m) for m in seen] == [canonical.TemplateRows]
    packs = [r for r in tracing.stage_records() if r[0].endswith(".pack")]
    by_name = {}
    for r in packs:
        by_name.setdefault(r[0], []).append(r[4])
    assert sorted(by_name) == (["ed25519.pack"] if keys == "ed25519" else
                               ["ed25519.pack", "sr25519.pack"])
    for name, args in by_name.items():
        assert len(args) >= 2 and args[0]["chunks"] == len(args)
        assert all(a["padded"] == T for a in args)
        # ed25519 builds them in C; sr25519 hashes them from a matrix
        lazy = native.available() or name == "sr25519.pack"
        assert [a["templated"] for a in args] == [int(lazy)] * len(args)
    assert sum(a["rows"] for a in sum(by_name.values(), [])) == len(seen[0])
    # the full check reached the nil rows' template
    assert (1 in seen[0].tmpl) == (variant == "full")


@pytest.mark.parametrize("kind", ["list", "malformed-key",
                                  "malformed-signature"])
def test_a_chunk_that_cannot_be_templated_packs_its_bytes(chunked, kind):
    """A plain list of bytes, or lazy rows beside a key or signature of
    the wrong length, go down pack_batch's list route: `templated` 0 on
    that chunk and the oracle's verdicts."""
    from cometbft_tpu.libs import tracing

    vs, commit, bid, _ = chunked_commit("ed25519", "ok")
    idxs = [i for i in range(T + 9) if commit.signatures[i].for_block()]
    pubs = [vs.validators[i].pub_key for i in idxs]
    sigs = [commit.signatures[i].signature for i in idxs]
    rows = commit.sign_rows(CHAIN_ID, idxs)
    lazy = [1, 1]  # two chunks
    if kind == "list":
        rows, lazy = list(rows), [0, 0]
    elif kind == "malformed-key":
        pubs[2] = type(pubs[2])(pubs[2].data[:31])
        lazy = [0, 1]
    else:
        sigs[T + 1] = sigs[T + 1][:63]
        lazy = [1, 0]
    want = validation.oracle_batch_fn()(pubs, rows, sigs)
    assert want.sum() == len(idxs) - (kind != "list")
    tracing.set_clock(None)  # an empty stage ring
    got = validation.device_batch_fn(use_pallas=False)(pubs, rows, sigs)
    np.testing.assert_array_equal(got, want)
    from cometbft_tpu import native

    assert [r[4]["templated"] for r in tracing.stage_records()
            if r[0] == "ed25519.pack"] == (
        lazy if native.available() else [0, 0])


# --------------------------------------------------------------------------
# device_batch_fn over secp256k1 rows: the ECDSA kernel fed as chunks
# --------------------------------------------------------------------------


def secp_privs(n):
    from cometbft_tpu.crypto.keys import Secp256k1PrivKey

    return [Secp256k1PrivKey.generate(
        (i + 1).to_bytes(2, "big") + b"\x5e" * 30) for i in range(n)]


def make_secp_rows(n, bad=()):
    privs = secp_privs(8)
    msgs = [b"chunked-secp-%d" % i for i in range(n)]
    sigs = [privs[i % 8].sign(m) for i, m in enumerate(msgs)]
    for i in bad:
        sigs[i] = sigs[i][:10] + bytes([sigs[i][10] ^ 1]) + sigs[i][11:]
    return [privs[i % 8].pub_key() for i in range(n)], msgs, sigs


def make_secp_commit(n_vals, invalid=(), every=1):
    """make_commit over secp256k1 validators of unequal power (the set's
    order is by power). With `every` = 2 the validators take ed25519
    and secp256k1 keys in turn."""
    secp = secp_privs(n_vals)
    privs = [secp[i] if i % every == 0
             else PrivKey.generate(i.to_bytes(2, "big") * 16)
             for i in range(n_vals)]
    vs = ValidatorSet([Validator(p.pub_key(), 500 + (37 * i) % 1001)
                       for i, p in enumerate(privs)])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\xab" * 32, PartSetHeader(2, b"\xcd" * 32))
    sigs = []
    for idx, v in enumerate(vs.validators):
        ts = Timestamp(1700000000 + idx % 5, idx)
        sig = by_addr[v.address].sign(canonical.canonical_vote_bytes(
            CHAIN_ID, canonical.PRECOMMIT_TYPE, HEIGHT, 2, bid, ts))
        if idx in invalid:
            sig = sig[:10] + bytes([sig[10] ^ 1]) + sig[11:]
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts, sig))
    return vs, Commit(HEIGHT, 2, bid, sigs), bid


@pytest.fixture
def ecdsa_standin(monkeypatch):
    """A host stand-in for the XLA ECDSA kernel (2.5 s a 64-row pass on
    a CPU): the true pack runs, and `verify_kernel` answers for the rows
    that pack was given with secp256k1_ref's verdicts, padded to the
    packed width and not ready until fetched. Returns the log:
    ("dispatch", "secp256k1", padded rows) and ("fetch", "secp256k1")."""
    from cometbft_tpu.crypto import secp256k1_ref as sc
    from cometbft_tpu.ops import ecdsa_kernel as eck

    log, packed = [], {}
    real_pack = eck.pack_batch

    def pack(pubs, msgs, sigs, pad_to=None):
        pb = real_pack(pubs, msgs, sigs, pad_to=pad_to)
        rows = msgs.tolist() if hasattr(msgs, "tolist") else list(msgs)
        packed[id(pb.qx)] = (pb, [sc.verify(p, bytes(m), s)
                                  for p, m, s in zip(pubs, rows, sigs)])
        return pb

    def verify_kernel(qx, qparity, u1dig, u2dig, xr1, xr2, precheck):
        pb, valid = packed.pop(id(qx))
        # what the reference refuses before the curve, the pack does too
        assert list(pb.precheck[:len(valid)]) >= valid
        log.append(("dispatch", "secp256k1", len(precheck)))
        return LazyVerdicts(valid + [False] * (len(precheck) - len(valid)),
                            log, "secp256k1")

    monkeypatch.setattr(eck, "pack_batch", pack)
    monkeypatch.setattr(eck, "verify_kernel", verify_kernel)
    return log


def test_secp256k1_chunks_through_the_real_kernel_match_the_oracle(
        chunked):
    """Two chunks of the one shape through the XLA ECDSA kernel itself
    (the shape tests/test_secp256k1.py compiles): verdict for verdict
    the host oracle's, the bad rows at a chunk's both ends."""
    from cometbft_tpu.libs import tracing

    bad = (0, T - 1, T, T + 4)
    pubs, msgs, sigs = make_secp_rows(T + 5, bad)
    tracing.set_clock(None)  # an empty stage ring
    got = validation.device_batch_fn(use_pallas=False)(pubs, msgs, sigs)
    want = validation.oracle_batch_fn()(pubs, msgs, sigs)
    np.testing.assert_array_equal(got, want)
    assert tuple(np.flatnonzero(~want)) == bad
    recs = tracing.stage_records()
    assert [r[0] for r in recs] == (
        ["secp256k1.pack", "secp256k1.dispatch"] * 2 + ["secp256k1.fetch"])
    assert [(r[4]["rows"], r[4]["padded"], r[4]["chunk"], r[4]["chunks"])
            for r in recs[0:4:2]] == [(T, T, 0, 2), (5, T, 1, 2)]
    assert [r[4]["templated"] for r in recs[0:4:2]] == [0, 0]  # a list


@pytest.mark.parametrize("n,chunks", [(T - 1, 1), (T, 1), (T + 1, 2),
                                      (3 * T + 5, 4)],
                         ids=["below", "at", "one-over", "tail"])
def test_every_secp256k1_chunk_has_one_shape(chunked, ecdsa_standin, n,
                                             chunks):
    from cometbft_tpu.libs import tracing

    pubs, msgs, sigs = make_secp_rows(8)
    reps = -(-n // 8)
    tracing.set_clock(None)
    got = validation.device_batch_fn(use_pallas=False)(
        (pubs * reps)[:n], (msgs * reps)[:n], (sigs * reps)[:n])
    assert got.shape == (n,) and got.all()
    # every pass dispatched, then every verdict fetched
    assert ecdsa_standin == ([("dispatch", "secp256k1", T)] * chunks
                             + [("fetch", "secp256k1")] * chunks)
    packs = [r[4] for r in tracing.stage_records()
             if r[0] == "secp256k1.pack"]
    assert [p["padded"] for p in packs] == [T] * chunks
    assert [p["flying"] for p in packs] == list(range(chunks))
    assert sum(p["rows"] for p in packs) == n


SECP_VARIANTS = {
    "light": lambda vs, bid, commit, fn: validation.verify_commit_light(
        CHAIN_ID, vs, bid, HEIGHT, commit, fn),
    "trusting": lambda vs, bid, commit, fn:
        validation.verify_commit_light_trusting(CHAIN_ID, vs, commit,
                                                (1, 3), fn),
}


@pytest.mark.parametrize("case", ["ok", "bad"])
@pytest.mark.parametrize("variant", sorted(SECP_VARIANTS))
def test_a_chunked_secp256k1_commit_gives_the_oracles_outcome(
        chunked, ecdsa_standin, variant, case):
    """A commit of secp256k1 validators of unequal power through both
    checks of a light step: the outcome and the blame (the COMMIT's
    index, in a later chunk) are the host oracle's, the rows reach the
    pack as the ONE matrix the commit's lazy rows expand to."""
    from cometbft_tpu.libs import tracing

    n = 6 * T  # a light check examines some 3.2 T rows, trusting 1.4 T
    bad = (T + 6, T + 9) if case == "bad" else ()
    vs, commit, bid = make_secp_commit(n, invalid=bad)
    want = outcome_of(lambda: SECP_VARIANTS[variant](
        vs, bid, commit, validation.oracle_batch_fn()))
    tracing.set_clock(None)
    got = outcome_of(lambda: SECP_VARIANTS[variant](
        vs, bid, commit, validation.device_batch_fn(use_pallas=False)))
    assert got == want
    assert got == (("invalid_signature", T + 6) if bad else ("ok",))
    packs = [r[4] for r in tracing.stage_records()
             if r[0] == "secp256k1.pack"]
    assert len(packs) == packs[0]["chunks"] >= 2
    assert all(p["padded"] == T and p["templated"] == 1 for p in packs)
    # unequal powers: the examined prefix is not n * 2 // 3 + 1 rows
    examined = sum(p["rows"] for p in packs)
    assert examined < (n * 2 // 3 if variant == "light" else n // 3)


def test_a_secp256k1_dispatch_fault_is_reverified_on_the_host(
        chunked, monkeypatch):
    """The second chunk's dispatch raises: that group is verified again
    on the host (one breaker fault), and the verdicts stay the oracle's."""
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.ops import ecdsa_kernel as eck

    calls = []

    def sick(*arrays):
        calls.append(len(arrays[-1]))
        if len(calls) == 2:
            raise RuntimeError("device lost")
        return LazyVerdicts(np.asarray(arrays[-1]) != 0, [], "secp256k1")

    brk = cbatch.CircuitBreaker(failure_threshold=5)
    monkeypatch.setattr(cbatch, "_DEVICE_BREAKER", brk)
    monkeypatch.setattr(eck, "verify_kernel", sick)
    bad = (3, T + 1)
    pubs, msgs, sigs = make_secp_rows(2 * T + 5, bad)
    tracing.set_clock(None)
    got = validation.device_batch_fn(use_pallas=False)(pubs, msgs, sigs)
    np.testing.assert_array_equal(
        got, validation.oracle_batch_fn()(pubs, msgs, sigs))
    assert tuple(np.flatnonzero(~got)) == bad
    assert calls == [T, T] and brk.faults == 1 and brk.state == "closed"
    names = [r[0] for r in tracing.stage_records()]
    assert names.count("secp256k1.pack") == 2  # the third was not packed
    assert "secp256k1.fetch" not in names


def test_a_mixed_ed25519_secp256k1_commit_shares_one_queue(
        chunked, ed_standin, ecdsa_standin):
    """A commit whose validators hold ed25519 and secp256k1 keys in
    turn: both groups are cut into chunks of the one shape, every chunk
    of both is dispatched before the first verdict is fetched, `flying`
    counts the chunks of either, and the blame is the oracle's."""
    from cometbft_tpu.libs import tracing

    n = 6 * T
    probe, _, _ = make_secp_commit(n, every=2)
    bad = next(i for i, v in enumerate(probe.validators)
               if i > 2 * T and v.pub_key.key_type == "secp256k1")
    for invalid in ((), (bad,)):
        vs, commit, bid = make_secp_commit(n, invalid=invalid, every=2)
        del ed_standin[:], ecdsa_standin[:]
        tracing.set_clock(None)
        got = outcome_of(lambda: validation.verify_commit_light(
            CHAIN_ID, vs, bid, HEIGHT, commit,
            validation.device_batch_fn(use_pallas=False)))
        assert got == outcome_of(lambda: validation.verify_commit_light(
            CHAIN_ID, vs, bid, HEIGHT, commit,
            validation.oracle_batch_fn()))
        assert got == (("invalid_signature", bad) if invalid else ("ok",))
        log = ed_standin + ecdsa_standin
        packs = [r for r in tracing.stage_records()
                 if r[0].endswith(".pack")]
        assert {r[0] for r in packs} == {"ed25519.pack", "secp256k1.pack"}
        assert len(packs) >= 4
        assert [r[4]["flying"] for r in packs] == list(range(len(packs)))
        assert all(r[4]["padded"] == T for r in packs)
        assert sorted(e[0] for e in log) == (
            ["dispatch"] * len(packs) + ["fetch"] * len(packs))
        # the stage ring's order: every dispatch, then the fetches
        names = [r[0] for r in tracing.stage_records()
                 if r[0].endswith((".dispatch", ".fetch"))]
        assert names[:len(packs)] == [r[0][:-4] + "dispatch" for r in packs]
        assert sorted(names[len(packs):]) == ["ed25519.fetch",
                                              "secp256k1.fetch"]
