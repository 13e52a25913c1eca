"""Differential tests: cached-valset ed25519 path vs oracle.

The cached path (ops.ed25519_cached) must be bit-for-bit equivalent to
the pure-Python ZIP-215 oracle — the per-validator window tables and
the in-kernel entry select are a pure re-layout of h*(-A), so any
divergence is a consensus fork.

RUNS ON THE CHIP ONLY (CBT_TEST_ON_TPU=1, through the chip tool): the
kernel keeps its valset table block in VMEM via a BlockSpec index_map,
and the Pallas INTERPRET path for that shape compiles for hours on a
CPU, where Mosaic takes 4-6 s on a v5e (PR 21 chip runs). CPU coverage
of the surrounding bookkeeping lives in test_ed25519_cached_host.py; the
kernel itself is exercised on the chip by these tests, by
`python tools/tpu_differential.py`, and by every `chip_smoke.py` run,
which sends the same edge vectors through it.
"""
import os

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_ref as ed
from cometbft_tpu.ops import ed25519_cached as ec
from cometbft_tpu.ops import ed25519_kernel as k

pytestmark = pytest.mark.skipif(
    not os.environ.get("CBT_TEST_ON_TPU"),
    reason="pallas-interpret compile of the in-kernel-gather kernel "
           "takes hours on CPU; set CBT_TEST_ON_TPU=1 on the chip. "
           "Chip coverage: chip_smoke.py + tools/tpu_differential.py.",
)


def make_sigs(n, msg_fn=lambda i: b"msg-%d" % i):
    seeds = [bytes([i + 1]) * 32 for i in range(n)]
    pubs = [ed.pubkey_from_seed(s) for s in seeds]
    msgs = [msg_fn(i) for i in range(n)]
    sigs = [ed.sign(s, m) for s, m in zip(seeds, msgs)]
    return pubs, msgs, sigs


def test_cached_mixed_batch_vs_oracle():
    """Valid rows, tampered sig, tampered msg, S>=L malleability, bad
    pubkey — all against the oracle, one batch."""
    pubs, msgs, sigs = make_sigs(8)
    sigs[2] = sigs[2][:10] + bytes([sigs[2][10] ^ 1]) + sigs[2][11:]
    msgs[5] = msgs[5] + b"tampered"
    sigs[6] = sigs[6][:32] + int.to_bytes(
        int.from_bytes(sigs[6][32:], "little") + ed.L, 32, "little"
    )
    pubs[7] = b"\xff" * 32
    got = ec.verify_batch_cached(pubs, msgs, sigs)
    exp = [ed.verify(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert got[0] and not got[2] and not got[5] and not got[6] \
        and not got[7]


def test_cached_zip215_edges():
    """Non-canonical y, small-order identity, -0 sign — the cached
    table build decompresses A exactly like the oracle."""
    ident = ed.pt_compress(ed.IDENT)
    cases = [(ident, b"m", ident + b"\x00" * 32)]
    for y in range(19):
        u, v = (y * y - 1) % ed.P, (ed.D * y * y + 1) % ed.P
        ok, x = ed._sqrt_ratio(u, v)
        if ok:
            enc_nc = int.to_bytes((y + ed.P) | ((x & 1) << 255), 32,
                                  "little")
            break
    seed = bytes(32)
    pub = ed.pubkey_from_seed(seed)
    sig = ed.sign(seed, b"x")
    cases.append((pub, b"x", enc_nc + sig[32:]))  # non-canonical R
    cases.append((enc_nc, b"x", sig))             # non-canonical A
    neg_zero = int.to_bytes(1 | (1 << 255), 32, "little")
    cases.append((neg_zero, b"m", neg_zero + b"\x00" * 32))
    pubs, msgs, sigs = (list(z) for z in zip(*cases))
    got = ec.verify_batch_cached(pubs, msgs, sigs)
    exp = [ed.verify(p, m, s) for p, m, s in cases]
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert any(exp)


def test_cached_table_lru():
    pubs, msgs, sigs = make_sigs(3)
    t1 = ec.table_for_pubs(pubs)
    t2 = ec.table_for_pubs(pubs)
    assert t1 is t2  # LRU hit
    # order matters: the validator index is the key into the table
    t3 = ec.table_for_pubs(list(reversed(pubs)))
    assert t3 is not t1
    got = ec.verify_batch_cached(
        list(reversed(pubs)), list(reversed(msgs)), list(reversed(sigs)),
        table=t3,
    )
    assert got.all()


def test_cached_multi_commit_stride_tally():
    """Two commits of the same 64-val set packed at the table stride M:
    per-commit tallies and quorums come out right, including an invalid
    row in commit 1 only."""
    pubs, msgs, sigs = make_sigs(64)
    table = ec.table_for_pubs(pubs, [5] * 64)
    M = table.n_vals
    assert M == 128
    B = 2 * M  # commit c occupies rows [c*M, c*M + 64)
    pubs2 = (pubs + [b""] * (M - 64)) * 2
    msgs2 = (msgs + [b""] * (M - 64)) * 2
    sig_rows = (sigs + [b""] * (M - 64)) * 2
    sig_rows[M + 7] = b"\x01" * 64  # bad sig in commit 1 at val 7
    pb = k.pack_batch(pubs2, msgs2, sig_rows, pad_to=B)
    counted = np.zeros(B, np.bool_)
    cids = np.zeros(B, np.int32)
    for c in range(2):
        counted[c * M:c * M + 64] = True
        cids[c * M:c * M + 64] = c
    thresh = k.threshold_limbs(64 * 5 * 2 // 3, n_commits=2)
    rows = ec.pack_rows_cached(pb, counted, cids, thresh)
    valid, tally, quorum = ec.verify_tally_rows_cached(rows, table, 2)
    valid = np.asarray(valid)
    assert valid[:64].all()
    assert valid[M:M + 64].sum() == 63 and not valid[M + 7]
    t = k.tally_to_int(np.asarray(tally))
    assert t[0] == 64 * 5 and t[1] == 63 * 5
    q = np.asarray(quorum)
    assert bool(q[0]) and bool(q[1])


def test_stream_verifier_cached_strided_path():
    """StreamVerifier with use_pallas=True routes same-valset chunks
    through the strided cached-table pack; blame and quorum still match
    the dense path. (B=256 — shares the stride test's compile.)"""
    from cometbft_tpu.blocksync.pipeline import CommitJob, StreamVerifier
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validation import InvalidSignatureError
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    privs = [PrivKey.generate(bytes([60 + i]) * 32) for i in range(64)]
    vs = ValidatorSet([Validator(p.pub_key(), 9) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    jobs = []
    for h in (1, 2):
        bid = BlockID(bytes([h]) * 32, PartSetHeader(1, b"\x0f" * 32))
        sigs = []
        for v in vs.validators:
            ts = Timestamp(1_700_000_000 + h, 0)
            sb = canonical.canonical_vote_bytes(
                "sv-chain", canonical.PRECOMMIT_TYPE, h, 0, bid, ts
            )
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                                  by_addr[v.address].sign(sb)))
        jobs.append(CommitJob(vs, bid, h, Commit(h, 0, bid, sigs),
                              "sv-chain"))
    # corrupt one signature in the second commit
    jobs[1].commit.signatures[11].signature = b"\x02" * 64
    sv = StreamVerifier(use_pallas=True, max_sigs=256,
                        min_device_sigs=2)
    table = sv._cached_table([(0, jobs[0]), (1, jobs[1])])
    assert table is not None and table.n_vals == 128
    res = sv.verify(jobs)
    assert res[0] is None
    assert isinstance(res[1], InvalidSignatureError) and res[1].idx == 11


def test_pad_rows_buckets():
    assert ec.pad_rows(1) == 128
    assert ec.pad_rows(129) == 256
    assert ec.pad_rows(2049) == 4096
    assert ec.pad_rows(5000) == 6144
    assert ec.pad_rows(10_000) == 10_240
    with pytest.raises(ValueError):
        ec.pad_rows(70_000)


def test_incremental_update_matches_rebuild():
    """Valset churn (types/validator_set.go:589-651 updateWithChangeSet):
    update_table on a small delta must verify exactly like a fresh
    build — changed slots verify new keys' sigs, old keys' sigs against
    changed slots now fail, untouched slots unaffected. Also covers a
    slot changed to garbage (ok=False)."""
    pubs, msgs, sigs = make_sigs(128)
    table = ec.table_for_pubs(pubs, [7] * 128)

    new_seeds = {3: b"\xaa" * 32, 77: b"\xbb" * 32, 120: b"\xcc" * 32}
    pubs2 = list(pubs)
    msgs2 = list(msgs)
    sigs2 = list(sigs)
    for i, s in new_seeds.items():
        pubs2[i] = ed.pubkey_from_seed(s)
        sigs2[i] = ed.sign(s, msgs[i])
    pubs2[9] = b"\x00" * 31  # bad length -> slot must go dead

    changes = [(i, pubs2[i]) for i in (3, 9, 77, 120)]
    t2 = ec.update_table(table, changes, {3: 9})
    got = ec.verify_batch_cached(pubs2, msgs2, sigs2, table=t2)
    exp = [ed.verify(p, m, s) if len(p) == 32 else False
           for p, m, s in zip(pubs2, msgs2, sigs2)]
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert got[3] and got[77] and got[120] and not got[9]
    # old signature against a replaced slot must now fail
    got_old = ec.verify_batch_cached(pubs2, msgs, sigs, table=t2)
    assert not got_old[3] and got_old[0]
    # power updated only where asked
    p5 = np.asarray(t2.power5)
    assert k.tally_to_int(p5[3]) == 9 and k.tally_to_int(p5[4]) == 7
    # the original table is untouched (functional update)
    assert ec.verify_batch_cached(pubs, msgs, sigs, table=table).all()


def test_table_for_pubs_near_miss_incremental():
    """A changed valset list hits the near-miss path (no full rebuild)
    and still verifies correctly under the new key list."""
    pubs, msgs, sigs = make_sigs(128, msg_fn=lambda i: b"nm-%d" % i)
    powers = list(range(1, 129))
    t1 = ec.table_for_pubs(pubs, powers)
    s = b"\xdd" * 32
    pubs2 = list(pubs)
    pubs2[50] = ed.pubkey_from_seed(s)
    sigs2 = list(sigs)
    sigs2[50] = ed.sign(s, msgs[50])
    powers2 = list(powers)
    powers2[50] = 1000
    t2 = ec.table_for_pubs(pubs2, powers2)
    assert t2 is not t1
    got = ec.verify_batch_cached(pubs2, msgs, sigs2, table=t2)
    assert got.all()
    assert k.tally_to_int(np.asarray(t2.power5)[50]) == 1000
    # second lookup is a plain LRU hit
    assert ec.table_for_pubs(pubs2, powers2) is t2


def test_near_miss_large_valset_power_delta():
    """Near-miss churn on a >128-slot valset must take the incremental
    path without tripping the update budget (the review-found crash:
    a full per-validator power map blew UPDATE_PAD), and only changed
    powers may ride the update."""
    pubs, msgs, sigs = make_sigs(130, msg_fn=lambda i: b"lg-%d" % i)
    powers = [3] * 130
    t1 = ec.table_for_pubs(pubs, powers)
    assert t1.n_vals == 256  # padded beyond one lane tile

    s = b"\xee" * 32
    pubs2 = list(pubs)
    pubs2[129] = ed.pubkey_from_seed(s)
    sigs2 = list(sigs)
    sigs2[129] = ed.sign(s, msgs[129])
    powers2 = list(powers)
    powers2[7] = 99  # power-only change on an untouched slot
    t2 = ec.table_for_pubs(pubs2, powers2)
    assert t2 is not t1
    # powers_host proves the incremental path ran (a rebuild would
    # also satisfy verification, so check the delta bookkeeping)
    assert t2.powers_host[7] == 99 and t2.powers_host[129] == 3
    assert t2.powers_host[0] == 3
    got = ec.verify_batch_cached(pubs2, msgs, sigs2, table=t2)
    assert got.all()

    # a delta larger than UPDATE_PAD falls back to a full rebuild
    # rather than raising (ValueError is caught in table_for_pubs)
    pubs3 = [ed.pubkey_from_seed(bytes([i % 251, 9]) + b"\x31" * 30)
             for i in range(130)]
    t3 = ec.table_for_pubs(pubs3, powers)
    assert t3 is not t2 and t3.n_vals == 256


def test_warm_incremental_byte_identical_to_cold_build():
    """The warmer's incremental patch must be indistinguishable from
    the full next-epoch build: every device/host array of the patched
    table equals the cold build_table result byte-for-byte."""
    pubs, _, _ = make_sigs(8, msg_fn=lambda i: b"wi-%d" % i)
    powers = list(range(1, 9))
    ec.table_for_pubs(pubs, powers)  # the base epoch's table
    s = b"\xcf" * 32
    pubs2 = list(pubs)
    pubs2[3] = ed.pubkey_from_seed(s)
    powers2 = list(powers)
    powers2[3] = 77
    key2 = tuple(pubs2)
    assert ec.warm_incremental(key2, powers2) is True
    patched = ec.table_for_pubs(key2, powers2)  # plain LRU hit now
    cold = ec.build_table(pubs2, powers2)
    assert patched is not cold
    np.testing.assert_array_equal(np.asarray(patched.tab),
                                  np.asarray(cold.tab))
    np.testing.assert_array_equal(np.asarray(patched.ok),
                                  np.asarray(cold.ok))
    np.testing.assert_array_equal(np.asarray(patched.power5),
                                  np.asarray(cold.power5))
    assert patched.pubs_host == cold.pubs_host
    np.testing.assert_array_equal(patched.powers_host,
                                  cold.powers_host)


def test_rekeyed_table_verifies_and_tallies_as_a_cold_build():
    """A power change that re-sorts the set is served by permuting a
    cached table of the same keys (`rekeyed`, no column built). Under it
    the cached verify and tally equal a cold build's, row by row and
    commit by commit, and the tally takes the NEW powers: commit 1 is
    signed by the validators that held 2/3 before the change and hold
    1/11 after it, so it clears 2/3 under the old powers and not under
    the new ones."""
    pubs, msgs, sigs = make_sigs(64, msg_fn=lambda i: b"rk-%d" % i)
    old_power = [10] * 32 + [1] * 32  # upstream's order: power down
    new_power = [1] * 32 + [10] * 32
    ec.table_for_pubs(pubs, old_power)
    order = list(range(32, 64)) + list(range(32))  # sorted anew
    keys = [pubs[i] for i in order]
    s0 = ec.table_cache_stats()
    rekeyed = ec.table_for_pubs(keys, [new_power[i] for i in order])
    before = ec.table_for_pubs(keys, [old_power[i] for i in order])
    s1 = ec.table_cache_stats()
    assert s1["rekeyed"] - s0["rekeyed"] == 2
    assert s1["misses"] == s0["misses"]
    cold = ec.build_table(keys, [new_power[i] for i in order])

    M = rekeyed.n_vals
    assert M == cold.n_vals == 128
    B = 2 * M  # commit c occupies rows [c*M, c*M + 64), set order
    signs = [[True] * 64, [i < 32 for i in order]]  # by slot
    pub_rows, msg_rows, sig_rows = [], [], []
    counted = np.zeros(B, np.bool_)
    cids = np.zeros(B, np.int32)
    for c in range(2):
        for j, v in enumerate(order):
            on = signs[c][j]
            pub_rows.append(keys[j])
            msg_rows.append(msgs[v] if on else b"")
            sig_rows.append(sigs[v] if on else b"")
            counted[c * M + j] = on
            cids[c * M + j] = c
        pub_rows += [b""] * (M - 64)
        msg_rows += [b""] * (M - 64)
        sig_rows += [b""] * (M - 64)
    sig_rows[5] = b"\x01" * 64  # a bad signature in commit 0, slot 5
    total = sum(new_power)
    thresh = k.threshold_limbs(total * 2 // 3, n_commits=2)
    pb = k.pack_batch(pub_rows, msg_rows, sig_rows, pad_to=B)
    rows = ec.pack_rows_cached(pb, counted, cids, thresh)

    got = [tuple(np.asarray(x) for x in
                 ec.verify_tally_rows_cached(rows, t, 2))
           for t in (rekeyed, cold, before)]
    for a, b in zip(got[0], got[1]):
        np.testing.assert_array_equal(a, b)
    valid, tally, quorum = got[0]
    exp = [bool(s) and ed.verify(p, m, s)
           for p, m, s in zip(pub_rows, msg_rows, sig_rows)]
    np.testing.assert_array_equal(valid, np.asarray(exp))
    assert not valid[5] and valid[M + 32:M + 64].all()
    t = k.tally_to_int(tally)
    assert t[0] == total - 10 * (order[5] >= 32) - (order[5] < 32)
    assert t[1] == 32 and not bool(quorum[1]) and bool(quorum[0])
    # the same rows under the set's previous powers: commit 1 clears
    assert k.tally_to_int(got[2][1])[1] == 320 and bool(got[2][2][1])
