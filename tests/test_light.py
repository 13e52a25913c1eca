"""Light client: adjacent/non-adjacent verification, bisection across
validator-set churn, witness divergence, trusting-period expiry.

Mirrors light/client_test.go + light/verifier_test.go case structure with
an in-process chain generator standing in for the RPC providers.
"""
import functools

import pytest

from cometbft_tpu.crypto.keys import PrivKey
from cometbft_tpu.light import client as lc
from cometbft_tpu.light import verifier as lv
from cometbft_tpu.types import canonical, validation
from cometbft_tpu.types.block import Header
from cometbft_tpu.types.block_id import BlockID, PartSetHeader
from cometbft_tpu.types.commit import (
    BLOCK_ID_FLAG_COMMIT,
    Commit,
    CommitSig,
)
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.validator import Validator, ValidatorSet

CHAIN_ID = "light-chain"
T0 = 1_700_000_000


def keys_for(tag, n):
    return [
        PrivKey.generate(bytes([tag, i + 1]) + b"\x07" * 30)
        for i in range(n)
    ]


class LightChain:
    """Deterministic chain builder: vals_plan[h] is the key list whose set
    signs height h; headers carry correct validators/next_validators
    hashes so adjacent links and bisection behave like the real chain."""

    def __init__(self, vals_plan):
        self.plan = vals_plan  # dict height -> list[PrivKey]
        self.max_height = max(vals_plan)
        self.blocks = {}
        prev_bid = BlockID()
        for h in range(1, self.max_height + 1):
            privs = self.plan[h]
            nxt = self.plan.get(h + 1, privs)
            vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
            nvs = ValidatorSet([Validator(p.pub_key(), 10) for p in nxt])
            header = Header(
                chain_id=CHAIN_ID, height=h,
                time=Timestamp(T0 + h, 0),
                last_block_id=prev_bid,
                validators_hash=vs.hash(),
                next_validators_hash=nvs.hash(),
                proposer_address=vs.validators[0].address,
                app_hash=b"\x01" * 32,
            )
            bid = BlockID(header.hash(), PartSetHeader(1, header.hash()))
            by_addr = {p.pub_key().address(): p for p in privs}
            sigs = []
            for v in vs.validators:
                ts = Timestamp(T0 + h, 42)
                sb = canonical.canonical_vote_bytes(
                    CHAIN_ID, canonical.PRECOMMIT_TYPE, h, 0, bid, ts
                )
                sigs.append(CommitSig(
                    BLOCK_ID_FLAG_COMMIT, v.address, ts,
                    by_addr[v.address].sign(sb),
                ))
            self.blocks[h] = lv.LightBlock(
                lv.SignedHeader(header, Commit(h, 0, bid, sigs)), vs
            )
            prev_bid = bid

    def provider(self):
        return lc.Provider(CHAIN_ID, lambda h: self.blocks.get(h))


NOW = Timestamp(T0 + 1000, 0)


def make_client(chain, **kw):
    kw.setdefault("trusting_period", 1e6)
    kw.setdefault("batch_fn", validation.oracle_batch_fn())
    c = lc.Client(CHAIN_ID, chain.provider(), **kw)
    c.trust_light_block(chain.blocks[1])
    return c


def test_skipping_one_jump_stable_valset():
    """Stable validator set: one non-adjacent verification reaches the
    target (the whole point of skipping mode)."""
    keys = keys_for(1, 4)
    chain = LightChain({h: keys for h in range(1, 21)})
    c = make_client(chain)
    lb = c.verify_light_block_at_height(20, now=NOW)
    assert lb.height == 20
    assert c.verifications == 1


def test_sequential_walks_every_height():
    keys = keys_for(1, 4)
    chain = LightChain({h: keys for h in range(1, 11)})
    c = make_client(chain, skipping=False)
    c.verify_light_block_at_height(10, now=NOW)
    assert c.verifications == 9
    assert c.store.heights() == list(range(1, 11))


def test_bisection_across_full_valset_rotation():
    """Heights 1-10 signed by era A, 11-20 by a disjoint era B: a direct
    jump fails the 1/3-trust check and bisection + the adjacent
    next-validators link must carry the client across (client.go:706)."""
    a, b = keys_for(1, 4), keys_for(2, 4)
    plan = {h: (a if h <= 10 else b) for h in range(1, 21)}
    chain = LightChain(plan)
    c = make_client(chain)
    lb = c.verify_light_block_at_height(20, now=NOW)
    assert lb.height == 20
    # must have passed through the era boundary via the adjacent link
    assert 11 in c.store.heights()
    assert c.verifications > 2


def test_gradual_churn_skips_far():
    """Replacing one of 6 validators every 3 heights keeps >1/3 overlap on
    moderate jumps — skipping should NOT need every height."""
    base = keys_for(3, 8)
    plan = {}
    cur = list(base)
    for h in range(1, 31):
        if h % 3 == 0:
            cur = cur[1:] + [keys_for(10 + h, 1)[0]]
        plan[h] = list(cur)
    chain = LightChain(plan)
    c = make_client(chain)
    c.verify_light_block_at_height(30, now=NOW)
    assert c.verifications < 29  # strictly better than sequential


def test_expired_trusted_header_rejected():
    keys = keys_for(1, 4)
    chain = LightChain({h: keys for h in range(1, 6)})
    c = make_client(chain, trusting_period=10.0)
    with pytest.raises(lv.ErrOldHeaderExpired):
        c.verify_light_block_at_height(5, now=Timestamp(T0 + 1000, 0))


def test_witness_divergence_detected():
    keys = keys_for(1, 4)
    chain = LightChain({h: keys for h in range(1, 6)})
    forged = LightChain({h: keys_for(9, 4) for h in range(1, 6)})
    c = lc.Client(
        CHAIN_ID, chain.provider(),
        witnesses=[forged.provider()],
        trusting_period=1e6, batch_fn=validation.oracle_batch_fn(),
    )
    c.trust_light_block(chain.blocks[1])
    with pytest.raises(lc.DivergenceError):
        c.verify_light_block_at_height(5, now=NOW)


def test_tampered_target_rejected():
    keys = keys_for(1, 4)
    chain = LightChain({h: keys for h in range(1, 6)})
    # swap height 5's commit sigs for garbage
    lb = chain.blocks[5]
    bad_sigs = [
        CommitSig(cs.flag, cs.validator_address, cs.timestamp, bytes(64))
        for cs in lb.signed_header.commit.signatures
    ]
    chain.blocks[5] = lv.LightBlock(
        lv.SignedHeader(
            lb.signed_header.header,
            Commit(5, 0, lb.signed_header.commit.block_id, bad_sigs),
        ),
        lb.validator_set,
    )
    c = make_client(chain)
    with pytest.raises(lv.ErrInvalidHeader):
        c.verify_light_block_at_height(5, now=NOW)


def test_backwards_verification():
    """Heights below the trust root verify via the last_block_id hash
    chain (light/client.go:734)."""
    keys = keys_for(7, 4)
    chain = LightChain({h: keys for h in range(1, 9)})
    c = lc.Client(CHAIN_ID, chain.provider(), trusting_period=1e6,
                  batch_fn=validation.oracle_batch_fn())
    c.trust_light_block(chain.blocks[6])
    lb = c.verify_light_block_at_height(2, now=NOW)
    assert lb.signed_header.header.height == 2
    assert lb.signed_header.header.hash() == \
        chain.blocks[2].signed_header.header.hash()
    # a tampered intermediate header breaks the chain walk
    import copy

    chain2 = LightChain({h: keys for h in range(1, 9)})
    bad = copy.deepcopy(chain2.blocks[3])
    bad.signed_header.header.app_hash = b"\x99" * 32
    chain2.blocks[3] = bad
    c2 = lc.Client(CHAIN_ID, chain2.provider(), trusting_period=1e6,
                   batch_fn=validation.oracle_batch_fn())
    c2.trust_light_block(chain2.blocks[6])
    with pytest.raises(lc.LightClientError):
        c2.verify_light_block_at_height(2, now=NOW)


def test_divergence_produces_attack_evidence():
    """A forged witness fork yields LightClientAttackEvidence naming the
    byzantine signers (detector.go -> types/evidence.go:193)."""
    keys = keys_for(9, 4)
    chain = LightChain({h: keys for h in range(1, 6)})
    # witness serves a conflicting chain signed by the SAME validators
    fork = LightChain({h: keys for h in range(1, 6)})
    fork.blocks[4].signed_header.header.app_hash = b"\x66" * 32
    # re-sign the forged header so the commit is internally consistent
    hdr = fork.blocks[4].signed_header.header
    hdr_hash = hdr.hash()
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    bid = BlockID(hdr_hash, PartSetHeader(1, hdr_hash))
    by_addr = {p.pub_key().address(): p for p in keys}
    sigs = []
    vs = fork.blocks[4].validator_set
    for v in vs.validators:
        ts = Timestamp(T0 + 4, 42)
        sb = canonical.canonical_vote_bytes(
            CHAIN_ID, canonical.PRECOMMIT_TYPE, 4, 0, bid, ts
        )
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                              by_addr[v.address].sign(sb)))
    fork.blocks[4] = lv.LightBlock(
        lv.SignedHeader(hdr, Commit(4, 0, bid, sigs)), vs
    )

    collected = []
    c = make_client(chain)
    c.witnesses = [fork.provider()]
    c.on_attack_evidence = collected.append
    with pytest.raises(lc.DivergenceError) as ei:
        c.verify_light_block_at_height(4, now=NOW)
    ev = ei.value.evidence
    assert ev is not None and ev.conflicting_height == 4
    assert len(ev.byzantine_validators) == 4  # all signed the fork
    assert collected and collected[0] is ev
    ev.validate_basic()


def test_persistent_store_roundtrip(tmp_path):
    """light/store/db/db.go: save/get/latest/first/prune/size survive a
    store reopen."""
    from cometbft_tpu.light.store import DBStore

    keys = keys_for(9, 4)
    chain = LightChain({h: keys for h in range(1, 8)})
    path = str(tmp_path / "light.db")
    st = DBStore(path)
    for h in (1, 3, 5, 7):
        st.save(chain.blocks[h])
    assert st.size() == 4
    assert st.first_height() == 1
    assert st.latest().height == 7
    st.close()

    st2 = DBStore(path)
    assert st2.heights() == [1, 3, 5, 7]
    lb = st2.get(3)
    assert lb.signed_header.header.hash() == \
        chain.blocks[3].signed_header.header.hash()
    assert lb.validator_set.hash() == chain.blocks[3].validator_set.hash()
    # commit sigs survive byte-exact (they re-verify)
    lb.validate_basic(CHAIN_ID)
    st2.prune(2)
    assert st2.heights() == [5, 7]
    st2.delete(5)
    assert st2.heights() == [7]
    st2.close()


def test_client_resumes_from_persisted_trust(tmp_path):
    """Restarting a client on the same DB keeps the trust root: no
    trust_light_block call needed, bisection proceeds from the stored
    latest (the VERDICT r4 gap: volatile trust defeats the trust-period
    model across restarts)."""
    from cometbft_tpu.light.store import DBStore

    keys = keys_for(11, 4)
    chain = LightChain({h: keys for h in range(1, 31)})
    path = str(tmp_path / "light.db")

    c1 = lc.Client(CHAIN_ID, chain.provider(), trusting_period=1e6,
                   batch_fn=validation.oracle_batch_fn(),
                   store=DBStore(path))
    c1.trust_light_block(chain.blocks[1])
    c1.verify_light_block_at_height(15, now=NOW)
    c1.store.close()

    # "restart": fresh client, same db, NO trust bootstrap
    c2 = lc.Client(CHAIN_ID, chain.provider(), trusting_period=1e6,
                   batch_fn=validation.oracle_batch_fn(),
                   store=DBStore(path))
    assert c2.store.latest().height == 15
    lb = c2.verify_light_block_at_height(30, now=NOW)
    assert lb.height == 30
    # and the new verification persisted too
    c2.store.close()
    assert DBStore(path).latest().height == 30


def test_proxy_refuses_expired_root_without_pinned_hash(tmp_path):
    """ADVICE r5 low: a light proxy whose PERSISTED trust root has aged
    past the trusting period must refuse to silently re-root on the
    primary (trust-on-first-use) unless the operator explicitly opted
    into the insecure mode or pinned a hash."""
    from cometbft_tpu.light.proxy import LightProxy, LightProxyError
    from cometbft_tpu.light.store import DBStore

    keys = keys_for(21, 3)
    chain = LightChain({h: keys for h in range(1, 6)})
    path = str(tmp_path / "light.db")
    st = DBStore(path)
    st.save(chain.blocks[3])  # T0-era root: years older than 14 days
    st.close()

    proxy = LightProxy(
        CHAIN_ID, "http://127.0.0.1:1",  # never contacted
        db_path=path,
    )
    try:
        with pytest.raises(LightProxyError, match="trusting period"):
            proxy._ensure_trust()
    finally:
        proxy.httpd.server_close()


def test_proxy_reroots_expired_root_when_explicitly_insecure(tmp_path):
    """The escape hatch: insecure_allow_reroot=True restores the old
    TOFU-with-warning behavior for dev setups."""
    from cometbft_tpu.light.proxy import LightProxy
    from cometbft_tpu.light.store import DBStore

    keys = keys_for(22, 3)
    chain = LightChain({h: keys for h in range(1, 6)})
    path = str(tmp_path / "light.db")
    st = DBStore(path)
    st.save(chain.blocks[3])
    st.close()

    proxy = LightProxy(
        CHAIN_ID, "http://127.0.0.1:1",
        trusted_height=5,
        db_path=path,
        insecure_allow_reroot=True,
    )
    try:
        # serve the "primary" from the in-process chain: the proxy
        # re-roots on its height-5 block without raising
        proxy.client.primary = chain.provider()
        proxy._ensure_trust()
        assert proxy.client.store.latest().height == 5
    finally:
        proxy.httpd.server_close()


def test_proxy_accepts_pinned_hash_reroot(tmp_path):
    """An operator-pinned --trusted-hash re-roots an expired store
    securely (and a WRONG pin is rejected)."""
    from cometbft_tpu.light.proxy import LightProxy, LightProxyError
    from cometbft_tpu.light.store import DBStore

    keys = keys_for(23, 3)
    chain = LightChain({h: keys for h in range(1, 6)})
    path = str(tmp_path / "light.db")
    st = DBStore(path)
    st.save(chain.blocks[2])
    st.close()

    good = chain.blocks[4].signed_header.header.hash()
    proxy = LightProxy(
        CHAIN_ID, "http://127.0.0.1:1",
        trusted_height=4, trusted_hash=good, db_path=path,
    )
    try:
        proxy.client.primary = chain.provider()
        proxy._ensure_trust()
        assert proxy.client.store.latest().height == 4
    finally:
        proxy.httpd.server_close()

    proxy2 = LightProxy(
        CHAIN_ID, "http://127.0.0.1:1",
        trusted_height=4, trusted_hash=b"\x13" * 32,
        db_path=str(tmp_path / "light2.db"),
    )
    try:
        proxy2.client.primary = chain.provider()
        with pytest.raises(LightProxyError, match="mismatch"):
            proxy2._ensure_trust()
    finally:
        proxy2.httpd.server_close()


def test_client_concurrent_access_hammer():
    """ISSUE 8 satellite: the gateway shares ONE Client across serving
    threads — hammer it: K threads bisecting random targets while
    another thread prunes, with no lost verification counts, no
    exceptions, and a store whose every block still matches the chain.
    The device-verify wait runs unlocked (coalesced flushes overlap),
    so this is exactly the concurrency shape the gateway produces."""
    import random
    import threading

    keys = keys_for(31, 3)
    chain = LightChain({h: keys for h in range(1, 25)})
    c = make_client(chain)
    targets = [6, 12, 18, 24]
    errs = []
    lock = threading.Lock()
    K = 8
    barrier = threading.Barrier(K + 1)

    def worker(seed):
        rng = random.Random(seed)
        try:
            barrier.wait()
            for t in rng.sample(targets, len(targets)):
                lb = c.verify_light_block_at_height(t, now=NOW)
                assert lb.height == t
                assert lb.signed_header.header.hash() == \
                    chain.blocks[t].signed_header.header.hash()
        except Exception as e:  # noqa: BLE001 - asserted below
            with lock:
                errs.append(repr(e))

    def pruner():
        barrier.wait()
        for _ in range(20):
            c.prune_expired(now=NOW)  # nothing expired: exercises the
            # heights()/get()/delete() walk against concurrent saves

    threads = [threading.Thread(target=worker, args=(1000 + k,))
               for k in range(K)] + [threading.Thread(target=pruner)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[:3]
    # every stored block is byte-honest chain state
    for h in c.store.heights():
        assert c.store.get(h).signed_header.header.hash() == \
            chain.blocks[h].signed_header.header.hash()
    # the locked counter lost no increments: every verification that
    # saved a NEW height counted at least once, and the counter is at
    # least the number of distinct verified heights
    assert c.verifications >= len([h for h in c.store.heights()
                                   if h > 1])
    # atomic anchor scan used by backwards verification
    assert c.store.lowest_at_or_above(7).height in c.store.heights()


def test_proxy_rides_mounted_gateway():
    """ISSUE 8 satellite: with a light-client gateway mounted, the
    proxy's verify path routes through the SHARED gateway verifier —
    one TrustedStore for both — and trust bookkeeping is the
    gateway's. The legacy standalone path stays available behind the
    gateway=False flag."""
    from cometbft_tpu.light.proxy import LightProxy
    from cometbft_tpu.lightgate import LightGateway, set_global_gateway

    keys = keys_for(33, 3)
    chain = LightChain({h: keys for h in range(1, 11)})
    gw = LightGateway(CHAIN_ID, chain.provider(), trusting_period=1e9,
                      batch_fn=validation.oracle_batch_fn())
    gw.client.trust_light_block(chain.blocks[1])
    gw.start()
    proxy = LightProxy(CHAIN_ID, "http://127.0.0.1:1")  # never dialed
    try:
        # shared verifier: the proxy's client IS the gateway's client
        assert proxy.client is gw.client
        out = proxy.commit(height=7)
        assert out["verified"] is True
        # the verification landed in the ONE shared store — a gateway
        # request for the same height is now a pure store hit
        assert 7 in gw.client.store.heights()
        v = gw.verify(1, 7)
        assert v["verify_steps"] == 0
        # _ensure_trust with a pin re-checks against the shared view
        proxy._trusted_height = 3
        proxy._trusted_hash = b"\x13" * 32
        from cometbft_tpu.light.proxy import LightProxyError

        with pytest.raises(LightProxyError, match="mismatch"):
            proxy._ensure_trust()
        proxy._trusted_hash = \
            chain.blocks[3].signed_header.header.hash()
        proxy._ensure_trust()  # correct pin passes
    finally:
        gw.stop()
        set_global_gateway(None)
        proxy.httpd.server_close()

    # unmounted again: the proxy is back on its own standalone client
    assert proxy.client is proxy._own_client

    # and the legacy flag pins standalone even WITH a gateway mounted
    gw2 = LightGateway(CHAIN_ID, chain.provider(), trusting_period=1e9,
                       batch_fn=validation.oracle_batch_fn())
    gw2.client.trust_light_block(chain.blocks[1])
    gw2.start()
    legacy = LightProxy(CHAIN_ID, "http://127.0.0.1:1", gateway=False)
    try:
        assert legacy.client is legacy._own_client
    finally:
        gw2.stop()
        set_global_gateway(None)
        legacy.httpd.server_close()


# --------------------------------------------------------------------------
# a skipping step over secp256k1 validators of unequal power, against the
# benchmark's plain reference (benchmarks/reference/ecdsa.py)
# --------------------------------------------------------------------------

SECP_H0, SECP_H1 = 10, 510
SECP_NOW = Timestamp(T0 + SECP_H1 + 60, 0)


def secp_chain(changed, tampered=None, doubled=None):
    """Two light blocks 500 heights apart over 48 secp256k1 validators
    of unequal power; `changed` seats go to newcomers in the second.
    `tampered` flips that row's signature in the second block's commit;
    `doubled` = (i, j) puts row i's vote in row j's place too. Returns
    ({height: LightBlock}, what the plain reference takes of the two
    blocks)."""
    from cometbft_tpu.crypto.keys import Secp256k1PrivKey

    privs = [Secp256k1PrivKey.generate(
        i.to_bytes(2, "big") + b"\x6b" * 30) for i in range(96)]
    power = {p.pub_key().address(): 500 + (37 * i) % 1001
             for i, p in enumerate(privs)}
    blocks, plain = {}, {}
    for h, seats in ((SECP_H0, privs[:48]),
                     (SECP_H1, privs[changed:48 + changed])):
        by_addr = {p.pub_key().address(): p for p in seats}
        vs = ValidatorSet([Validator(p.pub_key(), power[a])
                           for a, p in by_addr.items()])
        header = Header(
            chain_id=CHAIN_ID, height=h, time=Timestamp(T0 + h, 0),
            last_block_id=BlockID(), validators_hash=vs.hash(),
            next_validators_hash=vs.hash(),
            proposer_address=vs.validators[0].address,
            app_hash=b"\x01" * 32)
        bid = BlockID(header.hash(), PartSetHeader(1, header.hash()))
        rows = []
        for idx, v in enumerate(vs.validators):
            ts = Timestamp(T0 + h, idx)
            sb = canonical.canonical_vote_bytes(
                CHAIN_ID, canonical.PRECOMMIT_TYPE, h, 0, bid, ts)
            sig = by_addr[v.address].sign(sb)
            if h == SECP_H1 and idx == tampered:
                sig = sig[:9] + bytes([sig[9] ^ 1]) + sig[10:]
            rows.append((v.pub_key.data, sb, CommitSig(
                BLOCK_ID_FLAG_COMMIT, v.address, ts, sig)))
        if h == SECP_H1 and doubled:
            rows[doubled[1]] = rows[doubled[0]]
        blocks[h] = lv.LightBlock(lv.SignedHeader(header, Commit(
            h, 0, bid, [cs for _, _, cs in rows])), vs)
        plain[h] = {
            "height": h, "time_ns": header.time.to_ns(),
            "pubs": [pub for pub, _, _ in rows],
            "powers": [v.voting_power for v in vs.validators],
            "msgs": [sb for _, sb, _ in rows],
            "sigs": [cs.signature for _, _, cs in rows]}
    return blocks, plain


def secp_step(blocks):
    """One `verify_light_block_at_height` of the second block from the
    trusted first, through the device path's batch_fn (the XLA ECDSA
    kernel, one 64-row pass a check): (outcome in the reference's
    words, the stage ring's names)."""
    from cometbft_tpu.libs import tracing

    c = lc.Client(CHAIN_ID, lc.Provider(CHAIN_ID, blocks.get),
                  witnesses=[], skipping=True, trust_level=(1, 3),
                  batch_fn=validation.device_batch_fn(use_pallas=False))
    c.trust_light_block(blocks[SECP_H0])
    tracing.set_clock(None)  # an empty stage ring
    try:
        c.verify_light_block_at_height(SECP_H1, now=SECP_NOW)
        out = ("ok",)
    except lv.ErrInvalidHeader as e:
        cause = e.__cause__
        if isinstance(cause, validation.InvalidSignatureError):
            out = ("invalid_header", "invalid_signature", cause.idx)
        elif isinstance(cause, validation.NotEnoughPowerError):
            out = ("invalid_header", "not_enough_power", cause.needed)
        else:
            out = ("invalid_header", "double_vote", str(cause)[17:])
    except lv.ErrNewValSetCantBeTrusted:
        # the client asked for the pivot halfway, which this provider
        # lacks: upstream's loop then ends with the error that asked
        out = ("bisects",)
    stored = c.store.get(SECP_H1) is not None
    assert stored == (out == ("ok",))
    return out, [r for r in tracing.stage_records()
                 if r[0].startswith("light.")]


def between_the_checks(plain_reference, plain):
    """A commit index only the new-set check examines."""
    ecdsa = plain_reference.ecdsa
    old, new = plain[SECP_H0], plain[SECP_H1]
    trusting = ecdsa.trusting_rows(
        {ecdsa.address(k): (k, p)
         for k, p in zip(old["pubs"], old["powers"])},
        [ecdsa.address(k) for k in new["pubs"]], new["sigs"])[0]
    light = ecdsa.light_rows(new["powers"], new["sigs"])[0]
    assert trusting[-1] + 1 < light[-1]
    return trusting, light[-1] - 1


@pytest.mark.parametrize("case", ["accepted", "refused-by-the-new-set",
                                  "too-few-old-seats", "double-vote"])
def test_a_secp256k1_skip_ends_as_the_plain_reference_says(
        plain_reference, case):
    ecdsa = plain_reference.ecdsa
    kw = {"changed": 40 if case == "too-few-old-seats" else 5}
    _, plain = secp_chain(**kw)
    if case == "refused-by-the-new-set":
        kw["tampered"] = between_the_checks(plain_reference, plain)[1]
    if case == "double-vote":
        rows = between_the_checks(plain_reference, plain)[0]
        kw["doubled"] = (rows[1], rows[2])
    blocks, plain = secp_chain(**kw)
    want = ecdsa.verify_non_adjacent(plain[SECP_H0], plain[SECP_H1],
                                     SECP_NOW.to_ns(), 1e6)
    got, stages = secp_step(blocks)
    first = {"accepted": "ok", "too-few-old-seats": "cant_be_trusted"}
    assert want[0] == first.get(case, "invalid_header")
    # too few of the old seats signed: the client goes on to bisect
    assert got == (("bisects",) if case == "too-few-old-seats" else want)
    if case == "refused-by-the-new-set":
        assert want == ("invalid_header", "invalid_signature",
                        kw["tampered"])
    if case == "double-vote":
        assert want[:2] == ("invalid_header", "double_vote")
    # the target's fetch, then the step and the checks it reached,
    # each closed on its way out; a refused trusting check says so, and
    # the client goes on to fetch the pivot halfway
    names = [r[0] for r in stages]
    reached = {"accepted": 2, "refused-by-the-new-set": 2,
               "too-few-old-seats": 1, "double-vote": 1}[case]
    refused = case == "too-few-old-seats"
    assert names == ["light.fetch"] + ["light.trusting", "light.new_set"][
        :reached] + ["light.step"] + ["light.fetch"] * refused
    assert stages[0][4] == {"height": SECP_H1, "pivot": 0}
    if refused:
        assert stages[-1][4] == {"height": (SECP_H0 + SECP_H1) // 2,
                                 "pivot": 1}
    assert stages[1][4] == {"height": SECP_H1, "refused": int(refused)}
    step = stages[reached + 1]
    assert step[4] == {"adjacent": 0, "height": SECP_H1}
    for name, t0, dur, _, _ in stages[1:reached + 1]:
        assert step[1] <= t0 and t0 + dur <= step[1] + step[2]


def test_too_few_old_seats_cannot_be_trusted(plain_reference):
    """`verify_non_adjacent` itself, where the client above bisects:
    ErrNewValSetCantBeTrusted, with the power the reference names."""
    blocks, plain = secp_chain(changed=40)
    want = plain_reference.ecdsa.verify_non_adjacent(
        plain[SECP_H0], plain[SECP_H1], SECP_NOW.to_ns(), 1e6)
    old, new = blocks[SECP_H0], blocks[SECP_H1]
    with pytest.raises(lv.ErrNewValSetCantBeTrusted) as ei:
        lv.verify_non_adjacent(
            CHAIN_ID, old.signed_header, old.validator_set,
            new.signed_header, new.validator_set, 1e6, SECP_NOW,
            batch_fn=validation.device_batch_fn(use_pallas=False))
    assert want == ("cant_be_trusted", ei.value.__cause__.needed)


def test_an_adjacent_step_records_the_new_set_check_alone():
    from cometbft_tpu.libs import tracing

    keys = keys_for(1, 4)
    chain = LightChain({h: keys for h in range(1, 3)})
    c = make_client(chain)
    tracing.set_clock(None)
    c.verify_light_block_at_height(2, now=NOW)
    recs = [r for r in tracing.stage_records() if r[0].startswith("light.")]
    assert [r[0] for r in recs] == ["light.fetch", "light.new_set",
                                    "light.step"]
    assert recs[-1][4] == {"adjacent": 1, "height": 2}


# --------------------------------------------------------------------------
# upstream's skipping order, against the benchmark's plain reference
# (benchmarks/reference/bisection.py) at 48 ed25519 validators
# --------------------------------------------------------------------------

BIS_H0, BIS_GAP = 10, 100
BIS_NOW = Timestamp(T0 + BIS_H0 + 8 * BIS_GAP + 60, 0)


@functools.lru_cache(maxsize=1)
def _bis_keys():
    return [PrivKey.generate(i.to_bytes(2, "big") + b"\x5b" * 30)
            for i in range(160)]


def _bis_power(i):
    return 500 + (37 * i) % 1001


def bisect_chain(plan, tamper=None):
    """Light blocks k = 0..len(plan)-1 at BIS_H0 + BIS_GAP k, block k's
    set holding the keys `plan[k]` at unequal powers, every validator
    signing; `tamper` = (k, row) flips that row's signature. Returns
    ({height: LightBlock}, {height: the block as the plain reference
    takes it})."""
    keys = _bis_keys()
    blocks, plain = {}, {}
    for k, seats in enumerate(plan):
        h = BIS_H0 + BIS_GAP * k
        by_addr = {keys[i].pub_key().address(): keys[i] for i in seats}
        vs = ValidatorSet([Validator(keys[i].pub_key(), _bis_power(i))
                           for i in seats])
        header = Header(
            chain_id=CHAIN_ID, height=h, time=Timestamp(T0 + h, 0),
            last_block_id=BlockID(), validators_hash=vs.hash(),
            next_validators_hash=vs.hash(),
            proposer_address=vs.validators[0].address,
            app_hash=b"\x01" * 32)
        bid = BlockID(header.hash(), PartSetHeader(1, header.hash()))
        msgs, sigs = [], []
        for idx, v in enumerate(vs.validators):
            ts = Timestamp(T0 + h, idx)
            msgs.append(canonical.canonical_vote_bytes(
                CHAIN_ID, canonical.PRECOMMIT_TYPE, h, 0, bid, ts))
            sigs.append(by_addr[v.address].sign(msgs[-1]))
        if tamper is not None and tamper[0] == k:
            sig = sigs[tamper[1]]
            sigs[tamper[1]] = sig[:5] + bytes([sig[5] ^ 1]) + sig[6:]
        commit = Commit(h, 0, bid, [
            CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, Timestamp(T0 + h, i),
                      sig)
            for i, (v, sig) in enumerate(zip(vs.validators, sigs))])
        blocks[h] = lv.LightBlock(lv.SignedHeader(header, commit), vs)
        plain[h] = {
            "height": h, "time_ns": header.time.to_ns(),
            "validators_hash": header.validators_hash,
            "next_validators_hash": header.next_validators_hash,
            "pubs": [v.pub_key.data for v in vs.validators],
            "powers": [v.voting_power for v in vs.validators],
            "msgs": msgs, "sigs": sigs}
    return blocks, plain


def _sliding(slide=10):
    return [range(slide * k, slide * k + 48) for k in range(9)]


def _bisection_case(case, plain_reference):
    """(plan, tamper, heights the provider lacks) of each case."""
    if case == "changes-and-changes-back":
        a, e = range(48), [*range(24), *range(48, 72)]
        b, d = range(100, 148), [*range(8), *range(48, 88)]
        return [a, a, e, b, b, b, b, d, d], None, ()
    if case == "tampered-pivot":
        # a row of H6 the new-set check collects whose seat H4 lacks
        _, plain = bisect_chain(_sliding())
        h4, h6 = (plain[BIS_H0 + BIS_GAP * k] for k in (4, 6))
        ecdsa = plain_reference.ecdsa
        light = ecdsa.light_rows(h6["powers"], h6["sigs"])[0]
        row = next(i for i in light if h6["pubs"][i] not in h4["pubs"])
        return _sliding(), (6, row), ()
    missing = (BIS_H0 + 2 * BIS_GAP,) if case == "missing-pivot" else ()
    return _sliding(), None, missing


@pytest.mark.parametrize("case", ["sliding", "changes-and-changes-back",
                                  "tampered-pivot", "missing-pivot"])
def test_bisection_follows_upstreams_skipping_loop(
        plain_reference, monkeypatch, case):
    """The client's attempts (trusted height, candidate height, result),
    its verdict and the heights it verified equal upstream's
    `verifySkipping` as the plain reference writes it out."""
    bisection = plain_reference.bisection
    plan, tamper, missing = _bisection_case(case, plain_reference)
    blocks, plain = bisect_chain(plan, tamper)
    target = BIS_H0 + BIS_GAP * 8

    def fetch_plain(h):
        return None if h in missing else plain.get(h)

    want = bisection.verify_skipping(
        plain[BIS_H0], target, fetch_plain, BIS_NOW.to_ns(), 1e6)

    def outcome(err):
        if err is None:
            return ("ok",)
        cause = err.__cause__
        if isinstance(err, lv.ErrNewValSetCantBeTrusted):
            return ("cant_be_trusted", cause.needed)
        assert isinstance(cause, validation.InvalidSignatureError)
        return ("invalid_header", "invalid_signature", cause.idx)

    attempts = []

    def recorded(verify, new_at):
        def attempt(*args, **kw):
            err = None
            try:
                verify(*args, **kw)
            except lv.LightClientError as e:
                err = e
                raise
            finally:
                attempts.append((args[1].height, args[new_at].height,
                                 outcome(err)))
        return attempt

    monkeypatch.setattr(lc, "verify_non_adjacent",
                        recorded(lv.verify_non_adjacent, 3))
    monkeypatch.setattr(lc, "verify_adjacent",
                        recorded(lv.verify_adjacent, 2))
    c = lc.Client(CHAIN_ID, lc.Provider(
        CHAIN_ID, lambda h: None if h in missing else blocks.get(h)),
        witnesses=[], trusting_period=1e6, trust_level=(1, 3),
        batch_fn=validation.oracle_batch_fn())
    c.trust_light_block(blocks[BIS_H0])
    try:
        c.verify_light_block_at_height(target, now=BIS_NOW)
        verdict = ("trusted",)
    except lv.LightClientError as e:
        verdict = ("refused", attempts[-1][1]) + outcome(e)
    assert (verdict, c.store.heights(), attempts) == want
    pivots = {"sliding": 3, "changes-and-changes-back": 1,
              "tampered-pivot": 2, "missing-pivot": 0}[case]
    assert len(want[1]) - 1 - (want[0] == ("trusted",)) == pivots
    if case == "sliding":  # 4 refused, then H2, H4, H6 and the target
        assert [a[:2] for a in want[2]] == [
            (BIS_H0 + BIS_GAP * i, BIS_H0 + BIS_GAP * j)
            for i, j in ((0, 8), (0, 4), (0, 2), (2, 8), (2, 4), (4, 8),
                         (4, 6), (6, 8))]
        assert [a[2][0] for a in want[2]] == [
            "cant_be_trusted", "cant_be_trusted", "ok", "cant_be_trusted",
            "ok", "cant_be_trusted", "ok", "ok"]
    if case == "changes-and-changes-back":
        # after H2 the target is tried again and trusted from there; a
        # client that went on to its next cached pivot (H4) bisects into
        # the middle era instead, down to heights between light blocks
        assert want[1] == [BIS_H0, BIS_H0 + 2 * BIS_GAP, target]
    if case == "tampered-pivot":
        assert want[0] == ("refused", BIS_H0 + 6 * BIS_GAP, "invalid_header",
                           "invalid_signature", tamper[1])
        assert len(want[2]) == 7
    if case == "missing-pivot":
        assert want[0][:3] == ("refused", BIS_H0 + 4 * BIS_GAP,
                               "cant_be_trusted")
