"""Byzantine simnet: deterministic adversarial scenarios over real
node/consensus stacks (cometbft_tpu/simnet/).

Tier-1 scenarios are budgeted small (<= a few simulated heights, no
kernel compiles — everything is host-path crypto); the long randomized
schedules live in tools/simnet_fuzz.py. File named test_simnet.py so it
lands late in the alphabetical tier-1 order (ROADMAP timeout note).

Every scenario asserts safety (no conflicting commits) and, where the
schedule permits a quorum, liveness after heal. A failing assertion
raises SimnetFailure carrying the exact seed + schedule replay blob.
"""
import json

import pytest

from cometbft_tpu.libs import failpoints as fp
from cometbft_tpu.libs import tracing
from cometbft_tpu.simnet import (
    Simnet,
    SimnetFailure,
    schedule_to_json,
    validate_schedule,
)
from cometbft_tpu.types.evidence import (
    DuplicateVoteEvidence,
    LightClientAttackEvidence,
)

pytestmark = pytest.mark.simnet


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.reset()
    yield
    fp.reset()


FAULTY_SCHEDULE = [
    {"at": 0.05, "op": "link", "drop": 0.08, "delay": 0.02,
     "jitter": 0.01, "dup": 0.05, "reorder": 0.05},
    {"at": 0.2, "op": "partition", "groups": [[0, 1, 2], [3]]},
    {"at": 0.3, "op": "tx", "node": 0, "data": b"sim=net".hex()},
    {"at": 1.0, "op": "heal"},
]


def test_quick_consensus_no_faults(tmp_path):
    """Baseline: 4 simulated validators reach height 3 and agree."""
    with Simnet(4, seed=1, basedir=str(tmp_path)) as sim:
        assert sim.run([], until_height=3, max_time=60.0)
        assert all(n.height() >= 3 for n in sim.net.nodes)
        sim.assert_safety()
        # all four committed the same block 2
        hashes = sim.commit_hashes()
        assert len({h[2] for h in hashes}) == 1


def test_determinism_same_seed_same_chain(tmp_path):
    """ISSUE 3 acceptance: the same (seed, schedule) twice yields
    identical commit hashes at every height on every node — drops,
    duplication, reordering, and a partition included."""

    def run_once(tag):
        with Simnet(4, seed=77, basedir=str(tmp_path / tag)) as sim:
            assert sim.run(FAULTY_SCHEDULE, until_height=4,
                           max_time=120.0)
            sim.assert_safety()
            return sim.commit_hashes()

    assert run_once("a") == run_once("b")


def test_template_packing_determinism_vs_legacy(tmp_path):
    """ISSUE 4 satellite (zero-copy hot path): the same (seed,
    schedule) with the template-packing path FORCED ON yields commit
    hashes byte-identical to the legacy per-vote packing path at every
    height on every node — a patching bug that rejected (or mis-built)
    any sign-bytes would wedge a round or fork the runs. Also checks a
    REAL committed commit's template rows against its per-vote
    sign-bytes, byte for byte."""
    from cometbft_tpu.types import validation as tv

    sched = [
        {"at": 0.05, "op": "link", "drop": 0.05, "delay": 0.01,
         "jitter": 0.005},
        {"at": 0.3, "op": "tx", "node": 1, "data": b"zero=copy".hex()},
    ]

    def run_once(tag, on):
        prev = tv.set_template_packing(on)
        try:
            assert tv.template_packing_enabled() == on
            with Simnet(4, seed=44, basedir=str(tmp_path / tag)) as sim:
                assert sim.run(sched, until_height=2, max_time=120.0)
                sim.assert_safety()
                hashes = sim.commit_hashes()
                # byte-level guard on a commit the network produced
                store = sim.net.nodes[0].node.block_store
                commit = store.load_seen_commit(1)
                chain = sim.net.chain_id
                idxs = list(range(len(commit.signatures)))
                assert commit.sign_bytes_rows(chain, idxs) == [
                    commit.vote_sign_bytes(chain, i) for i in idxs
                ]
                return hashes
        finally:
            tv.set_template_packing(prev)

    assert run_once("tmpl", True) == run_once("legacy", False)


def test_device_stamping_toggle_determinism(tmp_path):
    """ISSUE 19 satellite: the same (seed, schedule) with device
    stamping enabled vs disabled yields commit hashes byte-identical
    at every height on every node, with a RUNNING verify plane
    mounted. The delta arm exercises the whole new seam — vote_set
    attaches per-row (template, secs, nanos) stamp metadata to every
    plane submission and requests a template prefetch — and on a
    host-path plane the flush must degrade to the host pack honestly
    (every ledger record's stamp column says "host"): metadata that
    perturbed packing, verdicts, or scheduling would fork the runs or
    wedge a round."""
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane
    from cometbft_tpu.verifyplane import fused as fz

    sched = [
        {"at": 0.05, "op": "link", "drop": 0.04, "delay": 0.01,
         "jitter": 0.005},
        {"at": 0.3, "op": "tx", "node": 1, "data": b"de=lta".hex()},
    ]

    def run_once(tag, on):
        prev = fz.DEVICE_STAMP
        fz.set_device_stamping(on)
        plane = VerifyPlane(window_ms=0.5, use_device=False)
        plane.start()
        set_global_plane(plane)
        try:
            with Simnet(4, seed=91, basedir=str(tmp_path / tag)) as sim:
                assert sim.run(sched, until_height=2, max_time=120.0)
                sim.assert_safety()
                hashes = sim.commit_hashes()
        finally:
            set_global_plane(None)
            plane.stop()
            fz.set_device_stamping(prev)
        assert plane.rows_verified > 0  # votes really rode the plane
        recs = plane.dump_flushes()["flushes"]
        assert recs and all(r["stamp"] == "host" for r in recs), recs
        return hashes

    assert run_once("stamp", True) == run_once("legacy", False)


def test_partition_minority_stalls_then_catches_up(tmp_path):
    """A partitioned validator cannot commit (safety) while the 3/4
    majority keeps going; after heal the catch-up pushes restore it."""
    with Simnet(4, seed=5, basedir=str(tmp_path)) as sim:
        sim.run([], until_height=2, max_time=60.0)
        cut = sim.net.now
        sim.run([{"at": cut, "op": "partition",
                  "groups": [[0, 1, 2], [3]]}], max_time=0.1)
        victim = sim.net.nodes[3]
        h_cut = victim.height()
        majority_target = max(n.height() for n in sim.net.nodes) + 2
        assert sim.run(
            [],
            until=lambda: all(sim.net.nodes[i].height()
                              >= majority_target for i in (0, 1, 2)),
            max_time=60.0,
        )
        assert victim.height() <= h_cut + 1  # at most one in-flight commit
        sim.run([{"at": sim.net.now, "op": "heal"}], max_time=0.1)
        assert sim.run(
            [], until=lambda: victim.height() >= majority_target,
            max_time=60.0,
        ), f"victim stuck at {victim.height()}"
        sim.assert_safety()


def test_equivocator_lands_in_committed_evidence(tmp_path):
    """ISSUE 3 acceptance: a double-signing validator's conflicting
    prevotes surface as DuplicateVoteEvidence (height_vote_set conflict
    detection), flow through the pool, and end committed in a block on
    every node — chain stays safe and live throughout."""
    with Simnet(4, seed=11, basedir=str(tmp_path)) as sim:
        sim.run([{"at": 0.12, "op": "equivocate", "node": 3, "votes": 2}],
                until_height=2, max_time=60.0)
        ev = sim.assert_evidence_committed(
            predicate=lambda e: isinstance(e, DuplicateVoteEvidence)
        )
        assert ev.vote_a.validator_address == \
            sim.net.privs[3].pub_key().address()
        sim.assert_safety()
        sim.assert_liveness(min_new_heights=2, max_time=30.0)


def test_garbage_signer_does_not_poison_verify_plane(tmp_path):
    """ISSUE 3 acceptance: forged signatures coalesce through a RUNNING
    verify plane with honest votes; verdicts reject them, consensus
    proceeds, and the circuit breaker stays closed (no permanent host
    fallback) — an invalid signature is a verdict, not a device
    fault."""
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    plane = VerifyPlane(window_ms=0.5, use_device=False)
    plane.start()
    set_global_plane(plane)
    try:
        with Simnet(4, seed=22, basedir=str(tmp_path)) as sim:
            assert sim.run(
                [{"at": 0.1, "op": "garbage", "node": 1, "votes": 4}],
                until_height=4, max_time=60.0,
            )
            sim.assert_safety()
        stats = plane.stats()
        assert stats["breaker_state"] == "closed", stats
        assert plane.rows_verified > 0  # votes really rode the plane
    finally:
        set_global_plane(None)
        plane.stop()


def test_flush_ledger_deterministic_under_simnet(tmp_path):
    """ISSUE 6 acceptance: the always-on flush ledger rides the virtual
    clock — the same (seed, schedule) with a verify plane running
    produces IDENTICAL ledger records (sequence, composition, paths,
    and every stage timing), because submissions are serialized by the
    single-threaded event loop and every stamp comes from
    tracing.monotonic_ns() (= Timestamp.now() under simnet). Also
    proves the ledger is on by default (no knob was touched) and
    survives plane.stop()."""
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    def run_once(tag):
        plane = VerifyPlane(window_ms=0.5, use_device=False)
        plane.start()
        set_global_plane(plane)
        try:
            with Simnet(3, seed=33, basedir=str(tmp_path / tag)) as sim:
                assert sim.run(
                    [{"at": 0.1, "op": "link", "drop": 0.03,
                      "delay": 0.01}],
                    until_height=2, max_time=60.0,
                )
                sim.assert_safety()
        finally:
            set_global_plane(None)
            plane.stop()
        recs = plane.dump_flushes()["flushes"]
        assert recs, "plane saw no flushes — ledger not always-on?"
        return recs

    a = run_once("a")
    b = run_once("b")
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    # and the stamps really rode the virtual clock: inside the sim epoch
    from cometbft_tpu.simnet.core import SIM_EPOCH_SECONDS

    assert all(r["ts_ms"] >= SIM_EPOCH_SECONDS * 1e3 for r in a)


def test_flush_ledger_deterministic_with_deck_enabled(tmp_path):
    """ISSUE 11: the pipelined flight deck must not perturb simnet
    determinism — the same (seed, schedule) with pipeline_flights=2
    produces byte-identical ledgers INCLUDING the airborne counts.
    Host-path flushes are synchronous (the deck only ever holds device
    flights), so airborne must stay 0 here: a nonzero count would mean
    the deck's real-clock landing poll leaked onto the simnet path."""
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    def run_once(tag):
        plane = VerifyPlane(window_ms=0.5, use_device=False,
                            pipeline_flights=2)
        plane.start()
        set_global_plane(plane)
        try:
            with Simnet(3, seed=47, basedir=str(tmp_path / tag)) as sim:
                assert sim.run(
                    [{"at": 0.1, "op": "link", "drop": 0.02,
                      "delay": 0.01}],
                    until_height=2, max_time=60.0,
                )
                sim.assert_safety()
        finally:
            set_global_plane(None)
            plane.stop()
        recs = plane.dump_flushes()["flushes"]
        assert recs, "plane saw no flushes"
        return recs

    a = run_once("a")
    b = run_once("b")
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert all(r["airborne"] == 0 and r["n_host"] == 1
               and r["dev0"] == 0 for r in a)


def test_height_ledger_deterministic_under_simnet(tmp_path):
    """ISSUE 13 acceptance: the always-on height ledger rides the
    virtual clock — the same (seed, schedule) produces byte-identical
    per-height records on every node (stage timeline, rounds, late
    offsets, absent bitmaps — everything), with a verify plane RUNNING
    so the flush-seq join is exercised too. Also proves the ledger is
    on by default and that the plane join attributes real flushes."""
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    def run_once(tag):
        plane = VerifyPlane(window_ms=0.5, use_device=False)
        plane.start()
        set_global_plane(plane)
        try:
            with Simnet(3, seed=61, basedir=str(tmp_path / tag)) as sim:
                assert sim.run(
                    [{"at": 0.1, "op": "link", "drop": 0.03,
                      "delay": 0.01}],
                    until_height=3, max_time=60.0,
                )
                sim.assert_safety()
                recs = [n.node.consensus.height_ledger.records()
                        for n in sim.net.nodes]
        finally:
            set_global_plane(None)
            plane.stop()
        for node_recs in recs:
            assert node_recs, "height ledger recorded nothing"
        return recs

    a = run_once("a")
    b = run_once("b")
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    # the stamps really rode the virtual clock, and the plane join
    # attributed at least one flush somewhere on the run
    from cometbft_tpu.simnet.core import SIM_EPOCH_SECONDS

    flat = [r for node_recs in a for r in node_recs]
    assert all(r["ts_ms"] >= SIM_EPOCH_SECONDS * 1e3 for r in flat)
    assert any(r["plane_flushes"] > 0 for r in flat), \
        "no height ever joined a verify-plane flush"
    assert all(r["apply_ms"] >= r["commit_ms"] >= 0 for r in flat)


def test_peer_ledger_partition_visible_and_deterministic(tmp_path):
    """ISSUE 14 acceptance: a scheduled partition is VISIBLE in the
    gossip observatory — messages lost on downed links are attributed
    to the partitioned peers (link_drops on exactly the cross-group
    records), injected drop faults attribute as inj_drops, vote
    first-seen routing is populated — and the same (seed, schedule)
    replays every node's peer ledger byte-identically (stamps on the
    virtual clock, traffic a pure function of the schedule), with a
    verify plane RUNNING so plane-era timing can't leak in."""
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    def run_once(tag):
        plane = VerifyPlane(window_ms=0.5, use_device=False)
        plane.start()
        set_global_plane(plane)
        try:
            with Simnet(4, seed=83, basedir=str(tmp_path / tag)) as sim:
                assert sim.run(
                    [{"at": 0.1, "op": "link", "drop": 0.05,
                      "delay": 0.01},
                     {"at": 0.5, "op": "partition",
                      "groups": [[0, 1], [2, 3]]},
                     {"at": 3.0, "op": "heal"}],
                    until_height=3, max_time=90.0,
                )
                sim.assert_safety()
                return [n.peer_ledger.dump() for n in sim.net.nodes]
        finally:
            set_global_plane(None)
            plane.stop()

    a = run_once("a")
    b = run_once("b")
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    # partition attribution: node 0's records for n2/n3 ate link
    # drops; its record for n1 (same side) never did
    n0 = {p["peer"]: p for p in a[0]["peers"]}
    assert n0["n2"]["link_drops"] + n0["n3"]["link_drops"] > 0, n0
    assert n0["n1"]["link_drops"] == 0, n0
    # the 5% drop fault attributed itself as injected, not network
    assert a[0]["summary"]["inj_drops"] > 0
    # real traffic flowed and votes were route-stamped on every node
    for dump in a:
        s = dump["summary"]
        assert s["msgs_tx"] > 0 and s["msgs_rx"] > 0
        assert s["votes"]["seen"] > 0
    for dump in a:
        for p in dump["peers"]:
            assert p["state"] in ("up", "dropped")


def test_incident_stream_deterministic_under_simnet(tmp_path):
    """ISSUE 13 acceptance: a partition-induced commit stall fires a
    commit_stall incident (plus round escalation), and the same (seed,
    schedule) freezes a byte-identical incident stream — the snapshot
    bundles (height/flush tails, counter samples, virtual timestamps)
    included."""
    from cometbft_tpu.libs import incidents

    def run_once(tag):
        rec = incidents.IncidentRecorder(
            commit_stall_s=3.0, round_limit=3, cooldown_s=5.0)
        old = incidents.install(rec)
        try:
            with Simnet(4, seed=71, basedir=str(tmp_path / tag)) as sim:
                sim.run([], until_height=2, max_time=60.0)
                cut = sim.net.now
                # 2/2 split: NO quorum anywhere — commits stop, rounds
                # escalate, and every step transition pokes the watchdog
                sim.run([{"at": cut, "op": "partition",
                          "groups": [[0, 1], [2, 3]]},
                         {"at": cut + 12.0, "op": "heal"}],
                        max_time=14.0)
                assert sim.run([], until_height=3, max_time=60.0), \
                    "chain did not recover after heal"
                sim.assert_safety()
                return rec.dump()
        finally:
            incidents.install(old)

    a = run_once("a")
    b = run_once("b")
    assert a["fired"].get("commit_stall", 0) >= 1, a["fired"]
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    snap = next(s for s in a["incidents"]
                if s["trigger"] == "commit_stall")
    assert snap["detail"]["stalled_s"] >= 3.0
    assert snap["height_tail"], "no height tail frozen in the snapshot"


def test_failure_blob_carries_incident_and_height_tails():
    """A SimnetFailure raised while incidents/heights were recorded
    attaches their tails ABOVE the replay blob (which must stay last
    and parseable) — the flush-ledger-tail contract extended to the
    flight recorder."""
    from cometbft_tpu.libs import incidents

    rec = incidents.IncidentRecorder(cooldown_s=0.0)
    old = incidents.install(rec)
    try:
        fp.registry().arm_from_spec("incidents.force=raise*1")
        incidents.poke(height=9, round_=2)
        msg = str(SimnetFailure("boom", 5, [{"at": 0.1, "op": "heal"}]))
    finally:
        incidents.install(old)
        fp.reset()
    assert "incidents: #0 forced h=9 r=2" in msg
    # the replay blob is still the LAST line and parses
    replay = msg.rsplit("replay: ", 1)[1]
    doc = json.loads(replay)
    assert doc["seed"] == 5


def test_light_client_attack_evidence_committed(tmp_path):
    """A >=1/3 coalition's forged header reaches one honest node as
    LightClientAttackEvidence (with its conflicting-commit proof),
    passes verify_light_client_attack, gossips, and is committed."""
    with Simnet(4, seed=12, basedir=str(tmp_path)) as sim:
        sim.run([], until_height=2, max_time=60.0)
        sim.run([{"at": sim.net.now + 0.05, "op": "light_attack",
                  "byz": [2, 3], "target": 0, "height": 1}],
                max_time=1.0)
        ev = sim.assert_evidence_committed(
            predicate=lambda e: isinstance(e, LightClientAttackEvidence)
        )
        assert len(ev.byzantine_validators) == 2
        assert ev.common_height == 1
        sim.assert_safety()


def test_failpoint_crash_and_wal_recovery(tmp_path):
    """A consensus.wal.post_vote crash failpoint armed on ONE node's
    private registry halts exactly that node; a later restart rebuilds
    it over the same home dir (WAL catchup replay + handshake replay)
    and it catches back up to the tip."""
    with Simnet(4, seed=21, basedir=str(tmp_path)) as sim:
        sim.run([
            {"at": 0.15, "op": "failpoint", "node": 2,
             "spec": "consensus.wal.post_vote=crash*1"},
            {"at": 2.0, "op": "restart", "node": 2},
        ], until_height=4, max_time=120.0)
        n2 = sim.net.nodes[2]
        # the crash fired on node 2's registry and nowhere else
        assert n2.registry.stats("consensus.wal.post_vote")["fires"] == 1
        for i in (0, 1, 3):
            st = sim.net.nodes[i].registry.stats(
                "consensus.wal.post_vote")
            assert st is None or st["fires"] == 0
        tip = max(n.height() for n in sim.net.nodes if n.alive)
        assert sim.run(
            [], until=lambda: n2.alive and n2.height() >= tip,
            max_time=60.0,
        ), (n2.alive, n2.height(), tip)
        assert n2.restarts == 1
        sim.assert_safety()


def test_failure_carries_replay_blob(tmp_path):
    """Every simnet assertion failure must print the reproducing seed +
    schedule: kill beyond quorum, then ask for liveness."""
    sched = [{"at": 0.2, "op": "kill", "node": 2},
             {"at": 0.25, "op": "kill", "node": 3}]
    with Simnet(4, seed=9, basedir=str(tmp_path)) as sim:
        sim.run(sched, max_time=0.5)
        with pytest.raises(SimnetFailure) as ei:
            sim.assert_liveness(min_new_heights=1, max_time=5.0)
        msg = str(ei.value)
        assert "replay:" in msg
        blob = json.loads(msg.split("replay:", 1)[1])
        assert blob["seed"] == 9
        assert blob["schedule"] == sched
        # the blob round-trips through the schedule validator
        validate_schedule(blob["schedule"], 4)
        assert schedule_to_json(9, sched) == json.dumps(
            blob, sort_keys=True)


def test_failure_carries_flush_ledger_tail():
    """ISSUE 6: when a verify plane ran, a SimnetFailure carries the
    ledger tail (the last flushes' stage costs) — and the replay blob
    stays the LAST line, still one parseable JSON document."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    plane = VerifyPlane(window_ms=0.2, use_device=False)
    plane.start()
    set_global_plane(plane)
    try:
        k = PrivKey.generate(b"\x09" * 32)
        plane.submit(k.pub_key(), b"m", k.sign(b"m")).result(5)
    finally:
        set_global_plane(None)
        plane.stop()
    sched = [{"at": 0.1, "op": "heal"}]
    msg = str(SimnetFailure("boom", 7, sched))
    assert "flush ledger tail:" in msg
    blob = json.loads(msg.split("replay:", 1)[1])
    assert blob["seed"] == 7 and blob["schedule"] == sched


def test_stale_ledger_tail_skipped(tmp_path):
    """The module-global ledger survives unrelated earlier planes in
    the same process; a simulation during which the ledger never moved
    must not attach that stale history to its failure blob."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    plane = VerifyPlane(window_ms=0.2, use_device=False)
    plane.start()
    set_global_plane(plane)
    try:
        k = PrivKey.generate(b"\x0c" * 32)
        plane.submit(k.pub_key(), b"m", k.sign(b"m")).result(5)
    finally:
        set_global_plane(None)
        plane.stop()
    # the stopped plane is still readable history (/dump_flushes), but
    # this sim never runs one — its blob must skip the foreign tail
    with Simnet(2, seed=13, basedir=str(tmp_path)) as sim:
        msg = str(sim._fail("boom"))
    assert "flush ledger tail:" not in msg
    blob = json.loads(msg.split("replay:", 1)[1])
    assert blob["seed"] == 13


def test_gateway_forged_header_scenario(tmp_path):
    """ISSUE 8: a light-client gateway mounted on a full node serves K
    clients while a lying primary feeds a SUBSET of them forged
    headers. The gateway answers the deceived clients with divergent
    verdicts, drives LightClientAttackEvidence through the existing
    pool -> gossip -> block pipeline, honest clients complete their
    sync untouched — and the whole verdict stream replays
    byte-identically for the same (seed, schedule)."""

    def run_once(tag):
        with Simnet(4, seed=37, basedir=str(tmp_path / tag)) as sim:
            sim.run([], until_height=2, max_time=60.0)
            sim.run([{"at": sim.net.now + 0.05, "op": "gateway_sync",
                      "node": 0, "clients": 6, "trusted": 1,
                      "target": 2, "forged": [1, 4], "byz": [2, 3]}],
                    max_time=2.0)
            assert len(sim.gateway_results) == 6
            ev = sim.assert_evidence_committed(
                predicate=lambda e: isinstance(
                    e, LightClientAttackEvidence)
            )
            assert ev.conflicting_height == 2
            assert ev.common_height == 1
            assert len(ev.byzantine_validators) == 2
            sim.assert_safety()
            return sim.gateway_results, ev.hash()

    results, ev_hash = run_once("a")
    by_seq = {r["seq"]: r for r in results}
    for k in range(6):
        if k in (1, 4):
            assert by_seq[k]["status"] == "divergent", by_seq[k]
        else:
            assert by_seq[k]["status"] == "verified", by_seq[k]
    # ONE attack entered the pool; the duplicate claim deduped there
    assert sum(1 for r in results if r.get("evidence_added")) == 1
    # honest clients all landed on the same (true) header
    honest = {r["target_hash"] for r in results
              if r["status"] == "verified"}
    assert len(honest) == 1

    # byte-identical replay: verdict stream AND committed evidence
    results2, ev_hash2 = run_once("b")
    assert json.dumps(results, sort_keys=True) == \
        json.dumps(results2, sort_keys=True)
    assert ev_hash == ev_hash2


def test_remembered_valset_roots_stay_fresh_through_a_rotation(
        tmp_path, checked_valset_roots):
    """ValidatorSet.hash() remembers its root (ISSUE 26). Over a
    schedule whose app emits validator updates (an `epoch` op: kvstore
    ``val:`` txs through the real ABCI -> update_with_change_set ->
    state/execution.py path), every root any node is handed, from a
    memo or computed, equals a fresh merkle root (the wrapper asserts
    it inside the run); the rotation lands, so roots of two memberships
    were in play; and most answers came from the memo (few
    `valset.hash` stages for many calls)."""
    sched = [{"at": 0.3, "op": "epoch", "node": 0, "churn": 0.5}]
    with Simnet(4, seed=26, basedir=str(tmp_path), power=100_000,
                extra_validators=8) as sim:
        genesis_root = sim.net.genesis.validators.hash()
        assert sim.run(sched, until_height=6, max_time=90.0)
        sim.assert_safety()
        rec = sim.epoch_results[0]
        assert "error" not in rec and rec["out"] and rec["in"], rec
        finals = {n.node.consensus.state.validators.hash()
                  for n in sim.net.nodes}
        # read here: leaving the simnet restores the clock, which
        # clears the stage ring
        computes = sum(1 for r in tracing.stages()
                       if r[0] == "valset.hash")
    assert len(finals) == 1 and genesis_root not in finals
    assert genesis_root in checked_valset_roots
    assert finals <= set(checked_valset_roots)
    assert 2 <= computes < len(checked_valset_roots) // 2
