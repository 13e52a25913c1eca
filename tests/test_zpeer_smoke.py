"""Gossip observatory tier-1 wiring (ISSUE 14): peer-ledger record
shape over a REAL Switch pair on TCP (traffic counts, ping RTT measured
for real, drop attribution), the MConnection full-queue observability
(blocked puts / full drops distinguishable from a stopped conn), the
fuzzer's injected-fault attribution, GET+JSON-RPC /dump_peers
(including the stopping-switch concurrency hammer — the _LAST
pattern), the peer_report --diff regression detector, the
peer_starvation incident trigger, and the < 10 us/message budget.

Late in the alphabet on purpose (tier-1 ordering note in ROADMAP).
Host-only: the whole file must run with NO jax import (asserted).
"""
import copy
import json
import sys
import threading
import time
import urllib.request

import pytest

from cometbft_tpu.libs import failpoints as fp
from cometbft_tpu.libs import incidents
from cometbft_tpu.p2p import peerledger

_JAX_LOADED_BEFORE = "jax" in sys.modules


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.reset()
    yield
    fp.reset()


def test_record_shape_and_seam():
    """Every hook on the shared seam lands in the right FIELDS column;
    the live scratch list becomes the drop-ring slot (FlushLedger
    discipline) and readers never see the internal ping-stamp slots."""
    led = peerledger.PeerLedger()
    rec = led.open_peer("peer-a", True)
    peerledger.note_sent(rec, 0x22, 500)
    peerledger.note_sent(rec, 0x21, 100)
    peerledger.note_recv(rec, 0x22, 80, eof=False)
    peerledger.note_recv(rec, 0x22, 80, eof=True)
    peerledger.note_queue_depth(rec, 9)
    peerledger.note_queue_depth(rec, 2)
    peerledger.note_throttle(rec, 5.0)
    peerledger.note_link_drop(rec)
    recs = led.records()
    assert len(recs) == 1 and set(recs[0]) == set(led.FIELDS)
    r = recs[0]
    assert r["peer"] == "peer-a" and r["dir"] == "out"
    assert r["state"] == "up" and r["msgs_tx"] == 2
    assert r["bytes_tx"] == 600
    # one logical message from two packets
    assert r["msgs_rx"] == 1 and r["bytes_rx"] == 160
    assert r["chans"]["0x22"] == {"msgs_tx": 1, "bytes_tx": 500,
                                  "msgs_rx": 1, "bytes_rx": 160}
    assert r["q_depth"] == 2 and r["q_hiwater"] == 9
    assert r["throttle_stalls"] == 1 and r["throttle_ms"] == 5.0
    # the SAME list object is the ring slot after the drop
    led.drop_peer(rec, "test_drop")
    assert len(led) == 0
    post = led.records()[0]
    assert post["state"] == "dropped" and post["reason"] == "test_drop"
    assert post["msgs_tx"] == 2  # history intact
    # double-drop is idempotent (reconnect racing its teardown)
    led.drop_peer(rec, "again")
    assert led.summary()["peers_dropped"] == 1
    # lifecycle events recorded with the drop
    assert [e["event"] for e in led.events()] == ["up", "drop"]


def test_vote_route_counts_first_sight_duplicate_and_relay():
    """One vote seen from two peers and relayed once: the route names
    the FIRST delivering peer and one duplicate, the relay is counted,
    a relay of a vote never seen is ignored, and the summary's votes
    block carries all five counters."""
    led = peerledger.PeerLedger()
    key = (1, 0, 2, 3)
    assert led.note_vote_seen(key, "peer-a") is True
    assert led.note_vote_seen(key, "peer-b") is False
    led.note_vote_relayed(key)
    led.note_vote_relayed((9, 0, 2, 0))  # never seen: no slot, no count
    peer, dups, relay_ms = led.vote_route(*key)
    assert (peer, dups) == ("peer-a", 1) and relay_ms >= 0.0
    assert led.vote_route(9, 0, 2, 0) is None
    assert led.summary()["votes"] == {"seen": 1, "dups": 1, "relayed": 1,
                                      "tracked": 1, "dropped": 0}


def test_summary_totals_monotone_across_ring_eviction():
    """Review regression: the drop ring evicting an old record must
    NOT subtract its traffic from the summary totals — the /metrics
    counters sampled from them would read as a reset and fabricate
    rate spikes. Evicted records fold into retired totals."""
    led = peerledger.PeerLedger(capacity=16)
    last = 0
    for i in range(40):  # well past the 16-slot ring
        rec = led.open_peer(f"churn-{i}", True)
        peerledger.note_sent(rec, 0x22, 100)
        peerledger.note_full_drop(rec)
        led.drop_peer(rec, "churn")
        s = led.summary()
        assert s["msgs_tx"] >= last, (i, s["msgs_tx"], last)
        last = s["msgs_tx"]
    s = led.summary()
    assert s["msgs_tx"] == 40 and s["full_drops"] == 40
    assert s["bytes_tx"] == 4000 and s["peers_dropped"] == 40
    # the per-record window is still bounded
    assert len(led.records()) == 16


def test_switch_pair_traffic_rtt_and_drop_attribution(monkeypatch):
    """A real Switch pair over TCP: the ledger counts both directions,
    the patched ping interval produces a REAL measured RTT on both
    sides (the pong stamp satellite), and stop_peer_for_error retires
    the record with the structured reason."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.p2p.conn import connection as connmod
    from cometbft_tpu.p2p.conn.connection import ChannelDescriptor
    from cometbft_tpu.p2p.key import NodeKey
    from cometbft_tpu.p2p.switch import Reactor, Switch

    monkeypatch.setattr(connmod, "PING_INTERVAL", 0.05)

    class Echo(Reactor):
        def __init__(self):
            super().__init__("ECHO")
            self.got = []

        def channel_descriptors(self):
            return [ChannelDescriptor(0x7F)]

        def receive(self, chan_id, peer, msg):
            self.got.append(msg)

    ka = NodeKey(PrivKey.generate(b"\x5a" * 32))
    kb = NodeKey(PrivKey.generate(b"\x5b" * 32))
    sa, sb = Switch(ka, "zpeer-net"), Switch(kb, "zpeer-net")
    ea, eb = Echo(), Echo()
    sa.add_reactor(ea)
    sb.add_reactor(eb)
    addr_a = sa.listen()
    sa.start()
    sb.start()
    try:
        sb.dial_peer(addr_a, persistent=False)
        deadline = time.time() + 10
        while sa.num_peers() < 1 or sb.num_peers() < 1:
            assert time.time() < deadline, "peers never connected"
            time.sleep(0.02)
        for i in range(5):
            sb.broadcast(0x7F, b"zpeer-%d" % i)
        deadline = time.time() + 10
        while len(ea.got) < 5:
            assert time.time() < deadline, "messages never arrived"
            time.sleep(0.02)

        # traffic attributed on both ledgers
        a_dump = sa.peer_ledger.dump()
        b_dump = sb.peer_ledger.dump()
        assert a_dump["summary"]["peers_live"] == 1
        a_rec = a_dump["peers"][0]
        b_rec = b_dump["peers"][0]
        assert a_rec["peer"] == kb.node_id[:12]
        assert b_rec["peer"] == ka.node_id[:12]
        assert {a_rec["dir"], b_rec["dir"]} == {"in", "out"}
        assert b_rec["msgs_tx"] >= 5
        assert a_rec["msgs_rx"] >= 5 and a_rec["bytes_rx"] > 0
        # channel split carries the echo channel
        assert b_rec["chans"]["0x7f"]["msgs_tx"] >= 5
        # dial lifecycle landed on the dialer's event ring
        assert any(e["event"] == "dial" for e in b_dump["events"])

        # ping RTT: the 50 ms interval has fired by now and the pong
        # stamped a real round trip on the side that pinged
        deadline = time.time() + 10
        while not (sa.peer_ledger.rtt_rows() or
                   sb.peer_ledger.rtt_rows()):
            assert time.time() < deadline, "no RTT ever measured"
            time.sleep(0.05)
        peer_label, rtt = (sa.peer_ledger.rtt_rows()
                           or sb.peer_ledger.rtt_rows())[0]
        assert rtt > 0.0, "pong arrived but RTT not computed"

        # structured drop reason
        peer_b = list(sa.peers.values())[0]
        sa.stop_peer_for_error(peer_b, "zpeer test reason")
        dropped = [p for p in sa.peer_ledger.records()
                   if p["state"] == "dropped"]
        assert dropped and dropped[-1]["reason"] == "zpeer test reason"
    finally:
        sa.stop()
        sb.stop()
    # post-stop: every record retired, history served via the module
    # fallback (_LAST pattern — sb registered last or sa did; either
    # way SOME switch's history is there)
    assert peerledger.dump_peers()["summary"]["peers_dropped"] >= 1


def test_mconnection_full_queue_observable(monkeypatch):
    """ISSUE 14 satellite: a full send queue is OBSERVABLE — the
    non-blocking send counts a full_drop, the blocking send counts a
    blocked_put and (after the timeout) a full_drop, and both return
    False only AFTER the ledger heard about it (previously
    indistinguishable from a stopped conn)."""
    from cometbft_tpu.p2p.conn import connection as connmod
    from cometbft_tpu.p2p.conn.connection import (
        ChannelDescriptor,
        MConnection,
    )

    monkeypatch.setattr(connmod, "SEND_TIMEOUT", 0.05)

    class _DeadConn:
        class _stream:  # noqa: N801 - stop() pokes conn._stream.close
            @staticmethod
            def close():
                pass

        def write_msg(self, b):
            pass

        def read_msg(self):
            time.sleep(3600)

    rec = peerledger.detached_record("full-q", True)
    # never start the routines: the queue can only fill
    mc = MConnection(_DeadConn(), [ChannelDescriptor(1,
                                                     send_queue_capacity=2)],
                     on_receive=lambda c, m: None, ledger_rec=rec)
    assert mc.send(1, b"a") and mc.send(1, b"b")
    # non-blocking on a full queue: explicit drop
    assert mc.send(1, b"c", block=False) is False
    assert rec[peerledger._P_FULLDROP] == 1
    assert rec[peerledger._P_BLOCKED] == 0
    # blocking on a full queue: blocked-put counted, then the timeout
    # drop — and the return is False, not a hang
    t0 = time.monotonic()
    assert mc.send(1, b"d", block=True) is False
    assert time.monotonic() - t0 < 2.0
    assert rec[peerledger._P_BLOCKED] == 1
    assert rec[peerledger._P_FULLDROP] == 2
    # a STOPPED conn still returns False without touching the counters
    mc._stop.set()
    assert mc.send(1, b"e", block=False) is False
    assert rec[peerledger._P_FULLDROP] == 2


def test_fuzzed_socket_attributes_injected_faults():
    """ISSUE 14 satellite: FuzzedSocket drops/delays land in the peer
    ledger as injected faults, so a chaos run's /dump_peers blames the
    fuzzer, not the network."""
    from cometbft_tpu.p2p.fuzz import FuzzConnConfig, FuzzedSocket

    class _Sock:
        def __init__(self):
            self.sent = []

        def sendall(self, b):
            self.sent.append(b)

        def close(self):
            pass

    rec = peerledger.detached_record("fuzzed", True)
    fz = FuzzedSocket(_Sock(), FuzzConnConfig(
        prob_drop_rw=1.0, seed=7), ledger_rec=rec)
    for _ in range(4):
        fz.sendall(b"x")
    assert rec[peerledger._P_INJDROP] == 4
    assert not fz._sock.sent, "dropped writes reached the socket"
    fz2 = FuzzedSocket(_Sock(), FuzzConnConfig(
        prob_drop_rw=0.0, prob_sleep=1.0, max_sleep_s=0.001, seed=7),
        ledger_rec=rec)
    fz2.sendall(b"y")
    assert rec[peerledger._P_INJDELAY] == 1
    assert fz2._sock.sent == [b"y"]  # delayed, not dropped


def test_peer_starvation_incident_trigger():
    """The ledger's full-drop/blocked-put counters feed the
    peer_starvation window: an in-window burst fires ONE incident
    whose snapshot carries the peer-ledger tail; a slow drip over
    longer than window_s stays quiet (the shed-storm expiry-first
    semantics)."""
    from cometbft_tpu.libs import tracing

    now = [10 ** 15]
    tracing.set_clock(lambda: now[0])
    led = peerledger.PeerLedger()
    rec_obj = incidents.IncidentRecorder(
        peer_starvation=10, window_s=2.0, commit_stall_s=0.0,
        cooldown_s=100.0)
    old = incidents.install(rec_obj)
    try:
        r = led.open_peer("starved", True)
        peerledger.set_global_ledger(led)
        for _ in range(5):
            peerledger.note_full_drop(r)
        rec_obj.poke(1, 0)          # anchors the starvation window
        now[0] += int(60e9)         # a minute of drip
        for _ in range(8):
            peerledger.note_blocked_put(r)
        rec_obj.poke(1, 0)          # expired window: 13 stalls, quiet
        assert "peer_starvation" not in rec_obj.fired, rec_obj.fired
        for _ in range(12):         # burst INSIDE the fresh window
            peerledger.note_full_drop(r)
        now[0] += int(1e9)
        rec_obj.poke(2, 0)
        assert rec_obj.fired.get("peer_starvation") == 1, rec_obj.fired
        snap = rec_obj.incidents()[-1]
        assert snap["detail"]["stalls"] == 12
        # the snapshot's peer tail names the starving peer
        assert any("starved" in ln for ln in snap["peer_tail"]), snap
        assert snap["counters"]["peers"]["full_drops"] == 17
        # thresholds surface the new knob
        assert rec_obj.thresholds()["peer_starvation"] == 10
    finally:
        incidents.install(old)
        peerledger.clear_global_ledger(led)
        tracing.set_clock(None)


def _mini_net(n_nodes=2):
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.consensus.ticker import TimeoutParams
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.node.node import LocalNetwork, Node
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.state.state import State
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    fast = TimeoutParams(propose=0.4, propose_delta=0.1, prevote=0.2,
                         prevote_delta=0.1, precommit=0.2,
                         precommit_delta=0.1, commit=0.05)
    privs = [PrivKey.generate(bytes([70 + i]) * 32)
             for i in range(n_nodes)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis("zpeer-chain", vals)
    net = LocalNetwork()
    nodes = []
    for i, priv in enumerate(privs):
        node = Node(KVStoreApplication(), state.copy(),
                    privval=FilePV(priv), broadcast=net.broadcaster(i),
                    timeouts=fast)
        net.add(node)
        nodes.append(node)
    return nodes


def test_dump_peers_over_real_rpc():
    """GET /dump_peers and the JSON-RPC form over a live server (the
    curl surface operators actually use). The LocalNetwork node has no
    switch, so the route serves the registered module-global ledger —
    the same fallback an inspect server uses post-mortem."""
    led = peerledger.PeerLedger()
    rec = led.open_peer("rpc-peer", False)
    peerledger.note_sent(rec, 0x22, 64)
    peerledger.set_global_ledger(led)
    nodes = _mini_net(2)
    try:
        for n in nodes:
            n.start()
        url = nodes[0].rpc_listen("127.0.0.1", 0)
        assert nodes[0].consensus.wait_for_height(1, timeout=30.0)
        with urllib.request.urlopen(url + "/dump_peers",
                                    timeout=10) as r:
            doc = json.loads(r.read().decode())
        assert doc["summary"]["peers_live"] == 1
        assert doc["peers"][0]["peer"] == "rpc-peer"
        body = json.dumps({"jsonrpc": "2.0", "id": 1,
                           "method": "dump_peers",
                           "params": {}}).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            rpc = json.loads(r.read().decode())
        assert rpc["result"]["summary"]["msgs_tx"] == 1
        # /metrics carries the new p2p families, sampled from the
        # registered ledger
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        for fam in ("cometbft_p2p_peer_msgs_total",
                    "cometbft_p2p_send_queue_full_drops_total",
                    "cometbft_p2p_send_blocked_puts_total",
                    "cometbft_p2p_link_drops_total",
                    "cometbft_p2p_injected_faults_total",
                    "cometbft_p2p_duplicate_votes_total",
                    "cometbft_p2p_ping_rtt_ms",
                    "cometbft_p2p_peer_ledger_peers"):
            assert fam in text, fam
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(
                        'cometbft_p2p_peer_msgs_total{dir="tx"}'))
        assert float(line.split()[-1]) == 1.0
    finally:
        peerledger.clear_global_ledger(led)
        for n in nodes:
            n.stop()


def test_dump_peers_concurrent_with_switch_stop():
    """The PR-13 dump-route pattern: threads hammer /dump_peers WHILE
    a real switch pair (plus its peers) stops — no crash, every
    response well-formed, post-stop history still served."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.p2p.conn.connection import ChannelDescriptor
    from cometbft_tpu.p2p.key import NodeKey
    from cometbft_tpu.p2p.switch import Reactor, Switch

    class Chan(Reactor):
        def __init__(self):
            super().__init__("CHAN")

        def channel_descriptors(self):
            return [ChannelDescriptor(0x7E)]

    ka = NodeKey(PrivKey.generate(b"\x6a" * 32))
    kb = NodeKey(PrivKey.generate(b"\x6b" * 32))
    sa, sb = Switch(ka, "zpeer-ham"), Switch(kb, "zpeer-ham")
    sa.add_reactor(Chan())
    sb.add_reactor(Chan())
    addr_a = sa.listen()
    sa.start()
    sb.start()
    stop_ev = threading.Event()
    errors = []
    responses = [0]
    try:
        sb.dial_peer(addr_a, persistent=False)
        deadline = time.time() + 10
        while sa.num_peers() < 1 or sb.num_peers() < 1:
            assert time.time() < deadline, "peers never connected"
            time.sleep(0.02)

        def hammer():
            while not stop_ev.is_set():
                try:
                    for led in (sa.peer_ledger, sb.peer_ledger):
                        json.dumps(led.dump())
                    json.dumps(peerledger.dump_peers())
                    responses[0] += 1
                except Exception as e:  # noqa: BLE001 - the assertion
                    errors.append(repr(e))
                    return

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        sa.stop()
        sb.stop()
        stop_ev.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors[:3]
        assert responses[0] > 0
    finally:
        stop_ev.set()
        if sa.is_running():
            sa.stop()
        if sb.is_running():
            sb.stop()
    # history after both switches stopped: records retired, not lost
    post = peerledger.dump_peers()
    assert post["summary"]["peers_dropped"] >= 1
    assert all(p["state"] == "dropped" for p in post["peers"])


def test_peer_report_diff_detects_synthetic_regression(tmp_path,
                                                       capsys):
    """The --diff CLI path flags an injected full-drop/RTT regression
    (exit 1 under --fail-on-regression), stays quiet on identical
    dumps, and errors on a miswired gate (--fail-on-regression without
    --diff)."""
    from tools import peer_report

    led = peerledger.PeerLedger()
    for i in range(3):
        r = led.open_peer(f"p{i}", True)
        peerledger.note_sent(r, 0x22, 1000)
        peerledger.note_recv(r, 0x22, 500)
        r[peerledger._P_PINGS] = 4
        r[peerledger._P_RTT] = 1.5
    dump = led.dump()
    a_path = tmp_path / "a.json"
    a_path.write_text(json.dumps(dump))
    doctored = copy.deepcopy(dump)
    for p in doctored["peers"]:
        p["full_drops"] += 50
        p["blocked_puts"] += 20
        p["rtt_ms"] += 40.0
    b_path = tmp_path / "b.json"
    b_path.write_text(json.dumps(doctored))

    rc = peer_report.main([str(a_path), str(a_path), "--diff",
                           "--fail-on-regression"])
    assert rc == 0
    capsys.readouterr()
    rc = peer_report.main([str(a_path), str(b_path), "--diff",
                           "--fail-on-regression"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out and "full_drops" in out
    assert "rtt_p50_ms" in out
    with pytest.raises(SystemExit):
        peer_report.main([str(a_path), "--fail-on-regression"])
    # the single-dump report renders the per-peer table
    capsys.readouterr()
    assert peer_report.main([str(a_path)]) == 0
    out = capsys.readouterr().out
    assert "p0" in out and "totals:" in out


def _peer_ledger_bookkeeping_us(k):
    """One replay of the exact per-message sequence the send and recv
    routines drive with tracing off (note_sent: totals + the channel
    slot; note_queue_depth after each enqueue; note_recv per packet).
    Returns (send us, recv us, allocated blocks per steady-state
    message on a warmed channel slot)."""
    from cometbft_tpu.libs import tracing

    assert not tracing.enabled(), "measure the DISABLED path"
    led = peerledger.PeerLedger()
    rec = led.open_peer("budget-peer", True)
    t0 = time.perf_counter()
    for i in range(k):
        peerledger.note_sent(rec, 0x22, 180)
        peerledger.note_queue_depth(rec, i & 15)
    send_us = (time.perf_counter() - t0) * 1e6 / k
    t1 = time.perf_counter()
    for i in range(k):
        peerledger.note_recv(rec, 0x22, 180, eof=(i & 1) == 0)
    recv_us = (time.perf_counter() - t1) * 1e6 / k
    blocks0 = sys.getallocatedblocks()  # first touch allocated the slot
    for i in range(1024):
        peerledger.note_sent(rec, 0x22, 180)
    return send_us, recv_us, (sys.getallocatedblocks() - blocks0) / 1024


def test_peer_ledger_message_budget():
    """ISSUE 14 acceptance: < 10 us per message with tracing OFF (best
    of 3 to dodge 1-core scheduler spikes; typical is < 1 us)."""
    rows = [_peer_ledger_bookkeeping_us(5_000) for _ in range(3)]
    best_send = min(r[0] for r in rows)
    best_recv = min(r[1] for r in rows)
    assert best_send < 10.0, f"send bookkeeping {best_send} us"
    assert best_recv < 10.0, f"recv bookkeeping {best_recv} us"
    # allocation-free in the FlushLedger sense on a warmed channel
    assert min(r[2] for r in rows) < 0.5


def test_no_jax_import():
    """Host-only contract: nothing in this file (peer ledger, real
    switches, RPC, peer_report, the budget replay) may pull jax into
    the process."""
    if not _JAX_LOADED_BEFORE:
        assert "jax" not in sys.modules
