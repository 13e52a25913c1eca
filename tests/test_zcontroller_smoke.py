"""Control-plane tier-1 wiring (ISSUE 16): GET+JSON-RPC
/dump_controller over a live server with a mounted controller,
post-stop history (the _LAST pattern), /metrics controller families
riding a real scrape, the incident-snapshot controller tail, and the
controller_report --diff regression detector (including the miswired
--fail-on-regression gate).

Late in the alphabet on purpose (tier-1 ordering note in ROADMAP).
Host-only: the whole file must run with NO jax import (asserted).
"""
import copy
import json
import sys
import urllib.request

import pytest

from cometbft_tpu.libs import controller as cp
from cometbft_tpu.libs import incidents

_JAX_LOADED_BEFORE = "jax" in sys.modules


class _Ledger:
    def __init__(self, p99=0.0):
        self.p99 = p99

    def __len__(self):
        return 1

    def summary(self):
        return {"commit_latency_ms": {"p99": self.p99}}


class _Admission:
    def __init__(self):
        self.high_watermark = 0.9
        self.low_watermark = 0.7
        self._fill_fn = lambda: 0.0

    def set_watermarks(self, high, low):
        self.high_watermark, self.low_watermark = high, low
        return (high, low)


def _decided_controller(n_moves=2):
    """A controller with real decisions on the ring, driven against
    fakes (decision_interval=1 so every poke evaluates)."""
    led = _Ledger(p99=500.0)
    ctl = cp.Controller(slo_commit_p99_ms=100.0, decision_interval=1,
                        cooldown=0)
    ctl.attach(admission=_Admission(), height_ledger=led,
               bounds={cp.ACT_ADMISSION: (0.2, 0.9)})
    for h in range(1, n_moves + 1):
        ctl.poke(h, 0)
    assert ctl.dump()["state"]["decisions_total"] >= 1
    return ctl


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
    privs = [PrivKey.generate(bytes([120 + i]) * 32)
             for i in range(n_nodes)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis("zctl-chain", vals)
    net = LocalNetwork()
    nodes = []
    for i, priv in enumerate(privs):
        node = Node(KVStoreApplication(), state.copy(),
                    privval=FilePV(priv), broadcast=net.broadcaster(i),
                    timeouts=fast)
        net.add(node)
        nodes.append(node)
    return nodes


def test_dump_controller_over_real_rpc():
    """GET /dump_controller and the JSON-RPC form over a live server
    (the curl surface), /metrics controller families on a real scrape,
    and post-stop history via the module global (_LAST)."""
    old_global, old_last = cp._GLOBAL, cp._LAST
    nodes = _mini_net(2)
    try:
        for n in nodes:
            n.start()
        # mount a decided controller on the serving node (the simnet
        # op and node lifecycle do the same wiring)
        ctl = _decided_controller()
        nodes[0].controller = ctl
        cp.set_global_controller(ctl)
        expected = ctl.dump()["state"]["decisions_total"]
        url = nodes[0].rpc_listen("127.0.0.1", 0)
        assert nodes[0].consensus.wait_for_height(1, timeout=30.0)
        with urllib.request.urlopen(url + "/dump_controller",
                                    timeout=10) as r:
            doc = json.loads(r.read().decode())
        # the live node's step seam keeps poking the mounted
        # controller, so totals only grow past the mount-time snapshot
        assert doc["state"]["decisions_total"] >= expected
        assert doc["actuators"]["admission_high_watermark"]["moves"] \
            >= 1
        assert doc["decisions"][0]["trigger"]["p99_ms"] == 500.0
        body = json.dumps({"jsonrpc": "2.0", "id": 1,
                           "method": "dump_controller",
                           "params": {}}).encode()
        req = urllib.request.Request(
            url, data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            rpc = json.loads(r.read().decode())
        assert rpc["result"]["state"]["decisions_total"] >= expected
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        for fam in ("cometbft_controller_decisions_total",
                    "cometbft_controller_actuator_value",
                    "cometbft_controller_slo_violation_seconds_total"):
            assert fam in text, fam
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith("cometbft_controller_decisions_total{")
            and 'actuator="admission_high_watermark"' in ln
            and 'direction="down"' in ln)
        assert float(line.split()[-1]) >= 1.0
    finally:
        for n in nodes:
            n.stop()
        cp._GLOBAL, cp._LAST = old_global, old_last
    # history after the node stopped: _LAST still serves (within the
    # try the globals were live; re-register to assert the pattern)
    cp.set_global_controller(ctl)
    cp.clear_global_controller(ctl)
    try:
        assert cp.dump_controller()["state"]["decisions_total"] \
            >= expected
    finally:
        cp._GLOBAL, cp._LAST = old_global, old_last


def test_incident_snapshot_carries_controller_tail():
    """A controller move inside an incident's window rides the frozen
    snapshot (the flight-recorder join)."""
    old_global, old_last = cp._GLOBAL, cp._LAST
    rec = incidents.IncidentRecorder(commit_stall_s=0.0, window_s=60.0,
                                     cooldown_s=0.0)
    old_rec = incidents.install(rec)
    try:
        ctl = _decided_controller()
        cp.set_global_controller(ctl)
        snap = rec._snapshot("forced", 1, 0, 5, 0, {})
        assert snap["controller_tail"], snap
        assert "admission_high_watermark" in snap["controller_tail"][0]
        assert " down " in snap["controller_tail"][0]
    finally:
        incidents.install(old_rec)
        cp._GLOBAL, cp._LAST = old_global, old_last


def test_controller_report_diff_detects_synthetic_regression(
        tmp_path, capsys):
    """The --diff CLI path flags injected violation/flap/displacement
    regressions (exit 1 under --fail-on-regression), stays quiet on
    identical dumps, and errors on a miswired gate
    (--fail-on-regression without --diff)."""
    from tools import controller_report

    ctl = _decided_controller()
    dump = ctl.dump()
    a_path = tmp_path / "a.json"
    a_path.write_text(json.dumps(dump))
    doctored = copy.deepcopy(dump)
    doctored["state"]["slo_violation_s"] += 7.5
    doctored["state"]["decisions_total"] += 200
    doctored["actuators"]["admission_high_watermark"]["value"] = 0.3
    b_path = tmp_path / "b.json"
    b_path.write_text(json.dumps(doctored))

    rc = controller_report.main([str(a_path), str(a_path), "--diff",
                                 "--fail-on-regression"])
    assert rc == 0
    capsys.readouterr()
    rc = controller_report.main([str(a_path), str(b_path), "--diff",
                                 "--fail-on-regression"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out
    assert "slo_violation_s" in out and "decisions_total" in out
    assert "displacement_total" in out
    # ANY violation growth flags — holding the SLO is the loop's one
    # job; a big baseline must not excuse new violation seconds
    small = copy.deepcopy(dump)
    small["state"]["slo_violation_s"] = 100.0
    more = copy.deepcopy(small)
    more["state"]["slo_violation_s"] = 100.5
    (tmp_path / "sm.json").write_text(json.dumps(small))
    (tmp_path / "mo.json").write_text(json.dumps(more))
    capsys.readouterr()
    rc = controller_report.main([str(tmp_path / "sm.json"),
                                 str(tmp_path / "mo.json"),
                                 "--diff", "--fail-on-regression"])
    assert rc == 1
    with pytest.raises(SystemExit):
        controller_report.main([str(a_path), "--fail-on-regression"])
    # the single-dump report renders the actuator table + timeline
    capsys.readouterr()
    assert controller_report.main([str(a_path)]) == 0
    out = capsys.readouterr().out
    assert "admission_high_watermark" in out
    assert "decision timeline" in out
    junk = tmp_path / "junk.json"
    junk.write_text(json.dumps({"nope": 1}))
    with pytest.raises(ValueError):
        controller_report.load_controller(str(junk))


def test_no_jax_import():
    """The whole file ran host-only: nothing here may pull jax in."""
    if not _JAX_LOADED_BEFORE:
        assert "jax" not in sys.modules
