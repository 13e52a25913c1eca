"""Multi-tenant plane tier-1 wiring (ISSUE 17): GET+JSON-RPC
/dump_tenants over a live server with a mounted multi-tenant plane,
post-stop history (the _LAST pattern), /metrics tenant families riding
a real scrape (top-K + _retired cardinality bound), and the
tenant_report --diff regression detector (including the miswired
--fail-on-regression gate).

Late in the alphabet on purpose (tier-1 ordering note in ROADMAP).
Host-only: the whole file must run with NO jax import (asserted).
"""
import copy
import json
import sys
import urllib.request

import pytest

from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane
from cometbft_tpu.verifyplane import plane as planemod
from cometbft_tpu.verifyplane import tenants as vtenants

_JAX_LOADED_BEFORE = "jax" in sys.modules

CHAIN = "ztenant-chain"


class _Pub:
    def verify_signature(self, msg, sig):
        return True


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
    privs = [PrivKey.generate(bytes([140 + i]) * 32)
             for i in range(n_nodes)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis(CHAIN, vals)
    net = LocalNetwork()
    nodes = []
    for i, priv in enumerate(privs):
        node = Node(KVStoreApplication(), state.copy(),
                    privval=FilePV(priv), broadcast=net.broadcaster(i),
                    timeouts=fast)
        net.add(node)
        nodes.append(node)
    return nodes


def test_dump_tenants_over_real_rpc():
    """GET /dump_tenants and the JSON-RPC form over a live server (the
    curl surface), /metrics tenant families on a real scrape with the
    top-K + _retired cardinality bound, and post-stop history via the
    module global (_LAST)."""
    old_g, old_l = planemod._GLOBAL, planemod._LAST
    old_rg, old_rl = vtenants._GLOBAL, vtenants._LAST
    plane = VerifyPlane(window_ms=0.5, use_device=False)
    plane.start()
    nodes = _mini_net(2)
    try:
        set_global_plane(plane)
        assert vtenants.global_registry() is plane.tenants
        for n in nodes:
            n.start()
        url = nodes[0].rpc_listen("127.0.0.1", 0)
        assert nodes[0].consensus.wait_for_height(1, timeout=30.0)
        # the live nodes' own vote traffic is tenant-keyed by chain_id;
        # a second chain's rows through the same plane makes the dump
        # (and the scrape) genuinely multi-tenant
        plane.tenants.register("other-chain", row_quota=1024)
        f = plane.submit_many([(_Pub(), b"m", b"s")] * 3,
                              chain_id="other-chain")
        assert f.result(5) == (True, True, True)
        with urllib.request.urlopen(url + "/dump_tenants",
                                    timeout=10) as r:
            doc = json.loads(r.read().decode())
        assert doc["tenants"][CHAIN]["rows"] >= 1
        assert doc["tenants"]["other-chain"]["rows"] == 3
        assert doc["tenants"]["other-chain"]["row_quota"] == 1024
        assert doc["registry_size"] >= 2
        body = json.dumps({"jsonrpc": "2.0", "id": 1,
                           "method": "dump_tenants",
                           "params": {}}).encode()
        req = urllib.request.Request(
            url, data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            rpc = json.loads(r.read().decode())
        assert rpc["result"]["tenants"]["other-chain"]["rows"] == 3
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        for fam in ("cometbft_verifyplane_tenant_rows_total",
                    "cometbft_verifyplane_tenant_sheds_total",
                    "cometbft_verifyplane_tenant_registry_size",
                    "cometbft_verifyplane_tenant_resident_bytes"):
            assert fam in text, fam
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith(
                "cometbft_verifyplane_tenant_rows_total{")
            and 'tenant="other-chain"' in ln)
        assert float(line.split()[-1]) == 3.0
        # the monotonicity accumulator's series is always exposed
        assert any('tenant="_retired"' in ln
                   for ln in text.splitlines()
                   if ln.startswith(
                       "cometbft_verifyplane_tenant_rows_total{"))
        snapshot = vtenants.dump_tenants()
    finally:
        for n in nodes:
            n.stop()
        set_global_plane(None)
        plane.stop()
        planemod._GLOBAL, planemod._LAST = old_g, old_l
        vtenants._GLOBAL, vtenants._LAST = old_rg, old_rl
    # history after the plane unmounted: _LAST still serves the dump
    vtenants.set_global_registry(plane.tenants)
    vtenants.clear_global_registry(plane.tenants)
    try:
        doc = vtenants.dump_tenants()
        assert doc["tenants"]["other-chain"]["rows"] == 3
        # the live nodes kept voting past the snapshot; history is
        # monotone, never rewound
        assert doc["tenants"][CHAIN]["rows"] >= \
            snapshot["tenants"][CHAIN]["rows"]
    finally:
        vtenants._GLOBAL, vtenants._LAST = old_rg, old_rl


def test_dump_tenants_empty_doc_fallback():
    """With no registry ever mounted, /dump_tenants serves the empty
    document, not an error (the curl-on-a-fresh-node case)."""
    old_rg, old_rl = vtenants._GLOBAL, vtenants._LAST
    vtenants._GLOBAL = vtenants._LAST = None
    try:
        doc = vtenants.dump_tenants()
        assert doc["tenants"] == {} and doc["registry_size"] == 0
    finally:
        vtenants._GLOBAL, vtenants._LAST = old_rg, old_rl


def test_tenant_report_diff_detects_synthetic_regression(
        tmp_path, capsys):
    """The --diff CLI path flags injected shed/wait regressions (exit
    1 under --fail-on-regression), stays quiet on identical dumps, and
    errors on a miswired gate (--fail-on-regression without --diff)."""
    from tools import tenant_report

    reg = vtenants.TenantRegistry()
    reg.register("chain-a", row_quota=64)
    reg.note_served("chain-a", "bulk", 100, 1.0)
    reg.note_served("chain-b", "consensus", 40, 0.5)
    dump = reg.dump()
    a_path = tmp_path / "a.json"
    a_path.write_text(json.dumps(dump))
    doctored = copy.deepcopy(dump)
    doctored["tenants"]["chain-a"]["sheds"] = 75
    doctored["tenants"]["chain-b"]["warm_skips"] = 30
    b_path = tmp_path / "b.json"
    b_path.write_text(json.dumps(doctored))

    rc = tenant_report.main([str(a_path), str(a_path), "--diff",
                             "--fail-on-regression"])
    assert rc == 0
    capsys.readouterr()
    rc = tenant_report.main([str(a_path), str(b_path), "--diff",
                             "--fail-on-regression"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out
    assert "sheds_total" in out and "warm_skips_total" in out
    assert "chain-a" in out  # the per-tenant shed-growth note
    with pytest.raises(SystemExit):
        tenant_report.main([str(a_path), "--fail-on-regression"])
    # the single-dump report renders the per-tenant table
    capsys.readouterr()
    assert tenant_report.main([str(a_path)]) == 0
    out = capsys.readouterr().out
    assert "chain-a" in out and "chain-b" in out
    assert "2 tenants" in out
    junk = tmp_path / "junk.json"
    junk.write_text(json.dumps({"nope": 1}))
    with pytest.raises(ValueError):
        tenant_report.load_tenants(str(junk))


def test_cross_dump_hammer_during_plane_stop():
    """ISSUE 20 satellite: reader threads hammering all three dump
    surfaces (tenants + devices + flushes — the module-level bodies
    the RPC handlers serve) WHILE the plane verifies fused
    multi-tenant batches and then WHILE it stops. No dump may raise or
    produce an unserializable document, and the post-stop history must
    still reconcile EXACTLY: the registry's per-tenant device totals
    equal the flush ledger's charged columns (integer us, drift all
    zero) even though the readers raced the ledger drain."""
    import threading
    import time

    from cometbft_tpu.libs import deviceledger

    old_g, old_l = planemod._GLOBAL, planemod._LAST
    old_rg, old_rl = vtenants._GLOBAL, vtenants._LAST
    plane = VerifyPlane(window_ms=0.5, use_device=False)
    plane.start()
    stop_hammer = threading.Event()
    served = {"tenants": 0, "devices": 0, "flushes": 0}
    errors = []

    def hammer(name, fn):
        while not stop_hammer.is_set():
            try:
                json.dumps(fn())
            except Exception as e:  # noqa: BLE001 - the assertion
                errors.append((name, repr(e)))
                return
            served[name] += 1
            time.sleep(0.002)  # 1-core host: don't starve the plane

    threads = [
        threading.Thread(target=hammer, args=pair, daemon=True)
        for pair in (("tenants", vtenants.dump_tenants),
                     ("devices", deviceledger.dump_devices),
                     ("flushes", planemod.dump_flushes))]
    try:
        set_global_plane(plane)
        for t in threads:
            t.start()
        # interleaved per-tenant work plus concurrent cross-tenant
        # bursts, so the rows split rule runs under the hammer too
        for i in range(6):
            futs = [plane.submit_many(
                        [(_Pub(), b"m", b"s")] * (2 + i % 3),
                        chain_id=c)
                    for c in ("hammer-a", "hammer-b")]
            for f in futs:
                assert all(f.result(30.0))
        # stop WHILE the dump threads hammer: the exact seam this
        # satellite targets — ledger drain + registry charge racing
        # the read side
        plane.stop()
        time.sleep(0.05)  # a few post-stop dumps land under the test
    finally:
        stop_hammer.set()
        for t in threads:
            t.join(timeout=10.0)
        plane.stop()
        set_global_plane(None)
        planemod._GLOBAL, planemod._LAST = old_g, old_l
        vtenants._GLOBAL, vtenants._LAST = old_rg, old_rl
    assert not errors, errors
    assert all(n >= 1 for n in served.values()), served
    assert not any(t.is_alive() for t in threads)
    # post-stop history: device columns present, charges conserved
    recs = plane.ledger.records()
    assert recs, "no flush recorded"
    doc = plane.tenants.dump()
    for col in ("device_ms", "comp_ms", "h2d_ms", "delta_bytes"):
        assert col in doc["tenants"]["hammer-a"], doc["tenants"]
    assert doc["tenants"]["hammer-a"]["rows"] >= 18  # 2+3+4 per pass
    rd = vtenants.reconcile_device(recs, plane.tenants)
    assert all(v == 0 for v in rd["drift"].values()), rd


def test_no_jax_import():
    """The whole file ran host-only: nothing here may pull jax in."""
    if not _JAX_LOADED_BEFORE:
        assert "jax" not in sys.modules
