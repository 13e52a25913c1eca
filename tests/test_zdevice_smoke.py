"""Device observatory tier-1 wiring (ISSUE 15): compile-ledger record
shape + attribution stack (innermost frame names the site/flush, ms
bubbles to every frame), the steady-state flag feeding the
compile_storm incident (burst fires with the compile tail frozen;
a drip past the window does not), the exact-accounting HBM residency
cross-check under 50 churn epochs (zero drift vs the cache truth),
GET+JSON-RPC /dump_devices (post-stop history — the ledger is
process-global), the device_report --diff regression detector, the
flush ledger's comp/h2d/dev/util columns on the host path, and the
< 10 us/flush hook budget.

Late in the alphabet on purpose (tier-1 ordering note in ROADMAP).
Host-only: the whole file must run with NO jax import (asserted).
"""
import copy
import json
import sys
import time
import urllib.request

import pytest

from cometbft_tpu.libs import deviceledger, incidents, tracing

_JAX_LOADED_BEFORE = "jax" in sys.modules


@pytest.fixture()
def fresh_ledger():
    led = deviceledger.CompileLedger()
    old = deviceledger.install(led)
    yield led
    deviceledger.install(old)


def test_compile_record_shape_and_attribution(fresh_ledger):
    """Innermost frame names the record's site/flush_seq; accumulated
    ms bubbles to every frame on the stack (an outer caller sees its
    nested plane flushes' compiles); the fallback frame only engages
    on an empty stack; the ring is bounded."""
    led = fresh_ledger
    outer = deviceledger.attr_begin("outer.caller")
    inner = deviceledger.attr_begin("plane.flush", 7)
    deviceledger.record_compile(0.05)
    deviceledger.attr_end(inner)
    deviceledger.record_compile(0.01)
    deviceledger.attr_end(outer)
    recs = led.records()
    assert set(recs[0]) == set(deviceledger.CompileLedger.FIELDS)
    assert recs[0]["site"] == "plane.flush"
    assert recs[0]["flush_seq"] == 7 and recs[0]["dur_ms"] == 50.0
    assert recs[1]["site"] == "outer.caller" \
        and recs[1]["flush_seq"] == -1
    assert inner.ms == 50.0 and inner.n == 1
    # ms bubbles to every frame; n counts only innermost-attributed
    assert outer.ms == 60.0 and outer.n == 1
    # fallback frames engage only with no richer frame active
    fb = deviceledger.attr_begin_fallback("mesh.step:fused")
    assert fb is not None
    deviceledger.record_compile(0.002)
    deviceledger.attr_end(fb)
    with deviceledger.attr_context("plane.flush", 1):
        assert deviceledger.attr_begin_fallback("mesh.step:fused") is None
    assert led.records()[-1]["site"] == "mesh.step:fused"
    # no frame: site is empty, never a guess
    deviceledger.record_compile(0.001)
    assert led.records()[-1]["site"] == ""
    # double attr_end never pops an outer caller's frame
    o2 = deviceledger.attr_begin("outer2")
    i2 = deviceledger.attr_begin("inner2")
    deviceledger.attr_end(i2)
    deviceledger.attr_end(i2)  # no-op, must not pop outer2
    deviceledger.record_compile(0.001)
    assert led.records()[-1]["site"] == "outer2"
    deviceledger.attr_end(o2)
    # the listener pair: JAX fires the duration event for a persistent-
    # cache hit too (after its cache_hits event, same thread). That is
    # one hit and no backend compile, and it names the function.
    before = led.counters()
    deviceledger._on_event(
        "/jax/compilation_cache/compile_requests_use_cache")
    deviceledger._on_event("/jax/compilation_cache/cache_hits")
    deviceledger._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.2,
        fun_name="jit(cached_fn)")
    deviceledger._on_event(
        "/jax/compilation_cache/compile_requests_use_cache")
    deviceledger._on_duration(
        "/jax/core/compile/backend_compile_duration", 3.0,
        fun_name="jit(cold_fn)")
    after = led.counters()
    assert after["pcache_hits"] - before["pcache_hits"] == 1
    assert after["compiles"] - before["compiles"] == 1
    hit, cold = led.records()[-2:]
    assert hit["pcache_hit"] == 1 and hit["fun"] == "jit(cached_fn)"
    assert cold["pcache_hit"] == 0 and cold["fun"] == "jit(cold_fn)"
    assert cold["dur_ms"] == 3000.0
    # bounded ring
    small = deviceledger.CompileLedger(capacity=16)
    for i in range(50):
        small.record(0.001, False, "s", i)
    assert len(small) == 16
    assert small.counters()["compiles"] == 50  # counters stay monotone


def test_steady_burst_fires_compile_storm_drip_does_not(fresh_ledger):
    """The round-5 guard: steady-state recompiles in a burst fire ONE
    compile_storm whose snapshot freezes the compile tail; the same
    count dripped out over longer than the window is reported as a
    drip (expiry checked BEFORE the threshold — the shed-storm
    semantics)."""
    now = [1_000_000_000]
    tracing.set_clock(lambda: now[0])
    # commit_stall_s=0 disables the stall trigger: the fake clock
    # jumps 20 s per drip step, which would otherwise read as a stall
    rec_obj = incidents.IncidentRecorder(compile_storm=3, window_s=10.0,
                                         cooldown_s=0.0,
                                         commit_stall_s=0.0)
    old = incidents.install(rec_obj)
    try:
        deviceledger.mark_steady()
        # drip: 3 steady compiles spread over 40 s > the 10 s window
        for _ in range(3):
            with deviceledger.attr_context("drip.site"):
                deviceledger.record_compile(0.004)
            incidents.poke()
            now[0] += int(20e9)
        incidents.poke()  # expire the last drip's window
        assert len(rec_obj) == 0, rec_obj.incidents()
        # burst: 3 steady compiles inside one window
        with deviceledger.attr_context("storm.site", 42):
            for _ in range(3):
                deviceledger.record_compile(0.004)
        incidents.poke()            # anchor
        now[0] += int(1e9)
        incidents.poke()            # evaluate
        snaps = rec_obj.incidents()
        assert [s["trigger"] for s in snaps] == ["compile_storm"]
        assert snaps[0]["detail"]["steady_compiles"] == 3
        tail = snaps[0]["device_tail"]
        assert any("storm.site" in ln and "STEADY" in ln
                   and "flush=42" in ln for ln in tail), tail
        # cold (pre-steady) compiles never feed the window
        fresh2 = deviceledger.CompileLedger()
        old2 = deviceledger.install(fresh2)
        try:
            for _ in range(5):
                deviceledger.record_compile(0.004)
            incidents.poke()
            now[0] += int(1e9)
            incidents.poke()
            assert len(rec_obj) == 1  # still just the one storm
        finally:
            deviceledger.install(old2)
        assert rec_obj.thresholds()["compile_storm"] == 3
    finally:
        incidents.install(old)
        tracing.set_clock(None)


class _FakeTable:
    """Duck-typed stand-in sized by table_cache.default_size via
    ``nbytes`` — exactly how the real sampler sizes real tables."""

    def __init__(self, nbytes, n_vals=0, m_shard=0, devs=None):
        self.nbytes = nbytes
        self.n_vals = n_vals
        self.m_shard = m_shard
        if devs is not None:
            self.devs = devs


def test_residency_exact_accounting_50_churn_epochs():
    """ISSUE 15 satellite: device_resident_bytes must reconcile with
    the caches' own resident_bytes EXACTLY — 50 churn epochs of
    inserts and LRU evictions, zero drift after every one."""
    from cometbft_tpu.ops import table_cache as tc

    inserted = []
    ev_before = tc.stats()["evictions_tables"]
    try:
        for epoch in range(50):
            key = b"zdev-epoch-%d" % epoch
            with tc.LOCK:
                tc.TABLES.put(key, _FakeTable(4096 + epoch,
                                              n_vals=2048))
                tc.SHARDS.put((key, "mesh"),
                              _FakeTable(8192 + epoch, m_shard=1024,
                                         devs=[0, 1]))
            inserted.append(key)
            rec = deviceledger.reconcile()
            assert rec["table_drift"] == 0, (epoch, rec)
            assert rec["staging_drift"] == 0, (epoch, rec)
            # the split itself is per-device-exact (odd bytes too)
            fams = deviceledger.residency()
            sh_total = sum(s["bytes"]
                           for s in fams["shard_tables"].values())
            assert sh_total == tc.SHARDS.resident_bytes()
        # churn pressure actually evicted (bounded caches)
        assert tc.stats()["evictions_tables"] > ev_before
        with tc.LOCK:
            assert len(tc.TABLES) <= tc.TABLES.capacity
        # headroom math over the live window
        fams = deviceledger.residency()
        head = deviceledger.headroom_rows(fams)
        assert all(isinstance(d, int) for d in head)
        for dev, n in head.items():
            assert n <= deviceledger.HBM_SLOT_BUDGET
    finally:
        with tc.LOCK:
            for key in inserted:
                tc.TABLES.pop(key)
                tc.SHARDS.pop((key, "mesh"))
    assert deviceledger.reconcile()["table_drift"] == 0


def test_staging_pools_attributed_to_host():
    """Every live StagingPool's pinned bytes land in the staging
    family under dev='host' — including pools no metrics sampler knew
    about (the weakref registry)."""
    import numpy as np

    from cometbft_tpu.libs.staging import StagingPool

    pool = StagingPool(slots=2)
    pool.get("zdev.buf", (64, 8), np.int32)
    fams = deviceledger.residency(tables=[], shards=[])
    assert fams["staging"]["host"]["bytes"] >= 64 * 8 * 4
    assert deviceledger.reconcile(fams)["staging_drift"] == 0


def test_residency_split_and_headroom_over_fake_tables():
    """The duck-typed split the real sampler uses, on tables handed in:
    an unsharded table lands whole on device 0, a sharded one splits
    its bytes over its devices exactly (odd byte counts too) and its
    slots per device, and headroom is the slot budget less both."""
    fams = deviceledger.residency(
        tables=[_FakeTable(1000, n_vals=4096),
                _FakeTable(500, n_vals=2048)],
        shards=[_FakeTable(901, m_shard=2048, devs=[0, 1, 2, 3])])
    vt = fams["valset_tables"]
    assert vt[0]["bytes"] == 1500 and vt[0]["slots"] == 6144
    sh = fams["shard_tables"]
    assert sum(s["bytes"] for s in sh.values()) == 901
    assert sh[1]["slots"] == 2048
    head = deviceledger.headroom_rows(fams)
    assert head[0] == deviceledger.HBM_SLOT_BUDGET - 6144 - 2048
    assert head[3] == deviceledger.HBM_SLOT_BUDGET - 2048


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
    privs = [PrivKey.generate(bytes([90 + i]) * 32)
             for i in range(n_nodes)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis("zdevice-chain", vals)
    net = LocalNetwork()
    nodes = []
    for i, priv in enumerate(privs):
        node = Node(KVStoreApplication(), state.copy(),
                    privval=FilePV(priv), broadcast=net.broadcaster(i),
                    timeouts=fast)
        net.add(node)
        nodes.append(node)
    return nodes


def test_dump_devices_over_real_rpc(fresh_ledger, monkeypatch,
                                    capsys):
    """GET /dump_devices and the JSON-RPC form over a live server (the
    curl surface), /metrics device families sampled from the jax-free
    core, and post-stop history (the ledger is process-global — the
    _LAST property for free)."""
    with deviceledger.attr_context("rpc.test", 3):
        deviceledger.record_compile(0.025)
    # the device this process verifies on, as the seam that asked JAX
    # recorded it (crypto.batch; not asked here: this file is jax-free)
    monkeypatch.setattr(deviceledger, "_DEVICE", {
        "platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 1})
    nodes = _mini_net(2)
    # a plane-enabled node says at start which device it verifies on
    from cometbft_tpu.verifyplane import VerifyPlane

    nodes[0].verify_plane = VerifyPlane(use_device=False)
    try:
        for n in nodes:
            n.start()
        assert ("verify plane: platform=tpu device_kind='TPU v5 lite' "
                "n_devices=1; host path, nothing to compile"
                ) in capsys.readouterr().out
        url = nodes[0].rpc_listen("127.0.0.1", 0)
        assert nodes[0].consensus.wait_for_height(1, timeout=30.0)
        with urllib.request.urlopen(url + "/dump_devices",
                                    timeout=10) as r:
            doc = json.loads(r.read().decode())
        assert doc["device"] == {"platform": "tpu",
                                 "device_kind": "TPU v5 lite",
                                 "n_devices": 1}
        assert set(doc["breaker"]) == {"state", "faults", "trips",
                                       "closes", "probes"}
        assert doc["summary"]["compiles"] == 1
        assert doc["compiles"][0]["site"] == "rpc.test"
        assert doc["compiles"][0]["flush_seq"] == 3
        assert doc["hbm_slot_budget"] == 65536
        assert doc["reconcile"]["table_drift"] == 0
        body = json.dumps({"jsonrpc": "2.0", "id": 1,
                           "method": "dump_devices",
                           "params": {}}).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            rpc = json.loads(r.read().decode())
        assert rpc["result"]["summary"]["compiles"] == 1
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        for fam in ("cometbft_device_compiles_total",
                    "cometbft_device_compile_seconds_total",
                    "cometbft_device_compile_pcache_hits_total",
                    "cometbft_device_resident_bytes",
                    "cometbft_device_hbm_headroom_rows",
                    "cometbft_device_compile_ledger_records"):
            assert fam in text, fam
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(
                        'cometbft_device_compiles_total{phase="cold"}'))
        assert float(line.split()[-1]) == 1.0
    finally:
        for n in nodes:
            n.stop()
    # history after the nodes stopped: the module core still serves
    post = deviceledger.dump_devices()
    assert post["summary"]["compiles"] == 1
    assert post["compiles"][0]["site"] == "rpc.test"


def test_device_report_diff_detects_synthetic_regression(
        fresh_ledger, tmp_path, capsys):
    """The --diff CLI path flags injected compile/steady/residency
    regressions (exit 1 under --fail-on-regression), stays quiet on
    identical dumps, and errors on a miswired gate
    (--fail-on-regression without --diff)."""
    from tools import device_report

    with deviceledger.attr_context("base.site"):
        for _ in range(4):
            deviceledger.record_compile(0.01)
    dump = deviceledger.dump_devices()
    a_path = tmp_path / "a.json"
    a_path.write_text(json.dumps(dump))
    doctored = copy.deepcopy(dump)
    s = doctored["summary"]
    s["compiles"] += 60
    s["compile_s"] += 12.0
    s["steady_compiles"] += 4
    s["resident_bytes"] += 1 << 22
    b_path = tmp_path / "b.json"
    b_path.write_text(json.dumps(doctored))

    rc = device_report.main([str(a_path), str(a_path), "--diff",
                             "--fail-on-regression"])
    assert rc == 0
    capsys.readouterr()
    rc = device_report.main([str(a_path), str(b_path), "--diff",
                             "--fail-on-regression"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out
    assert "steady_compiles" in out and "compiles" in out
    assert "resident_bytes" in out
    # ANY steady-recompile growth flags — the relative threshold must
    # not excuse one new round-5-class recompile on a big baseline
    big = copy.deepcopy(dump)
    big["summary"]["steady_compiles"] = 8
    one_more = copy.deepcopy(big)
    one_more["summary"]["steady_compiles"] = 9
    (tmp_path / "big.json").write_text(json.dumps(big))
    (tmp_path / "one_more.json").write_text(json.dumps(one_more))
    capsys.readouterr()
    rc = device_report.main([str(tmp_path / "big.json"),
                             str(tmp_path / "one_more.json"),
                             "--diff", "--fail-on-regression"])
    assert rc == 1
    with pytest.raises(SystemExit):
        device_report.main([str(a_path), "--fail-on-regression"])
    # the single-dump report renders the site table
    capsys.readouterr()
    assert device_report.main([str(a_path)]) == 0
    out = capsys.readouterr().out
    assert "base.site" in out and "compiles:" in out


def test_flush_ledger_device_columns_host_path():
    """The widened flush ledger on the host path: every record carries
    the comp_ms/h2d_ms/dev_ms/util columns (zeros — nothing compiled,
    nothing fused), the summary grows the device block, and
    /dump_flushes keeps its shape."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane import VerifyPlane

    priv = PrivKey.generate(b"\x2d" * 32)
    plane = VerifyPlane(window_ms=1.0, use_device=False)
    plane.start()
    try:
        fut = plane.submit(priv.pub_key(), b"zdev-msg",
                           priv.sign(b"zdev-msg"))
        assert fut.result(30.0) == (True,)
    finally:
        plane.stop()
    recs = plane.dump_flushes()["flushes"]
    assert recs, "no flush recorded"
    r = recs[0]
    for col in ("comp_ms", "h2d_ms", "dev_ms", "util"):
        assert col in r, r
        assert r[col] == 0.0
    dev = plane.dump_flushes()["summary"]["device"]
    assert dev["comp_ms"] == 0.0 and dev["fused_flushes"] == 0
    assert dev["util"]["p50"] == 0.0


def test_cross_dump_hammer_during_node_stop(fresh_ledger):
    """ISSUE 20 satellite: dump readers racing the write side AND a
    node teardown — one thread feeds the cost surfaces + compile ring
    at full rate while readers hammer dump_devices() (the
    /dump_devices body) across a live node's start/stop window. No
    dump may raise or fail to serialize, every served cost_surfaces
    row must be internally consistent (p50 <= p95, bounded samples),
    and the final document accounts for every observation."""
    import threading
    import time

    surf = deviceledger.CostSurfaces()
    old_surf = deviceledger.install_surfaces(surf)
    stop_evt = threading.Event()
    errors = []
    wrote = [0]

    def writer():
        i = 0
        while not stop_evt.is_set():
            stamp = "device" if i % 2 else "host"
            deviceledger.observe_flush("hammer", stamp, 8 << (i % 4),
                                       1, 0.01, 0.02, 0.5 + i % 7)
            with deviceledger.attr_context("hammer.site", i):
                deviceledger.record_compile(0.0001)
            i += 1
            wrote[0] = i
            time.sleep(0.001)

    def reader():
        while not stop_evt.is_set():
            try:
                doc = deviceledger.dump_devices()
                json.dumps(doc)
                for row in doc["cost_surfaces"]:
                    assert row["n"] >= 1
                    assert row["dev_ms_p50"] <= row["dev_ms_p95"]
                cm = deviceledger.cost_model()
                cm.estimate_dev_ms("hammer", 64)
            except Exception as e:  # noqa: BLE001 - the assertion
                errors.append(repr(e))
                return
            time.sleep(0.002)  # 1-core host: leave the nodes air

    threads = [threading.Thread(target=writer, daemon=True),
               threading.Thread(target=reader, daemon=True),
               threading.Thread(target=reader, daemon=True)]
    nodes = _mini_net(2)
    try:
        for t in threads:
            t.start()
        for n in nodes:
            n.start()
        assert nodes[0].consensus.wait_for_height(1, timeout=30.0)
        # the teardown races the readers — the satellite's point
        for n in nodes:
            n.stop()
        time.sleep(0.05)  # post-stop dumps land under the hammer too
    finally:
        stop_evt.set()
        for t in threads:
            t.join(timeout=10.0)
        for n in nodes:
            n.stop()
        deviceledger.install_surfaces(old_surf)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    assert wrote[0] >= 4, "writer barely ran"
    # the final document accounts for everything the writer observed
    # (>= because live node flushes feed the same global surfaces)
    final = surf.counters()
    assert final["observed"] >= wrote[0], (final, wrote[0])
    fams = {r["family"] for r in surf.surfaces()}
    assert {"hammer", "hammer:stamped"} <= fams, fams
    assert fresh_ledger.counters()["compiles"] >= wrote[0]


def _device_ledger_bookkeeping_us(k):
    """One replay of the exact per-flush sequence the dispatcher adds
    for the observatory with tracing off: one attribution frame
    push/pop around the dispatch, the two clock reads bracketing it and
    the three in-place ledger stamps (comp/h2d/util). The compile
    RECORDING path is off that budget (compiles are rare, ms-scale
    events) but is timed too, so a storm cannot hide a pathological
    record cost. Returns (flush hook us, compile record us)."""
    assert not tracing.enabled(), "measure the DISABLED path"
    led = deviceledger.CompileLedger()
    rec = [0, 0.0, 0.0, 0, "budget", -1, 0]
    t0 = time.perf_counter()
    for i in range(k):
        fr = deviceledger.attr_begin("plane.flush", i)
        a = tracing.monotonic_ns()
        b = tracing.monotonic_ns()
        deviceledger.attr_end(fr)
        rec[2] = round(fr.ms, 3)
        rec[3] = round(max((b - a) / 1e6 - fr.ms, 0.0), 3)
        rec[4] = 0.97
    attr_us = (time.perf_counter() - t0) * 1e6 / k
    t1 = time.perf_counter()
    for i in range(2000):
        led.record(0.001, False, "budget", i)
    return attr_us, (time.perf_counter() - t1) * 1e6 / 2000


def test_device_hook_budget():
    """ISSUE 15 acceptance: < 10 us per flush for the observatory's
    always-on hooks with tracing OFF (best of 3 to dodge 1-core
    scheduler spikes; typical is ~1-2 us)."""
    rows = [_device_ledger_bookkeeping_us(5_000) for _ in range(3)]
    best = min(r[0] for r in rows)
    assert best < 10.0, f"flush hooks {best} us"
    assert min(r[1] for r in rows) < 50.0


def _cost_hooks_bookkeeping_us(k):
    """One replay of the exact sequence _charge_flush adds to every
    flush with tracing off: one split_device_columns call over a fused
    three-tenant batch (integer shares plus the last-tenant residual:
    the worst common case), the per-share charge, and the cost-surface
    bucketing, against throwaway registry and surface instances."""
    from cometbft_tpu.verifyplane.plane import split_device_columns
    from cometbft_tpu.verifyplane.tenants import TenantRegistry

    assert not tracing.enabled(), "measure the DISABLED path"
    reg = TenantRegistry()
    surf = deviceledger.CostSurfaces()
    tens = (("budget-a", 24), ("budget-b", 24), ("budget-c", 16))
    t0 = time.perf_counter()
    for _ in range(k):
        _, shares = split_device_columns(tens, 64, 1.25, 0.5, 3.75, 5121)
        reg.note_device_shares(shares)
        surf.observe("fused:stamped", 64, 1, 1.25, 0.5, 3.75)
    return (time.perf_counter() - t0) * 1e6 / k


def test_cost_hook_budget():
    """ISSUE 20 acceptance: < 10 us per flush for the cost
    observatory's always-on hooks with tracing OFF (best of 3, as the
    other hook budgets take it: one reading on a shared host is what
    failed tier-1 at PR 28)."""
    best = min(_cost_hooks_bookkeeping_us(5_000) for _ in range(3))
    assert 0 < best < 10.0, f"cost hooks {best} us"


def test_cost_surface_bucket_marginal_and_estimate_math():
    """The cost surfaces' arithmetic against an isolated recorder:
    power-of-two rows buckets, one sorted row per (family, bucket)
    with the stamped family label, the marginal slope between adjacent
    buckets, and the model's estimate past the learned range (and none
    for a family never observed)."""
    assert [deviceledger.rows_bucket(n) for n in (0, 1, 2, 3, 64, 65)] \
        == [1, 1, 2, 4, 64, 128]
    prev = deviceledger.install_surfaces(deviceledger.CostSurfaces())
    try:
        for rows, dev in ((8, 0.6), (64, 1.1), (512, 4.0)):
            for _ in range(5):
                deviceledger.observe_flush(
                    "fused", "device", rows, 1, 0.0, 0.1, dev)
        cs = deviceledger.surfaces().surfaces()
        assert [r["family"] for r in cs] == ["fused:stamped"] * 3
        assert [r["dev_ms_p50"] for r in cs] == [0.6, 1.1, 4.0]
        assert cs[0]["marginal_ms_per_row"] is None
        assert cs[1]["marginal_ms_per_row"] == \
            round((1.1 - 0.6) / (64 - 8), 6)
        assert cs[2]["marginal_ms_per_row"] is not None
        model = deviceledger.cost_model()
        assert model.estimate_dev_ms("fused:stamped", 2000) is not None
        assert model.estimate_dev_ms("unobserved", 64) is None
    finally:
        deviceledger.install_surfaces(prev)


def test_no_jax_import():
    """Host-only contract: nothing in this file (the observatory core,
    residency sampling, RPC, device_report, the budget replays) may pull
    jax into the process."""
    if not _JAX_LOADED_BEFORE:
        assert "jax" not in sys.modules
