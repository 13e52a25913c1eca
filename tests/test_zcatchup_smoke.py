"""Archival bootstrap plane tier-1 wiring (ISSUE 18): GET+JSON-RPC
/dump_catchup over a live server, /metrics statesync families riding a
real scrape, and the catchup_report --diff regression detector
(including the miswired --fail-on-regression gate).

Late in the alphabet on purpose (tier-1 ordering note in ROADMAP).
"""
import copy
import json
import sys
import urllib.request

import pytest

from cometbft_tpu.blocksync import catchup as cu
from cometbft_tpu.blocksync.catchup import CatchupLedger
from cometbft_tpu.libs import tracing
from cometbft_tpu.statesync import stats as ss_stats

_JAX_LOADED_BEFORE = "jax" in sys.modules


def _ledger(n_flushes=10, blocks=10, sigs=30, gap_ms=100.0,
            verify_ms=2.0, resumes=0, boundaries_every=5,
            warm=True, skipped_first=0):
    """Deterministic ledger on a virtual clock: exact window rates."""
    now = [10 ** 12]
    tracing.set_clock(lambda: now[0])
    try:
        led = CatchupLedger()
        h = 1
        for i in range(n_flushes):
            skipped = skipped_first if i == 0 else 0
            boundary = boundaries_every and (i + 1) % boundaries_every == 0
            led.record(first=h, last=h + blocks - 1, blocks=blocks,
                       sigs=sigs, skipped=skipped, read_ms=0.5,
                       verify_ms=verify_ms, apply_ms=0.3,
                       boundary=boundary, warmed=boundary and warm)
            h += blocks
            now[0] += int(gap_ms * 1e6)
        for _ in range(resumes):
            led.note_resume()
        return led
    finally:
        tracing.set_clock(None)


def _dump(led):
    return {"records": led.records(), "summary": led.summary(),
            "counters": dict(led.counters)}


def test_dump_catchup_over_real_rpc(tmp_path):
    """GET /dump_catchup and the JSON-RPC form over a live server (the
    curl surface), plus the statesync metric families on a real
    /metrics scrape."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.consensus.ticker import TimeoutParams
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.node.node import Node
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.state.state import State
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    old_g, old_l = cu._GLOBAL, cu._LAST
    led = _ledger(n_flushes=4, resumes=1)
    cu.set_global_ledger(led)
    ss_stats.reset()
    ss_stats.bump("chunks_fetched", 7)
    ss_stats.bump("snapshots_shed", 2)
    priv = PrivKey.generate(b"\x18" * 32)
    vals = ValidatorSet([Validator(priv.pub_key(), 10)])
    state = State.make_genesis("zcatchup-chain", vals)
    fast = TimeoutParams(propose=0.4, propose_delta=0.1, prevote=0.2,
                         prevote_delta=0.1, precommit=0.2,
                         precommit_delta=0.1, commit=0.05)
    node = Node(KVStoreApplication(), state, privval=FilePV(priv),
                home=str(tmp_path / "n0"), timeouts=fast)
    node.start()
    try:
        url = node.rpc_listen("127.0.0.1", 0)
        assert node.consensus.wait_for_height(1, timeout=30.0)
        with urllib.request.urlopen(url + "/dump_catchup",
                                    timeout=10) as r:
            doc = json.loads(r.read().decode())
        assert doc["counters"]["flushes"] == 4
        assert doc["counters"]["resumes"] == 1
        assert len(doc["records"]) == 4
        assert doc["summary"]["blocks_per_s"] > 0
        body = json.dumps({"jsonrpc": "2.0", "id": 1,
                           "method": "dump_catchup",
                           "params": {}}).encode()
        req = urllib.request.Request(
            url, data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            rpc = json.loads(r.read().decode())
        assert rpc["result"]["counters"]["flushes"] == 4
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        for fam in ("cometbft_statesync_chunks_total",
                    "cometbft_statesync_fetch_timeouts_total",
                    "cometbft_statesync_providers_total",
                    "cometbft_statesync_retry_snapshot_rounds_total",
                    "cometbft_statesync_snapshots_total"):
            assert fam in text, fam
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("cometbft_statesync_chunks_total{")
                    and 'kind="fetched"' in ln)
        assert float(line.split()[-1]) == 7.0
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith("cometbft_statesync_snapshots_total{")
            and 'kind="shed"' in ln)
        assert float(line.split()[-1]) == 2.0
    finally:
        node.stop()
        ss_stats.reset()
        cu._GLOBAL, cu._LAST = old_g, old_l


def test_catchup_report_diff_detects_synthetic_regression(
        tmp_path, capsys):
    """The --diff CLI flags an injected throughput decay + verify-time
    growth (exit 1 under --fail-on-regression), stays quiet on
    identical dumps, and errors on a miswired gate."""
    from tools import catchup_report

    dump_a = _dump(_ledger())
    a_path = tmp_path / "a.json"
    a_path.write_text(json.dumps(dump_a))
    # B: the firehose got 4x slower and every flush pays cold tables
    led_b = _ledger(gap_ms=400.0, verify_ms=30.0, resumes=1,
                    warm=False)
    dump_b = _dump(led_b)
    b_path = tmp_path / "b.json"
    b_path.write_text(json.dumps(dump_b))

    rc = catchup_report.main([str(a_path), str(a_path), "--diff",
                              "--fail-on-regression"])
    assert rc == 0
    capsys.readouterr()
    rc = catchup_report.main([str(a_path), str(b_path), "--diff",
                              "--fail-on-regression"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out
    assert "blocks_per_s" in out and "verify_ms" in out
    # the resume-without-skips and cold-boundaries notes both fire
    assert "re-verified work" in out
    assert "ZERO warm-ahead" in out
    with pytest.raises(SystemExit):
        catchup_report.main([str(a_path), "--fail-on-regression"])
    # the single-dump report renders the per-flush table
    capsys.readouterr()
    assert catchup_report.main([str(a_path)]) == 0
    out = capsys.readouterr().out
    assert "100 blocks applied" in out
    assert "valset" in out and "boundaries" in out.replace(
        "boundaries,", "boundaries")
    junk = tmp_path / "junk.json"
    junk.write_text(json.dumps({"nope": 1}))
    with pytest.raises(ValueError):
        catchup_report.load_catchup(str(junk))


def test_report_figures_from_ledger_dump():
    from tools import catchup_report

    rep = catchup_report.catchup_report(_dump(_ledger(
        skipped_first=3, resumes=1)))
    assert rep["blocks_applied"] == 100
    assert rep["blocks_verified"] == 97
    assert rep["blocks_skipped"] == 3
    assert rep["resumes"] == 1
    assert rep["boundaries"] == 2
    assert rep["blocks_per_s"] == pytest.approx(100 / 0.9, rel=0.01)
    assert 0 < rep["verify_frac"] < 1


def test_no_jax_import():
    """The whole file ran host-only: nothing here may pull jax in."""
    if not _JAX_LOADED_BEFORE:
        assert "jax" not in sys.modules
