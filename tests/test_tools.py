"""wal_generator + loadtime tooling.

Reference: consensus/wal_generator.go:226, scripts/wal2json,
test/loadtime (load/main.go, report/report.go).
"""
import time

from cometbft_tpu.consensus.wal_generator import generate_wal, wal_to_json
from cometbft_tpu.tools import loadtime


def test_wal_generator_and_wal2json(tmp_path):
    dest = str(tmp_path / "gen.wal")
    assert generate_wal(3, dest) == dest
    recs = wal_to_json(dest)
    ends = [r for r in recs if r["kind"] == "end_height"]
    assert [r["height"] for r in ends][:2] == [1, 2]
    msgs = [r for r in recs if r["kind"] == "msg"]
    assert any(r["msg"].get("t") == "vote" for r in msgs)
    assert any(r["msg"].get("t") == "proposal" for r in msgs)


def test_payload_roundtrip():
    tx = loadtime.make_tx(7, size=100)
    assert len(tx) == 100
    seq, stamp = loadtime.parse_tx(tx)
    assert seq == 7
    assert abs(stamp - time.time_ns()) < 5 * 10**9
    assert loadtime.parse_tx(b"not a load tx") is None


def test_load_and_report(tmp_path):
    """Drive a live single-validator node with timestamped load and
    recompute per-tx latency from its block store."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.consensus.ticker import TimeoutParams
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.node.node import Node
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.state.state import State
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    fast = TimeoutParams(propose=0.4, propose_delta=0.1, prevote=0.2,
                         prevote_delta=0.1, precommit=0.2,
                         precommit_delta=0.1, commit=0.01)
    priv = PrivKey.generate(bytes([31]) * 32)
    vals = ValidatorSet([Validator(priv.pub_key(), 10)])
    state = State.make_genesis("load-chain", vals)
    node = Node(KVStoreApplication(), state, privval=FilePV(priv),
                home=str(tmp_path / "n0"), timeouts=fast)
    node.start()
    try:
        assert node.consensus.wait_for_height(1, timeout=30)
        n = loadtime.run_load(node.broadcast_tx, rate=50,
                              duration_s=1.0, size=80)
        assert n >= 10
        assert node.consensus.wait_for_height(node.height() + 2,
                                              timeout=30)
        rep = loadtime.report_from_blockstore(node.block_store)
    finally:
        node.stop()
    assert rep is not None and rep.n_txs >= 1
    # block time is the BFT median with second granularity, so a tx can
    # land in a block "timestamped" earlier than its own stamp; bounds
    # are sanity, not sign
    assert rep.min_ms <= rep.p50_ms <= rep.max_ms
    assert rep.max_ms < 60_000


def test_wal_rotation_and_group_replay(tmp_path):
    """autofile.Group analog: the WAL rotates at height boundaries once
    the head exceeds its size limit; replay and ENDHEIGHT search span
    the whole group; old segments are pruned."""
    import os
    import struct

    from cometbft_tpu.consensus import wal as walmod

    path = str(tmp_path / "cs.wal")
    w = walmod.WAL(path, head_size_limit=2000, max_segments=3)
    for h in range(1, 30):
        for k in range(3):
            w.write_sync(walmod.MSG_INFO, b"h%02d-msg%d" % (h, k) * 20)
        w.write_end_height(h)
    w.close()
    segs = [f for f in os.listdir(tmp_path) if f.startswith("cs.wal.")]
    assert segs, "never rotated"
    assert len(segs) <= 3, f"pruning failed: {segs}"
    # replay spans segments: the most recent heights are intact
    recs = list(walmod.WAL.iter_records(path))
    ends = [struct.unpack(">q", r.data)[0] for r in recs
            if r.kind == walmod.END_HEIGHT]
    assert ends[-1] == 29 and len(ends) >= 5
    # ENDHEIGHT search across the group finds a recent height
    idx = walmod.WAL.search_for_end_height(path, ends[-2])
    assert idx is not None
    tail = list(walmod.WAL.iter_records(path))[idx:]
    assert any(r.kind == walmod.END_HEIGHT
               and struct.unpack(">q", r.data)[0] == 29 for r in tail)
