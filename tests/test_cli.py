"""CLI + config: init/start/testnet drive real validators from home dirs.

Reference: cmd/cometbft/commands (init.go, run_node.go, testnet.go) and
config/config.go ValidateBasic.
"""
import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from cometbft_tpu.config.config import (
    Config,
    ConfigError,
    load_config,
    save_config,
)
from cometbft_tpu.cmd import cli


def test_config_roundtrip(tmp_path):
    cfg = Config()
    cfg.base.chain_id = "roundtrip"
    cfg.crypto.verifier = "cpu"
    cfg.consensus.timeout_propose = 1.5
    p = str(tmp_path / "config.toml")
    save_config(cfg, p)
    got = load_config(p)
    assert got.base.chain_id == "roundtrip"
    assert got.crypto.verifier == "cpu"
    assert got.consensus.timeout_propose == 1.5

    cfg.crypto.verifier = "gpu"
    with pytest.raises(ConfigError):
        cfg.validate_basic()


def test_init_start_rpc(tmp_path):
    """`init` then `start`: the validator commits blocks and serves RPC
    (the round-2 verdict item 8 done-condition)."""
    home = str(tmp_path / "node")
    assert cli.main(["init", "--home", home, "--chain-id", "cli-chain",
                     "--verifier", "cpu"]) == 0
    # speed up consensus + pick free ports for the test
    cfg = load_config(os.path.join(home, "config", "config.toml"))
    cfg.consensus.timeout_propose = 0.4
    cfg.consensus.timeout_commit = 0.01
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.base.blocksync = False
    save_config(cfg, os.path.join(home, "config", "config.toml"))

    node, cfg = cli.build_node(home)
    node.start()
    try:
        url = node.rpc_listen()
        assert node.consensus.wait_for_height(2, timeout=60)
        with urllib.request.urlopen(f"{url}/status", timeout=5) as r:
            j = json.loads(r.read().decode())
        assert j["result"]["sync_info"]["latest_block_height"] >= 2
        assert j["result"]["node_info"]["network"] == "cli-chain"
    finally:
        node.stop()


def test_start_verifier_override_keeps_off_the_device(tmp_path, capsys):
    """One process per chip: `start --verifier cpu` overrides a config
    that says tpu, says on its start line which device it verifies on
    (none), and sets no compile cache because it compiles nothing."""
    home = str(tmp_path / "node")
    assert cli.main(["init", "--home", home, "--chain-id", "cpu-chain"]) == 0
    path = os.path.join(home, "config", "config.toml")
    cfg = load_config(path)
    assert cfg.crypto.verifier == "tpu"  # the default init writes
    cfg.consensus.timeout_propose = 0.4
    cfg.consensus.timeout_commit = 0.01
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.base.blocksync = False
    save_config(cfg, path)
    assert cli.main(["start", "--home", home, "--run-for", "1.5",
                     "--verifier", "cpu"]) == 0
    out = capsys.readouterr().out
    assert "verifier cpu: platform=" in out
    assert "compile cache:" not in out
    assert "rpc listening on" in out


def test_testnet_generation(tmp_path):
    out = str(tmp_path / "net")
    assert cli.main(["testnet", "--v", "3", "--output", out,
                     "--chain-id", "net-chain"]) == 0
    geneses = set()
    for i in range(3):
        home = os.path.join(out, f"node{i}")
        cfg = load_config(os.path.join(home, "config", "config.toml"))
        assert cfg.base.chain_id == "net-chain"
        peers = cfg.p2p.persistent_peers.split(",")
        assert len(peers) == 2  # wired to the other two
        with open(os.path.join(home, "config", "genesis.json")) as f:
            geneses.add(f.read())
    assert len(geneses) == 1  # identical genesis everywhere
    from cometbft_tpu.types.genesis import GenesisDoc

    doc = GenesisDoc.from_file(
        os.path.join(out, "node0", "config", "genesis.json"))
    assert len(doc.validators) == 3


def test_reindex_event_rebuilds_indexes(tmp_path):
    """reindex_event.go: wipe tx_index.db + block_index.db, reindex
    from the block store + stored FinalizeBlock responses, and
    tx_search/the tx route serve the same answers as before."""
    import os

    home = str(tmp_path / "n0")
    assert cli.main(["init", "--home", home, "--chain-id", "ri-chain",
                     "--verifier", "cpu"]) == 0
    cfg = load_config(os.path.join(home, "config", "config.toml"))
    cfg.consensus.timeout_propose = 0.4
    cfg.consensus.timeout_commit = 0.01
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.base.blocksync = False
    save_config(cfg, os.path.join(home, "config", "config.toml"))
    node, _ = cli.build_node(home)
    node.start()
    try:
        node.broadcast_tx(b"ri=1")
        assert node.consensus.wait_for_height(node.height() + 2,
                                              timeout=60)
        import hashlib

        txh = hashlib.sha256(b"ri=1").hexdigest().upper()
        got = node.tx_indexer.get(bytes.fromhex(txh))
        assert got is not None
        h_indexed = got["height"]
    finally:
        node.stop()

    # wipe the indexes, then reindex from stores
    data = os.path.join(home, "data")
    for n in ("tx_index.db", "block_index.db"):
        os.remove(os.path.join(data, n))
    assert cli.main(["reindex-event", "--home", home]) == 0

    from cometbft_tpu.state.indexer import BlockIndexer, TxIndexer

    txi = TxIndexer(os.path.join(data, "tx_index.db"))
    got = txi.get(bytes.fromhex(txh))
    assert got is not None and got["height"] == h_indexed
    assert txi.search(f"tx.height={h_indexed}")
    bli = BlockIndexer(os.path.join(data, "block_index.db"))
    assert h_indexed in bli.search(f"block.height={h_indexed}")
    txi.close(); bli.close()


def test_debug_dump_and_kill(tmp_path):
    """debug.go: dump collects status/net_info/consensus/stacks from a
    live node's (unsafe) RPC; kill writes the zip and signals the pid
    (we hand it a throwaway child process)."""
    import json as _json
    import os
    import subprocess
    import sys as _sys
    import zipfile

    home = str(tmp_path / "nd")
    assert cli.main(["init", "--home", home, "--chain-id", "dbg-chain",
                     "--verifier", "cpu"]) == 0
    cfg = load_config(os.path.join(home, "config", "config.toml"))
    cfg.consensus.timeout_propose = 0.4
    cfg.consensus.timeout_commit = 0.01
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.base.blocksync = False
    save_config(cfg, os.path.join(home, "config", "config.toml"))
    node, _ = cli.build_node(home)
    node.start()
    url = node.rpc_listen(unsafe=True)
    try:
        assert node.consensus.wait_for_height(2, timeout=60)
        out = str(tmp_path / "snaps")
        assert cli.main(["debug", "dump", out, "--home", home,
                         "--rpc-laddr", url, "--frequency", "0.1",
                         "--count", "1"]) == 0
        snaps = os.listdir(out)
        assert len(snaps) == 1
        files = set(os.listdir(os.path.join(out, snaps[0])))
        assert {"status.json", "consensus_state.json",
                "stacks.txt", "config.toml"} <= files
        st = _json.load(open(os.path.join(out, snaps[0],
                                          "status.json")))
        assert st["result"]["node_info"]["network"] == "dbg-chain"

        child = subprocess.Popen([_sys.executable, "-c",
                                  "import time; time.sleep(60)"])
        zpath = str(tmp_path / "dump.zip")
        assert cli.main(["debug", "kill", str(child.pid), zpath,
                         "--home", home, "--rpc-laddr", url]) == 0
        assert child.wait(timeout=10) != 0  # SIGTERM'd
        with zipfile.ZipFile(zpath) as z:
            assert "status.json" in z.namelist()
    finally:
        node.stop()
