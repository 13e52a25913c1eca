"""Tier-1 guard for the light-client gateway RPC surface (ISSUE 8
satellite): the lightgate_* routes end-to-end against an in-process
node — host paths only, NO jax import, seconds not minutes. Late in
the alphabet like test_zloadtime_smoke: by the time
this runs, the unit tests have localized any real breakage.
"""
import json
import sys
import urllib.request

import pytest

from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.config.config import LightGateConfig
from cometbft_tpu.consensus.ticker import TimeoutParams
from cometbft_tpu.crypto.keys import PrivKey
from cometbft_tpu.node.node import Node
from cometbft_tpu.privval.file_pv import FilePV
from cometbft_tpu.rpc.client import HTTPClient
from cometbft_tpu.state.state import State
from cometbft_tpu.types.validator import Validator, ValidatorSet

FAST = TimeoutParams(
    propose=0.4, propose_delta=0.1,
    prevote=0.2, prevote_delta=0.1,
    precommit=0.2, precommit_delta=0.1,
    commit=0.01,
)


@pytest.fixture()
def gateway_node(tmp_path):
    priv = PrivKey.generate(b"\x5a" * 32)
    vals = ValidatorSet([Validator(priv.pub_key(), 10)])
    state = State.make_genesis("lightgate-rpc-chain", vals)
    node = Node(KVStoreApplication(), state, privval=FilePV(priv),
                home=str(tmp_path / "n0"), timeouts=FAST,
                lightgate=LightGateConfig(enable=True, cache_size=64))
    node.start()
    url = node.rpc_listen()
    try:
        assert node.consensus.wait_for_height(3, timeout=60)
        yield node, url, priv
    finally:
        node.stop()


def test_lightgate_rpc_end_to_end(gateway_node):
    jax_loaded_before = "jax" in sys.modules
    node, url, priv = gateway_node
    c = HTTPClient(url)

    # the gateway mounted with the node and registered globally
    from cometbft_tpu.lightgate import global_gateway

    assert node.lightgate is not None
    assert global_gateway() is node.lightgate

    # verify: client trusts height 1, wants the tip
    tip = node.block_store.height()
    v = c.call("lightgate_verify", trusted_height=1, target_height=tip)
    assert v["status"] == "verified"
    assert v["height"] == tip
    assert v["target"]["signed_header"]["header"]["height"] == tip

    # repeat sync over the popular pair: pure cache hit
    v2 = c.call("lightgate_verify", trusted_height=1, target_height=tip)
    assert v2["cached"] is True
    assert v2["target_hash"] == v["target_hash"]

    # batched header serving, range form + explicit list + cap
    hs = c.call("lightgate_headers", min_height=1, max_height=tip)
    assert [h["height"] for h in hs["headers"]] == list(range(1, tip + 1))
    hs2 = c.call("lightgate_headers", heights=[1, tip, 999_999],
                 with_validators=True)
    assert hs2["missing"] == [999_999]
    assert len(hs2["headers"][0]["validators"]) == 1

    # a forged claim (lying primary) yields a divergent verdict and
    # LightClientAttackEvidence in the node's pool
    from cometbft_tpu.simnet.actors import forged_claim
    from cometbft_tpu.types.evidence import LightClientAttackEvidence
    from cometbft_tpu.types.timestamp import Timestamp

    claim = forged_claim([priv], node.consensus.state.validators,
                         "lightgate-rpc-chain", [0], tip,
                         Timestamp.now())
    dv = c.call("lightgate_verify", trusted_height=1, target_height=tip,
                claimed=claim)
    assert dv["status"] == "divergent"
    assert dv["evidence_added"] is True
    evs = node.evidence_pool.pending_evidence()
    assert any(isinstance(e, LightClientAttackEvidence) for e in evs)

    # status + scrape-time metrics
    st = c.call("lightgate_status")
    assert st["requests"] >= 3 and st["verifies"] >= 1
    assert st["cache"]["hits"] >= 1
    with urllib.request.urlopen(f"{url}/metrics", timeout=5) as r:
        metrics = r.read().decode()
    assert 'cometbft_lightgate_cache_total{kind="hits"}' in metrics
    assert 'cometbft_lightgate_requests_total{kind="verifies"}' in metrics

    # GET (URI) form works too
    with urllib.request.urlopen(
        f"{url}/lightgate_verify?trusted_height=1&target_height={tip}",
        timeout=5,
    ) as r:
        j = json.loads(r.read().decode())
    assert j["result"]["status"] == "verified"

    # host-only contract: serving light clients must never pull in jax
    if not jax_loaded_before:
        assert "jax" not in sys.modules, "lightgate smoke imported jax"


def test_lightgate_routes_error_without_gateway(tmp_path):
    """A node without [lightgate] answers the routes with a clear
    error instead of AttributeError soup."""
    priv = PrivKey.generate(b"\x5b" * 32)
    vals = ValidatorSet([Validator(priv.pub_key(), 10)])
    state = State.make_genesis("nogw-chain", vals)
    node = Node(KVStoreApplication(), state, privval=FilePV(priv),
                home=str(tmp_path / "n1"), timeouts=FAST)
    node.start()
    url = node.rpc_listen()
    try:
        assert node.consensus.wait_for_height(1, timeout=60)
        c = HTTPClient(url)
        with pytest.raises(Exception, match="no light-client gateway"):
            c.call("lightgate_status")
    finally:
        node.stop()
