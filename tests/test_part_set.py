"""PartSet: split/prove/reassemble + multi-part block propagation over
real TCP consensus.

Reference: types/part_set_test.go (round trip, proof tamper) and the
consensus reactor's gossipDataRoutine part gossip (reactor.go:569) —
a block bigger than one part must still commit across a TCP mesh.
"""
import os
import time

import pytest

from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.consensus.ticker import TimeoutParams
from cometbft_tpu.crypto.keys import PrivKey
from cometbft_tpu.node.node import Node
from cometbft_tpu.p2p.key import NodeKey
from cometbft_tpu.privval.file_pv import FilePV
from cometbft_tpu.state.state import State
from cometbft_tpu.types import part_set as psmod
from cometbft_tpu.types.validator import Validator, ValidatorSet

FAST = TimeoutParams(
    propose=0.6, propose_delta=0.2,
    prevote=0.3, prevote_delta=0.1,
    precommit=0.3, precommit_delta=0.1,
    commit=0.02,
)


def test_round_trip_multi_part():
    data = os.urandom(5 * 65536 + 12345)
    ps = psmod.PartSet.from_data(data)
    assert ps.total() == 6
    assert ps.is_complete()
    hdr = ps.header()
    assert hdr.total == 6 and len(hdr.hash) == 32

    rx = psmod.PartSet.from_header(hdr)
    assert not rx.is_complete()
    # out-of-order arrival, with wire round trip per part
    for i in [3, 0, 5, 1, 4, 2]:
        wire = psmod.Part.from_j(ps.get_part(i).to_j())
        assert rx.add_part(wire) is True
        assert rx.add_part(wire) is False  # duplicate
    assert rx.is_complete()
    assert rx.assemble() == data
    assert rx.bit_array().get_index(3)


def test_tampered_part_rejected():
    data = os.urandom(3 * 65536)
    ps = psmod.PartSet.from_data(data)
    rx = psmod.PartSet.from_header(ps.header())
    part = ps.get_part(1)
    # flip the last byte: overwriting it with a constant leaves one
    # random payload in 256 unchanged
    evil = psmod.Part(1, part.data[:-1] + bytes([part.data[-1] ^ 0xFF]),
                      part.proof)
    with pytest.raises(psmod.PartSetError):
        rx.add_part(evil)
    # proof from the wrong slot
    wrong = psmod.Part(2, part.data, part.proof)
    with pytest.raises(psmod.PartSetError):
        rx.add_part(wrong)


def test_oversized_proof_rejected():
    """A peer cannot attach unbounded aunts/hashes to a part: the
    receive side buffers orphan parts before proof verification, so
    validate_basic must bound attacker-controlled proof bytes."""
    from cometbft_tpu.crypto import merkle

    ps = psmod.PartSet.from_data(os.urandom(65536 * 2))
    good = ps.get_part(0)
    # too many aunts
    bloated = psmod.Part(0, good.data, merkle.Proof(
        good.proof.total, 0, good.proof.leaf_hash,
        [os.urandom(32)] * (psmod.Part.MAX_AUNTS + 1)))
    with pytest.raises(psmod.PartSetError):
        bloated.validate_basic()
    # wrong-size aunt
    fat = psmod.Part(0, good.data, merkle.Proof(
        good.proof.total, 0, good.proof.leaf_hash,
        [os.urandom(1 << 20)]))
    with pytest.raises(psmod.PartSetError):
        fat.validate_basic()
    # wrong-size leaf hash
    badleaf = psmod.Part(0, good.data, merkle.Proof(
        good.proof.total, 0, b"\x00" * 31, list(good.proof.aunts)))
    with pytest.raises(psmod.PartSetError):
        badleaf.validate_basic()
    # absurd total
    badtotal = psmod.Part(0, good.data, merkle.Proof(
        psmod.PartSet.MAX_TOTAL + 1, 0, good.proof.leaf_hash,
        list(good.proof.aunts)))
    with pytest.raises(psmod.PartSetError):
        badtotal.validate_basic()
    good.validate_basic()  # the honest part still passes


def test_wal_rotated_segment_truncation_stops_replay(tmp_path):
    """A torn header inside a ROTATED segment is mid-stream corruption:
    replay must stop rather than splice older records onto newer ones.
    A torn header in the head file is a normal crash artifact."""
    import struct
    import zlib

    from cometbft_tpu.consensus.wal import WAL

    def rec(payload: bytes) -> bytes:
        body = b"\x01" + payload
        return struct.pack(">II", zlib.crc32(body) & 0xFFFFFFFF,
                           len(body)) + body

    head = str(tmp_path / "wal")
    # rotated segment with one good record + a 3-byte torn header
    with open(head + ".000", "wb") as f:
        f.write(rec(b"seg0") + b"\x00\x01\x02")
    with open(head, "wb") as f:
        f.write(rec(b"head0") + rec(b"head1"))
    got = [r.data for r in WAL.iter_records(head)]
    assert got == [b"seg0"], got  # stream stops at the rotated tear
    # same tear in the HEAD file: records before it replay fine
    os.truncate(head + ".000", len(rec(b"seg0")))
    with open(head, "ab") as f:
        f.write(b"\x00\x01")
    got = [r.data for r in WAL.iter_records(head)]
    assert got == [b"seg0", b"head0", b"head1"], got


def test_single_small_part():
    ps = psmod.PartSet.from_data(b"tiny")
    assert ps.total() == 1
    rx = psmod.PartSet.from_header(ps.header())
    rx.add_part(ps.get_part(0))
    assert rx.assemble() == b"tiny"


def test_block_id_psh_is_deterministic(tmp_path):
    """block_id()'s PartSetHeader must be a pure function of block
    content — every validator derives the identical BlockID to vote on
    (consensus-critical; types/block.go:140 MakePartSet)."""
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import StateStore
    from cometbft_tpu.types import serde
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.commit import Commit

    privs = [PrivKey.generate(bytes([i + 9]) * 32) for i in range(2)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis("psh-chain", vals)
    exec_ = BlockExecutor(KVStoreApplication(), StateStore(":memory:"))
    block = exec_.create_proposal_block(
        1, state, Commit(0, 0, BlockID(), []),
        vals.get_proposer().address, txs=[os.urandom(100_000).hex().encode()]
    )
    bid = block.block_id()
    assert bid.part_set_header.total >= 2  # really multi-part
    # wire round trip -> same BlockID
    again = serde.block_from_json(serde.block_to_json(block))
    assert again.block_id() == bid


@pytest.mark.slow
def test_multipart_block_commits_over_tcp(tmp_path):
    """A block whose wire form spans several 64KiB parts commits on a
    4-node TCP mesh — whole-block messages never cross the wire."""
    privs = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(4)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis("part-chain", vals)
    nodes, addrs = [], []
    for i, priv in enumerate(privs):
        n = Node(KVStoreApplication(), state.copy(), privval=FilePV(priv),
                 home=str(tmp_path / f"n{i}"), timeouts=FAST, p2p=True,
                 node_key=NodeKey(PrivKey.generate(bytes([0x60 + i]) * 32)))
        addrs.append(n.listen())
        nodes.append(n)
    for n in nodes:
        n.start()
    try:
        for i, n in enumerate(nodes):
            for j, a in enumerate(addrs):
                if i != j:
                    n.dial(a)
        deadline = time.time() + 10
        while any(n.switch.num_peers() < 3 for n in nodes):
            assert time.time() < deadline, "mesh never formed"
            time.sleep(0.05)
        # ~200 KiB of tx payload -> several parts once hex-encoded
        big = b"big=" + os.urandom(100_000).hex().encode()
        nodes[0].broadcast_tx(big)
        target = nodes[0].height() + 3
        for n in nodes:
            assert n.consensus.wait_for_height(target, timeout=120), \
                f"stuck at {n.height()}"
        # the big tx committed somewhere and all stores agree
        found = False
        for h in range(1, target + 1):
            b = nodes[1].block_store.load_block(h)
            if b and any(t == big for t in b.data.txs):
                found = True
                assert b.block_id().part_set_header.total >= 2
        assert found, "big tx never committed"
    finally:
        for n in nodes:
            n.stop()
