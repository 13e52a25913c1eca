"""Differential tests: Pallas fused ed25519 kernel vs oracle and XLA kernel.

Runs in Pallas interpret mode on the CPU test mesh (conftest forces
JAX_PLATFORMS=cpu); the same code path compiles to Mosaic on real TPU.
Covers the identical case matrix as tests/test_ed25519_kernel.py —
valid batches, the blame path, garbage inputs, and the ZIP-215 edge cases
whose CPU/TPU divergence would fork consensus.
"""
import os

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_ref as ed
from cometbft_tpu.ops import ed25519_kernel as k
from cometbft_tpu.ops import ed25519_pallas as kp


def make_sigs(n, msg_fn=lambda i: b"msg-%d" % i):
    seeds = [bytes([i + 1]) * 32 for i in range(n)]
    pubs = [ed.pubkey_from_seed(s) for s in seeds]
    msgs = [msg_fn(i) for i in range(n)]
    sigs = [ed.sign(s, m) for s, m in zip(seeds, msgs)]
    return pubs, msgs, sigs


@pytest.mark.slow  # ~75 s interpret-mode run on the 1-core host;
# zip215_edges/blame_path keep the quick-gate Pallas coverage
def test_all_valid_batch():
    pubs, msgs, sigs = make_sigs(5)
    got = kp.verify_batch(pubs, msgs, sigs)
    assert got.shape == (5,)
    assert got.all()


@pytest.mark.slow  # ~77 s on the 1-core host under suite load; the
# garbage/zip215/pad siblings keep the kernel in the quick gate
def test_blame_path_mixed_batch():
    pubs, msgs, sigs = make_sigs(8)
    bad = dict()
    sigs[2] = sigs[2][:10] + bytes([sigs[2][10] ^ 1]) + sigs[2][11:]
    bad[2] = True
    msgs[5] = msgs[5] + b"tampered"
    bad[5] = True
    sigs[6] = sigs[6][:32] + int.to_bytes(
        int.from_bytes(sigs[6][32:], "little") + ed.L, 32, "little"
    )  # S >= L: malleability reject in precheck
    bad[6] = True
    got = kp.verify_batch(pubs, msgs, sigs)
    for i in range(8):
        assert got[i] == (i not in bad), i
        assert got[i] == ed.verify(pubs[i], msgs[i], sigs[i]), i


@pytest.mark.slow  # ~115 s interpret-mode run on the 1-core host
# ([tier1-duration] flagged it past the 60 s line); zip215_edges keeps
# the quick-gate Pallas oracle-differential and the XLA twin
# (test_ed25519_kernel.py::test_matches_oracle_on_garbage) keeps the
# identical garbage matrix quick
def test_matches_oracle_on_garbage():
    rng = np.random.default_rng(3)
    pubs, msgs, sigs = [], [], []
    for i in range(16):
        pubs.append(rng.bytes(32))
        msgs.append(rng.bytes(i))
        sigs.append(rng.bytes(64))
    got = kp.verify_batch(pubs, msgs, sigs)
    exp = [ed.verify(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    np.testing.assert_array_equal(got, np.asarray(exp))


def test_zip215_edges():
    """Must match the oracle bit-for-bit on non-canonical encodings and
    small-order points — consensus forks otherwise."""
    ident = ed.pt_compress(ed.IDENT)
    cases = [(ident, b"m", ident + b"\x00" * 32)]
    for y in range(19):
        u, v = (y * y - 1) % ed.P, (ed.D * y * y + 1) % ed.P
        ok, x = ed._sqrt_ratio(u, v)
        if ok:
            enc_nc = int.to_bytes((y + ed.P) | ((x & 1) << 255), 32, "little")
            break
    seed = bytes(32)
    pub = ed.pubkey_from_seed(seed)
    sig = ed.sign(seed, b"x")
    cases.append((pub, b"x", enc_nc + sig[32:]))
    cases.append((enc_nc, b"x", sig))
    neg_zero = int.to_bytes(1 | (1 << 255), 32, "little")
    cases.append((neg_zero, b"m", neg_zero + b"\x00" * 32))
    pubs, msgs, sigs = zip(*cases)
    got = kp.verify_batch(list(pubs), list(msgs), list(sigs))
    exp = [ed.verify(p, m, s) for p, m, s in cases]
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert any(exp)


@pytest.mark.slow  # ~150 s interpret-mode cross-tile sweep
def test_matches_xla_kernel_cross_tile():
    """Pallas and XLA kernels agree on a batch spanning >1 tile (B=256)."""
    pubs, msgs, sigs = make_sigs(140)
    # corrupt a few spread across both tiles
    for i in (0, 63, 64, 127, 128, 139):
        sigs[i] = sigs[i][:8] + bytes([sigs[i][8] ^ 2]) + sigs[i][9:]
    got_p = kp.verify_batch(pubs, msgs, sigs)
    got_x = k.verify_batch(pubs, msgs, sigs)
    np.testing.assert_array_equal(got_p, got_x)
    exp = np.ones(140, bool)
    exp[[0, 63, 64, 127, 128, 139]] = False
    np.testing.assert_array_equal(got_p, exp)


def test_pad_to_tile():
    assert kp.pad_to_tile(1) == 128
    assert kp.pad_to_tile(64) == 128
    assert kp.pad_to_tile(129) == 256
    assert kp.pad_to_tile(257) == 1024


@pytest.mark.slow  # ~90 s interpret-mode multi-tile tally
def test_tally_multi_tile_with_invalid_and_quorum_miss():
    """verify_tally_rows across a >2-tile grid: invalid rows excluded
    from the tally, quorum-miss detected (round-2 verdict item 5 at a
    CPU-affordable 4-tile shape; the 10k shape runs on TPU below, in
    chip_smoke.py's commit leg and under the benchmark's `correct`)."""
    n = 4 * kp.B_TILE  # 512 rows, 4 grid steps
    pubs, msgs, sigs = make_sigs(64)
    pubs, msgs, sigs = pubs * 8, msgs * 8, sigs * 8
    bad = [3, 130, 300, 511]
    for i in bad:
        sigs[i] = sigs[i][:20] + bytes([sigs[i][20] ^ 4]) + sigs[i][21:]

    pb = k.pack_batch(pubs, msgs, sigs, pad_to=n)
    powers = np.full((n,), 7, np.int64)
    power5 = k.power_limbs(powers)
    counted = np.ones((n,), np.bool_)
    cids = np.zeros((n,), np.int32)
    # commit 0: all rows; threshold just under the honest sum -> quorum
    honest = (n - len(bad)) * 7
    thresh_ok = k.threshold_limbs(honest - 1)
    rows = kp.pack_rows(pb, power5, counted, cids, thresh_ok)
    valid, tally, quorum = kp.verify_tally_rows(rows, 1)
    exp = np.ones(n, bool)
    exp[bad] = False
    np.testing.assert_array_equal(np.asarray(valid)[:n], exp)
    assert k.tally_to_int(np.asarray(tally))[0] == honest
    assert bool(np.asarray(quorum)[0])
    # quorum-miss: threshold exactly the honest sum (needs MORE than)
    thresh_miss = k.threshold_limbs(honest)
    rows2 = kp.pack_rows(pb, power5, counted, cids, thresh_miss)
    _, _, q2 = kp.verify_tally_rows(rows2, 1)
    assert not bool(np.asarray(q2)[0])


@pytest.mark.skipif(
    not os.environ.get("CBT_TEST_ON_TPU"),
    reason="10,240-row grid is TPU-scale; CPU interpret takes minutes "
           "(on the chip: chip_smoke.py's commit leg and the benchmark's "
           "`correct` in valset-10k.commit check this shape)",
)
def test_tally_10k_shape_vs_xla():
    n = 10_240
    pubs, msgs, sigs = make_sigs(64)
    reps = n // 64
    pubs, msgs, sigs = pubs * reps, msgs * reps, sigs * reps
    bad = [5, 5000, 10_239]
    for i in bad:
        sigs[i] = b"\x00" * 64
    pb = k.pack_batch(pubs, msgs, sigs, pad_to=n)
    powers = np.full((n,), 1000, np.int64)
    power5 = k.power_limbs(powers)
    counted = np.ones((n,), np.bool_)
    cids = np.zeros((n,), np.int32)
    thresh = k.threshold_limbs(int(powers.sum()) * 2 // 3)
    rows = kp.pack_rows(pb, power5, counted, cids, thresh)
    valid, tally, quorum = kp.verify_tally_rows(rows, 1)
    exp = np.ones(n, bool)
    exp[bad] = False
    np.testing.assert_array_equal(np.asarray(valid)[:n], exp)
    # cross-check the fused tally against the XLA tally core on host data
    import jax.numpy as jnp

    ref_tally = k.tally_core(
        jnp.asarray(exp), jnp.asarray(power5), jnp.asarray(counted),
        jnp.asarray(cids), 1,
    )
    assert k.tally_to_int(np.asarray(ref_tally))[0] == k.tally_to_int(
        np.asarray(tally)
    )[0]
    assert bool(np.asarray(quorum)[0])
