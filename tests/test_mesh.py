"""Sharded verify+tally over the virtual 8-device CPU mesh."""
import sys
import threading

import pytest
import numpy as np

import jax

from cometbft_tpu.crypto import ed25519_ref as ed
from cometbft_tpu.ops import ed25519_kernel as k
from cometbft_tpu.parallel import mesh as pm


"""Both CPU cases are slow-marked: an 8-virtual-device compile of the
full verify graph costs minutes of wall on a CPU host. On the chip
(CBT_TEST_ON_TPU=1) the sharded paths run over the devices there are,
and chip_smoke.py's four-chip leg covers the plane's sharded flush."""


def _needs_virtual_mesh():
    """For tests whose numbers are those of conftest's 8 virtual CPU
    devices; under CBT_TEST_ON_TPU there are 1 or 4 real ones."""
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual CPU mesh")


def test_rows_builders_memoized_and_share_verify_program():
    """ISSUE 3 satellite (round-5 MULTICHIP regression): repeated
    builder calls return the SAME compiled closure, and every tally
    width reuses ONE Pallas verify step per mesh — no per-call
    shard_map rebuilds. Pure cache identity, no compiles."""
    mesh = pm.make_mesh()
    assert pm.sharded_verify_tally_rows(mesh, 1) is \
        pm.sharded_verify_tally_rows(mesh, 1)
    assert pm.sharded_verify_tally(mesh, 2) is \
        pm.sharded_verify_tally(mesh, 2)
    assert pm.sharded_stream_verify(mesh, 4) is \
        pm.sharded_stream_verify(mesh, 4)
    # an equivalent mesh (same devices/axes) hits the same entries
    assert pm.sharded_verify_tally_rows(pm.make_mesh(), 1) is \
        pm.sharded_verify_tally_rows(mesh, 1)
    # n_commits=1 and n_commits=16 share the expensive verify program
    pm.sharded_verify_tally_rows(mesh, 16)
    assert pm._STEP_CACHE[("rows", pm._mesh_key(mesh), 1)] is not \
        pm._STEP_CACHE[("rows", pm._mesh_key(mesh), 16)]
    assert pm._sharded_verify_rows_step(mesh) is \
        pm._sharded_verify_rows_step(mesh)
    assert sum(1 for key in pm._STEP_CACHE
               if key[0] == "pallas-verify") == 1


def test_step_cache_hit_counters():
    """ISSUE 4 satellite (MULTICHIP_r05 rc=124 guard): the memoized
    builders expose hit/miss counters, and REPEATED builder calls are
    observable HITS — a regression back to per-call shard_map rebuilds
    would show up as misses here (and as minutes of recompile on the
    harness). Pure cache identity, no compiles."""
    mesh = pm.make_mesh()
    pm.sharded_verify_tally(mesh, 3)  # ensure the entry exists
    before = pm.cache_stats()
    for _ in range(4):
        pm.sharded_verify_tally(mesh, 3)
    after = pm.cache_stats()
    assert after["hits"] >= before["hits"] + 4
    assert after["misses"] == before["misses"]
    # a NEW width is one miss (the cheap tally step), then hits
    pm.sharded_verify_tally(mesh, 5)
    mid = pm.cache_stats()
    assert mid["misses"] == after["misses"] + 1
    pm.sharded_verify_tally(mesh, 5)
    assert pm.cache_stats()["hits"] == mid["hits"] + 1


def test_cache_stats_exact_under_two_threads():
    """ISSUE 10 satellite: the memo counters are mutated by the verify
    plane's dispatcher thread AND test/scrape probes concurrently
    — increments ride one module lock, so two hammering threads land
    EXACTLY 2N hits (an unguarded += loses counts under preemption,
    the same race the sheds counter fixed in PR 7)."""
    mesh = pm.make_mesh()
    pm.sharded_verify_tally(mesh, 7)  # ensure the entry exists (1 miss)
    before = pm.cache_stats()
    n_iter = 2000
    old_si = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)  # make preemption aggressive
    try:
        def worker():
            for _ in range(n_iter):
                pm.sharded_verify_tally(mesh, 7)

        threads = [threading.Thread(target=worker) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(old_si)
    after = pm.cache_stats()
    assert after["hits"] - before["hits"] == 2 * n_iter
    assert after["misses"] == before["misses"]


def test_rows_split_plumbing_with_stub_kernel(monkeypatch):
    """Execute the split verify->tally pipeline over the 8-device mesh
    with a STUB verify kernel (the real Pallas program costs minutes of
    interpret compile on CPU): the per-device column extraction, psum,
    limb carry, and quorum plumbing must tally exactly."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.ops import ed25519_pallas as kp

    def fake_verify(rows, base):
        return (rows[kp.C_CID] & 1) == 0  # even commits "verify"

    fake_verify.__wrapped__ = fake_verify
    monkeypatch.setattr(kp, "_verify_rows", fake_verify)
    pm._STEP_CACHE.clear()
    try:
        mesh = pm.make_mesh()
        n_dev = len(jax.devices())
        n_commits = 4
        n = n_dev * kp.B_TILE
        keys = [PrivKey.generate(i.to_bytes(4, "big") + b"\x33" * 28)
                for i in range(8)]
        pubs = [keys[i % 8].pub_key().data for i in range(n)]
        msgs = [b"stub-%d" % i for i in range(n)]
        sigs = [b"\x00" * 64] * n  # content is irrelevant to the stub
        pb = k.pack_batch(pubs, msgs, sigs, pad_to=n)
        powers = np.full((n,), 7, np.int64)
        power5 = k.power_limbs(powers)
        counted = np.ones((n,), np.bool_)
        cids = (np.arange(n, dtype=np.int32) % n_commits)
        thresh = k.threshold_limbs(1, n_commits)
        rows = kp.pack_rows(pb, power5, counted, cids, thresh)
        rows[kp.C_THRESH:] = 0
        from jax.sharding import NamedSharding, PartitionSpec as P

        step = pm.sharded_verify_tally_rows(mesh, n_commits)
        rows_d = jax.device_put(
            rows, NamedSharding(mesh, P(None, mesh.axis_names[0])))
        valid, tally, quorum = jax.block_until_ready(
            step(rows_d, kp.base_f32(), thresh))
        v = np.asarray(valid)[:n]
        np.testing.assert_array_equal(v, cids % 2 == 0)
        t = k.tally_to_int(np.asarray(tally))
        per_commit = n // n_commits * 7
        assert [int(x) for x in t] == [
            per_commit if c % 2 == 0 else 0 for c in range(n_commits)
        ]
        q = np.asarray(quorum)
        assert list(q) == [c % 2 == 0 for c in range(n_commits)]
    finally:
        pm._STEP_CACHE.clear()  # stub-compiled steps must not leak


def test_padded_sharded_tally_matches_unpadded():
    """ISSUE 10 satellite: shard_batch_arrays' mesh padding rows carry
    counted=False EXPLICITLY (bool-cast, zeroed past the original
    padding). Padding rows necessarily claim commit_id=0, so a counted
    leak would inflate exactly commit 0's tally — the padded sharded
    tally must bit-match the unpadded single-device tally. valid is
    forced all-True so ONLY the counted mask keeps padding out (the
    regression this guards)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    _needs_virtual_mesh()
    n, pad = 24, 60  # 60 % 8 devices != 0: forces the padding path
    pubs = [b"\x01" * 32] * n
    msgs = [b"pad-%d" % i for i in range(n)]
    sigs = [b"\x00" * 64] * n
    pb = k.pack_batch(pubs, msgs, sigs, pad_to=pad)
    powers = np.arange(1, n + 1, dtype=np.int64) * 111
    power5 = np.zeros((pad, k.POWER_LIMBS), np.int32)
    power5[:n] = k.power_limbs(powers)
    counted = np.zeros((pad,), np.int64)  # hostile dtype: must be cast
    counted[:n] = 1
    cids = np.zeros((pad,), np.int32)
    cids[n // 2:n] = 1

    mesh = pm.make_mesh()
    pb2, args = pm.shard_batch_arrays(mesh, pb, power5, counted, cids)
    assert pb2.padded == 64
    power5_d, counted_d, cids_d = args[7], args[8], args[9]
    assert np.asarray(counted_d).dtype == np.bool_
    assert not np.asarray(counted_d)[pad:].any()
    assert not np.asarray(args[6])[pad:].any()  # precheck pads False too

    thresh = k.threshold_limbs(1, 2)
    step = pm._sharded_tally_step(mesh, 2)
    axis = mesh.axis_names[0]
    valid = jax.device_put(np.ones((pb2.padded,), np.bool_),
                           NamedSharding(mesh, P(axis)))
    tally, _ = step(valid, power5_d, counted_d, cids_d, thresh)
    exp = k.tally_core(jnp.ones((pad,), bool), jnp.asarray(power5),
                       jnp.asarray(counted.astype(np.bool_)),
                       jnp.asarray(cids), 2)
    np.testing.assert_array_equal(np.asarray(tally), np.asarray(exp))
    # and in ints: commit 0 is exactly the first half's power sum
    t = k.tally_to_int(np.asarray(tally))
    assert int(t[0]) == int(powers[: n // 2].sum())
    assert int(t[1]) == int(powers[n // 2:].sum())


def test_sharded_fused_layout_with_stub_kernel(monkeypatch):
    """ISSUE 10 tentpole plumbing: the verify plane's cross-chip fused
    step (sharded_fused_verify) over the 8-device mesh with a STUB
    cached kernel — proves the layout contract between
    fused.shard_positions and the kernel's local `row mod M ->
    validator` map, the per-shard ok/power table wiring, global commit
    ids through the psum tally, and the replicated-threshold quorum.
    The real Pallas program costs minutes of interpret compile on CPU;
    the stub keeps validity = precheck & ok[vidx], which exercises
    every sharded seam."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from _kernel_stubs import fake_verify_tally_cached
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.verifyplane.fused import shard_positions

    monkeypatch.setattr(ec, "_verify_tally_cached",
                        fake_verify_tally_cached)
    pm._STEP_CACHE.clear()
    try:
        mesh = pm.make_mesh()
        n_dev = len(jax.devices())
        m_s = 128                      # one table block per device
        nvals = n_dev * m_s
        n_strides = 2                  # the vote + extension shape
        b_loc = n_strides * m_s
        B = n_dev * b_loc
        n_commits = 2

        # position-ordered fixture: position p holds validator v of
        # stride s per the sharded layout; assert the layout helper
        # agrees before driving the device
        v_of = np.empty(B, np.int64)
        s_of = np.empty(B, np.int64)
        for p in range(B):
            d, q = divmod(p, b_loc)
            s_of[p], v_of[p] = divmod(q, m_s)
            v_of[p] += d * m_s
        np.testing.assert_array_equal(
            shard_positions(v_of, s_of, m_s, n_strides), np.arange(B))

        precheck_ok = (v_of * 7 + s_of) % 5 != 0
        ok_host = np.asarray([v % 3 != 0 for v in range(nvals)])
        powers = np.arange(1, nvals + 1, dtype=np.int64)
        counted = s_of == 0
        cids = (v_of % n_commits).astype(np.int32)

        pubs = [b"\x02" * 32] * B
        msgs = [b"fx-%d" % p for p in range(B)]
        sigs = [b"\x00" * 64] * B
        pb = k.pack_batch(pubs, msgs, sigs, pad_to=B)
        pb = pb._replace(precheck=np.asarray(precheck_ok, np.bool_))
        rows = ec.pack_rows_cached(pb, counted, cids)

        axis = mesh.axis_names[0]
        tab = jax.device_put(
            np.zeros((nvals // 128 * ec.ENT_BLOCK, 128), np.int16),
            NamedSharding(mesh, P(axis, None)))
        ok_d = jax.device_put(ok_host, NamedSharding(mesh, P(axis)))
        p5 = jax.device_put(k.power_limbs(powers),
                            NamedSharding(mesh, P(axis, None)))
        exp_tally = []
        for c in range(n_commits):
            sel = [v for v in range(nvals)
                   if v % n_commits == c and ok_host[v]
                   and (v * 7) % 5 != 0]
            exp_tally.append(int(powers[sel].sum()))
        thresh = np.zeros((n_commits, k.TALLY_LIMBS), np.int32)
        thresh[0] = k.threshold_limbs(exp_tally[0] - 1)[0]  # quorum True
        thresh[1] = k.threshold_limbs(exp_tally[1])[0]      # quorum False

        step = pm.sharded_fused_verify(mesh, n_commits)
        rows_d = jax.device_put(rows,
                                NamedSharding(mesh, P(None, axis)))
        valid, tally, quorum = jax.block_until_ready(
            step(rows_d, tab, ok_d, p5, ec.base60_f32(), thresh))
        exp_valid = precheck_ok & ok_host[v_of]
        np.testing.assert_array_equal(np.asarray(valid), exp_valid)
        t = k.tally_to_int(np.asarray(tally))
        assert [int(x) for x in t] == exp_tally
        assert list(np.asarray(quorum)) == [True, False]
        # memoized: the second build is the same closure, observably
        before = pm.cache_stats()
        assert pm.sharded_fused_verify(mesh, n_commits) is step
        assert pm.cache_stats()["hits"] == before["hits"] + 1
    finally:
        pm._STEP_CACHE.clear()  # stub-compiled steps must not leak


def test_effective_mesh_clamps_empty_shards():
    """Review fix: coarse table_pad buckets can leave trailing shards
    EMPTY (10k validators over 8 devices -> 4096-slot stride -> 3
    shards used); the flush must clamp to a sub-mesh instead of
    staging/verifying pure padding on 5 chips."""
    from cometbft_tpu.verifyplane import fused as fz

    _needs_virtual_mesh()
    mesh = pm.make_mesh()
    m_eff, n_dev, m_s = fz.effective_mesh(mesh, 10_000)
    assert (n_dev, m_s) == (3, 4096)
    assert m_eff.devices.size == 3
    assert tuple(m_eff.devices.flat) == tuple(mesh.devices.flat)[:3]
    # sub-meshes are memoized: identity feeds the step/table memos
    assert fz.effective_mesh(mesh, 10_000)[0] is m_eff
    # a valset filling every stride keeps the full mesh object
    full = fz.effective_mesh(mesh, 2048)
    assert full[0] is mesh and full[1] == 8 and full[2] == 256
    # one that fits a single stride is single-device business
    assert fz.effective_mesh(mesh, 100) == (None, 1, 256)
    assert fz.effective_mesh(None, 100) == (None, 1, 256)
    # past even the full mesh's table budget: loud, not wrong
    with pytest.raises(ValueError):
        fz.effective_mesh(mesh, 8 * 65536 + 1)


def test_thresh_from_rows_pads_short_sharded_slice():
    """Review fix: a lane-sharded flush packs ONE zero threshold row,
    so a device's local slice can hold fewer than n_commits *
    TALLY_LIMBS elements when a flush carries many commit groups —
    the kernel's threshold read must zero-pad instead of crashing at
    trace time (which would falsely trip the device breaker)."""
    import jax.numpy as jnp

    from cometbft_tpu.ops import ed25519_cached as ec

    # 40 commits * 6 limbs = 240 > the 128 elements one zero row holds
    short = jnp.zeros((ec.V_THRESH + 1, 128), jnp.int32)
    t = ec._thresh_from_rows(short, 40)
    assert t.shape == (40, k.TALLY_LIMBS)
    assert not np.asarray(t).any()
    # the single-device path still reads its packed values back
    thresh = np.arange(3 * k.TALLY_LIMBS, dtype=np.int32).reshape(3, -1)
    pubs = [b"\x01" * 32] * 8
    pb = k.pack_batch(pubs, [b"m"] * 8, [b"\x00" * 64] * 8, pad_to=128)
    rows = ec.pack_rows_cached(pb, thresh=thresh)
    got = ec._thresh_from_rows(jnp.asarray(rows), 3)
    np.testing.assert_array_equal(np.asarray(got), thresh)


@pytest.mark.slow
def test_sharded_matches_single_device():
    _needs_virtual_mesh()
    n = 24
    seeds = [bytes([i + 1]) * 32 for i in range(n)]
    pubs = [ed.pubkey_from_seed(s) for s in seeds]
    msgs = [b"commit-sig-%d" % i for i in range(n)]
    sigs = [ed.sign(s, m) for s, m in zip(seeds, msgs)]
    sigs[4] = sigs[4][:8] + bytes([sigs[4][8] ^ 2]) + sigs[4][9:]

    pb = k.pack_batch(pubs, msgs, sigs, pad_to=64)
    powers = np.arange(1, n + 1, dtype=np.int64) * 1000
    power5 = np.zeros((pb.padded, k.POWER_LIMBS), np.int32)
    power5[:n] = k.power_limbs(powers)
    counted = np.zeros((pb.padded,), np.bool_)
    counted[:n] = True
    commit_ids = np.zeros((pb.padded,), np.int32)
    commit_ids[n // 2 :] = 1
    thresh = np.zeros((2, k.TALLY_LIMBS), np.int32)
    thresh[0, 0] = 1
    thresh[1, 0] = 2

    mesh = pm.make_mesh()
    step = pm.sharded_verify_tally(mesh, n_commits=2)
    pb2, args = pm.shard_batch_arrays(mesh, pb, power5, counted, commit_ids)
    valid, tally, quorum = step(*args, thresh)

    exp_valid = np.array([i != 4 for i in range(n)])
    np.testing.assert_array_equal(np.asarray(valid)[:n], exp_valid)
    t = k.tally_to_int(np.asarray(tally))
    exp0 = sum(int(powers[i]) for i in range(n // 2) if i != 4)
    exp1 = sum(int(powers[i]) for i in range(n // 2, n))
    assert int(t[0]) == exp0 and int(t[1]) == exp1
    assert bool(quorum[0]) and bool(quorum[1])


@pytest.mark.slow
def test_sharded_pallas_rows():
    """The flagship Mosaic kernel under shard_map: a 1024-row packed
    batch lane-sharded over the 8-device mesh, per-device Pallas tiles,
    psum tally (round-2 verdict item 7)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.ops import ed25519_pallas as kp

    n_dev = len(jax.devices())
    n = n_dev * kp.B_TILE
    keys = [PrivKey.generate(i.to_bytes(4, "big") + b"\x19" * 28)
            for i in range(n)]
    pubs = [q.pub_key().data for q in keys]
    msgs = [b"sharded-%d" % i for i in range(n)]
    sigs = [q.sign(m) for q, m in zip(keys, msgs)]
    sigs[7] = sigs[7][:12] + bytes([sigs[7][12] ^ 1]) + sigs[7][13:]
    sigs[900 % n] = b"\x00" * 64

    pb = k.pack_batch(pubs, msgs, sigs, pad_to=n)
    powers = np.full((n,), 10, np.int64)
    power5 = k.power_limbs(powers)
    counted = np.ones((n,), np.bool_)
    cids = np.zeros((n,), np.int32)
    thresh = k.threshold_limbs(int(powers.sum()) * 2 // 3)
    rows = kp.pack_rows(pb, power5, counted, cids, thresh)
    rows[kp.C_THRESH:] = 0  # thresholds ride separately when sharded

    mesh = pm.make_mesh()
    step = pm.sharded_verify_tally_rows(mesh, n_commits=1)
    rows_d = jax.device_put(
        rows, NamedSharding(mesh, P(None, mesh.axis_names[0]))
    )
    valid, tally, quorum = jax.block_until_ready(
        step(rows_d, kp.base_f32(), thresh)
    )
    exp = np.ones(n, bool)
    exp[[7, 900 % n]] = False
    np.testing.assert_array_equal(np.asarray(valid)[:n], exp)
    assert k.tally_to_int(np.asarray(tally))[0] == int(powers.sum()) - 20
    assert bool(np.asarray(quorum)[0])


@pytest.mark.skipif(
    not __import__("os").environ.get("CBT_TEST_ON_TPU"),
    reason="cached kernel under shard_map: pallas-interpret compile "
           "takes hours on CPU (see test_ed25519_cached.py); set "
           "CBT_TEST_ON_TPU=1 on the chip."
)
def test_sharded_stream_cached_multi_commit():
    """The blocksync streaming shape multi-device: a 16-commit chunk of
    one 128-validator valset through the cached-table kernel, sharded
    at commit granularity over the mesh, per-commit psum tallies; one bad
    signature flips exactly its commit's row and no quorum bit (each
    commit has 128/128 power, so one loss still clears 2/3)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.parallel import mesh as pm

    # the devices there are: 1 on a one-chip machine, 4 on a 2x2 host,
    # 8 on the virtual CPU mesh (16 commits divide over each)
    n_dev = min(8, 1 << (len(jax.devices()).bit_length() - 1))
    mesh = pm.make_mesh(jax.devices()[:n_dev])
    n_commits = 16
    keys = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(128)]
    pubs = [k.pub_key().data for k in keys]
    table = ec.build_table(pubs, [10] * 128)
    # the layout contract: commit c occupies rows [c*M, (c+1)*M) with
    # validator i at row c*M + i, M the table's PADDED size (256 for
    # 128 validators), the tail of each commit dead
    M = table.n_vals
    B = n_commits * M
    spubs, smsgs, ssigs = [], [], []
    for c in range(n_commits):
        for i, k in enumerate(keys):
            m = b"mesh-stream-%d-%d" % (c, i)
            spubs.append(pubs[i])
            smsgs.append(m)
            ssigs.append(k.sign(m))
        spubs += [b""] * (M - 128)
        smsgs += [b""] * (M - 128)
        ssigs += [b""] * (M - 128)
    bad = 5 * M + 17  # commit 5, validator 17
    ssigs[bad] = b"\x01" * 64
    pb = ek.pack_batch(spubs, smsgs, ssigs, pad_to=B)
    counted = (np.arange(B) % M) < 128
    cids = np.repeat(np.arange(n_commits, dtype=np.int32), M)
    thresh = ek.threshold_limbs(128 * 10 * 2 // 3, n_commits)
    rows = ec.pack_rows_cached(pb, counted, cids, thresh)
    step = pm.sharded_stream_verify(mesh, n_commits)
    rows_d = jax.device_put(
        rows, NamedSharding(mesh, P(None, mesh.axis_names[0])))
    valid, tally, quorum = jax.block_until_ready(
        step(rows_d, table.tab, table.ok, table.power5,
             ec.base60_f32(), thresh))
    v = np.asarray(valid)
    assert not v[bad] and v.sum() == n_commits * 128 - 1
    assert not v[~counted].any()
    t = ek.tally_to_int(np.asarray(tally))
    assert int(t[5]) == 127 * 10
    assert all(int(t[c]) == 128 * 10 for c in range(n_commits) if c != 5)
    assert np.asarray(quorum).all()
