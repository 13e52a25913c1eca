"""Benchmark helpers that need product internals (kept out of bench.py so
the repo-root script stays a thin driver).

Currently: the BASELINE config #3 mixed ed25519/sr25519 fused-tally
commit bench — the shape crypto/batch/batch.go cannot express at all
(one BatchVerifier per key type, no cross-type tally)."""
from __future__ import annotations

import time

import numpy as np

CHAIN_ID_DEFAULT = "bench-chain"


def _now_ms():
    return time.perf_counter() * 1000


def tally_int(tally_limbs) -> int:
    """(TALLY_LIMBS,) 13-bit limbs -> Python int."""
    v = 0
    for i, limb in enumerate(np.asarray(tally_limbs).tolist()):
        v += int(limb) << (13 * i)
    return v


def mixed_commit_bench(chain_id: str, n_vals: int = 10_000,
                       steady_k: int = 8):
    """10k-validator commit, half ed25519 / half sr25519, verified as two
    fused device passes (one per key-type group, each verify+tally fused)
    with the cross-group power reduction on host (a 6-limb add)."""
    import jax

    from cometbft_tpu.crypto.keys import PrivKey, Sr25519PrivKey
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.ops import ed25519_pallas as kp
    from cometbft_tpu.ops import sr25519_kernel as srk
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    half = n_vals // 2
    privs = [
        PrivKey.generate((100 + i).to_bytes(4, "big") + b"\x44" * 28)
        for i in range(half)
    ] + [
        Sr25519PrivKey.generate((7 + i).to_bytes(4, "big") + b"\x55" * 28)
        for i in range(n_vals - half)
    ]
    power = 1000
    vs = ValidatorSet([Validator(p.pub_key(), power) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\xee" * 32, PartSetHeader(2, b"\xcd" * 32))
    height = 777
    t_gen = _now_ms()
    sigs = []
    msgs = []
    for idx, v in enumerate(vs.validators):
        ts = Timestamp(1_700_000_000 + idx, 0)
        sb = canonical.canonical_vote_bytes(
            chain_id, canonical.PRECOMMIT_TYPE, height, 0, bid, ts
        )
        msgs.append(sb)
        sigs.append(
            CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                      by_addr[v.address].sign(sb))
        )
    commit = Commit(height, 0, bid, sigs)
    gen_s = (_now_ms() - t_gen) / 1000

    # group rows by key type (crypto/batch.py dispatch shape)
    ed_rows = [i for i, v in enumerate(vs.validators)
               if v.pub_key.key_type == "ed25519"]
    sr_rows = [i for i, v in enumerate(vs.validators)
               if v.pub_key.key_type == "sr25519"]
    total_power = vs.total_voting_power()
    threshold = total_power * 2 // 3

    def pack_group(idxs, sr: bool):
        pubs = [vs.validators[i].pub_key.data for i in idxs]
        gmsgs = [msgs[i] for i in idxs]
        gsigs = [commit.signatures[i].signature for i in idxs]
        powers = np.asarray(
            [vs.validators[i].voting_power for i in idxs], np.int64
        )
        n = len(idxs)
        pad = kp.pad_to_tile(n)
        power5 = np.zeros((pad, ek.POWER_LIMBS), np.int32)
        power5[:n] = ek.power_limbs(powers)
        counted = np.zeros((pad,), np.bool_)
        counted[:n] = True
        cid = np.zeros((pad,), np.int32)
        # per-group threshold is a placeholder; the real quorum compare
        # happens host-side on the SUM of group tallies
        th = ek.threshold_limbs(1)
        if sr:
            return srk.pack_batch_sr(pubs, gmsgs, gsigs, pad_to=pad,
                                     power5=power5, counted=counted,
                                     commit_ids=cid, thresh=th)
        pb = ek.pack_batch(pubs, gmsgs, gsigs, pad_to=pad)
        return kp.pack_rows(pb, power5, counted, cid, th)

    t_pack = _now_ms()
    rows_ed = pack_group(ed_rows, sr=False)
    rows_sr = pack_group(sr_rows, sr=True)
    pack_ms = _now_ms() - t_pack

    import functools

    import jax.numpy as jnp

    # ONE compiled program: both key-type kernels + the cross-group
    # tally sum + the quorum compare, all device-side (round-4 verdict:
    # "fuse the ed25519+sr25519 tallies device-side into one quorum
    # answer" — the host 6-limb add also forced two separate syncs)
    @functools.partial(jax.jit, static_argnames=())
    def fused_pass(red, rsr, base, th6):
        v_ed, t_ed, _ = kp._verify_tally_rows.__wrapped__(red, base, 1)
        v_sr, t_sr, _ = srk._verify_tally_rows_sr.__wrapped__(
            rsr, base, 1)
        tot = t_ed + t_sr
        for i in range(ek.TALLY_LIMBS - 1):
            c = tot[..., i] >> ek.POWER_LIMB_BITS
            tot = tot.at[..., i].add(-(c << ek.POWER_LIMB_BITS)) \
                     .at[..., i + 1].add(c)
        return v_ed, v_sr, tot, ek.quorum_core(tot, th6)

    th6 = jnp.asarray(ek.threshold_limbs(threshold))
    base = kp.base_dev()

    def one_pass(red, rsr):
        return fused_pass(red, rsr, base, th6)

    d_ed = jax.device_put(rows_ed)
    d_sr = jax.device_put(rows_sr)
    v_ed, v_sr, tot, quorum = one_pass(d_ed, d_sr)
    ed_ok = np.asarray(v_ed)[: len(ed_rows)].all()
    sr_ok = np.asarray(v_sr)[: len(sr_rows)].all()
    got_power = tally_int(np.asarray(tot)[0])
    assert ed_ok and sr_ok, "mixed commit must verify"
    assert got_power == total_power
    assert bool(np.asarray(quorum)[0])

    # best-of-3 steady loops
    steady = float("inf")
    for _ in range(3):
        t = _now_ms()
        outs = None
        for _ in range(steady_k):
            outs = one_pass(jax.device_put(rows_ed),
                            jax.device_put(rows_sr))
        assert bool(np.asarray(outs[3])[0])
        steady = min(steady, (_now_ms() - t) / steady_k)

    # CPU baseline: measured OpenSSL (C-speed) ed25519 verify per-sig,
    # applied to all 10k rows (conservative: CPU schnorrkel verification
    # costs at least as much as ed25519 per signature). NOT the
    # pure-Python ZIP-215 oracle, which would inflate vs_baseline ~40x.
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    sample = 300
    pks = [
        Ed25519PublicKey.from_public_bytes(vs.validators[i].pub_key.data)
        for i in ed_rows[:sample]
    ]
    t = _now_ms()
    for j, i in enumerate(ed_rows[:sample]):
        pks[j].verify(commit.signatures[i].signature, msgs[i])
    per_sig = (_now_ms() - t) / sample
    cpu_ms = per_sig * n_vals
    return {
        "metric": "cfg3 10k mixed ed25519/sr25519 fused tally",
        "value": round(steady, 2),
        "unit": "ms",
        "vs_baseline": round(cpu_ms / steady, 2),
        "extra": {
            "ed_rows": len(ed_rows),
            "sr_rows": len(sr_rows),
            "host_pack_ms": round(pack_ms, 1),
            "cpu_measured_ms": round(cpu_ms, 1),
            "fixture_gen_s": round(gen_s, 1),
            "sigs_per_sec": round(n_vals / (steady / 1000)),
            "note": "two fused verify+tally device passes (one per key "
                    "type) + host 6-limb tally add; the reference cannot "
                    "run this config at all in one batch verifier",
        },
    }
