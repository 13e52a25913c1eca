#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the served verify paths run on
the chip.

    python3 chip_smoke.py        (on the TPU machine, from the repo root)

One process, no arguments. It drives, through the entry points a node
or an operator calls, every path that puts signatures on the device:

  gate     JAX sees a TPU (anything else: exit 2, nothing on stdout);
           native/hostaccel.cpp is compiled in this run; Pallas runs
           under Mosaic, not the interpreter
  kernels  the ZIP-215 edge vectors (tools/tpu_differential.edge_cases)
           through the three ed25519 kernels, secp256k1 vectors
           through theirs in one pass (the plane's and bare callers'
           way), sr25519 and secp256k1 vectors through the node's
           batch_fn as chunks of the served shape (1,024 rows), and one
           interleaved mixed-key batch through that batch_fn (sr25519
           and secp256k1 chunks and an ed25519 pass in one queue), row
           for row against the pure-Python references
  commit   Config().crypto.batch_fn() (what a node is assembled with)
           under validation.verify_commit_light on a seeded
           10,000-validator commit: accepted; tampered -> the host's
           blame index; under 2/3 -> NotEnoughPowerError
  light    light.client.Client with that batch_fn, one skipping step
           (verify_non_adjacent: the trusting check by address, then
           the 2/3 check) over a 96-validator secp256k1 chain of
           unequal powers with a third of its seats changed: stored;
           a signature flipped where only the second check looks ->
           ErrInvalidHeader with the host's index
  stream   blocksync.pipeline.make_stream_verifier() over 64 blocks of
           1,000-validator commits with one bad block and one validator
           set change, per job against catchup.HostCommitVerifier
  plane    VerifyPlaneConfig(enable=True).build(), global, primed like
           a node's; a 1,000-validator VoteSet fed every prevote by
           add_vote from 8 threads: quorum fires, the flush ledger shows
           fused device-stamped flushes only, the breaker has no fault
  node     the four-validator kvstore network in this process
           (node.LocalNetwork), assembled with the arguments
           cmd/cli.build_node passes, [crypto] verifier = "tpu" and
           [verify_plane] enable = true: commits heights, and /status,
           /dump_devices and /dump_flushes answer over HTTP
  mesh     only where JAX sees >= 4 chips: the plane with mesh = true on
           a 10,000-validator set: fused_sharded flushes over the chips
           the set fills (three of four at the 4,096-slot table stride),
           verdicts and tally equal the one-chip commit leg's

All data comes from SEED. The host references (pure Python, ~5 ms per
signature) run in worker processes that never touch JAX, while this
process, which owns the chip, compiles and verifies. A failed check
raises and ends the run: no leg's exception is caught to carry on.

Every leg prints one JSON line: the device as JAX reports it, the
versions, the compile cache in use, the leg's backend compiles, compile
seconds and persistent-cache hits (libs/deviceledger), and the jitted
functions it compiled. The line before the last, {"run": "total", ...},
holds the whole run's compile counts; a second run on the same cache
directory shows hits there and no backend compile of a kernel. The last
line of stdout is {"ok": true, "device": {"platform", "kind", "count"}}
and holds nothing else.
"""
import json
import os
import sys
import time

SEED = 21
CHAIN = "chip-smoke"
N_COMMIT = 10_000      # types/vote.py MAX_VOTES_COUNT; BASELINE configs 3, 5
N_STREAM_VALS = 1_000  # BASELINE config 4's validator count
N_STREAM_BLOCKS = 64   # config 4 replays 5,000; one fused run is 64
KERNEL_SECONDS = 1.0   # a backend compile this long is a kernel family


class SmokeFailure(Exception):
    pass


def check(cond, msg, *detail):
    if not cond:
        raise SmokeFailure(f"{msg}: {detail!r}" if detail else msg)


# --------------------------------------------------------------------------
# host references: run in spawned workers (module top level, so that
# they pickle by import path). Pure Python; a worker never asks JAX for
# a device.
# --------------------------------------------------------------------------


def _host_init():
    os.environ["JAX_PLATFORMS"] = "cpu"


def _outcome(err):
    """A verification result as plain data (None = accepted)."""
    from cometbft_tpu.types import validation as tv

    if err is None:
        return ("ok",)
    if isinstance(err, tv.InvalidSignatureError):
        return ("invalid_signature", err.idx)
    if isinstance(err, tv.NotEnoughPowerError):
        # the streamed device path reports got=-1: compare what both know
        return ("not_enough_power", err.needed)
    return ("error", str(err))


def _host_commits(jobs):
    """[CatchupJob] -> [outcome] by validation.verify_commit_light with
    batch_fn=None (catchup.HostCommitVerifier)."""
    from cometbft_tpu.blocksync.catchup import HostCommitVerifier

    return [_outcome(e) for e in HostCommitVerifier().verify(jobs)]


def _host_voteset(chain_id, height, vote_type, vals, votes):
    """The serial host VoteSet: (maj23 block key, sum, voted bits)."""
    from cometbft_tpu.types.vote_set import VoteSet

    vset = VoteSet(chain_id, height, 0, vote_type, vals)
    added = [vset.add_vote(v) for v in votes]
    maj = vset.two_thirds_majority()
    return (None if maj is None else maj.key(), vset.sum, added)


# --------------------------------------------------------------------------
# seeded data
# --------------------------------------------------------------------------


def _privs(tag: int, n: int):
    from cometbft_tpu.crypto.keys import PrivKey

    return [PrivKey.generate(bytes([SEED, tag]) + i.to_bytes(4, "big")
                             + b"\x5a" * 26) for i in range(n)]


def _valset(privs, power=1000):
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    vs = ValidatorSet([Validator(p.pub_key(), power) for p in privs])
    return vs, {p.pub_key().address(): p for p in privs}


def _block_id(h: int):
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader

    return BlockID(bytes([h % 251 + 1]) * 32,
                   PartSetHeader(2, bytes([h % 241 + 3]) * 32))


def _commit(vs, by_addr, height: int, bid=None):
    """Every validator's real precommit for block `height` (for `bid`
    where a header names one)."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp

    bid = bid or _block_id(height)
    sigs = []
    for idx, v in enumerate(vs.validators):
        ts = Timestamp(1_700_000_000 + 7 * height + idx % 5, idx)
        sb = canonical.canonical_vote_bytes(
            CHAIN, canonical.PRECOMMIT_TYPE, height, 0, bid, ts)
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                              by_addr[v.address].sign(sb)))
    return Commit(height, 0, bid, sigs), bid


def _flip(sig: bytes, at: int = 5) -> bytes:
    return sig[:at] + bytes([sig[at] ^ 1]) + sig[at + 1:]


def _tampered(commit, idxs):
    from dataclasses import replace

    from cometbft_tpu.types.commit import Commit

    sigs = list(commit.signatures)
    for i in idxs:
        sigs[i] = replace(sigs[i], signature=_flip(sigs[i].signature))
    return Commit(commit.height, commit.round, commit.block_id, sigs)


# --------------------------------------------------------------------------
# the run: device identity + per-leg compile accounting
# --------------------------------------------------------------------------


class Run:
    def __init__(self, device: dict, cache_dir: str):
        import importlib.metadata as md

        import jax
        import jaxlib

        self.ident = {
            "platform": device["platform"],
            "device_kind": device["device_kind"],
            "n_devices": device["n_devices"],
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": md.version("libtpu"),
            "compile_cache": cache_dir,
        }
        self.t0 = time.monotonic()
        self.legs = []

    @staticmethod
    def _mark():
        from cometbft_tpu.libs import deviceledger

        recs = deviceledger.ledger().records()
        return deviceledger.counters(), (recs[-1]["seq"] + 1 if recs else 0)

    def leg(self, name, fn):
        """Run one leg (its exception ends the run) and print its line."""
        from cometbft_tpu.libs import deviceledger

        c0, seq0 = self._mark()
        t0 = time.monotonic()
        facts = fn() or {}
        c1 = deviceledger.counters()
        recs = [r for r in deviceledger.ledger().records()
                if r["seq"] >= seq0]
        kernels = {}  # jitted function -> [backend compile seconds]
        for r in recs:
            if not r["pcache_hit"] and r["dur_ms"] >= KERNEL_SECONDS * 1e3:
                kernels.setdefault(r["fun"], []).append(
                    round(r["dur_ms"] / 1e3, 1))
        line = {"leg": name, "ok": True, **self.ident,
                "seconds": round(time.monotonic() - t0, 1),
                "compiles": c1["compiles"] - c0["compiles"],
                "compile_s": round(c1["compile_s"] - c0["compile_s"], 1),
                "pcache_hits": c1["pcache_hits"] - c0["pcache_hits"],
                "kernels_compiled": kernels,
                "kernels_from_cache": sorted(
                    {r["fun"] for r in recs if r["pcache_hit"]}),
                **facts}
        print(json.dumps(line), flush=True)
        self.legs.append(name)
        return facts


# --------------------------------------------------------------------------
# legs
# --------------------------------------------------------------------------


def leg_gate():
    from cometbft_tpu import native
    from cometbft_tpu.ops.field_lf import interpret_mode

    info = native.rebuild()
    check(info["available"] and info["built_s"] is not None,
          "native/hostaccel.cpp did not build in this run", info)
    check(native.available(), "native.available() is False")
    check(interpret_mode() is False,
          "Pallas kernels would run in the interpreter")
    return {"hostaccel": info, "pallas_interpret": False}


def _kernel_batches():
    """(ed25519, sr25519, secp256k1) batches of (pub, msg, sig) with
    oracle verdicts. Sizes land each in the bucket the mixed batch of
    the same rows uses, so a kernel compiles once for both."""
    from cometbft_tpu.crypto import ed25519_ref as ed
    from cometbft_tpu.crypto import secp256k1_ref as sc
    from cometbft_tpu.crypto import sr25519_ref as sr
    from tools.tpu_differential import edge_cases

    ed_rows = edge_cases()
    for i, p in enumerate(_privs(1, 576 - len(ed_rows))):
        m = b"smoke-ed-%d" % i
        s = p.sign(m)
        if i % 97 == 13:
            s = _flip(s, 40)
        elif i % 97 == 51:
            m += b"!"
        ed_rows.append((p.pub_key().data, m, s))
    ed_exp = [ed.verify(*r) for r in ed_rows]

    sr_rows = []
    for i in range(224):
        seed = bytes([SEED, 2]) + i.to_bytes(4, "big") + b"\x33" * 26
        m = b"smoke-sr-%d" % i
        s = sr.sign(seed, m, rng=bytes([i % 256]) * 32)
        if i % 31 == 7:
            s = _flip(s, 9)
        elif i % 31 == 19:
            m += b"!"
        sr_rows.append((sr.pubkey_from_seed(seed), m, s))
    sr_exp = [sr.verify(*r) for r in sr_rows]

    sc_rows = []
    for i in range(224):
        d = 10_000 * SEED + i + 1
        m = b"smoke-secp-%d" % i
        s = sc.sign(d, m)
        if i % 31 == 7:
            s = _flip(s, 9)
        elif i % 31 == 19:
            m += b"!"
        sc_rows.append((sc.pubkey_from_secret(d), m, s))
    sc_exp = [sc.verify(*r) for r in sc_rows]
    for name, exp in (("ed25519", ed_exp), ("sr25519", sr_exp),
                      ("secp256k1", sc_exp)):
        check(any(exp) and not all(exp), f"{name} vectors not mixed")
    return (ed_rows, ed_exp), (sr_rows, sr_exp), (sc_rows, sc_exp)


def leg_kernels():
    import numpy as np

    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.crypto.keys import PubKey
    from cometbft_tpu.ops import (
        ecdsa_pallas,
        ed25519_cached,
        ed25519_kernel,
        ed25519_pallas,
    )
    from cometbft_tpu.types import validation

    (ed_rows, ed_exp), (sr_rows, sr_exp), (sc_rows, sc_exp) = \
        _kernel_batches()
    # sr25519 as the served commit check feeds it: more rows than
    # validation.COMMIT_CHUNK_ROWS, so chunks of that one shape (1,024)
    # through device_batch_fn's queue, fetched after the last dispatch
    sr_rows, sr_exp = sr_rows * 5, sr_exp * 5
    served = validation.device_batch_fn()

    def chunked(key_type):
        return lambda pubs, msgs, sigs: served(
            [PubKey(p, key_type) for p in pubs], msgs, sigs)

    # secp256k1 both ways: the one padded pass crypto/batch gives the
    # plane and bare callers, then two chunks of the served shape
    sc_served = (sc_rows * 5, sc_exp * 5)

    out = {}
    for name, fn, rows, exp in (
            ("ed25519_pallas", ed25519_pallas.verify_batch, ed_rows, ed_exp),
            ("ed25519_cached", ed25519_cached.verify_batch_cached,
             ed_rows, ed_exp),
            ("ed25519_kernel", ed25519_kernel.verify_batch, ed_rows, ed_exp),
            ("sr25519_kernel", chunked("sr25519"), sr_rows, sr_exp),
            ("ecdsa_pallas", ecdsa_pallas.verify_batch, sc_rows, sc_exp),
            ("ecdsa_chunked", chunked("secp256k1"), *sc_served)):
        pubs, msgs, sigs = (list(z) for z in zip(*rows))
        got = np.asarray(fn(pubs, msgs, sigs), np.bool_)
        bad = np.flatnonzero(got != np.asarray(exp))
        check(bad.size == 0, f"{name} disagrees with its oracle at rows",
              bad[:8].tolist())
        out[name] = {"rows": len(rows), "valid": int(got.sum())}

    # the key-type grouping seam: one interleaved mixed-key batch through
    # the node's batch_fn (ed25519 in one pass, sr25519 and secp256k1 in
    # two chunks each, all in flight before any is fetched)
    sc_rows, sc_exp = sc_served
    mixed = ([(PubKey(p, "ed25519"), m, s, e)
              for (p, m, s), e in zip(ed_rows, ed_exp)]
             + [(PubKey(p, "sr25519"), m, s, e)
                for (p, m, s), e in zip(sr_rows, sr_exp)]
             + [(PubKey(p, "secp256k1"), m, s, e)
                for (p, m, s), e in zip(sc_rows, sc_exp)])
    order = np.random.RandomState(SEED).permutation(len(mixed))
    mixed = [mixed[i] for i in order]
    faults0 = cbatch.device_breaker().faults
    got = served([r[0] for r in mixed], [r[1] for r in mixed],
                 [r[2] for r in mixed])
    bad = np.flatnonzero(np.asarray(got) != np.asarray(
        [r[3] for r in mixed]))
    check(bad.size == 0, "device_batch_fn disagrees at mixed rows",
          bad[:8].tolist())
    check(cbatch.device_breaker().faults == faults0,
          "a kernel dispatch faulted and fell back to the host")
    out["mixed_batch"] = {"rows": len(mixed), "valid": int(got.sum())}
    return out


def leg_commit(data, host):
    import numpy as np

    from cometbft_tpu.config.config import Config
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.types import validation as tv

    vs, commit, bid = data["vs10k"], data["commit10k"], data["bid10k"]
    height = commit.height
    batch_fn = Config().crypto.batch_fn()  # what cli.build_node passes
    check(batch_fn is not None, "default [crypto] verifier is not tpu")
    faults0 = cbatch.device_breaker().faults

    def device(c):
        try:
            tv.verify_commit_light(CHAIN, vs, bid, height, c, batch_fn)
        except tv.VerificationError as e:
            return _outcome(e)
        return _outcome(None)

    got = {"good": device(commit),
           "tampered": device(data["commit10k_bad"]),
           "short": device(data["commit10k_short"])}
    check(cbatch.device_breaker().faults == faults0,
          "the commit path faulted and fell back to the host")
    exp = dict(zip(("good", "tampered", "short"), host["commits"].result()))
    check(got == exp, "10k commit outcomes differ from the host's", got, exp)
    check(got["good"] == ("ok",), "good commit not accepted", got)
    check(got["tampered"] == ("invalid_signature",
                              min(data["bad_idxs"])), "blame", got)
    check(got["short"][0] == "not_enough_power", "short commit", got)
    # per-row verdicts of the tampered commit on this one chip: what the
    # four-chip leg's sharded flushes must reproduce
    pubs = [v.pub_key for v in vs.validators]
    msgs = [data["commit10k_bad"].vote_sign_bytes(CHAIN, i)
            for i in range(len(pubs))]
    sigs = [cs.signature for cs in data["commit10k_bad"].signatures]
    row_valid = np.asarray(batch_fn(pubs, msgs, sigs), np.bool_)
    check(sorted(np.flatnonzero(~row_valid).tolist())
          == sorted(data["bad_idxs"]), "row verdicts of the tampered commit")
    data["row_valid10k"] = row_valid
    return {"validators": len(pubs), "outcomes": got,
            "host_reference": "verify_commit_light(batch_fn=None)"}


def _light_chain(tampered=None):
    """Two light blocks 1,000 heights apart over 96 secp256k1
    validators of unequal power; 32 seats change hands between them.
    `tampered` flips that row's signature in the second block."""
    import hashlib
    from dataclasses import replace

    from cometbft_tpu.crypto.keys import Secp256k1PrivKey
    from cometbft_tpu.light import verifier as lv
    from cometbft_tpu.types.block import Header
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import Commit
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    privs = [Secp256k1PrivKey.generate(
        bytes([SEED, 7]) + i.to_bytes(4, "big") + b"\x3c" * 26)
        for i in range(128)]
    blocks = {}
    for height, seats in ((1_000, privs[:96]), (2_000, privs[32:])):
        vs = ValidatorSet([
            Validator(p.pub_key(), 500 + (37 * privs.index(p)) % 1001)
            for p in seats])
        by_addr = {p.pub_key().address(): p for p in seats}
        header = Header(
            chain_id=CHAIN, height=height,
            time=Timestamp(1_700_000_000 + 7 * height, 0),
            last_block_id=_block_id(height - 1),
            validators_hash=vs.hash(), next_validators_hash=vs.hash(),
            proposer_address=vs.validators[0].address,
            app_hash=bytes([height % 251]) * 32)
        h = header.hash()
        bid = BlockID(h, PartSetHeader(1, hashlib.sha256(h).digest()))
        commit, _ = _commit(vs, by_addr, height, bid)
        if tampered is not None and height == 2_000:
            commit = _tampered(commit, [tampered])
        blocks[height] = lv.LightBlock(lv.SignedHeader(header, commit), vs)
    return blocks


def leg_light():
    from cometbft_tpu.config.config import Config
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.light import client as lc
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.types import validation as tv
    from cometbft_tpu.types.timestamp import Timestamp

    now = Timestamp(1_700_000_000 + 7 * 2_000 + 60, 0)
    faults0 = cbatch.device_breaker().faults

    def step(blocks, batch_fn):
        c = lc.Client(CHAIN, lc.Provider(CHAIN, blocks.get), witnesses=[],
                      skipping=True, batch_fn=batch_fn)
        c.trust_light_block(blocks[1_000])
        try:
            c.verify_light_block_at_height(2_000, now=now)
        except lc.LightClientError as e:
            return (type(e).__name__, _outcome(e.__cause__))
        return ("stored", c.verifications)

    served = Config().crypto.batch_fn()
    good = _light_chain()
    tracing.set_clock(None)  # an empty stage ring
    got = step(good, served)
    check(got == ("stored", 1), "the light step was not stored", got)
    names = [r[0] for r in tracing.stage_records()]
    check(all(names.count(n) == 1 for n in (
        "light.step", "light.trusting", "light.new_set")),
        "the light step's stages", names)
    # the rows each check collected, by the stage ring. Every validator
    # signs, so the second check's are the commit's first packs[1]; the
    # first check passes over the rows the old set does not know
    packs = [r[4]["rows"] for r in tracing.stage_records()
             if r[0] == "secp256k1.pack"]
    check(len(packs) == 2, "one pass a check", packs)
    old, new = (good[h].validator_set for h in (1_000, 2_000))
    known = [i for i, v in enumerate(new.validators)
             if old.has_address(v.address)]
    at = packs[1] - 2  # after the first check's last row, before the
    check(known[packs[0] - 1] < at, "no row between the checks' ends",
          known[packs[0] - 1], packs)  # second's: only that one sees it
    bad = _light_chain(tampered=at)
    got = step(bad, served)
    want = step(bad, tv.oracle_batch_fn())
    check(got == want == ("ErrInvalidHeader", ("invalid_signature", at)),
          "the tampered light step", got, want)
    check(cbatch.device_breaker().faults == faults0,
          "the light step faulted and fell back to the host")
    return {"validators": 96, "seats_changed": 32, "checks_rows": packs,
            "tampered_at": at, "outcome": got}


def leg_stream(data, host):
    from cometbft_tpu.blocksync.pipeline import (
        CommitJob,
        make_stream_verifier,
    )
    from cometbft_tpu.ops import ed25519_cached as ec

    sv = make_stream_verifier()  # the catch-up engine's verifier
    check(sv.use_pallas, "make_stream_verifier() chose the CPU path")
    stats0 = ec.table_cache_stats()
    got = []
    # a fused run never spans two validator sets (catchup.py segments at
    # the boundary, the blocksync reactor verifies one set per run)
    for seg in data["stream_segments"]:
        jobs = [CommitJob(j.vals, j.block_id, j.height, j.commit,
                          j.chain_id) for j in seg]
        got += [_outcome(e) for e in sv.verify(jobs)]
    exp = [o for f in host["stream"] for o in f.result()]
    check(got == exp, "stream results differ from HostCommitVerifier's",
          [(i, g, e) for i, (g, e) in enumerate(zip(got, exp)) if g != e])
    bad = [i for i, o in enumerate(got) if o != ("ok",)]
    check(bad == [data["stream_bad_block"]]
          and got[bad[0]] == ("invalid_signature", data["stream_bad_sig"]),
          "the bad block", bad, [got[i] for i in bad])
    stats = ec.table_cache_stats()
    # a segment is cut into chunks of sv.max_sigs device rows
    cap = sv.max_sigs // ec.table_pad(N_STREAM_VALS)
    n_chunks = sum(-(-len(seg) // cap) for seg in data["stream_segments"])
    check(sv.chunks["stamped"] == n_chunks
          and sv.chunks["host_packed"] == 0 and sv.chunks["dense"] == 0,
          "chunks left the device-stamped cached-table path", sv.chunks,
          n_chunks)
    check(stats["misses"] - stats0["misses"] == 2,
          "expected one table build or patch per validator set",
          stats0, stats)
    return {"blocks": len(got), "validators": N_STREAM_VALS,
            "note": "BASELINE config 4 replays 5,000 blocks; 64 is one "
                    "full fused run (blocksync MAX_RUN) and is enough to "
                    "prove the path",
            "bad_block": bad[0], "chunks": dict(sv.chunks),
            "table_cache": {k: stats[k] - stats0[k]
                            for k in ("misses", "valset_hits",
                                      "incremental_patches",
                                      "template_misses")}}


def _plane_assertions(plane, what, timeouts0):
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.verifyplane import plane as vplane

    s = plane.ledger.summary()
    brk = cbatch.device_breaker()
    check(set(s["paths"]) == {what},
          f"flush ledger paths are not {what} only", s["paths"])
    check(s["stamp"]["host"] == 0 and s["stamp"]["device"] == s["flushes"],
          "flushes were host-packed", s["stamp"])
    check(brk.state == "closed" and brk.faults == 0, "device breaker",
          brk.state, brk.faults)
    check(vplane.result_timeouts() == timeouts0,
          "a waiter timed out and verified on the host")
    return {"flushes": s["flushes"], "paths": s["paths"],
            "stamp": s["stamp"], "rows_per_flush": s["rows_per_flush"],
            "shard": s["shard"], "device_ms": s["device"],
            "breaker": {"state": brk.state, "faults": brk.faults}}


def leg_plane(data, host):
    import threading

    from cometbft_tpu import verifyplane
    from cometbft_tpu.config.config import VerifyPlaneConfig
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.vote_set import VoteSet
    from cometbft_tpu.verifyplane import plane as vplane

    vs, votes, bid = data["vs1k"], data["prevotes"], data["prevote_bid"]
    timeouts0 = vplane.result_timeouts()
    plane = VerifyPlaneConfig(enable=True).build()
    plane.start()
    verifyplane.set_global_plane(plane)
    try:
        primed_s = plane.prime(vs, CHAIN)  # as Node.on_start does
        check(primed_s is not None, "the plane verifies on the host")
        vset = VoteSet(CHAIN, votes[0].height, 0, canonical.PREVOTE_TYPE, vs)
        added = [None] * len(votes)
        raised = []

        def feed(k):
            try:
                for i in range(k, len(votes), 8):
                    added[i] = vset.add_vote(votes[i])
            except BaseException as e:  # re-raised below, on this thread
                raised.append(e)

        threads = [threading.Thread(target=feed, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if raised:
            raise raised[0]
        group = vset._plane_groups[bid.key()]
        check(group.wait_quorum(0), "the quorum event did not fire")
        maj = vset.two_thirds_majority()
        got = (None if maj is None else maj.key(), vset.sum, added)
        tally = group.tally
    finally:
        verifyplane.clear_global_plane(plane)
        plane.stop()
    check(got == host["voteset"].result(),
          "VoteSet state differs from the serial host VoteSet's")
    check(got[0] == bid.key() and all(added), "votes", got[:2])
    check(tally == vset.sum, "device tally", tally, vset.sum)
    return {"votes": len(votes), "threads": 8,
            "primed_s": round(primed_s, 1), "tally": tally,
            **_plane_assertions(plane, "fused", timeouts0)}


def leg_node():
    import tempfile
    import urllib.request

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config.config import Config
    from cometbft_tpu.node.node import LocalNetwork, Node
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.state.state import State
    from cometbft_tpu.verifyplane import plane as vplane

    cfg = Config()  # [crypto] verifier = "tpu" is the default
    cfg.verify_plane.enable = True
    cfg.base.chain_id = CHAIN + "-node"
    privs = _privs(6, 4)
    vs, _ = _valset(privs, power=10)
    state = State.make_genesis(cfg.base.chain_id, vs)
    net, nodes = LocalNetwork(), []
    target = 3

    def get(url, path):
        with urllib.request.urlopen(url + path, timeout=30) as r:
            return json.loads(r.read())

    with tempfile.TemporaryDirectory() as home:
        try:
            for i, priv in enumerate(privs):
                # cmd/cli.build_node's arguments; the in-memory hub
                # stands where its p2p switch would
                node = Node(
                    KVStoreApplication(), state.copy(),
                    privval=FilePV(priv), home=f"{home}/n{i}",
                    broadcast=net.broadcaster(i),
                    timeouts=cfg.consensus.timeout_params(),
                    batch_fn=cfg.crypto.batch_fn(),
                    verify_plane=cfg.verify_plane,
                    mempool_config=cfg.mempool,
                    lightgate=cfg.lightgate, controller=cfg.controller)
                net.add(node)
                nodes.append(node)
            for n in nodes:
                n.start()
            # every in-process node verifies through the global plane:
            # the one the last node registered
            url = nodes[-1].rpc_listen("127.0.0.1", 0)
            t0 = time.monotonic()
            check(nodes[0].consensus.wait_for_height(target, timeout=900),
                  "the network did not commit", nodes[0].height())
            secs = time.monotonic() - t0
            status = get(url, "/status")
            devices = get(url, "/dump_devices")
            flushes = get(url, "/dump_flushes")
        finally:
            for n in nodes:
                if n.is_running():
                    n.stop()
    sync = status["result"]["sync_info"]
    check(int(sync["latest_block_height"]) >= target, "/status", sync)
    check(devices["device"]["platform"] == "tpu", "/dump_devices names",
          devices["device"])
    paths = flushes["summary"]["paths"]
    check(paths.get("fused", 0) > 0 and not set(paths) & {
        "host", "failpoint_host", "fused_host_fallback"},
        "/dump_flushes paths", paths)
    check(devices["breaker"]["faults"] == 0, "device breaker",
          devices["breaker"])
    return {"validators": 4, "height": int(sync["latest_block_height"]),
            "seconds_to_height": round(secs, 1),
            "dump_devices_device": devices["device"],
            "dump_flushes_paths": paths,
            "dump_flushes_stamp": flushes["summary"]["stamp"],
            "compile_ms_in_flushes":
                flushes["summary"]["device"]["comp_ms"],
            "result_timeouts": vplane.result_timeouts(),
            "breaker": devices["breaker"]}


def leg_mesh(data):
    import numpy as np

    from cometbft_tpu import verifyplane
    from cometbft_tpu.config.config import VerifyPlaneConfig
    from cometbft_tpu.libs import deviceledger
    from cometbft_tpu.types import canonical
    from cometbft_tpu.verifyplane import QuorumGroup
    from cometbft_tpu.verifyplane import fused
    from cometbft_tpu.verifyplane import plane as vplane

    timeouts0 = vplane.result_timeouts()
    vs, commit = data["vs10k"], data["commit10k_bad"]
    row_valid = data["row_valid10k"]
    n = len(vs.validators)
    power = vs.validators[0].voting_power
    # the fan-out the plane will choose: a flush shards over the devices
    # the validator set FILLS, and 10,000 validators at the 4,096-slot
    # table stride fill three of four chips (fused.effective_mesh)
    fan_out = fused.effective_mesh(fused.plane_mesh(4), n)[1]
    check(fan_out >= 3, "a 10k valset should shard over >= 3 chips",
          fan_out)
    # mesh_min_rows = 1: every flush of this leg takes the sharded
    # program, the prime included
    plane = VerifyPlaneConfig(enable=True, mesh=True, mesh_devices=4,
                              mesh_min_rows=1).build()
    plane.start()
    verifyplane.set_global_plane(plane)
    try:
        primed_s = plane.prime(vs, CHAIN)
        tmpl = canonical.VoteRowTemplate(
            CHAIN, canonical.PRECOMMIT_TYPE, commit.height, 0,
            commit.block_id)
        group = QuorumGroup(
            n * power * 2 // 3 + 1, name="mesh-leg",
            valset_pubs=tuple(v.pub_key.data for v in vs.validators),
            valset_powers=tuple(v.voting_power for v in vs.validators))
        futs = []
        for i, (v, cs) in enumerate(zip(vs.validators, commit.signatures)):
            # VoteSet._add_vote_plane's submission, without its wait
            futs.append(plane.submit_many(
                [(v.pub_key, commit.vote_sign_bytes(CHAIN, i),
                  cs.signature)],
                power=power, group=group, counted=True, vidx=(i,),
                chain_id=CHAIN,
                stamp=[(tmpl, cs.timestamp.seconds, cs.timestamp.nanos)]))
        got = np.asarray([f.result(600.0)[0] for f in futs], np.bool_)
        tally = group.tally
        shards = deviceledger.residency()["shard_tables"]
    finally:
        verifyplane.clear_global_plane(plane)
        plane.stop()
    check((got == row_valid).all(), "sharded verdicts differ from the "
          "one-chip leg's at", np.flatnonzero(got != row_valid)[:8].tolist())
    check(tally == int(row_valid.sum()) * power and group.quorum_reached,
          "sharded tally", tally, int(row_valid.sum()) * power)
    facts = _plane_assertions(plane, "fused_sharded", timeouts0)
    check(facts["shard"]["n_dev_max"] == fan_out, "fan-out",
          facts["shard"], fan_out)
    # read from the live table shards' .devices(): each chip of the
    # fan-out holds its shard's bytes, not only the first
    check(len(shards) == fan_out
          and all(s["bytes"] > 0 for s in shards.values()),
          "table shards are not spread over the fan-out", shards)
    return {"validators": n, "fan_out": fan_out,
            "primed_s": round(primed_s, 1),
            "tally": tally, "invalid_rows": int((~got).sum()),
            "shard_table_bytes": {str(d): s["bytes"]
                                  for d, s in shards.items()},
            **facts}


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------


def make_data():
    """Everything the device legs and the host references share."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.commit import Commit, CommitSig
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.vote import Vote

    d = {}
    # the 10,000-validator commit and its two bad copies
    vs, by_addr = _valset(_privs(3, N_COMMIT))
    commit, bid = _commit(vs, by_addr, 12345)
    d.update(vs10k=vs, commit10k=commit, bid10k=bid,
             bad_idxs=[4321, 17, 6000])
    d["commit10k_bad"] = _tampered(commit, d["bad_idxs"])
    d["commit10k_short"] = Commit(
        commit.height, 0, bid,
        [cs if i < 6000 else CommitSig.absent()
         for i, cs in enumerate(commit.signatures)])
    # the streamed replay: validator set A, then B = A with 10 seats
    # re-elected, one bad signature in one block of A
    privs_a = _privs(4, N_STREAM_VALS)
    privs_b = list(privs_a)
    for k, p in enumerate(_privs(5, 10)):
        privs_b[(97 * k + 11) % N_STREAM_VALS] = p
    vs_a, by_a = _valset(privs_a)
    vs_b, by_b = _valset(privs_b)
    change_at, bad_block, bad_sig = 40, 23, 411
    from cometbft_tpu.blocksync.catchup import CatchupJob

    jobs = []
    for k in range(N_STREAM_BLOCKS):
        vals, by = (vs_a, by_a) if k < change_at else (vs_b, by_b)
        c, b = _commit(vals, by, 1000 + k)
        if k == bad_block:
            c = _tampered(c, [bad_sig])
        jobs.append(CatchupJob(vals, b, 1000 + k, c, CHAIN))
    d.update(stream_segments=[jobs[:change_at], jobs[change_at:]],
             stream_bad_block=bad_block, stream_bad_sig=bad_sig, vs1k=vs_a)
    # every prevote of validator set A for one block
    pbid = _block_id(7)
    votes = []
    for idx, v in enumerate(vs_a.validators):
        vote = Vote(vote_type=canonical.PREVOTE_TYPE, height=77, round=0,
                    block_id=pbid,
                    timestamp=Timestamp(1_700_000_777 + idx % 3, idx),
                    validator_address=v.address, validator_index=idx)
        vote.signature = by_a[v.address].sign(vote.sign_bytes(CHAIN))
        votes.append(vote)
    d.update(prevotes=votes, prevote_bid=pbid)
    return d


def start_host_references(pool, d):
    from cometbft_tpu.blocksync.catchup import CatchupJob
    from cometbft_tpu.types import canonical

    h = {}
    h["commits"] = pool.submit(_host_commits, [
        CatchupJob(d["vs10k"], d["bid10k"], d["commit10k"].height, c, CHAIN)
        for c in (d["commit10k"], d["commit10k_bad"], d["commit10k_short"])])
    h["stream"] = [pool.submit(_host_commits, seg[k:k + 4])
                   for seg in d["stream_segments"]
                   for k in range(0, len(seg), 4)]
    h["voteset"] = pool.submit(
        _host_voteset, CHAIN, d["prevotes"][0].height,
        canonical.PREVOTE_TYPE, d["vs1k"], d["prevotes"])
    return h


def verdict_line(device: dict) -> str:
    """The last line of stdout. The driver's contract: these keys and no
    other, the device as JAX reports it."""
    return json.dumps({
        "ok": True,
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"],
                   "count": device["n_devices"]},
    })


def main() -> int:
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    from cometbft_tpu.libs import deviceledger
    from cometbft_tpu.libs.jax_cache import enable_persistent_compile_cache

    cache_dir = enable_persistent_compile_cache()
    deviceledger.arm_compile_listener()
    try:
        device = deviceledger.require_accelerator()
        if device["platform"] != "tpu":
            raise deviceledger.NoAcceleratorError(
                f"JAX reports platform {device['platform']!r}, not 'tpu'")
    except deviceledger.NoAcceleratorError as e:
        print(f"chip_smoke: no TPU, nothing proven: {e}", file=sys.stderr)
        return 2
    # a ring wide enough to name every compile of a leg
    deviceledger.install(deviceledger.CompileLedger(capacity=8192))
    run = Run(device, cache_dir)

    run.leg("gate", leg_gate)
    workers = max(2, min(10, (os.cpu_count() or 4) - 3))
    pool = ProcessPoolExecutor(
        max_workers=workers, initializer=_host_init,
        mp_context=multiprocessing.get_context("spawn"))
    try:
        t0 = time.monotonic()
        data = make_data()
        host = start_host_references(pool, data)
        print(json.dumps({"setup": "seeded data", "seed": SEED,
                          "seconds": round(time.monotonic() - t0, 1),
                          "host_reference_workers": workers}), flush=True)
        run.leg("kernels", leg_kernels)
        run.leg("commit", lambda: leg_commit(data, host))
        run.leg("light", leg_light)
        run.leg("stream", lambda: leg_stream(data, host))
        run.leg("plane", lambda: leg_plane(data, host))
        run.leg("node", leg_node)
        if device["n_devices"] >= 4:
            run.leg("mesh", lambda: leg_mesh(data))
    finally:
        pool.shutdown(wait=True, cancel_futures=True)

    total = deviceledger.counters()
    print(json.dumps({
        "run": "total",
        "legs": run.legs,
        "seconds": round(time.monotonic() - run.t0, 1),
        "compile_cache": cache_dir,
        "compiles": total["compiles"],
        "compile_s": total["compile_s"],
        "pcache_hits": total["pcache_hits"],
        "kernel_compiles": sum(
            1 for r in deviceledger.ledger().records()
            if not r["pcache_hit"]
            and r["dur_ms"] >= KERNEL_SECONDS * 1e3),
    }), flush=True)
    print(verdict_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
