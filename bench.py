"""Benchmark: the five BASELINE.md configs through the product paths.

Prints one JSON line per config, then ONE final headline line (the
driver-recorded metric): 10k-validator VerifyCommitLight fused p50.

Baseline methodology (round-3 rework — no assumed factors):
  * The CPU baseline is MEASURED on this host: a single-threaded OpenSSL
    (`cryptography`) per-signature verify loop over the same real
    canonical sign-bytes the device verifies.
  * The reference's Go batch path (curve25519-voi ZIP-215 RLC batch)
    would beat a single-verify loop by at most ~2x single-threaded; we
    report that bound as `cpu_batch_bound_2x_ms` in extra (a sensitivity
    endpoint, NOT a divisor applied to vs_baseline).
  * vs_baseline = measured CPU ms / device steady-state ms, nothing else.

Timing methodology: JAX dispatch is asynchronous, so every timed region
ends by fetching its result to the host. The fixed cost of one trivial
jitted call fetched back is measured live as `dispatch_floor_ms`.
Production consensus/blocksync streams commits, so the headline value
is the steady-state per-commit latency (K pipelined calls / K, including
per-call H2D upload of the compact packed batch); the raw single-shot
p50 of the served call is reported alongside.

Full mode measures the chip and refuses to start without one
(libs/deviceledger.require_accelerator): a number taken on the CPU
backend is never written under a device cell's name. `--smoke` is the
host-only tier-1 slice and never imports jax.
"""
import json
import os
import sys
import time

import numpy as np

CHAIN_ID = "bench-chain"
RAW_REPS = 8
STEADY_K = 12
# streaming configs report best-of-N whole-run walls (a later benchmark
# PR replaces minima with medians of many readings, ROADMAP S0d)
WALL_RUNS = 3


def _now_ms():
    return time.perf_counter() * 1000


def p50(xs):
    return float(np.percentile(xs, 50))


# --------------------------------------------------------------------------
# jax compile-event watch: per-config compile counts/time + persistent-
# cache hits, so cold-compile pollution of a streaming config is VISIBLE
# in its JSON instead of inferred from a suspicious wall clock
# --------------------------------------------------------------------------


class CompileWatch:
    """Per-config compile deltas, read from the device observatory's
    process-global compile ledger (libs/deviceledger) — the ONE
    jax.monitoring listener bench AND production share, so
    `extra.jax_compile` here and /dump_devices on a node can never
    report different compile truth. This class is a thin snapshot
    adapter kept for the established bench API (snap/delta)."""

    def arm(self) -> bool:
        from cometbft_tpu.libs import deviceledger

        return deviceledger.arm_compile_listener()

    def snap(self) -> dict:
        from cometbft_tpu.libs import deviceledger

        c = deviceledger.counters()
        return {"compiles": c["compiles"],
                "compile_s": round(c["compile_s"], 3),
                "pcache_hits": c["pcache_hits"]}

    def delta(self, before: dict) -> dict:
        now = self.snap()
        return {k: round(now[k] - before[k], 3) for k in now}


# --------------------------------------------------------------------------
# baseline comparison: current run vs a stored BENCH_rNN.json
# --------------------------------------------------------------------------

# units where a LARGER value is better; everything else (ms) is
# smaller-is-better
BETTER_HIGHER_UNITS = ("sigs/sec", "tx/s", "headers/sec", "x")
BASELINE_THRESHOLD_PCT = 30.0  # not yet set from a measured spread


def load_bench_results(path: str) -> dict:
    """Parse a stored bench output into {cfg_name: result_dict}.

    Accepts three shapes: the driver's BENCH_rNN.json (a dict whose
    "tail" holds the bench's JSON-line stdout, possibly truncated at
    the head), a `--json-out` evidence file ({"results": {...}}), or a
    raw stdout capture (one JSON object per line). Unparseable lines
    (the tail's cut-off first line) are skipped."""
    with open(path) as f:
        text = f.read()
    lines = text.splitlines()
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, dict):
        if "results" in doc:
            return dict(doc["results"])
        if "tail" in doc:
            lines = str(doc["tail"]).splitlines()
    out = {}
    for ln in lines:
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            r = json.loads(ln)
        except ValueError:
            continue  # truncated first line of a driver tail
        m = r.get("metric", "")
        if m.startswith("cfg"):
            out[m.split()[0]] = r
        elif "VerifyCommitLight fused p50" in m:
            out["headline"] = r
    return out


def compare_to_baseline(results: dict, baseline: dict,
                        threshold_pct: float = BASELINE_THRESHOLD_PCT,
                        ) -> dict:
    """Thresholded per-config pass/fail against a stored run. Direction
    is unit-aware (ms down = good, sigs/sec up = good); configs missing
    on either side (or failed: value None) are reported, not judged."""
    rows, regressed, missing = [], [], []
    for name in sorted(set(results) | set(baseline)):
        cur, base = results.get(name), baseline.get(name)
        cv = cur.get("value") if cur else None
        bv = base.get("value") if base else None
        if cv in (None, 0) or bv in (None, 0):
            missing.append(name)
            continue
        unit = (cur.get("unit") or base.get("unit") or "")
        higher_better = unit in BETTER_HIGHER_UNITS
        delta_pct = (float(cv) - float(bv)) / float(bv) * 100.0
        # flagging is RATIO-based, symmetric in both directions: a
        # percent delta saturates at -100% for higher-better units (a
        # 20x throughput collapse is "-95%"), which would make big
        # thresholds unable to flag throughput regressions at all
        slowdown = (float(bv) / float(cv) if higher_better
                    else float(cv) / float(bv))
        lim = 1.0 + threshold_pct / 100.0
        status = ("REGRESSED" if slowdown > lim else
                  "improved" if slowdown < 1.0 / lim else "ok")
        if status == "REGRESSED":
            regressed.append(name)
        rows.append({"config": name, "unit": unit, "current": cv,
                     "baseline": bv, "delta_pct": round(delta_pct, 1),
                     "status": status})
    return {"threshold_pct": threshold_pct, "rows": rows,
            "regressed": regressed, "missing": missing,
            "ok": not regressed}


def measure_dispatch_floor():
    """Fixed cost of one trivial jitted call fetched back to the host:
    (median, min) ms over 50 calls. What any single device call pays
    before it does work, and so the size below which a batch is all
    overhead (ROADMAP S2)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def trivial(x):
        return x + 1

    x = jnp.zeros((8, 128), jnp.int32)
    np.asarray(trivial(x))
    ts = []
    for _ in range(50):
        t = _now_ms()
        np.asarray(trivial(x))
        ts.append(_now_ms() - t)
    return p50(ts), min(ts)


# --------------------------------------------------------------------------
# fixtures: real validator sets + real commits (canonical sign-bytes)
# --------------------------------------------------------------------------


def make_ed_commit(n_vals, height=12345, power=1000, seed=7):
    """n_vals distinct ed25519 keys, each signing its real precommit
    sign-bytes (types/vote.go:139 canonical encoding)."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    privs = [
        PrivKey.generate(seed.to_bytes(2, "big") + i.to_bytes(4, "big")
                         + b"\x11" * 26)
        for i in range(n_vals)
    ]
    vs = ValidatorSet([Validator(p.pub_key(), power) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\xab" * 32, PartSetHeader(2, b"\xcd" * 32))
    sigs = []
    for idx, v in enumerate(vs.validators):
        ts = Timestamp(1_700_000_000 + idx, 0)
        sb = canonical.canonical_vote_bytes(
            CHAIN_ID, canonical.PRECOMMIT_TYPE, height, 0, bid, ts
        )
        sigs.append(
            CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                      by_addr[v.address].sign(sb))
        )
    return vs, Commit(height, 0, bid, sigs), bid


def cpu_ed25519_per_sig_ms(vs, commit, sample=400):
    """Measured OpenSSL (C-speed) verify of the commit's own sign-bytes.

    Deliberately NOT PubKey.verify_signature — that is the pure-Python
    ZIP-215 oracle (~40x slower than OpenSSL), which would inflate
    vs_baseline dishonestly. OpenSSL's cofactorless verify accepts all
    honestly-generated signatures, which is all this fixture contains.
    """
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    n = min(sample, len(vs.validators))
    msgs = [commit.vote_sign_bytes(CHAIN_ID, i) for i in range(n)]
    pks = [
        Ed25519PublicKey.from_public_bytes(vs.validators[i].pub_key.data)
        for i in range(n)
    ]
    t = _now_ms()
    for i in range(n):
        pks[i].verify(commit.signatures[i].signature, msgs[i])
    return (_now_ms() - t) / n


# --------------------------------------------------------------------------
# configs
# --------------------------------------------------------------------------


def cfg1_live_node():
    """#1: kvstore ABCI app, 4 validators — live in-process net, then
    VerifyCommitLight on a commit the network actually produced."""
    import tempfile

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.consensus.ticker import TimeoutParams
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.node.node import LocalNetwork, Node
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.state.state import State
    from cometbft_tpu.types import validation as tv
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    fast = TimeoutParams(propose=0.4, propose_delta=0.1, prevote=0.2,
                         prevote_delta=0.1, precommit=0.2,
                         precommit_delta=0.1, commit=0.01)
    privs = [PrivKey.generate(bytes([i + 1]) * 32) for i in range(4)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis("bench-live", vals)
    net = LocalNetwork()
    nodes = []
    with tempfile.TemporaryDirectory() as home:
        for i, priv in enumerate(privs):
            node = Node(KVStoreApplication(), state.copy(),
                        privval=FilePV(priv), home=f"{home}/n{i}",
                        broadcast=net.broadcaster(i), timeouts=fast)
            net.add(node)
            nodes.append(node)
        t_net = _now_ms()
        for n in nodes:
            n.start()
        try:
            ok = nodes[0].consensus.wait_for_height(4, timeout=60)
            net_ms = _now_ms() - t_net
            assert ok, "live net stalled"
            store = nodes[0].block_store
            block = store.load_block(3)
            commit = store.load_block_commit(3)  # block 4's LastCommit
            # the real part-set BlockID the network committed under
            bid = block.block_id()
        finally:
            for n in nodes:
                n.stop()

    def run_cpu():
        t = _now_ms()
        tv.verify_commit_light("bench-live", vals, bid, 3, commit,
                               batch_fn=None)
        return _now_ms() - t

    cpu = [run_cpu() for _ in range(20)]
    return {
        "metric": "cfg1 live 4-val kvstore net VerifyCommitLight",
        "value": round(p50(cpu), 3),
        "unit": "ms",
        "vs_baseline": 1.0,
        "extra": {
            "net_to_height4_ms": round(net_ms, 1),
            "note": "4 sigs is below any sane device batch threshold; "
                    "the product path verifies on CPU (shouldBatchVerify "
                    "economics), so baseline == value",
        },
    }


def _device_commit_bench(vs, commit, bid, height, steady_k=STEADY_K):
    """Product-path VerifyCommitLight on device: raw p50 + steady state.

    Steady state uses the cached-valset kernel (ops.ed25519_cached): the
    per-validator window table is built ONCE per valset (reported as
    table_build_ms) and amortized over the stream, which is exactly how
    consensus/blocksync verify thousands of commits against a slowly-
    changing set. Each steady iteration still pays the full per-commit
    host->device upload of the packed signature rows.

    host_pack_ms is the ZERO-COPY pack path (this PR): commit ->
    native template pack (ed25519_pack_commits, no Python sign-bytes
    objects) -> pack_rows_cached into a rotated pinned staging buffer.
    It now INCLUDES sign-bytes assembly (the old number excluded it),
    so it is the honest all-in host cost per flush. steady_overlap_ms
    runs the double-buffered loop — pack k+1 while the device verifies
    k with the rows buffer donated — and staging_overlap_eff is the
    fraction of pack time hidden behind the device.

    host_pack_stamped_ms is the DEVICE-STAMPED path's residual host
    cost: signature scatter + timestamp word split + flags into the
    per-row delta buffers. Sign-bytes assembly, SHA-512 padding and
    mod-L moved into the device prologue, but this residual is not 0
    and is reported so the r-series trajectory stays honest.
    """
    import jax

    from cometbft_tpu.crypto.batch import staging_pool
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.types import validation as tv

    batch_fn = tv.device_batch_fn(use_pallas=True)
    tv.verify_commit_light(CHAIN_ID, vs, bid, height, commit, batch_fn)
    raw = []
    for _ in range(RAW_REPS):
        t = _now_ms()
        tv.verify_commit_light(CHAIN_ID, vs, bid, height, commit, batch_fn)
        raw.append(_now_ms() - t)

    n = len(vs.validators)
    pubs = [v.pub_key.data for v in vs.validators]
    powers = np.asarray([v.voting_power for v in vs.validators], np.int64)
    t = _now_ms()
    table = ec.table_for_pubs(pubs, powers)
    np.asarray(table.ok).sum()  # fetch: the build is done when it lands
    table_build_ms = _now_ms() - t
    # valset-churn costs (round-4 verdict item 2): warm full rebuild
    # (compile cached) and a 10-validator incremental update — the
    # epoch-change price while streaming against a live valset
    t = _now_ms()
    t2 = ec.build_table(pubs, powers)
    np.asarray(t2.ok).sum()
    rebuild_warm_ms = _now_ms() - t
    from cometbft_tpu.crypto.keys import PrivKey as _PK

    churn = [(i * (n // 16) + 3,
              _PK.generate((5000 + i).to_bytes(4, "big") + b"\x66" * 28)
              .pub_key().data)
             for i in range(10)]
    t3 = ec.update_table(table, churn)  # compile
    np.asarray(t3.ok).sum()
    t = _now_ms()
    t3 = ec.update_table(table, churn, {churn[0][0]: 123})
    np.asarray(t3.ok).sum()
    update10_ms = _now_ms() - t
    pad = ec.pad_rows(n)
    counted = np.zeros((pad,), np.bool_)
    counted[:n] = True
    cid = np.zeros((pad,), np.int32)
    thresh = ek.threshold_limbs(int(powers.sum()) * 2 // 3)
    pool = staging_pool()

    def pack_once():
        pb, _ = tv.commit_packed_batch(CHAIN_ID, commit, pubs, pad_to=pad)
        out = pool.get("bench.rows", ec.packed_rows_shape(pad), np.int32)
        return ec.pack_rows_cached(pb, counted, cid, thresh, out=out)

    pack_times = []
    for _ in range(3):
        t = _now_ms()
        rows = pack_once()
        pack_times.append(_now_ms() - t)
    pack_ms = min(pack_times)

    valid, tally, quorum = ec.verify_tally_rows_cached(
        jax.device_put(rows), table, 1
    )
    assert bool(np.asarray(quorum)[0]) and np.asarray(valid)[:n].all()
    # steady state WITH the per-commit upload (the product streaming
    # shape). Best of 3 loops.
    def steady_loop(get_rows):
        best = float("inf")
        for _ in range(3):
            outs = None
            t = _now_ms()
            for _ in range(steady_k):
                outs = ec.verify_tally_rows_cached(get_rows(), table, 1)
            assert bool(np.asarray(outs[2])[0])
            best = min(best, (_now_ms() - t) / steady_k)
        return best

    steady = steady_loop(lambda: jax.device_put(rows))
    dev_rows = jax.device_put(rows)
    steady_resident = steady_loop(lambda: dev_rows)

    # double-buffered overlap: re-pack EVERY iteration into the rotated
    # staging buffer while the previous flush is still on the device —
    # the verify-plane dispatcher's loop shape
    def overlap_loop():
        best = float("inf")
        for _ in range(3):
            pending = None
            t = _now_ms()
            for _ in range(steady_k):
                r = pack_once()
                nxt = ec.verify_tally_rows_cached(
                    jax.device_put(r), table, 1
                )
                if pending is not None:
                    assert bool(np.asarray(pending[2])[0])
                pending = nxt
            assert bool(np.asarray(pending[2])[0])
            best = min(best, (_now_ms() - t) / steady_k)
        return best

    steady_overlap = overlap_loop()
    eff = (pack_ms + steady - steady_overlap) / pack_ms if pack_ms else 0.0

    # the DEVICE-STAMPED path's residual host cost (ISSUE 19): raw-sig
    # scatter + (secs_lo, secs_hi, nanos) word extraction + flags. The
    # sign-bytes/SHA-512/mod-L work moved on device, but this is NOT 0
    # and the r-series trajectory must say so honestly.
    css = commit.signatures

    def delta_pack_once():
        sec_a = np.fromiter((cs.timestamp.seconds for cs in css),
                            np.int64, n)
        nan_a = np.fromiter((cs.timestamp.nanos for cs in css),
                            np.int64, n)
        dsig = pool.get("bench.dsig", (pad, 64), np.uint8)
        dsig[:n] = np.frombuffer(
            b"".join(cs.signature for cs in css), np.uint8
        ).reshape(-1, 64)
        dts = pool.get("bench.dts", (pad, 3), np.int32)
        dts[:n, 0] = (sec_a & 0xFFFFFFFF).astype(np.uint32) \
            .view(np.int32)
        dts[:n, 1] = (sec_a >> 32).astype(np.int32)
        dts[:n, 2] = nan_a.astype(np.int32)
        dfl = pool.get("bench.dflags", (pad,), np.int32)
        dfl[:n] = 3  # live | counted (single template, commit 0)
        return dsig, dts, dfl

    delta_times = []
    for _ in range(3):
        t = _now_ms()
        delta_pack_once()
        delta_times.append(_now_ms() - t)

    overlap = {
        "steady_overlap_ms": round(steady_overlap, 2),
        "staging_overlap_eff": round(max(0.0, min(1.0, eff)), 3),
        "host_pack_stamped_ms": round(min(delta_times), 3),
    }
    return (raw, steady, pack_ms,
            {"cold": table_build_ms, "rebuild_warm": rebuild_warm_ms,
             "update10": update10_ms},
            steady_resident, overlap)


def cfg2_1k_commit():
    """#2: 1000-validator ed25519 commit, batch verified on device."""
    vs, commit, bid = make_ed_commit(1000)
    per_sig = cpu_ed25519_per_sig_ms(vs, commit)
    cpu_ms = per_sig * 1000
    raw, steady, pack_ms, tbl_ms, resident, overlap = _device_commit_bench(
        vs, commit, bid, 12345
    )
    return {
        "metric": "cfg2 1000-validator commit batch verify",
        "value": round(steady, 3),
        "unit": "ms",
        "vs_baseline": round(cpu_ms / steady, 2),
        "extra": {
            "raw_p50_ms": round(p50(raw), 2),
            "host_pack_ms": round(pack_ms, 2),
            # residual host cost when the flush ships per-row deltas
            # and sign-bytes are stamped ON DEVICE (ISSUE 19) — small,
            # but not 0: sig scatter + ts word split + flags
            "host_pack_stamped_ms": overlap["host_pack_stamped_ms"],
            "steady_overlap_ms": overlap["steady_overlap_ms"],
            "staging_overlap_eff": overlap["staging_overlap_eff"],
            "table_build_ms": round(tbl_ms["cold"], 1),
            "table_rebuild_warm_ms": round(tbl_ms["rebuild_warm"], 1),
            "table_update_10vals_ms": round(tbl_ms["update10"], 1),
            "steady_resident_ms": round(resident, 2),
            "cpu_measured_ms": round(cpu_ms, 1),
            "cpu_batch_bound_2x_ms": round(cpu_ms / 2, 1),
            "sigs_per_sec": round(1000 / (steady / 1000)),
        },
    }


def cfg3_mixed():
    """#3: 10000-validator mixed ed25519/sr25519, fused quorum tally."""
    try:
        from cometbft_tpu.ops import sr25519_kernel  # noqa: F401
    except ImportError:
        return {
            "metric": "cfg3 10k mixed ed25519/sr25519 fused tally",
            "value": None,
            "unit": "ms",
            "vs_baseline": None,
            "extra": {"status": "sr25519 kernel not yet available"},
        }
    from cometbft_tpu.bench_support import mixed_commit_bench

    return mixed_commit_bench(CHAIN_ID)


def cfg4_streaming(n_blocks=256, n_vals=1000):
    """#4: blocksync replay — streamed batch verify through StreamVerifier
    (fused multi-commit chunks, double-buffered dispatch)."""
    from cometbft_tpu.blocksync.pipeline import CommitJob, StreamVerifier
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    privs = [
        PrivKey.generate((900 + i).to_bytes(4, "big") + b"\x22" * 28)
        for i in range(n_vals)
    ]
    vs = ValidatorSet([Validator(p.pub_key(), 50) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    t_gen = _now_ms()
    jobs = []
    for h in range(1, n_blocks + 1):
        bid = BlockID(h.to_bytes(4, "big") * 8,
                      PartSetHeader(1, b"\x0f" * 32))
        sigs = []
        for v in vs.validators:
            ts = Timestamp(1_700_000_000 + h, 0)
            sb = canonical.canonical_vote_bytes(
                CHAIN_ID, canonical.PRECOMMIT_TYPE, h, 0, bid, ts
            )
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                                  by_addr[v.address].sign(sb)))
        jobs.append(CommitJob(vs, bid, h, Commit(h, 0, bid, sigs),
                              CHAIN_ID))
    gen_s = (_now_ms() - t_gen) / 1000

    sv = StreamVerifier(use_pallas=True)
    # warm (compiles every bucket shape used)
    r = sv.verify(jobs[:80])
    assert all(e is None for e in r)
    # best-of-N whole-run walls
    walls = []
    for _ in range(WALL_RUNS):
        t = _now_ms()
        results = sv.verify(jobs)
        walls.append(_now_ms() - t)
        assert all(e is None for e in results)
    wall_ms = min(walls)
    total_sigs = n_blocks * n_vals
    per_sig = cpu_ed25519_per_sig_ms(vs, jobs[0].commit, sample=300)
    cpu_wall_ms = per_sig * total_sigs
    return {
        "metric": "cfg4 blocksync streamed batch verify",
        "value": round(total_sigs / (wall_ms / 1000)),
        "unit": "sigs/sec",
        "vs_baseline": round(cpu_wall_ms / wall_ms, 2),
        "extra": {
            "blocks": n_blocks,
            "vals_per_block": n_vals,
            "wall_ms": round(wall_ms, 1),
            "wall_ms_runs": [round(w, 1) for w in walls],
            "commits_per_sec": round(n_blocks / (wall_ms / 1000), 1),
            "cpu_measured_ms": round(cpu_wall_ms, 1),
            "fixture_gen_s": round(gen_s, 1),
            "note": "streaming overlap: host packs chunk k+1 while device "
                    "verifies chunk k (async dispatch)",
        },
    }


def cfg5_light_secp(n_vals=10_000, target_height=256):
    """#5: light-client skipping verification, 10k secp256k1 validators.

    The reference CANNOT batch this at all (crypto/batch/batch.go:12-21
    has no secp256k1 verifier; it falls to verifyCommitSingle,
    types/validation.go:266). Ours batches ECDSA on device."""
    from cometbft_tpu.crypto.keys import PubKey, Secp256k1PrivKey
    from cometbft_tpu.light import client as lc
    from cometbft_tpu.light import verifier as lv
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types import validation as tv
    from cometbft_tpu.types.block import Header
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    T0 = 1_700_000_000
    privs = [
        Secp256k1PrivKey.generate((3000 + i).to_bytes(4, "big") + b"\x33" * 28)
        for i in range(n_vals)
    ]
    vs = ValidatorSet([Validator(p.pub_key(), 5) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    blocks = {}

    def make_block(h):
        if h in blocks:
            return blocks[h]
        header = Header(
            chain_id=CHAIN_ID, height=h, time=Timestamp(T0 + h, 0),
            last_block_id=BlockID(), validators_hash=vs.hash(),
            next_validators_hash=vs.hash(),
            proposer_address=vs.validators[0].address,
            app_hash=b"\x01" * 32,
        )
        bid = BlockID(header.hash(), PartSetHeader(1, header.hash()))
        sigs = []
        for v in vs.validators:
            ts = Timestamp(T0 + h, 42)
            sb = canonical.canonical_vote_bytes(
                CHAIN_ID, canonical.PRECOMMIT_TYPE, h, 0, bid, ts
            )
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                                  by_addr[v.address].sign(sb)))
        blocks[h] = lv.LightBlock(
            lv.SignedHeader(header, Commit(h, 0, bid, sigs)), vs
        )
        return blocks[h]

    t_gen = _now_ms()
    make_block(1)
    make_block(target_height)
    gen_s = (_now_ms() - t_gen) / 1000

    # CPU baseline: serial secp256k1 verify (the reference's only option)
    b1 = blocks[1]
    sample = 200
    msgs = [b1.signed_header.commit.vote_sign_bytes(CHAIN_ID, i)
            for i in range(sample)]
    t = _now_ms()
    for i in range(sample):
        assert vs.validators[i].pub_key.verify_signature(
            msgs[i], b1.signed_header.commit.signatures[i].signature
        )
    secp_per_sig = (_now_ms() - t) / sample
    # bisection with a stable valset = one non-adjacent verify of the
    # target (1/3 trusting + 2/3 light): ~2 batch passes over 10k sigs
    cpu_ms = secp_per_sig * n_vals * 2

    provider = lc.Provider(CHAIN_ID, lambda h: make_block(h))
    batch_fn = tv.device_batch_fn(use_pallas=True)

    def run():
        c = lc.Client(CHAIN_ID, provider, trusting_period=1e6,
                      batch_fn=batch_fn)
        c.trust_light_block(blocks[1])
        t = _now_ms()
        c.verify_light_block_at_height(target_height,
                                       now=Timestamp(T0 + 500, 0))
        return _now_ms() - t

    run()  # warm compile
    times = [run() for _ in range(5)]
    val = p50(times)
    return {
        "metric": "cfg5 light-client skipping verify 10k secp256k1",
        "value": round(val, 1),
        "unit": "ms",
        "vs_baseline": round(cpu_ms / val, 2),
        "extra": {
            "cpu_measured_ms": round(cpu_ms, 1),
            "cpu_per_sig_us": round(secp_per_sig * 1000, 1),
            "fixture_gen_s": round(gen_s, 1),
            "note": "reference has NO secp batch path (verifyCommitSingle)",
        },
    }


def cfg6_vote_plane(n_vals=256, n_threads=8):
    """#6: concurrent single-vote gossip through the verify plane.

    N threads each gossip a disjoint slice of one height's precommits
    into a shared VoteSet — the consensus hot path where, pre-plane,
    every vote signature single-verified serially on the host under the
    VoteSet lock. With the plane on, verification leaves the lock and
    concurrent votes coalesce into shared bucket passes (the fused
    cached-table pass on TPU backends), with the 2/3 tally computed in
    the same flush."""
    import threading

    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.types.vote import Vote
    from cometbft_tpu.types.vote_set import VoteSet
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    privs = [
        PrivKey.generate((7000 + i).to_bytes(4, "big") + b"\x44" * 28)
        for i in range(n_vals)
    ]
    vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    bid = BlockID(b"\x6b" * 32, PartSetHeader(1, b"\x6c" * 32))
    votes = []
    for p in privs:
        idx, _ = vs.get_by_address(p.pub_key().address())
        v = Vote(vote_type=canonical.PRECOMMIT_TYPE, height=9, round=0,
                 block_id=bid, timestamp=Timestamp(1_700_000_000, 0),
                 validator_address=p.pub_key().address(),
                 validator_index=idx)
        v.signature = p.sign(v.sign_bytes(CHAIN_ID))
        votes.append(v)

    def run(plane_on):
        vset = VoteSet(CHAIN_ID, 9, 0, canonical.PRECOMMIT_TYPE, vs)
        plane = None
        if plane_on:
            plane = VerifyPlane(window_ms=1.5, max_batch=4096,
                                max_queue=16384)
            plane.start()
            set_global_plane(plane)
        lats, errs = [], []

        def worker(lo):
            mine = []
            for v in votes[lo::n_threads]:
                t = _now_ms()
                try:
                    vset.add_vote(v)
                except Exception as e:  # noqa: BLE001 - recorded below
                    errs.append(repr(e))
                mine.append(_now_ms() - t)
            lats.extend(mine)

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        t0 = _now_ms()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = _now_ms() - t0
        stats = plane.stats() if plane else None
        if plane:
            set_global_plane(None)
            plane.stop()
        assert not errs, errs[:3]
        assert vset.has_two_thirds_majority()
        return p50(lats), wall, stats

    serial_p50, serial_wall, _ = run(False)
    plane_p50, plane_wall, pstats = run(True)
    plane_sps = n_vals / (plane_wall / 1000)
    serial_sps = n_vals / (serial_wall / 1000)
    return {
        "metric": "cfg6 concurrent vote gossip via verify plane",
        "value": round(plane_sps),
        "unit": "sigs/sec",
        "vs_baseline": round(plane_sps / serial_sps, 2),
        "extra": {
            "threads": n_threads,
            "votes": n_vals,
            "plane_vote_p50_ms": round(plane_p50, 3),
            "serial_vote_p50_ms": round(serial_p50, 3),
            "plane_wall_ms": round(plane_wall, 1),
            "serial_wall_ms": round(serial_wall, 1),
            "serial_sigs_per_sec": round(serial_sps),
            "plane_batches": pstats["batches"] if pstats else None,
            "plane_rows": pstats["rows_verified"] if pstats else None,
            "plane_pack_ms_total": round(pstats["pack_seconds"] * 1000, 2)
            if pstats else None,
            "plane_h2d_bytes": pstats["h2d_bytes"] if pstats else None,
            "plane_overlapped_flushes": pstats["overlapped"]
            if pstats else None,
            "note": "baseline = serial host verify under the VoteSet "
                    "lock (the pre-plane product path)",
        },
    }


def disabled_flush_bookkeeping_us(k: int = 20_000) -> dict:
    """Per-flush cost of the verify plane's ALWAYS-ON accounting with
    tracing disabled — the r05 post-mortem's suspect #1, measured.

    Replays the exact bookkeeping sequence _stage/_finish_flight run
    per flush on the disabled path (four monotonic_ns reads, the one
    FIELDS-ordered scratch list that becomes the ring slot, the
    in-place stage fills, the ring append) plus the cost of one
    disabled tracing.span() call, in isolation, so the number is the
    hook overhead itself and not the workload around it."""
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.verifyplane.plane import (PATH_HOST, STAMP_HOST,
                                                FlushLedger)

    assert not tracing.enabled(), "measure the DISABLED path"
    led = FlushLedger()
    t_led = _now_ms()
    for i in range(k):
        t0 = tracing.monotonic_ns()
        gen = tracing.clock_gen()
        rec = [i, round(t0 / 1e6, 3), 64, 4,
               round((t0 - t0) / 1e6, 3), 0.0, 0.0, 0.0, 0.0, 0,
               PATH_HOST, STAMP_HOST, "closed", 0, 0, 64, 0, 0, 0, 1,
               1, 0, 0, 0.0, 0.0, 0, 0.0, 0.0, (), t0, t0, gen, 0]
        t1 = tracing.monotonic_ns()
        rec[5] = round((t1 - t0) / 1e6, 3)
        t2 = tracing.monotonic_ns()
        rec[7] = round((t2 - t1) / 1e6, 3)
        t3 = tracing.monotonic_ns()
        rec[8] = round((t3 - t2) / 1e6, 3)
        led.record(rec)
    ledger_us = (_now_ms() - t_led) * 1000 / k
    t_span = _now_ms()
    for _ in range(k):
        if tracing.enabled():  # the guard every flush-path hook uses
            pass
        with tracing.span("bench.noop", cat="bench"):
            pass
    span_us = (_now_ms() - t_span) * 1000 / k
    return {
        "ledger_bookkeeping_us_per_flush": round(ledger_us, 3),
        "disabled_span_us_per_call": round(span_us, 3),
        "note": "always-on ledger + one disabled span, per flush; a "
                "cfg2 steady iteration is ~10^4x this",
    }


def device_ledger_bookkeeping_us(k: int = 20_000) -> dict:
    """Per-flush cost of the device observatory's ALWAYS-ON hooks with
    tracing disabled (ISSUE 15 acceptance: < 10 us/flush).

    Replays the exact per-flush sequence the dispatcher adds for the
    observatory — one attribution frame push/pop around the dispatch
    (attr_begin/attr_end), the two extra clock reads bracketing it,
    and the three in-place ledger stamps (comp/h2d/util) — in
    isolation. The compile RECORDING path itself is off this budget
    (compiles are rare, ms-scale events), but it is measured too so a
    storm can't hide a pathological record cost."""
    from cometbft_tpu.libs import deviceledger, tracing

    assert not tracing.enabled(), "measure the DISABLED path"
    led = deviceledger.CompileLedger()
    rec = [0, 0.0, 0.0, 0, "bench", -1, 0]
    t0 = _now_ms()
    for i in range(k):
        fr = deviceledger.attr_begin("plane.flush", i)
        a = tracing.monotonic_ns()
        b = tracing.monotonic_ns()
        deviceledger.attr_end(fr)
        rec[2] = round(fr.ms, 3)
        rec[3] = round(max((b - a) / 1e6 - fr.ms, 0.0), 3)
        rec[4] = 0.97
    attr_us = (_now_ms() - t0) * 1000 / k
    t1 = _now_ms()
    for i in range(2000):
        led.record(0.001, False, "bench", i)
    record_us = (_now_ms() - t1) * 1000 / 2000
    return {
        "flush_hook_us_per_flush": round(attr_us, 3),
        "compile_record_us": round(record_us, 3),
        "note": "always-on device observatory; the flush-path budget "
                "is <10us per flush (compile records are rare "
                "ms-scale events, off that budget)",
    }


def height_ledger_bookkeeping_us(k: int = 20_000) -> dict:
    """Per-step-transition cost of the ALWAYS-ON consensus height
    ledger with tracing disabled (ISSUE 13 acceptance: < 10 us/step,
    allocation-free in the FlushLedger sense — the scratch list is the
    ring slot; the step path builds no dicts/spans/strings).

    Replays the exact per-transition sequence _set_step drives
    (on_step: clock read + step-slot dict lookup + in-place stores,
    plus the once-per-height fsync anchor check) and the per-precommit
    note_vote stamp, in isolation, over a full open->steps->finalize
    height cycle per 8 transitions so the ring append amortizes in
    like production."""
    from cometbft_tpu.consensus.heightledger import HeightLedger
    from cometbft_tpu.libs import tracing

    assert not tracing.enabled(), "measure the DISABLED path"
    led = HeightLedger()
    steps = (2, 3, 4, 6, 8)  # new_round/propose/prevote/precommit/commit
    t0 = _now_ms()
    h = 0
    for i in range(k):
        if i % len(steps) == 0:
            h += 1
        led.on_step(h, 0, steps[i % len(steps)])
        led.note_wal_fsync_base(1234)
    step_us = (_now_ms() - t0) * 1000 / k
    # allocation audit: steady-state step transitions WITHIN one height
    # (no height open, no ring append) must hold the process block
    # count flat — the scratch list absorbs every stamp in place (the
    # clock's int objects churn through the freelist, netting zero)
    import sys as _sys

    led.on_step(h + 1, 0, 2)  # open once, off the measured window
    blocks0 = _sys.getallocatedblocks()
    for i in range(1024):
        led.on_step(h + 1, 0, steps[i % len(steps)])
    alloc_per_step = (_sys.getallocatedblocks() - blocks0) / 1024
    t1 = _now_ms()
    for i in range(k):
        led.note_vote(0, i & 63)
    vote_us = (_now_ms() - t1) * 1000 / k
    # one full height close (the once-per-height cost, NOT on the
    # step budget): record with a tiny synthetic commit
    class _Sig:
        def is_absent(self):
            return False

    t2 = _now_ms()
    for j in range(64):
        led.on_step(h + 1 + j, 0, 4)
        led.record_height(h + 1 + j, 0, "deadbeef", 0, 0,
                          commit_sigs=[_Sig()] * 4)
    finalize_us = (_now_ms() - t2) * 1000 / 64
    return {
        "step_transition_us": round(step_us, 3),
        "steady_alloc_blocks_per_step": round(alloc_per_step, 3),
        "note_vote_us": round(vote_us, 3),
        "finalize_record_us": round(finalize_us, 3),
        "note": "always-on height ledger, tracing off; budget is "
                "<10us per step transition (the finalize record runs "
                "once per height and is off that budget)",
    }


def peer_ledger_bookkeeping_us(k: int = 20_000) -> dict:
    """Per-message cost of the ALWAYS-ON gossip observatory with
    tracing disabled (ISSUE 14 acceptance: < 10 us/message — the seam
    rides every MConnection send/recv and every SimConn hop, so it
    must be integer stores, not dicts-per-message).

    Replays the exact per-message sequence the send and recv routines
    drive (note_sent: totals + the first-touch channel slot;
    note_recv per packet; note_queue_depth after each enqueue) plus
    the per-vote route stamp, in isolation."""
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.p2p import peerledger

    assert not tracing.enabled(), "measure the DISABLED path"
    led = peerledger.PeerLedger()
    rec = led.open_peer("bench-peer", True)
    t0 = _now_ms()
    for i in range(k):
        peerledger.note_sent(rec, 0x22, 180)
        peerledger.note_queue_depth(rec, i & 15)
    send_us = (_now_ms() - t0) * 1000 / k
    t1 = _now_ms()
    for i in range(k):
        peerledger.note_recv(rec, 0x22, 180, eof=(i & 1) == 0)
    recv_us = (_now_ms() - t1) * 1000 / k
    # allocation audit: steady-state messages on a warmed channel slot
    # hold the process block count flat (first touch allocated it)
    import sys as _sys

    blocks0 = _sys.getallocatedblocks()
    for i in range(1024):
        peerledger.note_sent(rec, 0x22, 180)
    alloc_per_msg = (_sys.getallocatedblocks() - blocks0) / 1024
    t2 = _now_ms()
    for i in range(k):
        # prune periodically so the loop measures the steady-state
        # INSERT path, not the cheap at-capacity drop branch
        if i % 8000 == 0:
            led.prune_votes(1 << 60)
        led.note_vote_seen((i >> 6, 0, 2, i & 63), "bench-peer")
    vote_us = (_now_ms() - t2) * 1000 / k
    led.prune_votes(1 << 60)
    return {
        "send_us_per_msg": round(send_us, 3),
        "recv_us_per_msg": round(recv_us, 3),
        "steady_alloc_blocks_per_msg": round(alloc_per_msg, 3),
        "vote_seen_us": round(vote_us, 3),
        "note": "always-on peer ledger, tracing off; budget is <10us "
                "per message (vote stamps ride only VOTE_CHANNEL "
                "receives)",
    }


def cfg7_pack_only(n_vals=10_000):
    """#7: host packing microbench — template row packing vs the legacy
    per-vote sign-bytes paths, device-free.

    Three ways to build the same 10k canonical sign-bytes:
      legacy    — full canonical_vote_bytes re-encode per signature
                  (the reference's loop, types/validation.go:207);
      encoder   — the splice-cached CanonicalVoteEncoder loop
                  (Commit.vote_sign_bytes, the round-4 path);
      template  — ONE vectorized numpy patch over all rows
                  (Commit.sign_bytes_rows, this PR).
    All three are asserted byte-identical; value = legacy/template
    speedup (acceptance: >= 5x)."""
    from cometbft_tpu.types import canonical

    vs, commit, bid = make_ed_commit(n_vals, seed=9)

    def run_legacy():
        t = _now_ms()
        out = [
            canonical.canonical_vote_bytes(
                CHAIN_ID, canonical.PRECOMMIT_TYPE, commit.height,
                commit.round, bid, cs.timestamp,
            )
            for cs in commit.signatures
        ]
        return _now_ms() - t, out

    def run_encoder():
        t = _now_ms()
        out = [commit.vote_sign_bytes(CHAIN_ID, i) for i in range(n_vals)]
        return _now_ms() - t, out

    def run_template():
        t = _now_ms()
        out = commit.sign_bytes_rows(CHAIN_ID)
        return _now_ms() - t, out

    legacy_ms = min(run_legacy()[0] for _ in range(3))
    encoder_ms = min(run_encoder()[0] for _ in range(3))
    template_ms = min(run_template()[0] for _ in range(3))
    a, b, c = run_legacy()[1], run_encoder()[1], run_template()[1]
    assert a == b == c, "packing paths diverged"
    speedup = legacy_ms / template_ms if template_ms else float("inf")
    return {
        "metric": "cfg7 pack-only: template rows vs per-vote sign-bytes",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup, 2),
        "extra": {
            "rows": n_vals,
            "legacy_per_vote_ms": round(legacy_ms, 2),
            "encoder_splice_ms": round(encoder_ms, 2),
            "template_rows_ms": round(template_ms, 2),
            "encoder_vs_template": round(encoder_ms / template_ms, 2)
            if template_ms else None,
            # the r05 suspect-#1 exoneration row: the per-flush cost of
            # the flush ledger + disabled trace hooks, in microseconds
            "disabled_flush_path": disabled_flush_bookkeeping_us(),
            # the ISSUE-13 sibling: the always-on height ledger's
            # per-step-transition cost (budget < 10 us, tracing off)
            "height_ledger_path": height_ledger_bookkeeping_us(),
            "note": "host-only; same bytes asserted across all three "
                    "paths (the zero-copy hot path invariant)",
        },
    }


def cfg8_multichip_smoke(n_sigs=64):
    """#8: small-scale multichip smoke — the sharded fused verify+tally
    step over every local device, sized to finish well under the
    harness timeout (the round-5 MULTICHIP run was killed at rc=124).
    Also asserts the mesh step builders are memoized (a second build
    must HIT the step cache, not re-trace — the regression that caused
    the timeout)."""
    import jax

    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.parallel import mesh as pm

    keys = [PrivKey.generate((600 + i).to_bytes(4, "big") + b"\x55" * 28)
            for i in range(n_sigs)]
    pubs = [kq.pub_key().data for kq in keys]
    msgs = [b"multichip-smoke-%d" % i for i in range(n_sigs)]
    sigs = [kq.sign(m) for kq, m in zip(keys, msgs)]
    n_dev = len(jax.devices())
    pad = max(64, n_dev)
    pb = ek.pack_batch(pubs, msgs, sigs, pad_to=pad)
    powers = np.full((n_sigs,), 1000, np.int64)
    power5 = np.zeros((pb.padded, ek.POWER_LIMBS), np.int32)
    power5[:n_sigs] = ek.power_limbs(powers)
    counted = np.zeros((pb.padded,), np.bool_)
    counted[:n_sigs] = True
    cids = np.zeros((pb.padded,), np.int32)
    thresh = ek.threshold_limbs(int(powers.sum()) * 2 // 3)

    mesh = pm.make_mesh()
    t = _now_ms()
    step = pm.sharded_verify_tally(mesh, n_commits=1)
    pb2, args = pm.shard_batch_arrays(mesh, pb, power5, counted, cids)
    valid, tally, quorum = jax.block_until_ready(step(*args, thresh))
    first_ms = _now_ms() - t
    assert np.asarray(valid)[:n_sigs].all() and bool(np.asarray(quorum)[0])
    before = pm.cache_stats()
    assert pm.sharded_verify_tally(mesh, n_commits=1) is step
    after = pm.cache_stats()
    assert after["hits"] > before["hits"], "mesh step cache not hit"
    t = _now_ms()
    jax.block_until_ready(step(*args, thresh))
    warm_ms = _now_ms() - t
    return {
        "metric": "cfg8 multichip smoke sharded verify+tally",
        "value": round(warm_ms, 2),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {
            "devices": n_dev,
            "sigs": n_sigs,
            "first_call_ms": round(first_ms, 1),
            "mesh_cache": pm.cache_stats(),
            "note": "builders memoized per (mesh, n_commits); the "
                    "expensive programs are shared across tally widths",
        },
    }


def cfg9_sustained(rate=120.0, duration=45.0, n_nodes=4):
    """#9: sustained open-loop throughput — the ROADMAP item-5 metric.

    An in-process LocalNetwork commits blocks while node 0 eats an
    open-loop signed-tx flood through broadcast_tx (admission control +
    sigtx verification on the BULK lane of a running verify plane).
    Open-loop (tools/loadtime discipline): injections fire at fixed
    target times regardless of response latency, so overload shows up
    as queueing delay and explicit OVERLOADED verdicts instead of the
    generator politely backing off. Reports accepted tx/s + commits/s
    over the window and the per-lane submit-to-result p99s — the
    numbers the chaos-soak test bounds (zero CONSENSUS sheds, vote p99
    within 2x no-flood) are REPORTED here so --baseline can watch the
    sustained story drift release-over-release."""
    from tools.loadtime import run_inprocess

    rep = run_inprocess(rate, duration, n_nodes=n_nodes, signed=True,
                        plane=True)
    lane_waits = (rep.get("plane") or {}).get("lane_waits", {})
    sheds = (rep.get("plane") or {}).get("sheds", {})
    cons = lane_waits.get("consensus", {})
    bulk = lane_waits.get("bulk", {})
    return {
        "metric": "cfg9 sustained open-loop throughput",
        "value": rep["accepted_tx_per_s"],
        "unit": "tx/s",
        "vs_baseline": None,
        "extra": {
            "nodes": n_nodes,
            "offered_tx_per_s": rep["offered_tx_per_s"],
            "duration_s": rep["wall_s"],
            "commits": rep["commits"],
            "commits_per_s": rep["commits_per_s"],
            "accepted": rep["accepted"],
            "overloaded": rep["overloaded"],
            "rejected_other": rep["rejected_other"],
            "late_injections": rep["late_injections"],
            "checktx_p50_ms": rep["checktx_latency"].get("p50_ms"),
            "checktx_p99_ms": rep["checktx_latency"].get("p99_ms"),
            "vote_submit_p99_ms": cons.get("p99_ms"),
            "bulk_submit_p99_ms": bulk.get("p99_ms"),
            "consensus_sheds": sheds.get("consensus"),
            "bulk_sheds": sheds.get("bulk"),
            "admission": rep.get("admission"),
            # per-height commit-latency attribution (height ledger ->
            # tools/height_report): the sustained-load commit p50/p99
            # are first-class baseline numbers now
            "commit_p50_ms": rep.get("commit_p50_ms"),
            "commit_p99_ms": rep.get("commit_p99_ms"),
            "height_stage_table": rep.get("height_stage_table"),
            "height_dump": rep.get("height_dump"),
            "note": "open-loop signed flood vs a live committing net; "
                    "QoS invariants asserted in tests/test_soak.py",
        },
    }


def _make_light_chain(n_heights, n_vals, seed=9100):
    """Deterministic ed25519 light-block chain (stable valset) for the
    gateway benches: {height: LightBlock} + the Provider over it."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.light import client as lc
    from cometbft_tpu.light import verifier as lv
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import Header
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    T0 = 1_700_000_000
    privs = [
        PrivKey.generate((seed + i).to_bytes(4, "big") + b"\x55" * 28)
        for i in range(n_vals)
    ]
    vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    blocks = {}
    prev_bid = BlockID()
    for h in range(1, n_heights + 1):
        header = Header(
            chain_id=CHAIN_ID, height=h, time=Timestamp(T0 + h, 0),
            last_block_id=prev_bid, validators_hash=vs.hash(),
            next_validators_hash=vs.hash(),
            proposer_address=vs.validators[0].address,
            app_hash=b"\x01" * 32,
        )
        bid = BlockID(header.hash(), PartSetHeader(1, header.hash()))
        sigs = []
        for v in vs.validators:
            ts = Timestamp(T0 + h, 42)
            sb = canonical.canonical_vote_bytes(
                CHAIN_ID, canonical.PRECOMMIT_TYPE, h, 0, bid, ts
            )
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                                  by_addr[v.address].sign(sb)))
        blocks[h] = lv.LightBlock(
            lv.SignedHeader(header, Commit(h, 0, bid, sigs)), vs
        )
        prev_bid = bid
    provider = lc.Provider(CHAIN_ID, lambda h: blocks.get(h))
    return blocks, provider, (T0 + n_heights + 100)


def _gateway_run(blocks, provider, now_s, n_clients, targets_of,
                 use_gateway, ledger_cap=8192):
    """Drive n_clients worth of light-client syncs, with or without
    the gateway, against a FRESH host-path verify plane — and read the
    plane's flush ledger for the submission count (the acceptance
    metric: coalescing must be visible in ledger rows, not inferred).

    use_gateway=False is the uncoalesced baseline: every client owns a
    private light.Client + store (what N independent light clients do
    today). use_gateway=True routes everyone through ONE LightGateway
    (coalescer + shared store + LRU)."""
    import threading

    from cometbft_tpu.light import client as lc
    from cometbft_tpu.lightgate import LightGateway, gateway_batch_fn
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane
    from cometbft_tpu.verifyplane.plane import FlushLedger

    now = Timestamp(now_s, 0)
    plane = VerifyPlane(window_ms=0.5, use_device=False)
    plane.ledger = FlushLedger(capacity=ledger_cap)
    plane.start()
    set_global_plane(plane)
    gw = None
    if use_gateway:
        gw = LightGateway(CHAIN_ID, provider, cache_size=1024)
        gw.client.trust_light_block(blocks[1])
        gw.start(register=False)
    lats, errs = [], []
    lock = threading.Lock()

    def worker(k):
        mine = []
        try:
            if use_gateway:
                for t in targets_of(k):
                    t0 = _now_ms()
                    v = gw.verify(1, t, now=now)
                    mine.append(_now_ms() - t0)
                    assert v["status"] == "verified"
            else:
                c = lc.Client(CHAIN_ID, provider, trusting_period=1e6,
                              batch_fn=gateway_batch_fn())
                c.trust_light_block(blocks[1])
                for t in targets_of(k):
                    t0 = _now_ms()
                    c.verify_light_block_at_height(t, now=now)
                    mine.append(_now_ms() - t0)
        except Exception as e:  # noqa: BLE001 - recorded below
            with lock:
                errs.append(repr(e))
        with lock:
            lats.extend(mine)

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(n_clients)]
    t0 = _now_ms()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = _now_ms() - t0
    set_global_plane(None)
    plane.stop()
    assert not errs, errs[:3]
    recs = plane.dump_flushes()["flushes"]
    subs = sum(r["subs"] for r in recs)
    g_rows = sum(r["g_rows"] for r in recs)
    out = {"wall_ms": wall, "lats": lats, "plane_subs": subs,
           "gateway_rows": g_rows, "flushes": len(recs)}
    if gw is not None:
        out["gw_stats"] = gw.stats()
    return out


def cfg10_gateway(n_clients=32, n_heights=48, n_vals=8):
    """#10: light-client gateway — N concurrent clients, coalesced
    skipping verification (ROADMAP item 3; ISSUE 8 acceptance).

    Each client syncs a mix of SHARED targets (the popular heights a
    wallet fleet all jumps to) and a personal one (disjoint spread).
    The uncoalesced baseline is N private light clients doing the same
    work — today's serving story. The acceptance bar: with the gateway,
    verify-plane submissions (counted from the always-on flush ledger,
    not inferred) must be <= 0.5x the uncoalesced count."""
    blocks, provider, now_s = _make_light_chain(n_heights, n_vals)
    shared = [n_heights // 3, 2 * n_heights // 3, n_heights]

    def targets_of(k):
        return sorted(set(shared + [2 + (k % 8)]))

    base = _gateway_run(blocks, provider, now_s, n_clients, targets_of,
                        use_gateway=False)
    gwr = _gateway_run(blocks, provider, now_s, n_clients, targets_of,
                       use_gateway=True)
    n_requests = len(gwr["lats"])
    assert gwr["plane_subs"] <= 0.5 * base["plane_subs"], (
        f"coalescing failed: gateway plane submissions "
        f"{gwr['plane_subs']} > 0.5x uncoalesced {base['plane_subs']}"
    )
    gws = gwr["gw_stats"]
    hdr_per_s = n_requests / (gwr["wall_ms"] / 1000)
    return {
        "metric": "cfg10 light-client gateway coalesced serving",
        "value": round(hdr_per_s),
        "unit": "headers/sec",
        "vs_baseline": round(base["wall_ms"] / gwr["wall_ms"], 2),
        "extra": {
            "clients": n_clients,
            "requests": n_requests,
            "client_p50_ms": round(p50(gwr["lats"]), 2),
            "client_p99_ms": round(
                float(np.percentile(gwr["lats"], 99)), 2),
            "uncoalesced_p50_ms": round(p50(base["lats"]), 2),
            "plane_subs_gateway": gwr["plane_subs"],
            "plane_subs_uncoalesced": base["plane_subs"],
            "coalesce_sub_ratio": round(
                gwr["plane_subs"] / max(1, base["plane_subs"]), 3),
            "verifies": gws["verifies"],
            "coalesced_requests": gws["coalesced"],
            "verifies_coalesced_ratio": round(
                gws["verifies"] / max(1, gws["requests"]), 3),
            "cache": {k: gws["cache"][k]
                      for k in ("hits", "misses", "size")},
            "gateway_lane_rows": gwr["gateway_rows"],
            "uncoalesced_wall_ms": round(base["wall_ms"], 1),
            "gateway_wall_ms": round(gwr["wall_ms"], 1),
            "note": "uncoalesced = N private light clients, same "
                    "targets, same host plane; submissions counted "
                    "from the flush ledger",
        },
    }


def cfg11_sharded_tally(n_vals=10_000, target_big=100_000):
    """#11: multichip sharded fused flush vs single-device (ISSUE 10).

    One valset, one commit group, the verify plane's fused layout at
    two row scales: a ~10k-row flush (where the single-device cached
    kernel is the baseline) and the biggest cross-chip flush the mesh
    supports up to ~100k rows (past 65536 a single device CANNOT run
    it at all — the sharded plane is the only path). Rows reuse each
    validator's one real signature across strides (verification cost
    is identical; fixture generation stays at one sign per validator).
    Asserts sharded verdicts/tally/quorum bit-match the single-device
    pass at the small shape, and that the mesh step + sharded table
    memos HIT on repeat dispatch (no steady-state re-trace/re-upload).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.parallel import mesh as pm
    from cometbft_tpu.verifyplane.fused import (
        effective_mesh,
        shard_positions,
    )

    n_local = len(jax.devices())
    keys = [PrivKey.generate((8100 + i).to_bytes(4, "big") + b"\x66" * 28)
            for i in range(n_vals)]
    pubs = [kq.pub_key().data for kq in keys]
    msgs = [b"cfg11-%d" % i for i in range(n_vals)]
    sigs = [kq.sign(m) for kq, m in zip(keys, msgs)]
    powers = np.full((n_vals,), 100, np.int64)
    thresh = ek.threshold_limbs(int(powers.sum()) * 2 // 3)

    # clamp like plan_fused does: empty shards would verify padding
    mesh, n_dev, m_s = effective_mesh(pm.make_mesh(), n_vals)
    if mesh is None:
        # 1-chip host / small valset: the degenerate 1-mesh still
        # measures the sharded program so --baseline has a row
        mesh = pm.make_mesh(jax.devices()[:1])
        n_dev, m_s = 1, ec.shard_stride(n_vals, 1)
    b_stride = n_dev * m_s          # rows per stride, all used devices
    max_strides = 65536 // m_s      # per-device kernel budget

    def build_rows(n_strides):
        """Position-ordered packed rows for the sharded fused layout
        (stride 0 counted; strides > 0 duplicate the signatures)."""
        b_loc = n_strides * m_s
        B = n_dev * b_loc
        p_pubs, p_msgs, p_sigs = [], [], []
        counted = np.zeros((B,), np.bool_)
        for p in range(B):
            d, q = divmod(p, b_loc)
            s, vloc = divmod(q, m_s)
            v = d * m_s + vloc
            if v < n_vals:
                p_pubs.append(pubs[v])
                p_msgs.append(msgs[v])
                p_sigs.append(sigs[v])
                counted[p] = s == 0
            else:
                p_pubs.append(b"")
                p_msgs.append(b"")
                p_sigs.append(b"")
        pb = ek.pack_batch(p_pubs, p_msgs, p_sigs, pad_to=B)
        rows = ec.pack_rows_cached(pb, counted,
                                   np.zeros((B,), np.int32))
        return rows, B, n_strides * n_vals  # real (non-padding) rows

    t = _now_ms()
    table_sh = ec.sharded_table_for_pubs(tuple(pubs),
                                         tuple(int(p) for p in powers),
                                         mesh)
    step = pm.sharded_fused_verify(mesh, 1)
    shard_table_ms = _now_ms() - t
    axis = mesh.axis_names[0]
    rows_sh = NamedSharding(mesh, P(None, axis))
    repl = NamedSharding(mesh, P(None, None))
    thresh_d = jax.device_put(thresh, repl)
    base_d = ec.base60_repl(mesh)

    def sharded_steady(rows, reps=STEADY_K):
        out = step(jax.device_put(rows, rows_sh), table_sh.tab,
                   table_sh.ok, table_sh.power5, base_d, thresh_d)
        assert bool(np.asarray(out[2])[0]), "sharded quorum missed"
        best = float("inf")
        for _ in range(3):
            t = _now_ms()
            for _ in range(reps):
                out = step(jax.device_put(rows, rows_sh), table_sh.tab,
                           table_sh.ok, table_sh.power5, base_d,
                           thresh_d)
            assert bool(np.asarray(out[2])[0])
            best = min(best, (_now_ms() - t) / reps)
        return best, out

    # small shape: ~n_vals rows, single-device comparable
    rows_small, b_small, real_small = build_rows(1)
    small_ms, out_small = sharded_steady(rows_small)

    # single-device baseline + bit-identity at the same scale —
    # impossible past the one-chip table budget (table_pad RAISES for
    # n > 65536; guard on n_vals, the sharded path is the only one)
    single_ms = None
    bit_identical = None
    if n_vals <= 65536:
        m_single = ec.table_pad(n_vals)
        table_1 = ec.table_for_pubs(tuple(pubs),
                                    tuple(int(p) for p in powers))
        pb1 = ek.pack_batch(pubs, msgs, sigs, pad_to=m_single)
        c1 = np.zeros((m_single,), np.bool_)
        c1[:n_vals] = True
        rows_1 = ec.pack_rows_cached(pb1, c1,
                                     np.zeros((m_single,), np.int32),
                                     thresh)
        out1 = ec.verify_tally_rows_cached(jax.device_put(rows_1),
                                           table_1, 1)
        best = float("inf")
        for _ in range(3):
            t = _now_ms()
            for _ in range(STEADY_K):
                out1 = ec.verify_tally_rows_cached(
                    jax.device_put(rows_1), table_1, 1)
            best = min(best, (_now_ms() - t) / STEADY_K)
        single_ms = best
        # map both layouts back to (validator) verdicts and compare
        v_sh = np.asarray(out_small[0])
        v_1 = np.asarray(out1[0])
        vv = np.arange(n_vals)
        pos_sh = shard_positions(vv, np.zeros(n_vals, np.int64), m_s, 1)
        bit_identical = bool(
            np.array_equal(v_sh[pos_sh], v_1[vv])
            and np.array_equal(np.asarray(out_small[1]),
                               np.asarray(out1[1]))
            and np.array_equal(np.asarray(out_small[2]),
                               np.asarray(out1[2])))
        assert bit_identical, "sharded != single-device at 10k rows"

    # big shape: as close to target_big as the mesh allows
    n_strides_big = max(1, min(max_strides,
                               -(-target_big // b_stride)))
    rows_big, b_big, real_big = build_rows(n_strides_big)
    big_ms, _ = sharded_steady(rows_big, reps=max(4, STEADY_K // 2))

    # steady state must hit the memos, not re-trace/re-upload
    mesh_before = pm.cache_stats()
    assert pm.sharded_fused_verify(mesh, 1) is step
    assert pm.cache_stats()["hits"] > mesh_before["hits"]
    tbl_before = ec.table_cache_stats()
    ec.sharded_table_for_pubs(tuple(pubs),
                              tuple(int(p) for p in powers), mesh)
    tbl_after = ec.table_cache_stats()
    assert tbl_after["shard_hits"] > tbl_before["shard_hits"]

    sps_big = round(real_big / (big_ms / 1000))
    return {
        "metric": "cfg11 sharded cross-chip fused verify+tally",
        "value": sps_big,
        "unit": "sigs/sec",
        "vs_baseline": (round(single_ms / small_ms, 2)
                        if single_ms else None),
        "extra": {
            "devices": n_local,
            "devices_used": n_dev,
            "shard_stride": m_s,
            "rows_small": real_small,
            "rows_big": real_big,
            "slots_small": b_small,
            "slots_big": b_big,
            "rows_big_target": target_big,
            # rows-x-cost utilization (the device observatory's util
            # model): live rows over padded slots swept per pass —
            # how much of the mesh the flush actually used
            "util_small": round(real_small / b_small, 4),
            "util_big": round(real_big / b_big, 4),
            "sharded_small_ms": round(small_ms, 2),
            "sharded_big_ms": round(big_ms, 2),
            "single_device_small_ms": (round(single_ms, 2)
                                       if single_ms else None),
            "bit_identical_small": bit_identical,
            "shard_table_build_ms": round(shard_table_ms, 1),
            "mesh_cache": pm.cache_stats(),
            "shard_table_cache": {
                k: v for k, v in ec.table_cache_stats().items()
                if k.startswith("shard")},
            "note": "one cross-chip pass per flush: per-shard "
                    "device-resident tables, psum tally, quorum on "
                    "device; rows > 65536 have NO single-device path",
        },
    }


def cfg12_pipelined(n_vals=4096, n_flushes=24):
    """#12: pipelined mesh halves (ISSUE 11) — deck-on vs deck-off
    sustained flush throughput through the REAL plane dispatcher.

    Streams fused valset-backed flushes (one submission = one flush,
    max_batch pinned to the flush size) through three plane arms:
    pipeline_flights=1 (the PR-9 single-flight baseline),
    pipeline_flights=2 at half-mesh size (alternating flushes fly
    DISJOINT halves; pack+dispatch of k+1 overlaps flight k), and
    pipeline_flights=2 with half_mesh_rows=1 (every flush forced to
    the full mesh — the drain-the-deck policy arm, bounding what the
    halves buy). Verdicts must match across arms; on a >=4-device
    host the deck arm's ledger must show genuinely concurrent flights
    (deck airborne_max >= 1). On a host without halves the deck
    equals the baseline; the row still records."""
    import jax

    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane import QuorumGroup, VerifyPlane

    n_local = len(jax.devices())
    keys = [PrivKey.generate((9400 + i).to_bytes(4, "big") + b"\x55" * 28)
            for i in range(n_vals)]
    pubs_t = tuple(k.pub_key().data for k in keys)
    powers_t = tuple(100 for _ in range(n_vals))
    msgs = [b"cfg12-%d" % i for i in range(n_vals)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    rows_all = [(k.pub_key(), m, s) for k, m, s in zip(keys, msgs, sigs)]
    vidx_all = tuple(range(n_vals))

    def run(flights, half_rows=0, timed_flushes=n_flushes):
        plane = VerifyPlane(
            window_ms=0.5, max_batch=n_vals,
            max_queue=n_vals * (timed_flushes + 2),
            use_device=True,
            mesh_devices=0, mesh_min_rows=1, pipeline_flights=flights,
            half_mesh_rows=half_rows)
        plane.start()
        try:
            def burst(k):
                groups = [QuorumGroup(10 ** 15, valset_pubs=pubs_t,
                                      valset_powers=powers_t)
                          for _ in range(k)]
                futs = [plane.submit_many(rows_all, group=g,
                                          vidx=vidx_all)
                        for g in groups]
                return [f.result(300.0) for f in futs]

            burst(2)  # warm: compile the mesh programs off the clock
            t = _now_ms()
            verd = burst(timed_flushes)
            wall = _now_ms() - t
        finally:
            plane.stop()
        summary = plane.dump_flushes()["summary"]
        return wall, verd, summary, plane.stats()

    wall_1, verd_1, sum_1, st_1 = run(1)
    wall_deck, verd_deck, sum_deck, st_deck = run(2)
    wall_full, verd_full, sum_full, _ = run(2, half_rows=1)
    assert verd_deck == verd_1, "deck arm verdicts diverged"
    assert verd_full == verd_1, "full-mesh arm verdicts diverged"
    halves = st_deck["halves"]
    if halves == 2:
        assert sum_deck["deck"]["airborne_max"] >= 1, (
            "deck never flew two flights on a half-capable mesh",
            sum_deck)
    fps = n_flushes / (wall_deck / 1000) if wall_deck else 0.0
    return {
        "metric": "cfg12 pipelined mesh halves sustained flushes",
        "value": round(n_flushes * n_vals / (wall_deck / 1000))
        if wall_deck else None,
        "unit": "sigs/sec",
        "vs_baseline": round(wall_1 / wall_deck, 2) if wall_deck else None,
        "extra": {
            "devices": n_local,
            "halves": halves,
            "flushes": n_flushes,
            "rows_per_flush": n_vals,
            "flushes_per_sec_deck": round(fps, 2),
            "wall_single_ms": round(wall_1, 1),
            "wall_deck_ms": round(wall_deck, 1),
            "wall_full_mesh_ms": round(wall_full, 1),
            "deck_airborne_max": sum_deck["deck"]["airborne_max"],
            "deck_overlapped_flushes":
                sum_deck["deck"]["overlapped_flushes"],
            "deck_peak": st_deck["deck_peak"],
            "single_airborne_max": sum_1["deck"]["airborne_max"],
            "full_mesh_airborne_max": sum_full["deck"]["airborne_max"],
            # the device observatory's per-flush split over the deck
            # arm: utilization (half-mesh flushes should pack denser
            # than forced-full-mesh ones) + on-device time estimates
            "util_est": sum_deck["device"]["util"],
            "util_full_mesh": sum_full["device"]["util"],
            "dev_ms_est": sum_deck["device"]["dev_ms"],
            "comp_ms_timed": sum_deck["device"]["comp_ms"],
            "note": "deck-on vs deck-off through the real dispatcher; "
                    "full-mesh arm exercises the drain-first policy",
        },
    }


def _churn_height_probe(n_nodes=3, rotate_at=3, target=8):
    """A LIVE consensus probe for cfg13: a small LocalNetwork commits
    through ONE real validator rotation (kvstore ``val:`` tx -> ABCI
    validator update -> update_with_change_set at H+2), and the
    always-on height ledger attributes per-height commit latency
    before vs after the rotation — plus the late/absent columns (the
    added validator never votes, so every post-rotation commit carries
    an absent precommit the ledger must attribute). Host-only, no jax,
    a few seconds; the device-side table-build numbers stay in the
    main cfg13 arms."""
    import base64

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.consensus.ticker import TimeoutParams
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.node.node import LocalNetwork, Node
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.state.state import State
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from tools import height_report

    fast = TimeoutParams(propose=0.4, propose_delta=0.1, prevote=0.2,
                         prevote_delta=0.1, precommit=0.2,
                         precommit_delta=0.1, commit=0.05)
    privs = [PrivKey.generate(bytes([40 + i]) * 32)
             for i in range(n_nodes)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis("cfg13-probe-chain", vals)
    net = LocalNetwork()
    nodes = []
    for i, priv in enumerate(privs):
        node = Node(KVStoreApplication(), state.copy(),
                    privval=FilePV(priv), broadcast=net.broadcaster(i),
                    timeouts=fast)
        net.add(node)
        nodes.append(node)
    extra_pub = PrivKey.generate(b"\x77" * 32).pub_key().data
    tx = b"val:" + base64.b64encode(extra_pub) + b"!5"
    try:
        for n in nodes:
            n.start()
        assert nodes[0].consensus.wait_for_height(rotate_at, 30.0)
        # LocalNetwork mempools don't gossip: every node carries the
        # rotation so whichever proposes next includes it
        for n in nodes:
            n.mempool.check_tx(tx)
        assert nodes[0].consensus.wait_for_height(target, 30.0), \
            "probe chain stalled after the rotation"
    finally:
        for n in nodes:
            n.stop()
    dump = nodes[0].consensus.height_ledger.dump()
    rep = height_report.stage_report(dump)
    recs = dump["heights"]
    rot_h = next((r["height"] for r in recs
                  if len(r["absent_bitmap"]) > 0), None)
    pre = [r["apply_ms"] for r in recs
           if r["via"] == "consensus" and r["apply_ms"] > 0
           and (rot_h is None or r["height"] < rot_h)]
    post = [r["apply_ms"] for r in recs
            if rot_h is not None and r["height"] >= rot_h]
    assert rot_h is not None, \
        "rotation never landed — no absent precommit attributed"
    dump["heights"] = recs[-32:]  # trim before embedding
    return {
        "rotation_height": rot_h,
        "pre_rotation_commit_p50_ms": round(p50(pre), 3) if pre else None,
        "post_rotation_commit_ms": [round(x, 3) for x in post[:4]],
        "commit_p50_ms": rep["commit_p50_ms"],
        "commit_p99_ms": rep["commit_p99_ms"],
        "absent_votes": rep["absent_votes"],
        "height_stage_table": rep["stages"],
        "height_dump": dump,
    }


def cfg13_churn(n_vals=10_000, churn=0.01):
    """#13: epoch churn (ISSUE 12) — first-commit-after-rotation
    latency, cold vs warmed.

    Epoch A's 10k-validator table is resident; the committee then
    rotates churn*n_vals members (past MAX_INCREMENTAL, so the cold
    path pays a FULL table rebuild — the worst post-rotation stall).
    The cold arm measures the first cached-path commit verify against
    the unseen epoch-B valset (build + verify inline, exactly what a
    node without the warmer pays); the warmed arm lets the next-epoch
    TableWarmer build epoch C's table in the background first, then
    measures the same first verify as a cache hit. value = the cold
    stall; the warmed/cold ratio is the warmer's win."""
    from cometbft_tpu.ops import table_cache as tcache
    from cometbft_tpu.verifyplane.warmer import TableWarmer

    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.ops import ed25519_cached as ec

    # the base committee derives ONCE; each epoch copies it and
    # re-elects only its churned slots (10k key derivations are the
    # fixture's dominant cost — 3 full regenerations tripled it)
    base_privs = [
        PrivKey.generate((13_000 + i).to_bytes(4, "big") + b"\x31" * 28)
        for i in range(n_vals)
    ]

    def epoch_keys(epoch: int):
        """Epoch e's keys: the base committee with `churn` of the
        slots re-elected per epoch (distinct per epoch)."""
        k = max(1, int(n_vals * churn))
        privs = list(base_privs)
        if epoch:
            for j in range(k):
                slot = (epoch * 37 + j * 97) % n_vals
                privs[slot] = PrivKey.generate(
                    (13_000 + epoch).to_bytes(4, "big")
                    + slot.to_bytes(4, "big") + b"\x32" * 24)
        return privs

    def arm(privs):
        pubs = tuple(p.pub_key().data for p in privs)
        powers = tuple(100 for _ in privs)
        msgs = [b"cfg13-%d" % i for i in range(len(privs))]
        sigs = [p.sign(m) for p, m in zip(privs, msgs)]
        return pubs, powers, msgs, sigs

    # epoch A: warm the kernel + epoch-A table off the clock (every
    # epoch pads to the same bucket, so no compile rides the arms)
    pubs_a, powers, msgs, sigs_a = arm(epoch_keys(0))
    table_a = ec.table_for_pubs(pubs_a, powers)
    valid = ec.verify_batch_cached(pubs_a, msgs, sigs_a, table=table_a)
    assert bool(valid.all()), "epoch-A fixture failed to verify"

    # COLD: epoch B is unseen — the first verify pays the table build
    privs_b = epoch_keys(1)
    pubs_b, _, _, sigs_b = arm(privs_b)
    assert sum(a != b for a, b in zip(pubs_a, pubs_b)) \
        > ec.MAX_INCREMENTAL, "churn under the incremental budget"
    t = _now_ms()
    valid = ec.verify_batch_cached(pubs_b, msgs, sigs_b)
    cold_ms = _now_ms() - t
    assert bool(valid.all())

    # WARMED: the warmer pre-builds epoch C; the first verify hits
    hits0 = tcache.STATS["warmed_hits"]
    privs_c = epoch_keys(2)
    pubs_c, _, _, sigs_c = arm(privs_c)
    warmer = TableWarmer(use_device=True)
    warmer.start()
    try:
        warmer.request(pubs_c, powers)
        assert warmer.wait_idle(300.0), "warm build never finished"
    finally:
        warmer.stop()
    t = _now_ms()
    table_c, warm = ec.table_for_pubs_info(pubs_c, powers)
    valid = ec.verify_batch_cached(pubs_c, msgs, sigs_c, table=table_c)
    warmed_ms = _now_ms() - t
    assert bool(valid.all())
    assert warm, "warmed lookup was not a cache hit"
    assert warmed_ms < cold_ms, (warmed_ms, cold_ms)
    hits = tcache.STATS["warmed_hits"] - hits0
    return {
        "metric": "cfg13 first-commit-after-rotation cold stall",
        "value": round(cold_ms, 1),
        "unit": "ms",
        "vs_baseline": round(cold_ms / warmed_ms, 2) if warmed_ms else None,
        "extra": {
            "vals": n_vals,
            "churned": max(1, int(n_vals * churn)),
            "warmed_ms": round(warmed_ms, 1),
            "warmed_hits": hits,
            "warmer_build_ms": warmer.last_build_ms,
            "cache": {k: v for k, v in ec.table_cache_stats().items()
                      if k.startswith("evictions") or k == "warmed_hits"},
            "resident_bytes": ec.table_cache_resident_bytes(),
            **_cfg13_probe_extra(),
            "note": "cold = first cached-path verify after rotation "
                    "(full table rebuild inline); warmed = same verify "
                    "after the background warmer built the table",
        },
    }


def _cfg13_probe_extra() -> dict:
    """The live-consensus churn probe, fault-isolated: cfg13's table
    numbers must survive a probe failure (the probe adds the
    commit-latency columns, it is not the metric)."""
    try:
        probe = _churn_height_probe()
        return {"height_probe": probe,
                "commit_p50_ms": probe["commit_p50_ms"],
                "commit_p99_ms": probe["commit_p99_ms"]}
    except Exception as e:  # noqa: BLE001 - report, don't fail cfg13
        return {"height_probe_error": repr(e)[:200]}


def headline_10k():
    """The driver metric: 10k-validator VerifyCommitLight fused p50."""
    vs, commit, bid = make_ed_commit(10_000)
    per_sig = cpu_ed25519_per_sig_ms(vs, commit)
    cpu_ms = per_sig * 10_000
    raw, steady, pack_ms, tbl_ms, resident, overlap = _device_commit_bench(
        vs, commit, bid, 12345
    )
    return cpu_ms, raw, steady, pack_ms, tbl_ms, resident, overlap


# --------------------------------------------------------------------------
# --smoke: tier-1-safe miniatures. Tiny shapes, HOST paths only (no jax
# import, no accelerator), seconds not minutes — enough to
# catch bench.py rot (broken fixtures, drifted APIs, dead result shapes)
# in CI without pretending to measure device performance. Metric names
# carry a "_smoke" suffix so a smoke run can never be compared against
# a real BENCH_rNN baseline by accident.
# --------------------------------------------------------------------------


def smoke_commit_verify(n_vals=8):
    """Product-path VerifyCommitLight through the host verifier."""
    from cometbft_tpu.types import validation as tv

    vs, commit, bid = make_ed_commit(n_vals, seed=11)
    tv.verify_commit_light(CHAIN_ID, vs, bid, 12345, commit)  # warm
    best = float("inf")
    for _ in range(3):
        t = _now_ms()
        tv.verify_commit_light(CHAIN_ID, vs, bid, 12345, commit)
        best = min(best, _now_ms() - t)
    return {
        "metric": "cfg2_smoke host VerifyCommitLight",
        "value": round(best, 3),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {"vals": n_vals, "path": "host batch (no device)"},
    }


def smoke_pack_rows(n_vals=64):
    """Template row packing byte-equality at tiny scale (cfg7's core)."""
    vs, commit, bid = make_ed_commit(n_vals, seed=12)
    t = _now_ms()
    rows = commit.sign_bytes_rows(CHAIN_ID)
    pack_ms = _now_ms() - t
    legacy = [commit.vote_sign_bytes(CHAIN_ID, i) for i in range(n_vals)]
    assert rows == legacy, "template rows diverged from encoder"
    return {
        "metric": "cfg4_smoke template pack rows",
        "value": round(pack_ms, 3),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {"rows": n_vals, "byte_equality": True,
                  "disabled_flush_path":
                      disabled_flush_bookkeeping_us(k=2000),
                  "height_ledger_path":
                      height_ledger_bookkeeping_us(k=2000)},
    }


def smoke_vote_plane(n_sigs=32):
    """A host-path verify plane end to end: coalescing dispatcher,
    futures, and the always-on flush ledger."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane import VerifyPlane

    keys = [PrivKey.generate((7000 + i).to_bytes(4, "big") + b"\x44" * 28)
            for i in range(n_sigs)]
    subs = [(k.pub_key(), b"smoke-%d" % i, k.sign(b"smoke-%d" % i))
            for i, k in enumerate(keys)]
    plane = VerifyPlane(window_ms=0.2, use_device=False)
    plane.start()
    try:
        t = _now_ms()
        futs = [plane.submit(p, m, s) for p, m, s in subs]
        verdicts = [f.result(10) for f in futs]
        wall_ms = _now_ms() - t
    finally:
        plane.stop()
    # result() yields a per-row verdict tuple, so check the rows — a
    # bare truthiness test passes even on (False,)
    assert all(all(v) for v in verdicts), "valid sigs rejected"
    # the ledger record lands after the futures resolve; stop() joins
    # the dispatcher, so only now is the last flush guaranteed visible
    led = plane.dump_flushes()["summary"]
    assert led["flushes"] > 0, "flush ledger recorded nothing"
    return {
        "metric": "cfg6_smoke host verify plane",
        "value": round(n_sigs / (wall_ms / 1000)),
        "unit": "sigs/sec",
        "vs_baseline": None,
        "extra": {"sigs": n_sigs, "wall_ms": round(wall_ms, 2),
                  "ledger": {"flushes": led["flushes"],
                             "rows": led["rows"],
                             "paths": led["paths"]}},
    }


def smoke_gateway(n_clients=4, n_heights=6, n_vals=3):
    """cfg10's miniature: the gateway end to end on the host plane —
    coalescer, shared store, LRU, and the ledger-counted coalescing
    assertion — at tier-1-safe scale (pure-Python crypto, no jax)."""
    blocks, provider, now_s = _make_light_chain(n_heights, n_vals,
                                                seed=9700)
    targets = [n_heights - 2, n_heights]

    def targets_of(k):
        return targets

    base = _gateway_run(blocks, provider, now_s, n_clients, targets_of,
                        use_gateway=False, ledger_cap=256)
    gwr = _gateway_run(blocks, provider, now_s, n_clients, targets_of,
                       use_gateway=True, ledger_cap=256)
    assert gwr["plane_subs"] <= 0.5 * base["plane_subs"], (
        gwr["plane_subs"], base["plane_subs"])
    gws = gwr["gw_stats"]
    assert gws["verifies"] < gws["requests"], gws
    assert gwr["gateway_rows"] > 0, "gateway rows never rode its lane"
    n_requests = len(gwr["lats"])
    return {
        "metric": "cfg10_smoke light-client gateway",
        "value": round(n_requests / (gwr["wall_ms"] / 1000)),
        "unit": "headers/sec",
        "vs_baseline": None,
        "extra": {
            "clients": n_clients,
            "plane_subs_gateway": gwr["plane_subs"],
            "plane_subs_uncoalesced": base["plane_subs"],
            "verifies": gws["verifies"],
            "requests": gws["requests"],
            "cache_hits": gws["cache"]["hits"],
        },
    }


def smoke_sharded_layout(n_vals=300, n_strides=2):
    """cfg11's host-only miniature: the sharded fused LAYOUT math and
    the ledger's cross-chip attribution surfaces, with no jax in the
    process. shard_positions is the one home of the scatter formula
    (plan_fused and the per-shard tables both trust it), so the smoke
    brute-forces the bijection; a host-plane flush then proves the
    n_dev ledger column and shard summary the TPU-round cfg11 reads."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane import VerifyPlane
    from cometbft_tpu.verifyplane.fused import shard_positions
    from cometbft_tpu.verifyplane.plane import FlushLedger

    assert "n_dev" in FlushLedger.FIELDS
    m_s = 128  # a table_pad bucket; jax-free smoke pins it explicitly
    t = _now_ms()
    rng = np.random.RandomState(11)
    v = rng.randint(0, n_vals, size=512).astype(np.int64)
    s = rng.randint(0, n_strides, size=512).astype(np.int64)
    pos = shard_positions(v, s, m_s, n_strides)
    b_loc = n_strides * m_s
    # brute-force the layout contract: device owns v // m_s, local
    # column s*m_s + v % m_s — and distinct (v, s) never collide
    for vi, si, pi in zip(v, s, pos):
        assert pi == (vi // m_s) * b_loc + si * m_s + vi % m_s
    # injectivity: DISTINCT (v, s) pairs must never share a position
    # (a collision would silently overwrite one signature's rows)
    pairs = set(zip(v.tolist(), s.tolist()))
    by_pair = {(vi, si): pi for vi, si, pi in
               zip(v.tolist(), s.tolist(), pos.tolist())}
    assert len(set(by_pair.values())) == len(pairs)
    layout_ms = _now_ms() - t

    # ledger attribution on a host plane: single-device flushes stamp
    # n_dev=1, the shard summary exists and stays empty
    plane = VerifyPlane(window_ms=0.2, use_device=False)
    plane.start()
    try:
        kq = PrivKey.generate(b"\x13" * 32)
        fut = plane.submit(kq.pub_key(), b"cfg11-smoke",
                           kq.sign(b"cfg11-smoke"))
        assert fut.result(10) == (True,)
    finally:
        plane.stop()
    dump = plane.dump_flushes()
    recs = dump["flushes"]
    assert recs and all(r["n_dev"] == 1 for r in recs), recs
    shard = dump["summary"]["shard"]
    assert shard["flushes"] == 0 and shard["n_dev_max"] == 1
    assert plane.stats()["mesh_ndev"] == 0  # no mesh configured
    return {
        "metric": "cfg11_smoke sharded layout + ledger attribution",
        "value": round(layout_ms, 3),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {
            "positions_checked": int(len(pos)),
            "shard_summary": shard,
            "ledger_n_dev": recs[-1]["n_dev"],
        },
    }


def smoke_pipelined_deck(n_sigs=24):
    """cfg12's host-only miniature: the flight-deck plumbing with no
    jax in the process — the ledger's airborne/n_host/dev0 columns and
    deck summary, the staging-pool depth wired to pipeline_flights,
    the out-of-order landing picker, and the [verify_plane] knob path
    into a live (host) plane."""
    from cometbft_tpu.config.config import Config
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane import plane as vp

    for col in ("airborne", "n_dev", "n_host", "dev0"):
        assert col in vp.FlushLedger.FIELDS, col

    # the ready-first landing picker: a later flight whose probe says
    # ready lands FIRST (out-of-order — no head-of-line blocking);
    # with no probe (or none ready) callers fall back to FIFO
    class _F:
        def __init__(self, ready):
            self.ready = ready

    deck = [_F(lambda: False), _F(lambda: True), _F(lambda: False)]
    assert vp._ready_index(deck) == 1
    assert vp._ready_index([_F(None), _F(lambda: False)]) is None

    cfg = Config()
    cfg.verify_plane.enable = True
    cfg.verify_plane.pipeline_flights = 2
    cfg.verify_plane.half_mesh_rows = 512
    cfg.validate_basic()
    plane = cfg.verify_plane.build()
    assert plane.flights == 2 and plane.half_mesh_rows == 512
    # the multi-flight staging contract: flights+1 slots per shape so
    # pack(k+2) never lands in a buffer still pinned under flight k
    assert plane._staging.slots == 3
    plane.start()
    try:
        keys = [PrivKey.generate((9500 + i).to_bytes(4, "big")
                                 + b"\x21" * 28) for i in range(n_sigs)]
        t = _now_ms()
        futs = [plane.submit(k.pub_key(), b"deck-%d" % i,
                             k.sign(b"deck-%d" % i))
                for i, k in enumerate(keys)]
        verdicts = [f.result(10) for f in futs]
        wall_ms = _now_ms() - t
    finally:
        plane.stop()
    assert all(all(v) for v in verdicts), "valid sigs rejected"
    dump = plane.dump_flushes()
    recs = dump["flushes"]
    # host flushes are synchronous: never airborne, single host+device,
    # and the legacy overlapped bool derives from the airborne count
    assert recs and all(
        r["airborne"] == 0 and r["overlapped"] is False
        and r["n_host"] == 1 and r["dev0"] == 0 for r in recs), recs
    deck_sum = dump["summary"]["deck"]
    assert deck_sum == {"airborne_max": 0, "overlapped_flushes": 0}
    st = plane.stats()
    assert st["flights"] == 2 and st["deck_peak"] == 0
    assert st["halves"] == 0  # no mesh on a host plane
    return {
        "metric": "cfg12_smoke flight-deck plumbing",
        "value": round(wall_ms, 3),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {
            "sigs": n_sigs,
            "staging_slots": plane._staging.slots,
            "deck_summary": deck_sum,
            "ledger_cols": [c for c in ("airborne", "n_dev", "n_host",
                                        "dev0")],
        },
    }


def smoke_churn_warmer(epochs=12):
    """cfg13's host-only miniature: epoch churn through the bounded
    valset-table caches and the next-epoch warmer, with no jax in the
    process — eviction pressure holds resident bytes flat, the live
    key never evicts, the warmer's failpoint degrade leaves the cold
    path intact, and a warmed lookup is attributed (warmed_hits)."""
    import hashlib

    from cometbft_tpu.libs import failpoints as fp
    from cometbft_tpu.ops import table_cache as tcache
    from cometbft_tpu.verifyplane import plane as vp
    from cometbft_tpu.verifyplane.warmer import TableWarmer

    assert "warm" in vp.FlushLedger.FIELDS  # the ledger's churn column

    class _Tbl:
        __slots__ = ("nbytes",)

        def __init__(self):
            self.nbytes = 4096

    cache = tcache.BoundedLRU("tables", 4, size_fn=tcache.default_size)
    live = b"live"
    cache.put(live, _Tbl())
    ev0 = tcache.STATS["evictions_tables"]
    peak = 0
    t = _now_ms()
    for e in range(epochs):
        assert cache.get(live) is not None, "live table evicted"
        cache.put(b"epoch-%d" % e, _Tbl())
        peak = max(peak, cache.resident_bytes())
    churn_ms = _now_ms() - t
    evictions = tcache.STATS["evictions_tables"] - ev0
    assert evictions == epochs - 3 and peak <= 4 * 4096

    # warmer plumbing: a failed build degrades (nothing inserted), a
    # clean build lands + attributes its first hit
    built = []

    def build(pubs, powers):
        key = hashlib.sha256(b"".join(pubs)).digest()
        with tcache.LOCK:
            tcache.TABLES.put(key, _Tbl())
        tcache.note_warmed(key)
        built.append(key)

    fp.registry().arm_from_spec("warmer.build=raise*1")
    w = TableWarmer(build_fn=build, use_device=False)
    w.start()
    try:
        w.request((b"epoch-f",), None)
        assert w.wait_idle(10.0)
        assert not built and w.stats()["builds_failed"] == 1
        hits0 = tcache.STATS["warmed_hits"]
        w.request((b"epoch-w",), None)
        assert w.wait_idle(10.0)
        assert len(built) == 1
        with tcache.LOCK:
            assert tcache.TABLES.get(built[0]) is not None
        assert tcache.consume_warmed(built[0])
        assert tcache.STATS["warmed_hits"] - hits0 == 1
    finally:
        w.stop()
        fp.reset()
    return {
        "metric": "cfg13_smoke churn cache + warmer plumbing",
        "value": round(churn_ms, 3),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {
            "epochs": epochs,
            "evictions": evictions,
            "resident_bytes_peak": peak,
            "warmer": w.stats(),
        },
    }


def smoke_peer_ledger(n_msgs=512):
    """cfg14's host-only miniature: the gossip observatory end to end
    with no jax in the process — record shape over the FlushLedger
    discipline (the live scratch list becomes the drop-ring slot),
    per-channel split, vote first-seen/dup/relay routing, the
    starvation counters the peer_starvation incident watches, and the
    per-message bookkeeping budget."""
    from cometbft_tpu.p2p import peerledger

    led = peerledger.PeerLedger()
    rec = led.open_peer("smoke-peer", True)
    t = _now_ms()
    for i in range(n_msgs):
        peerledger.note_sent(rec, 0x22, 200)
        peerledger.note_recv(rec, 0x21, 100)
        peerledger.note_queue_depth(rec, i % 7)
    wall_ms = _now_ms() - t
    peerledger.note_full_drop(rec)
    peerledger.note_blocked_put(rec)
    led.note_vote_seen((1, 0, 2, 3), "smoke-peer")
    led.note_vote_seen((1, 0, 2, 3), "other")     # duplicate receipt
    led.note_vote_relayed((1, 0, 2, 3))
    route = led.vote_route(1, 0, 2, 3)
    assert route is not None and route[0] == "smoke-peer" \
        and route[1] == 1, route
    led.drop_peer(rec, "smoke_done")
    dump = led.dump()
    assert set(dump["peers"][0]) == set(peerledger.PeerLedger.FIELDS)
    p = dump["peers"][0]
    assert p["state"] == "dropped" and p["reason"] == "smoke_done"
    assert p["msgs_tx"] == n_msgs and p["bytes_tx"] == 200 * n_msgs
    assert p["chans"]["0x22"]["msgs_tx"] == n_msgs
    assert p["chans"]["0x21"]["msgs_rx"] == n_msgs
    assert p["q_hiwater"] == 6
    s = dump["summary"]
    assert s["full_drops"] == 1 and s["blocked_puts"] == 1
    assert s["votes"] == {"seen": 1, "dups": 1, "relayed": 1,
                          "tracked": 1, "dropped": 0}
    budget = peer_ledger_bookkeeping_us(k=2000)
    return {
        "metric": "cfg14_smoke peer ledger bookkeeping",
        "value": round(wall_ms, 3),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {
            "msgs": n_msgs,
            "peer_path": budget,
            "summary": {k: s[k] for k in
                        ("msgs_tx", "msgs_rx", "full_drops",
                         "blocked_puts")},
        },
    }


def smoke_device_observatory(n_compiles=64):
    """cfg15's host-only miniature: the device observatory end to end
    with no jax in the process — compile-record shape over the
    attribution stack (site + flush seq + steady flag), the
    compile_storm incident trigger (burst fires, snapshot carries the
    compile tail), per-family/per-device residency math over fake
    tables with the exact-accounting split, and the per-flush hook
    budget."""
    from cometbft_tpu.libs import deviceledger, incidents

    led = deviceledger.CompileLedger()
    old_led = deviceledger.install(led)
    old_rec = incidents.install(incidents.IncidentRecorder(
        compile_storm=3, window_s=60.0, cooldown_s=0.0))
    try:
        t = _now_ms()
        for i in range(n_compiles):
            fr = deviceledger.attr_begin("smoke.flush", i)
            deviceledger.record_compile(0.002)
            deviceledger.attr_end(fr)
        wall_ms = _now_ms() - t
        recs = led.records()
        assert set(recs[0]) == set(deviceledger.CompileLedger.FIELDS)
        assert recs[0]["site"] == "smoke.flush"
        assert recs[0]["flush_seq"] == 0 and not recs[0]["steady"]
        c = led.counters()
        assert c["compiles"] == n_compiles
        assert c["steady_compiles"] == 0
        # steady-state recompiles: the round-5 class — a burst past
        # the threshold fires ONE compile_storm whose snapshot
        # carries the compile tail naming the sites
        led.mark_steady()
        with deviceledger.attr_context("smoke.storm", 99):
            for _ in range(3):
                deviceledger.record_compile(0.004)
        incidents.poke()   # anchor the window
        incidents.poke()   # evaluate it
        snaps = incidents.recorder().incidents()
        assert len(snaps) == 1, [s["trigger"] for s in snaps]
        assert snaps[0]["trigger"] == "compile_storm"
        assert any("smoke.storm" in ln and "STEADY" in ln
                   for ln in snaps[0]["device_tail"]), snaps[0]
        assert led.counters()["steady_compiles"] == 3

        # residency: fake tables through the same duck-typed split the
        # real sampler uses — bytes and slots land per device, exactly
        class _T:
            def __init__(self, nbytes, n_vals=0, m_shard=0, devs=None):
                self.nbytes = nbytes
                self.n_vals = n_vals
                self.m_shard = m_shard
                if devs is not None:
                    self.devs = devs

        fams = deviceledger.residency(
            tables=[_T(1000, n_vals=4096), _T(500, n_vals=2048)],
            shards=[_T(901, m_shard=2048, devs=[0, 1, 2, 3])])
        vt = fams["valset_tables"]
        assert vt[0]["bytes"] == 1500 and vt[0]["slots"] == 6144
        sh = fams["shard_tables"]
        assert sum(s["bytes"] for s in sh.values()) == 901  # exact
        assert sh[1]["slots"] == 2048
        head = deviceledger.headroom_rows(fams)
        assert head[0] == deviceledger.HBM_SLOT_BUDGET - 6144 - 2048
        assert head[3] == deviceledger.HBM_SLOT_BUDGET - 2048
        budget = device_ledger_bookkeeping_us(k=2000)
        return {
            "metric": "cfg15_smoke device observatory",
            "value": round(wall_ms, 3),
            "unit": "ms",
            "vs_baseline": None,
            "extra": {
                "compiles": n_compiles,
                "storm_fired": snaps[0]["trigger"],
                "flush_hooks": budget,
                "headroom_dev0": head[0],
            },
        }
    finally:
        deviceledger.install(old_led)
        incidents.install(old_rec)


def cfg15_device(n_vals=1024, steady_reps=5):
    """#15: the device observatory on the REAL device path — cold
    compile attribution, zero steady-state recompiles (the r05/round-5
    guard, asserted), HBM residency + headroom, and the
    exact-accounting cross-check, all read from the same module core
    /dump_devices serves.

    Runs LAST in the full set, by which point the plane has long since
    declared the process steady — so the config installs its OWN fresh
    compile ledger (the jax listener writes through the module global)
    and measures cold-vs-steady as this config's delta, not the whole
    run's. The fresh ledger's dump is what gets embedded for
    device_report; the process ledger is restored on exit and keeps
    accumulating the run-wide truth."""
    import jax
    import numpy as np

    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.libs import deviceledger
    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.ops import ed25519_kernel as ek

    deviceledger.arm_compile_listener()
    privs = [PrivKey.generate(i.to_bytes(32, "big"))
             for i in range(1, n_vals + 1)]
    pubs = [p.pub_key().data for p in privs]
    msgs = [b"cfg15-%d" % i for i in range(n_vals)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    led = deviceledger.CompileLedger()
    old_led = deviceledger.install(led)
    try:
        with deviceledger.attr_context("cfg15.cold"):
            table = ec.table_for_pubs(tuple(pubs))
            m_pad = ec.table_pad(n_vals)
            pb = ek.pack_batch(pubs, msgs, sigs, pad_to=m_pad)
            rows = ec.pack_rows_cached(
                pb, np.zeros((m_pad,), np.bool_),
                np.zeros((m_pad,), np.int32),
                np.zeros((1, ek.TALLY_LIMBS), np.int32))
            out = ec.verify_tally_rows_cached(jax.device_put(rows),
                                              table, 1)
            assert bool(np.asarray(out[0])[:n_vals].all())
        cold = led.counters()
        led.mark_steady()
        t = _now_ms()
        with deviceledger.attr_context("cfg15.steady"):
            for _ in range(steady_reps):
                out = ec.verify_tally_rows_cached(
                    jax.device_put(rows), table, 1)
            np.asarray(out[0])
        steady_ms = (_now_ms() - t) / steady_reps
        after = led.counters()
        steady_compiles = after["steady_compiles"]
        # THE acceptance: a steady-state verify stream recompiles
        # nothing (the round-5 class the compile_storm trigger
        # watches) — measured on THIS config's fresh ledger, so the
        # earlier configs' compiles can't pollute it either way
        assert steady_compiles == 0, \
            f"steady-state recompiled {steady_compiles}x"
        fams = deviceledger.residency()
        rec = deviceledger.reconcile(fams)
        assert rec["table_drift"] == 0, rec
        head = deviceledger.headroom_rows(fams)
        dump = deviceledger.dump_devices()
        dump["compiles"] = dump["compiles"][-32:]
        return {
            "metric": "cfg15 device observatory steady verify",
            "value": round(steady_ms, 2),
            "unit": "ms",
            "vs_baseline": None,
            "extra": {
                "cold_compiles": cold["compiles"],
                "cold_compile_s": round(cold["compile_s"], 3),
                "pcache_hits": after["pcache_hits"],
                "steady_compiles": steady_compiles,
                "resident_bytes": {
                    fam: sum(s["bytes"] for s in devs.values())
                    for fam, devs in fams.items()},
                "headroom_rows_min": min(head.values()) if head
                else None,
                "reconcile": rec,
                "compile_sites": [r["site"]
                                  for r in led.records()[-8:]],
                # the config's own dump (compile ring trimmed) so
                # tools/device_report.py can read this --json-out
                # file directly and --diff it against the next
                # round's; extra.jax_compile reads ~0 for cfg15 by
                # design (its compiles land on this private ledger)
                "device_dump": dump,
            },
        }
    finally:
        deviceledger.install(old_led)


def _controller_closed_loop(n_cycles, peak_evals, trough_evals):
    """Shared cfg16 driver: a real host-path VerifyPlane + real
    AdmissionController as ACTUATORS, a synthetic commit-latency
    sensor as the pressure input, cycled peak -> trough. Returns
    (wall_ms, evals, ctl_dump, checks)."""
    from cometbft_tpu.libs import controller as controlplane
    from cometbft_tpu.mempool.admission import AdmissionController
    from cometbft_tpu.verifyplane.plane import VerifyPlane

    class _Sensor:
        p99 = 0.0

        def __len__(self):
            return 1

        def summary(self):
            return {"commit_latency_ms": {"p99": self.p99}}

    fill = {"v": 0.1}
    plane = VerifyPlane(window_ms=0.5, use_device=False)
    adm = AdmissionController(high_watermark=0.9, low_watermark=0.7,
                              fill_fn=lambda: fill["v"])
    sensor = _Sensor()
    ctl = controlplane.Controller(slo_commit_p99_ms=100.0,
                                  decision_interval=1, cooldown=0)
    try:
        ctl.attach(plane=plane, admission=adm, height_ledger=sensor,
                   bounds={
                       controlplane.ACT_BULK_WINDOW: (1.0, 8.0),
                       controlplane.ACT_GATEWAY_WINDOW: (0.5, 4.0),
                       controlplane.ACT_ADMISSION: (0.3, 0.9),
                   })
        consensus_window = plane.window
        base_bulk = plane.bulk_window
        height, evals = 0, 0
        t = _now_ms()
        for _ in range(n_cycles):
            sensor.p99, fill["v"] = 500.0, 0.8   # peak: 5x over SLO
            for _ in range(peak_evals):
                height += 1
                ctl.poke(height, 0)
            tightened = (plane.bulk_window > base_bulk
                         and adm.high_watermark < 0.9)
            sensor.p99, fill["v"] = 10.0, 0.1    # trough: headroom
            for _ in range(trough_evals):
                height += 1
                ctl.poke(height, 0)
            evals += peak_evals + trough_evals
        wall_ms = _now_ms() - t
        dump = ctl.dump()
        checks = {
            "tightened_at_peak": tightened,
            "relaxed_to_base": (
                abs(plane.bulk_window - base_bulk) < 1e-9
                and adm.high_watermark == 0.9),
            "consensus_untouched": plane.window == consensus_window,
            "all_within_bounds": all(
                a["min"] - 1e-9 <= d["new"] <= a["max"] + 1e-9
                for d in dump["decisions"]
                for a in (dump["actuators"][d["actuator"]],)),
        }
        return wall_ms, evals, dump, checks
    finally:
        controlplane.clear_global_controller(ctl)
        plane.stop()


def smoke_controller(n_cycles=3):
    """cfg16's host-only miniature: the closed loop end to end with no
    jax in the process — tighten BEFORE the static config would shed
    (windows widen, watermark drops on the pressure latch), relax back
    to the configured base at the trough, clamp bounds honored on
    every decision, the CONSENSUS lane untouched by construction, and
    the decision dump embedded so tools/controller_report.py reads
    this --json-out file directly."""
    wall_ms, evals, dump, checks = _controller_closed_loop(
        n_cycles, peak_evals=8, trough_evals=16)
    assert all(checks.values()), checks
    assert dump["state"]["decisions_total"] >= 2 * n_cycles
    return {
        "metric": "cfg16_smoke closed-loop controller",
        "value": round(wall_ms, 3),
        "unit": "ms",
        "vs_baseline": None,
        "extra": {
            "evals": evals,
            "decisions_total": dump["state"]["decisions_total"],
            "checks": checks,
            "controller_dump": dump,
        },
    }


def cfg16_controller(n_cycles=50):
    """#16: the self-tuning control plane at sustained cadence. The
    loop is host-side BY DESIGN (decisions ride consensus step
    transitions; nothing in the decision path may touch the device),
    so this config measures what production pays: per-eval overhead on
    the step-transition seam across many peak/trough cycles, plus the
    same closed-loop invariants as the smoke (tighten at peak, relax
    to base, clamps, consensus untouched). The embedded dump is the
    --diff input for tools/controller_report.py across rounds."""
    wall_ms, evals, dump, checks = _controller_closed_loop(
        n_cycles, peak_evals=8, trough_evals=16)
    assert all(checks.values()), checks
    dump["decisions"] = dump["decisions"][-64:]
    return {
        "metric": "cfg16 controller eval overhead",
        "value": round(wall_ms * 1000.0 / max(1, evals), 3),
        "unit": "us",
        "vs_baseline": None,
        "extra": {
            "evals": evals,
            "decisions_total": dump["state"]["decisions_total"],
            "wall_ms": round(wall_ms, 3),
            "checks": checks,
            "controller_dump": dump,
        },
    }


def _tenant_pod(k_chains, rounds, rows_per_sub):
    """Shared cfg17 driver: the SAME K-chain ed25519 verify workload
    run two ways — K chains sharing ONE multi-tenant plane (per-round
    submissions from every chain coalesce into fused flushes with
    per-tenant ledger attribution) vs one plane per chain (the
    pod-per-chain status quo this subsystem replaces). Returns
    (shared_ms, split_ms, checks, figures)."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.verifyplane.plane import LANE_BULK, VerifyPlane

    chains = [f"bench-{i}" for i in range(k_chains)]
    rows = {}
    for i, chain in enumerate(chains):
        msg = b"cfg17:" + chain.encode()
        rows[chain] = []
        for j in range(rows_per_sub):
            priv = PrivKey.generate(bytes([200 + i, j]) + b"\x33" * 30)
            rows[chain].append((priv.pub_key(), msg, priv.sign(msg)))

    def drive(plane_of):
        verdicts = []
        t = _now_ms()
        for _ in range(rounds):
            futs = [plane_of(c).submit_many(
                        list(rows[c]), lane=LANE_BULK, chain_id=c)
                    for c in chains]
            verdicts.append(tuple(f.result(30.0) for f in futs))
        return _now_ms() - t, verdicts

    shared = VerifyPlane(window_ms=0.5, use_device=False)
    shared.start()
    try:
        shared_ms, v_shared = drive(lambda c: shared)
        summary = shared.ledger.summary()
        recs = shared.ledger.records()
        dump = shared.tenants.dump()
        flushes_shared = len(recs)
    finally:
        shared.stop()

    split = {c: VerifyPlane(window_ms=0.5, use_device=False)
             for c in chains}
    for p in split.values():
        p.start()
    try:
        split_ms, v_split = drive(lambda c: split[c])
        flushes_split = sum(len(p.ledger.records())
                            for p in split.values())
    finally:
        for p in split.values():
            p.stop()

    total_rows = k_chains * rounds * rows_per_sub
    checks = {
        # sharing the plane changes the economics, never the verdicts
        "verdicts_identical": v_shared == v_split,
        "all_verified": all(all(v) for r in v_shared for v in r),
        # the ledger's per-tenant attribution sums to each flush total
        "attribution_sums": all(
            sum(n for _, n in r["tenants"]) == r["rows"]
            for r in recs),
        # the whole point: multi-chain rows landed in FUSED flushes
        "coalesced": summary.get("coalesced_flushes", 0) >= 1,
        "every_tenant_accounted": all(
            dump["tenants"][c]["rows"] == rounds * rows_per_sub
            for c in chains),
    }
    figures = {
        "k_chains": k_chains,
        "rows_total": total_rows,
        "flushes_shared": flushes_shared,
        "flushes_split": flushes_split,
        "coalesced_flushes": summary.get("coalesced_flushes", 0),
        "split_ms": round(split_ms, 3),
        "speedup_vs_split": round(split_ms / max(shared_ms, 1e-9), 3),
        "tenants_dump": dump,
    }
    return shared_ms, split_ms, checks, figures


def smoke_tenants(k_chains=2, rounds=3, rows_per_sub=4):
    """cfg17's host-only miniature: two chains on one plane with no
    jax in the process — identical verdicts to the per-chain-plane
    arm, fused cross-tenant flushes on the ledger, attribution sums
    exact, and the tenants_dump embedded so tools/tenant_report.py
    reads this --json-out file directly."""
    shared_ms, _, checks, figures = _tenant_pod(
        k_chains, rounds, rows_per_sub)
    assert all(checks.values()), checks
    return {
        "metric": "cfg17_smoke multi-tenant pod",
        "value": round(shared_ms, 3),
        "unit": "ms",
        "vs_baseline": None,
        "extra": dict(figures, checks=checks),
    }


def cfg17_tenants(k_chains=8, rounds=12, rows_per_sub=16):
    """#17: the multi-tenant verify plane at pod scale — K chains'
    BULK verify traffic through ONE plane vs K per-chain planes over
    the same signed rows. The shared arm's flush count collapses
    (cross-tenant coalescing: one drain cycle serves many chains) and
    its throughput is the headline figure; verdicts must match the
    split arm bit-for-bit. The embedded tenants_dump is the --diff
    input for tools/tenant_report.py across rounds."""
    shared_ms, split_ms, checks, figures = _tenant_pod(
        k_chains, rounds, rows_per_sub)
    assert all(checks.values()), checks
    total_rows = figures["rows_total"]
    return {
        "metric": "cfg17 shared-plane verify throughput",
        "value": round(total_rows / max(shared_ms, 1e-9) * 1000.0, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "extra": dict(figures, checks=checks,
                      shared_ms=round(shared_ms, 3)),
    }


def _catchup_history(n_blocks, n_vals=3, epoch_len=0,
                     chain_id="cfg18-chain"):
    """A real ed25519-signed history: per-epoch valsets (rotated every
    ``epoch_len`` blocks when set), real Block objects whose
    block_id()s the commits actually sign. Returns (items, vals_at)
    with items = {h: (block, commit)} and vals_at(h) the valset that
    signs block h."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import Block, Data, Header
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT,
        Commit,
        CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    n_epochs = (n_blocks // epoch_len + 2) if epoch_len else 1
    epochs = []
    for e in range(n_epochs):
        privs = [PrivKey.generate(bytes([40 + e, i + 1]) + b"\x18" * 30)
                 for i in range(n_vals)]
        vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
        by_addr = {p.pub_key().address(): p for p in privs}
        epochs.append((vs, by_addr))

    def vals_at(h):
        e = (h - 1) // epoch_len if epoch_len else 0
        return epochs[min(e, n_epochs - 1)][0]

    items = {}
    last_bid = None
    for h in range(1, n_blocks + 1):
        vs, by_addr = epochs[min((h - 1) // epoch_len
                                 if epoch_len else 0, n_epochs - 1)]
        hdr = Header(
            chain_id=chain_id, height=h,
            time=Timestamp(1700000000 + h, 0),
            validators_hash=vs.hash(),
            next_validators_hash=vals_at(h + 1).hash(),
            proposer_address=vs.validators[0].address,
        )
        if last_bid is not None:
            hdr.last_block_id = last_bid
        blk = Block(hdr, Data())
        blk.fill_header()
        bid = blk.block_id()
        sigs = []
        for v in vs.validators:
            ts = Timestamp(1700000000 + h, 1)
            sb = canonical.canonical_vote_bytes(
                chain_id, canonical.PRECOMMIT_TYPE, h, 0, bid, ts)
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                                  by_addr[v.address].sign(sb)))
        items[h] = (blk, Commit(h, 0, bid, sigs))
        last_bid = bid
    return items, vals_at


class _HistorySource:
    """In-memory history source for the catch-up bench drivers."""

    def __init__(self, items):
        self.items = items

    def base(self):
        return min(self.items)

    def tip(self):
        return max(self.items)

    def load(self, h):
        return self.items[h]


class _ReplayState:
    """The slice of State the catch-up engine reads, without dragging
    the execution stack into a bench driver."""

    __slots__ = ("chain_id", "last_block_height", "validators",
                 "next_validators")

    def __init__(self, chain_id, h, validators, next_validators):
        self.chain_id = chain_id
        self.last_block_height = h
        self.validators = validators
        self.next_validators = next_validators


class _RecordingWarmer:
    def __init__(self):
        self.requests = []

    def request_valset(self, vals, chain_id=None):
        self.requests.append((vals.hash(), chain_id))


def _catchup_drive(items, vals_at, *, verifier, cursor_path,
                   read_ahead=128, max_run=64, kill_at_read=0,
                   warm_ahead=True, start_height=0):
    """Run one CatchupEngine pass over an in-memory history. Returns
    (engine, wall_ms, crashed) — with ``kill_at_read`` > 0 the
    catchup.read_ahead failpoint raises at that read and the partial
    run returns crashed=True (the persisted cursor is the evidence)."""
    from cometbft_tpu.blocksync.catchup import CatchupEngine
    from cometbft_tpu.libs import failpoints as fp

    chain_id = getattr(items[min(items)][0].header, "chain_id",
                       "cfg18-chain")
    state = _ReplayState(chain_id, start_height,
                         vals_at(start_height + 1),
                         vals_at(start_height + 2))

    def apply_fn(st, blk, commit):
        h = blk.header.height
        return _ReplayState(st.chain_id, h, vals_at(h + 1),
                            vals_at(h + 2))

    warmer = _RecordingWarmer()
    eng = CatchupEngine(
        _HistorySource(items), state, apply_fn=apply_fn,
        verifier=verifier, cursor_path=cursor_path,
        read_ahead=read_ahead, max_run=max_run,
        warm_ahead=warm_ahead, warmer=warmer)
    crashed = False
    if kill_at_read:
        # flake fires on the Nth evaluation: a kill mid-replay, with
        # whatever the cursor persisted by then as the resume point
        fp.arm("catchup.read_ahead", "flake", kill_at_read, count=1)
    t = _now_ms()
    try:
        eng.run()
    except fp.FailpointError:
        crashed = True
    finally:
        fp.disarm("catchup.read_ahead")
    return eng, _now_ms() - t, crashed


def smoke_catchup(n_blocks=12, n_vals=3, epoch_len=5):
    """cfg18's host-only miniature: a real ed25519-signed history
    replayed through the catch-up firehose with the jax-free host
    verifier — fused cross-height segments bounded at REAL valset
    boundaries, warm-ahead requests fired before each boundary, then a
    mid-replay kill + resume from the persisted cursor re-verifying
    ZERO already-verified blocks. The catchup_dump is embedded so
    tools/catchup_report.py reads this --json-out file directly."""
    import tempfile

    from cometbft_tpu.blocksync.catchup import HostCommitVerifier

    items, vals_at = _catchup_history(n_blocks, n_vals, epoch_len)
    with tempfile.TemporaryDirectory() as td:
        cursor = os.path.join(td, "cursor.json")
        # phase 1: kill at the 8th read-ahead read
        eng1, _, crashed = _catchup_drive(
            items, vals_at, verifier=HostCommitVerifier(),
            cursor_path=cursor, read_ahead=4, max_run=4,
            kill_at_read=8)
        assert crashed and eng1.cursor.applied >= 1
        verified_at_crash = eng1.cursor.verified

        # phase 2: resume from the persisted cursor + applied state
        class _CountingVerifier(HostCommitVerifier):
            def __init__(self):
                self.heights = []

            def verify(self, jobs):
                self.heights.extend(j.height for j in jobs)
                return super().verify(jobs)

        v2 = _CountingVerifier()
        eng2, wall_ms, crashed2 = _catchup_drive(
            items, vals_at, verifier=v2, cursor_path=cursor,
            read_ahead=4, max_run=4,
            start_height=eng1.cursor.applied)
        reverified = [h for h in v2.heights if h <= verified_at_crash]
        checks = {
            "resumed_clean": not crashed2,
            "caught_up": eng2.state.last_block_height == n_blocks,
            "zero_reverified": not reverified,
            "cursor_resumed": eng2.cursor.resumed,
            "boundaries_found": eng2.ledger.counters["boundaries"]
            + eng1.ledger.counters["boundaries"] >= 1,
            "warm_ahead_fired": eng2.ledger.counters["warm_requests"]
            + eng1.ledger.counters["warm_requests"] >= 1,
        }
        assert all(checks.values()), checks
        from cometbft_tpu.blocksync import catchup as catchup_mod

        dump = catchup_mod.dump_catchup()
        return {
            "metric": "cfg18_smoke catch-up firehose",
            "value": round(wall_ms, 3),
            "unit": "ms",
            "vs_baseline": None,
            "extra": {
                "blocks": n_blocks,
                "verified_at_crash": verified_at_crash,
                "reverified_after_resume": len(reverified),
                "checks": checks,
                "catchup_dump": dump,
            },
        }


def _cfg18_machinery(n_blocks=100_000, epoch_len=10_000, max_run=64):
    """The ≥100k-block synthetic replay: stub crypto (the engine
    MACHINERY is the thing under test — read-ahead, segmentation,
    cursor persistence, ledger accounting — not the host's ed25519
    throughput), with a mid-replay kill + resume proving zero
    re-verification at scale."""
    import tempfile

    class _FakeVals:
        __slots__ = ("tag",)

        def __init__(self, tag):
            self.tag = tag

        def hash(self):
            return self.tag

    class _FakeHeader:
        __slots__ = ("validators_hash", "height")

        def __init__(self, vh, h):
            self.validators_hash = vh
            self.height = h

    class _FakeBlock:
        __slots__ = ("header", "_bid")

        def __init__(self, hdr):
            self.header = hdr
            self._bid = ("bid", hdr.height)

        def block_id(self):
            return self._bid

    class _FakeSig:
        __slots__ = ()
        signature = b"\x01"

    class _FakeCommit:
        __slots__ = ("signatures",)

        def __init__(self, sigs):
            self.signatures = sigs

    class _StubVerifier:
        def __init__(self):
            self.heights = []

        def verify(self, jobs):
            self.heights.extend(j.height for j in jobs)
            return [None] * len(jobs)

    n_epochs = n_blocks // epoch_len + 2
    epoch_vals = [_FakeVals(b"epoch-%d" % e) for e in range(n_epochs)]

    def vals_at(h):
        return epoch_vals[min((h - 1) // epoch_len, n_epochs - 1)]

    shared_sigs = tuple(_FakeSig() for _ in range(4))
    items = {h: (_FakeBlock(_FakeHeader(vals_at(h).hash(), h)),
                 _FakeCommit(shared_sigs))
             for h in range(1, n_blocks + 1)}

    with tempfile.TemporaryDirectory() as td:
        cursor = os.path.join(td, "cursor.json")
        v1 = _StubVerifier()
        eng1, _, crashed = _catchup_drive(
            items, vals_at, verifier=v1, cursor_path=cursor,
            max_run=max_run, kill_at_read=n_blocks // 2)
        assert crashed, "mid-replay kill did not fire"
        verified_at_crash = eng1.cursor.verified
        v2 = _StubVerifier()
        eng2, wall_ms, crashed2 = _catchup_drive(
            items, vals_at, verifier=v2, cursor_path=cursor,
            max_run=max_run, start_height=eng1.cursor.applied)
        reverified = sum(1 for h in v2.heights
                         if h <= verified_at_crash)
        resumed_blocks = n_blocks - eng1.cursor.applied
        checks = {
            "caught_up": eng2.state.last_block_height == n_blocks,
            "resumed_clean": not crashed2,
            "zero_reverified": reverified == 0,
            # boundary crossings left after the resume point: epoch
            # walls at k*epoch_len strictly below the tip
            "every_boundary_found":
                eng2.ledger.counters["boundaries"]
                == (n_blocks - 1) // epoch_len
                - eng1.cursor.applied // epoch_len,
            "warm_ahead_per_boundary":
                eng2.ledger.counters["warm_requests"]
                >= eng2.ledger.counters["boundaries"],
        }
        assert all(checks.values()), checks
        summary = eng2.ledger.summary()
        return {
            "blocks": n_blocks,
            "epoch_len": epoch_len,
            "resumed_blocks": resumed_blocks,
            "verified_at_crash": verified_at_crash,
            "reverified_after_resume": reverified,
            "wall_ms": round(wall_ms, 3),
            "blocks_per_s": round(
                resumed_blocks / max(wall_ms, 1e-9) * 1000.0, 1),
            "flushes": eng2.ledger.counters["flushes"],
            "boundaries": eng2.ledger.counters["boundaries"],
            "warm_requests": eng2.ledger.counters["warm_requests"],
            "checks": checks,
            "summary": summary,
        }


def cfg18_catchup(n_blocks=768, n_vals=64, epoch_len=256):
    """#18: the archival catch-up firehose. Host machinery figures ride
    a 100k-block synthetic replay (kill mid-replay, resume, ZERO
    re-verified); on a real accelerator the same engine replays a
    real-signed multi-epoch history through the fused device pipeline
    twice — COLD (no warm-ahead: every valset boundary pays its table
    build inside the verify path) vs WARMED (epoch tables built ahead
    of the replay cursor) — and the headline is warmed sigs/s."""
    import tempfile

    from cometbft_tpu.blocksync.pipeline import make_stream_verifier
    from cometbft_tpu.verifyplane.warmer import TableWarmer

    machinery = _cfg18_machinery()
    items, vals_at = _catchup_history(n_blocks, n_vals, epoch_len)
    total_sigs = n_blocks * n_vals

    def run(warm_ahead):
        with tempfile.TemporaryDirectory() as td:
            from cometbft_tpu.blocksync.catchup import CatchupEngine

            state = _ReplayState("cfg18-chain", 0, vals_at(1),
                                 vals_at(2))

            def apply_fn(st, blk, commit):
                h = blk.header.height
                return _ReplayState(st.chain_id, h, vals_at(h + 1),
                                    vals_at(h + 2))

            warmer = TableWarmer()
            warmer.start()
            try:
                eng = CatchupEngine(
                    _HistorySource(items), state, apply_fn=apply_fn,
                    verifier=make_stream_verifier(),
                    cursor_path=os.path.join(td, "cursor.json"),
                    warm_ahead=warm_ahead, warmer=warmer)
                t = _now_ms()
                eng.run()
                wall_ms = _now_ms() - t
                return wall_ms, eng.ledger
            finally:
                warmer.stop()

    cold_ms, _ = run(warm_ahead=False)
    warm_ms, led = run(warm_ahead=True)
    boundary_recs = [r for r in led.records() if r["boundary"]]
    return {
        "metric": "cfg18 catch-up firehose warmed replay",
        "value": round(total_sigs / max(warm_ms, 1e-9) * 1000.0, 1),
        "unit": "sigs/s",
        "vs_baseline": None,
        "extra": {
            "blocks": n_blocks,
            "sigs": total_sigs,
            "cold_ms": round(cold_ms, 3),
            "warm_ms": round(warm_ms, 3),
            "cold_vs_warm_speedup": round(
                cold_ms / max(warm_ms, 1e-9), 3),
            "boundaries": led.counters["boundaries"],
            "warm_requests": led.counters["warm_requests"],
            "boundary_verify_ms": [r["verify_ms"]
                                   for r in boundary_recs],
            "machinery": {k: v for k, v in machinery.items()
                          if k != "summary"},
        },
    }


def smoke_device_stamp(n_rows=10_000):
    """cfg19's host-only miniature (no jax): the delta extraction that
    feeds device stamping, proven byte-equal to the host packer across
    fuzzed varint widths, plus the staged-bytes budget (delta slots vs
    full-row slots at the 10k-row flush shape — the ISSUE 19 >=4x
    acceptance line) and the flush ledger's stamp/delta_bytes
    attribution."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.verifyplane import fused as fz
    from cometbft_tpu.verifyplane.plane import (
        STAMP_DEVICE,
        STAMP_HOST,
        FlushLedger,
    )

    bid = BlockID(b"\x19" * 32, PartSetHeader(1, b"\x91" * 32))
    tpl = canonical.VoteRowTemplate(
        CHAIN_ID, canonical.PRECOMMIT_TYPE, 4242, 1, bid)
    # every varint width boundary, zero-skip, and negative (64-bit
    # two's complement) case, then a deterministic bulk fill
    edge_s = [0, 1, 127, 128, 16383, 16384, 1_700_000_000,
              2 ** 31 - 1, 2 ** 31, 2 ** 40, 2 ** 62, -1, -2 ** 33]
    edge_n = [0, 1, 127, 128, 999_999_999, 5, 42, 999, 7, 0, 1, 0, 3]
    secs = np.resize(np.array(edge_s, np.int64), n_rows)
    secs[len(edge_s):] = 1_700_000_000 + np.arange(
        n_rows - len(edge_s), dtype=np.int64)
    nanos = np.resize(np.array(edge_n, np.int64), n_rows)
    nanos[len(edge_n):] = np.arange(n_rows - len(edge_n),
                                    dtype=np.int64) % 1_000_000_000
    t = _now_ms()
    dr = tpl.delta_rows(secs, nanos)
    got = dr.expand()
    expand_ms = _now_ms() - t
    t = _now_ms()
    ref = tpl.patch_rows(secs, nanos)
    patch_ms = _now_ms() - t
    assert dr.stampable()
    assert all(got.row(i) == ref.row(i) for i in range(n_rows)), (
        "delta expansion diverged from patch_rows")

    # staged bytes per flush at the 10k-row bucket: what the delta
    # path puts on the bus vs the full-row pack (pure slot-spec
    # arithmetic — the same shapes plan_fused stages)
    B = 10240
    delta_b = fz.specs_bytes(fz.delta_slot_specs(B))
    legacy_b = fz.specs_bytes(fz.legacy_slot_specs(B))
    ratio = legacy_b / delta_b
    assert ratio >= 4.0, (legacy_b, delta_b, ratio)

    # ledger attribution: stamp + delta_bytes are first-class FIELDS
    # (built from FIELDS so this can't drift from the plane)
    assert "stamp" in FlushLedger.FIELDS
    assert "delta_bytes" in FlushLedger.FIELDS
    led = FlushLedger()

    def rec(seq, stamp, dbytes):
        base = {f: 0 for f in FlushLedger.FIELDS}
        base.update(seq=seq, ts_ms=0.0, rows=B, subs=1, path="fused",
                    stamp=stamp, breaker="closed",
                    delta_bytes=dbytes, tenants=())
        return [base[f] for f in FlushLedger.FIELDS] + [0, 0, 0, 0]

    led.record(rec(1, STAMP_DEVICE, delta_b))
    led.record(rec(2, STAMP_HOST, 0))
    s = led.summary()
    assert s["stamp"]["device"] == 1 and s["stamp"]["host"] == 1, s
    assert s["stamp"]["delta_bytes"] == delta_b, s
    return {
        "metric": "cfg19_smoke delta staging shrink",
        "value": round(ratio, 2),
        "unit": "x",
        "vs_baseline": None,
        "extra": {
            "rows": n_rows,
            "byte_equality": True,
            "staged_bytes_delta": delta_b,
            "staged_bytes_legacy": legacy_b,
            "delta_bytes_per_row": round(delta_b / B, 1),
            "legacy_bytes_per_row": round(legacy_b / B, 1),
            "expand_ms": round(expand_ms, 3),
            "patch_ms": round(patch_ms, 3),
            "ledger_stamp": s["stamp"],
        },
    }


def cfg19_device_stamp(n_vals=2048, reps=5, n_flushes=12):
    """#19: device-side sign-bytes stamping through the REAL plane
    dispatcher — delta-staged flushes (template resident, 80 B/row on
    the bus) vs the legacy full-row pack, same rows, verdicts
    bit-equal. The headline is the stamped arm's sigs/s; the ledger's
    h2d_ms / pack_ms / delta_bytes deltas are the mechanism evidence."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.verifyplane import QuorumGroup, VerifyPlane
    from cometbft_tpu.verifyplane import fused as fz

    n_rows = n_vals * reps
    keys = [PrivKey.generate((9900 + i).to_bytes(4, "big") + b"\x66" * 28)
            for i in range(n_vals)]
    pubs_t = tuple(k.pub_key().data for k in keys)
    powers_t = tuple(100 for _ in range(n_vals))
    bid = BlockID(b"\x19" * 32, PartSetHeader(1, b"\x92" * 32))
    tpl = canonical.VoteRowTemplate(
        CHAIN_ID, canonical.PRECOMMIT_TYPE, 1919, 0, bid)
    rows_all, vidx_all, stamp_all = [], [], []
    for r in range(reps):
        secs = 1_700_000_000 + r
        sr = tpl.patch_rows(
            np.full(n_vals, secs, np.int64),
            np.arange(n_vals, dtype=np.int64) + r)
        for i, k in enumerate(keys):
            msg = sr.row(i)
            rows_all.append((k.pub_key(), msg, k.sign(msg)))
            vidx_all.append(i)
            stamp_all.append((tpl, secs, i + r))

    def run(stamped):
        fz.set_device_stamping(stamped)
        plane = VerifyPlane(
            window_ms=0.5, max_batch=n_rows,
            max_queue=n_rows * (n_flushes + 2),
            use_device=True,
            mesh_devices=0, mesh_min_rows=1)
        plane.start()
        try:
            def burst(k):
                futs = [plane.submit_many(
                    rows_all, group=QuorumGroup(
                        10 ** 15, valset_pubs=pubs_t,
                        valset_powers=powers_t),
                    vidx=vidx_all, stamp=stamp_all)
                    for _ in range(k)]
                return [f.result(300.0) for f in futs]

            burst(2)  # warm: compile + template/table residency
            t = _now_ms()
            verd = burst(n_flushes)
            wall = _now_ms() - t
        finally:
            plane.stop()
            fz.set_device_stamping(True)
        dump = plane.dump_flushes()
        recs = [r for r in dump["flushes"]
                if r["path"].startswith("fused")][-n_flushes:]
        return wall, verd, dump["summary"], recs

    wall_s, verd_s, sum_s, recs_s = run(True)
    wall_l, verd_l, sum_l, recs_l = run(False)
    assert verd_s == verd_l, "stamped arm verdicts diverged"

    def med(recs, field):
        return round(float(np.median([r[field] for r in recs])), 3) \
            if recs else None

    stamped_recs = [r for r in recs_s if r["stamp"] == "device"]
    sps = n_rows * n_flushes / (wall_s / 1000) if wall_s else 0.0
    return {
        "metric": "cfg19 device-stamped flush throughput",
        "value": round(sps),
        "unit": "sigs/sec",
        "vs_baseline": round(wall_l / wall_s, 2) if wall_s else None,
        "extra": {
            "rows_per_flush": n_rows,
            "flushes": n_flushes,
            "wall_stamped_ms": round(wall_s, 1),
            "wall_legacy_ms": round(wall_l, 1),
            "stamped_flushes": len(stamped_recs),
            "h2d_ms_stamped": med(recs_s, "h2d_ms"),
            "h2d_ms_legacy": med(recs_l, "h2d_ms"),
            "pack_ms_stamped": med(recs_s, "pack_ms"),
            "pack_ms_legacy": med(recs_l, "pack_ms"),
            "delta_bytes_per_flush": med(stamped_recs, "delta_bytes"),
            "stamp_split": sum_s.get("stamp"),
        },
    }


def cost_hooks_bookkeeping_us(k: int = 20_000) -> dict:
    """Per-flush cost of the ISSUE 20 cost-observatory hooks with
    tracing disabled (< 10 us/flush, tier-1-asserted).

    Replays the exact sequence _charge_flush adds to every flush — one
    split_device_columns call over a fused three-tenant batch (the
    worst common case: integer shares plus the last-tenant residual),
    the per-share note_device accumulation, and the cost-surface
    observe() bucketing — against throwaway registry/surface instances
    so the session's live observatory is untouched."""
    from cometbft_tpu.libs import deviceledger, tracing
    from cometbft_tpu.verifyplane.plane import split_device_columns
    from cometbft_tpu.verifyplane.tenants import TenantRegistry

    assert not tracing.enabled(), "measure the DISABLED path"
    reg = TenantRegistry()
    surf = deviceledger.CostSurfaces()
    tens = (("bench-a", 24), ("bench-b", 24), ("bench-c", 16))
    t0 = _now_ms()
    for _ in range(k):
        rule, shares = split_device_columns(tens, 64, 1.25, 0.5,
                                            3.75, 5121)
        reg.note_device_shares(shares)
        surf.observe("fused:stamped", 64, 1, 1.25, 0.5, 3.75)
    hook_us = (_now_ms() - t0) * 1000 / k
    return {
        "cost_hooks_us_per_flush": round(hook_us, 3),
        "note": "tenant split + per-share charge + cost-surface "
                "bucket, per flush; always-on (<10us budget)",
    }


def smoke_cost_observatory():
    """cfg20's host-only miniature (no jax, no plane): the cost
    observatory's arithmetic proven in isolation — the tenant split
    rule (exact at sub-flush boundaries, row-proportional with an
    integer last-tenant residual inside a fused batch), charge
    conservation across eviction/retirement (reconcile_device drift
    identically zero — integer us, no tolerance band), the
    rows-bucket / percentile / marginal-slope math of the cost
    surfaces, the CostModel estimate extension past the learned
    range, and the always-on per-flush hook budget."""
    from cometbft_tpu.libs import deviceledger
    from cometbft_tpu.verifyplane.plane import (
        SPLIT_EXACT,
        SPLIT_ROWS,
        ms_to_us,
        split_device_columns,
    )
    from cometbft_tpu.verifyplane.tenants import (
        TenantRegistry,
        reconcile_device,
    )

    checks = {}
    # the split rule: nothing charged without tenants, full charge for
    # a single tenant, row-proportional shares that conserve EVERY
    # column exactly (the residual lands on the last tenant)
    checks["empty_tenants_no_charge"] = split_device_columns(
        (), 0, 1.0, 1.0, 1.0, 64) == (SPLIT_EXACT, [])
    rule, shares = split_device_columns(
        (("a", 64),), 64, 1.25, 0.5, 3.75, 5120)
    checks["single_tenant_exact"] = (
        rule == SPLIT_EXACT
        and shares == [("a", 1250, 500, 3750, 5120)])
    rule, shares = split_device_columns(
        (("a", 24), ("b", 24), ("c", 16)), 64, 1.25, 0.5, 3.75, 5121)
    checks["fused_rows_rule"] = rule == SPLIT_ROWS
    checks["fused_conserves_every_column"] = all(
        sum(s[i] for s in shares) == tot
        for i, tot in ((1, ms_to_us(1.25)), (2, ms_to_us(0.5)),
                       (3, ms_to_us(3.75)), (4, 5121)))

    # conservation across eviction: charge a registry from synthetic
    # ledger records, reconcile (drift zero), retire one tenant, and
    # reconcile again — the retired fold must keep the totals exact
    reg = TenantRegistry()
    recs = [
        {"tenants": (("a", 8),), "rows": 8, "comp_ms": 2.0,
         "h2d_ms": 0.25, "dev_ms": 1.5, "delta_bytes": 640},
        {"tenants": (("a", 30), ("b", 34)), "rows": 64,
         "comp_ms": 0.0, "h2d_ms": 0.125, "dev_ms": 3.125,
         "delta_bytes": 5120},
        # shed-only record: () tenants, never charged
        {"tenants": (), "rows": 16, "comp_ms": 9.0, "h2d_ms": 9.0,
         "dev_ms": 9.0, "delta_bytes": 999},
    ]
    for r in recs:
        if r["tenants"]:
            _, sh = split_device_columns(
                r["tenants"], r["rows"], r["comp_ms"], r["h2d_ms"],
                r["dev_ms"], r["delta_bytes"])
            for chain, comp_us, h2d_us, dev_us, dbytes in sh:
                reg.note_device(chain, comp_us, h2d_us, dev_us, dbytes)
    checks["conservation"] = all(
        v == 0 for v in reconcile_device(recs, reg)["drift"].values())
    reg.evict("a")
    checks["conservation_after_retirement"] = all(
        v == 0 for v in reconcile_device(recs, reg)["drift"].values())
    checks["retired_fold"] = reg.dump()["retired"]["device_us"] > 0

    # cost-bucket math against an isolated recorder: power-of-two
    # buckets, sorted surfaces, the marginal slope between adjacent
    # buckets, and the estimate extension past the learned range
    checks["bucket_boundaries"] = (
        [deviceledger.rows_bucket(n) for n in (0, 1, 2, 3, 64, 65)]
        == [1, 1, 2, 4, 64, 128])
    prev = deviceledger.install_surfaces(deviceledger.CostSurfaces())
    try:
        for rows, dev in ((8, 0.6), (64, 1.1), (512, 4.0)):
            for _ in range(5):
                deviceledger.observe_flush(
                    "fused", "device", rows, 1, 0.0, 0.1, dev)
        cs = deviceledger.surfaces().surfaces()
        p50s = [r["dev_ms_p50"] for r in cs]
        checks["surfaces_populated"] = len(cs) == 3
        checks["stamped_family_label"] = all(
            r["family"] == "fused:stamped" for r in cs)
        checks["monotone_dev_p50"] = p50s == sorted(p50s)
        checks["marginal_math"] = (
            cs[1]["marginal_ms_per_row"]
            == round((1.1 - 0.6) / (64 - 8), 6))
        model = deviceledger.cost_model()
        checks["estimate_extends"] = (
            model.estimate_dev_ms("fused:stamped", 2000) is not None
            and model.estimate_dev_ms("unobserved", 64) is None)
    finally:
        deviceledger.install_surfaces(prev)

    budget = cost_hooks_bookkeeping_us(k=2000)
    checks["hook_budget"] = budget["cost_hooks_us_per_flush"] < 10.0
    assert all(checks.values()), checks
    return {
        "metric": "cfg20_smoke cost observatory hooks",
        "value": budget["cost_hooks_us_per_flush"],
        "unit": "us/flush",
        "vs_baseline": None,
        "extra": {"checks": checks, "budget": budget,
                  "surfaces_sample": cs},
    }


def cfg20_cost_pod(rounds=6, row_sizes=(12, 96, 768)):
    """#20: the cost observatory end to end — K chains at DISTINCT
    flush shapes through one shared plane, so the per-flush hook
    populates separated rows-buckets of the cost surfaces while the
    tenant registry accrues each chain's device charge. Sequential
    per-chain rounds give each shape its own bucket; a final
    concurrent round coalesces cross-tenant rows into a fused flush
    and exercises the row-proportional split. The row sizes sit
    MID-bucket (12->16, 96->128, 768->1024) so any cross-tenant
    fusion lands in the largest member's own bucket with MORE rows —
    coalescing can only pull a bucket's p50 up, never park a
    bottom-of-bucket flush under the previous bucket's top. Evidence:
    (a) reconcile_device drift is exactly zero against the flush
    ledger; (b) cost_surfaces is non-empty with dev p50 monotone
    non-decreasing across rows-buckets within each (family, n_dev)
    series; (c) the embedded tenants_dump / devices_dump are the
    tenant_report / device_report inputs."""
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.libs import deviceledger
    from cometbft_tpu.verifyplane.plane import (
        LANE_BULK,
        SPLIT_ROWS,
        VerifyPlane,
    )
    from cometbft_tpu.verifyplane.tenants import reconcile_device

    chains = {}
    for i, n in enumerate(row_sizes):
        chain = f"cost-{n}"
        msg = b"cfg20:" + chain.encode()
        rows = []
        for j in range(n):
            priv = PrivKey.generate(
                bytes([210 + i]) + j.to_bytes(2, "big") + b"\x44" * 29)
            rows.append((priv.pub_key(), msg, priv.sign(msg)))
        chains[chain] = rows

    prev = deviceledger.install_surfaces(deviceledger.CostSurfaces())
    plane = VerifyPlane(window_ms=0.5, use_device=False,
                        max_batch=2 * sum(row_sizes))
    plane.start()
    t = _now_ms()
    try:
        for _ in range(rounds):
            for c, rows in chains.items():
                assert all(plane.submit_many(
                    list(rows), lane=LANE_BULK,
                    chain_id=c).result(60.0))
        futs = [plane.submit_many(list(rows), lane=LANE_BULK,
                                  chain_id=c)
                for c, rows in chains.items()]
        for f in futs:
            assert all(f.result(60.0))
        wall_ms = _now_ms() - t
        recs = plane.ledger.records()
        rd = reconcile_device(recs, plane.tenants)
        tenants_dump = plane.tenants.dump()
        devices_dump = deviceledger.dump_devices()
        cs = devices_dump["cost_surfaces"]
        model = deviceledger.cost_model()
    finally:
        plane.stop()
        deviceledger.install_surfaces(prev)

    series = {}
    for r in cs:
        series.setdefault((r["family"], r["n_dev"]), []).append(
            (r["rows_bucket"], r["dev_ms_p50"]))
    fam0 = cs[0]["family"] if cs else ""
    checks = {
        "conservation_drift_zero": all(
            v == 0 for v in rd["drift"].values()),
        "surfaces_nonempty": len(cs) >= len(row_sizes),
        "buckets_separated": len({r["rows_bucket"] for r in cs})
        >= len(row_sizes),
        "monotone_dev_p50": all(
            p[1] <= q[1]
            for pts in series.values()
            for p, q in zip(sorted(pts), sorted(pts)[1:])),
        "fused_split_recorded": any(
            r["split"] == SPLIT_ROWS for r in recs
            if len(r["tenants"]) > 1),
        "every_flush_observed":
            devices_dump["cost_counters"]["observed"] >= len(recs),
        "estimate_available": bool(cs) and model.estimate_dev_ms(
            fam0, row_sizes[0]) is not None,
    }
    assert all(checks.values()), checks
    total_rows = (rounds + 1) * sum(row_sizes)
    budget = cost_hooks_bookkeeping_us()
    return {
        "metric": "cfg20 cost-observatory pod throughput",
        "value": round(total_rows / max(wall_ms, 1e-9) * 1000.0, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "extra": {
            "rows_total": total_rows,
            "flushes": len(recs),
            "split_rules": {
                rule: sum(1 for r in recs if r["split"] == rule)
                for rule in {r["split"] for r in recs}},
            "reconcile": rd,
            "cost_counters": devices_dump["cost_counters"],
            "cost_surfaces": cs,
            "budget": budget,
            "checks": checks,
            "tenants_dump": tenants_dump,
            "devices_dump": devices_dump,
        },
    }


SMOKE_CONFIGS = [("cfg2_smoke", smoke_commit_verify),
                 ("cfg4_smoke", smoke_pack_rows),
                 ("cfg6_smoke", smoke_vote_plane),
                 ("cfg10_smoke", smoke_gateway),
                 ("cfg11_smoke", smoke_sharded_layout),
                 ("cfg12_smoke", smoke_pipelined_deck),
                 ("cfg13_smoke", smoke_churn_warmer),
                 ("cfg14_smoke", smoke_peer_ledger),
                 ("cfg15_smoke", smoke_device_observatory),
                 ("cfg16_smoke", smoke_controller),
                 ("cfg17_smoke", smoke_tenants),
                 ("cfg18_smoke", smoke_catchup),
                 ("cfg19_smoke", smoke_device_stamp),
                 ("cfg20_smoke", smoke_cost_observatory)]

TRACED_CONFIGS = ("cfg2", "cfg6")  # flush-pipeline configs worth a trace

# the full (TPU-host) config set, in run order — tools/bench_history.py
# seeds its per-config rows from these names so a config added here is
# trackable from the next bench round onward even before any BENCH
# file records it
FULL_CONFIGS = [("cfg1", cfg1_live_node), ("cfg2", cfg2_1k_commit),
                ("cfg3", cfg3_mixed), ("cfg4", cfg4_streaming),
                ("cfg5", cfg5_light_secp), ("cfg6", cfg6_vote_plane),
                ("cfg7", cfg7_pack_only), ("cfg8", cfg8_multichip_smoke),
                ("cfg9", cfg9_sustained), ("cfg10", cfg10_gateway),
                ("cfg11", cfg11_sharded_tally),
                ("cfg12", cfg12_pipelined), ("cfg13", cfg13_churn),
                ("cfg15", cfg15_device), ("cfg16", cfg16_controller),
                ("cfg17", cfg17_tenants),
                ("cfg18", cfg18_catchup),
                ("cfg19", cfg19_device_stamp),
                ("cfg20", cfg20_cost_pod)]
FULL_CONFIG_NAMES = [name for name, _ in FULL_CONFIGS] + ["headline"]


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description="BASELINE configs bench")
    ap.add_argument(
        "--trace-out", default="",
        help="path prefix: run cfg2/cfg6 with tracing ON, write "
             "<prefix>.<cfg>.trace.json (perfetto-loadable) and embed "
             "the trace-derived stage table in each config's JSON. "
             "Tracing stays OFF for every other config and when the "
             "flag is absent — the headline numbers are untraced.")
    ap.add_argument(
        "--baseline", default="",
        help="a stored bench output (driver BENCH_rNN.json, --json-out "
             "file, or raw stdout capture): compare this run per-config "
             "with thresholded pass/fail and print the table as the "
             "last JSON line")
    ap.add_argument(
        "--baseline-threshold", type=float,
        default=BASELINE_THRESHOLD_PCT,
        help="regression threshold in percent (default %(default)s)")
    ap.add_argument(
        "--fail-on-regression", action="store_true",
        help="exit 1 when --baseline flags any config REGRESSED")
    ap.add_argument(
        "--json-out", default="",
        help="also write {results, baseline_check} to this path (the "
             "evidence-file shape load_bench_results() accepts)")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tier-1 mode: tiny shapes, host paths only, no jax import "
             "and no accelerator; catches bench.py rot in seconds")
    args = ap.parse_args(argv)
    if args.fail_on_regression and not args.baseline:
        # a CI gate that never compares anything would be permanently
        # green — surface the misconfiguration instead
        ap.error("--fail-on-regression requires --baseline")

    t0 = time.time()
    results = {}

    if args.smoke:
        for name, fn in SMOKE_CONFIGS:
            try:
                r = fn()
            except Exception as e:  # a config failure must not kill it
                r = {"metric": f"{name} FAILED", "value": None,
                     "unit": "", "vs_baseline": None,
                     "extra": {"error": repr(e)[:300]}}
            results[name] = r
            print(json.dumps(r), flush=True)
        print(json.dumps({
            "metric": "smoke summary",
            "value": len([r for r in results.values()
                          if r.get("value") is not None]),
            "unit": "configs",
            "vs_baseline": None,
            "extra": {"mode": "smoke (host-only, tiny shapes)",
                      "total_bench_s": round(time.time() - t0, 2)},
        }), flush=True)
        return _finish(args, results)

    # full mode measures the chip: no accelerator, no run (exit before
    # any cell), and every compile goes through the persistent cache
    from cometbft_tpu.libs import deviceledger, tracing
    from cometbft_tpu.libs.jax_cache import enable_persistent_compile_cache
    from tools import trace_report

    cache_dir = enable_persistent_compile_cache()
    try:
        device = deviceledger.require_accelerator()
    except deviceledger.NoAcceleratorError as e:
        print(f"bench.py full mode: {e}", file=sys.stderr)
        return 2
    device["compile_cache"] = cache_dir

    watch = CompileWatch()
    watch.arm()
    failed = []

    for name, fn in FULL_CONFIGS:
        traced = bool(args.trace_out) and name in TRACED_CONFIGS
        if traced:
            tracing.enable(capacity=1 << 18)
        compile_before = watch.snap()
        try:
            # the attribution frame names this config as the compile
            # site in /dump_devices (plane flushes carry their own
            # richer per-flush frames on the dispatcher thread)
            with deviceledger.attr_context(f"bench.{name}"):
                r = fn()
        except Exception as e:  # the other cells still run; the run
            # as a whole fails (exit code below)
            failed.append(name)
            r = {"metric": f"{name} FAILED", "value": None, "unit": "",
                 "vs_baseline": None, "extra": {"error": repr(e)[:300]}}
        # cold-compile pollution must be VISIBLE per config: how many
        # backend compiles ran during this config, their total seconds,
        # and how many were absorbed by the persistent cache
        r.setdefault("extra", {})["jax_compile"] = \
            watch.delta(compile_before)
        r["extra"]["device"] = device
        if traced:
            try:
                path = f"{args.trace_out}.{name}.trace.json"
                doc = tracing.export_chrome()  # one ring snapshot
                with open(path, "w") as f:
                    json.dump(doc, f)
                rep = trace_report.stage_report(doc["traceEvents"])
                extra = r.setdefault("extra", {})
                extra["trace_file"] = path
                extra["trace_stages"] = rep["stages"]
                if rep["plane"]:
                    extra["trace_plane"] = rep["plane"]
            except Exception as e:  # noqa: BLE001 - a bad --trace-out
                # path must not kill the remaining configs
                r.setdefault("extra", {})["trace_error"] = repr(e)[:200]
            finally:
                # never leak tracing into the untraced configs/headline
                tracing.disable()
        results[name] = r
        print(json.dumps(r), flush=True)

    floor_p50, floor_min = measure_dispatch_floor()
    compile_before = watch.snap()
    cpu_ms, raw, steady, pack_ms, tbl_ms, resident, overlap = headline_10k()
    headline = {
                "metric": "10k-validator VerifyCommitLight fused p50",
                "value": round(steady, 2),
                "unit": "ms",
                "vs_baseline": round(cpu_ms / steady, 2),
                "extra": {
                    "device": device,
                    "kernel": "pallas-valset-cached + int8 MXU entry fetch",
                    "sigs_per_sec": round(10_000 / (steady / 1000)),
                    "raw_single_shot_p50_ms": round(p50(raw), 2),
                    "dispatch_floor_ms": round(floor_p50, 3),
                    "dispatch_floor_min_ms": round(floor_min, 3),
                    "host_pack_ms": round(pack_ms, 2),
                    # stamped path's residual host cost (sig scatter +
                    # ts word split + flags) — not 0, just small
                    "host_pack_stamped_ms":
                        overlap["host_pack_stamped_ms"],
                    "steady_overlap_ms": overlap["steady_overlap_ms"],
                    "staging_overlap_eff": overlap["staging_overlap_eff"],
                    "table_build_ms_cold_compile": round(tbl_ms["cold"], 1),
                    "table_rebuild_warm_ms": round(tbl_ms["rebuild_warm"], 1),
                    "table_update_10vals_ms": round(tbl_ms["update10"], 1),
                    "steady_resident_ms": round(resident, 2),
                    "sigs_per_sec_resident": round(
                        10_000 / (resident / 1000)),
                    "end_to_end_ms": round(pack_ms + steady, 1),
                    "cpu_measured_ms": round(cpu_ms, 1),
                    "cpu_batch_bound_2x_ms": round(cpu_ms / 2, 1),
                    "baseline_method": "measured single-threaded "
                                       "OpenSSL verify loop on real "
                                       "sign-bytes (no fudge factors)",
                    "configs": {
                        k: {"value": v.get("value"),
                            "unit": v.get("unit"),
                            "vs_baseline": v.get("vs_baseline")}
                        for k, v in results.items()
                    },
                    "total_bench_s": round(time.time() - t0, 1),
                },
            }
    headline["extra"]["jax_compile"] = watch.delta(compile_before)
    print(json.dumps(headline))
    results["headline"] = headline
    rc = _finish(args, results)
    if failed:
        print(f"bench.py: cells raised: {failed}", file=sys.stderr)
        return rc or 1
    return rc


def _finish(args, results: dict) -> int:
    """Shared tail for full and smoke runs: the --baseline comparison
    table (printed as the LAST JSON line so drivers and eyeballs both
    find it), the --json-out evidence file, and the exit code."""
    cmp_doc = None
    if args.baseline:
        cmp_doc = compare_to_baseline(
            results, load_bench_results(args.baseline),
            threshold_pct=args.baseline_threshold)
        print(json.dumps({
            "metric": f"baseline comparison vs {args.baseline}",
            "value": len(cmp_doc["regressed"]),
            "unit": "regressions",
            "vs_baseline": None,
            "extra": cmp_doc,
        }), flush=True)
    if args.json_out:
        doc = {"results": results}
        if cmp_doc is not None:
            doc["baseline_check"] = cmp_doc
        with open(args.json_out, "w") as f:
            json.dump(doc, f, indent=1)
    if args.fail_on_regression and cmp_doc is not None \
            and not cmp_doc["ok"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
