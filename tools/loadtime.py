"""loadtime: open-loop transaction load generator (test/loadtime analog).

Open-loop means HONEST: txs are injected at fixed target times derived
only from the configured rate — never gated on the previous response —
so the measured latencies include queueing delay under overload instead
of the generator politely slowing down to whatever the node can absorb
(closed-loop generators hide exactly the collapse this tool exists to
measure; see test/loadtime in the reference repo).

Three modes:

  * in-process (default): a LocalNetwork of real Nodes (kvstore app,
    fast timeouts, admission control + sigtx verification on) floods
    node 0's broadcast_tx path while the net commits blocks — reports
    offered/accepted txs/sec, commits/sec, CheckTx latency percentiles,
    and every overload verdict observed;
  * --rpc URL: drive a LIVE node's JSON-RPC broadcast_tx_sync with the
    same open-loop discipline (urllib, thread pool sized to the rate);
  * --smoke: tier-1 mode — mempool + admission + host verify plane
    only (no consensus, NO jax import), tiny rates, finishes in a few
    seconds; exists so CI catches loadtime rot and keeps the
    overload verdict path (explicit OVERLOADED codes with retry hints)
    continuously exercised.

Every mode prints one JSON document on stdout.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time


def _percentiles(xs):
    from cometbft_tpu.libs.quantiles import wait_summary_ms

    return wait_summary_ms(xs)


class OpenLoopRun:
    """Aggregates one open-loop run's per-tx outcomes."""

    def __init__(self):
        self._lock = threading.Lock()
        self.offered = 0
        self.codes: dict = {}
        self.lat_ms = []
        self.overload_logs = []
        self.late = 0  # injections that missed their target slot >50ms

    def record(self, code, lat_ms: float, log: str = "") -> None:
        with self._lock:
            self.offered += 1
            self.codes[code] = self.codes.get(code, 0) + 1
            self.lat_ms.append(lat_ms)
            if code == 1001 and len(self.overload_logs) < 8:
                self.overload_logs.append(log)

    def report(self, wall_s: float, extra=None) -> dict:
        from cometbft_tpu.abci import types as abci

        accepted = self.codes.get(abci.CODE_TYPE_OK, 0)
        overloaded = self.codes.get(abci.CODE_TYPE_OVERLOADED, 0)
        out = {
            "offered": self.offered,
            "accepted": accepted,
            "overloaded": overloaded,
            "rejected_other": self.offered - accepted - overloaded,
            "offered_tx_per_s": round(self.offered / wall_s, 1)
            if wall_s else 0.0,
            "accepted_tx_per_s": round(accepted / wall_s, 1)
            if wall_s else 0.0,
            "checktx_latency": _percentiles(self.lat_ms),
            "codes": {str(k): v for k, v in sorted(self.codes.items())},
            "late_injections": self.late,
            "overload_log_samples": self.overload_logs,
            "wall_s": round(wall_s, 2),
        }
        if extra:
            out.update(extra)
        return out


def open_loop(rate: float, duration: float, make_tx, submit,
              run: OpenLoopRun, workers: int = 4) -> float:
    """Fire `rate * duration` submissions at fixed target times on a
    small worker pool (a slow response must not stall the schedule —
    that is the whole point). Returns the wall seconds elapsed."""
    import queue as _q

    count = int(round(rate * duration))
    q: "_q.Queue" = _q.Queue()
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            try:
                k, tx = q.get(timeout=0.2)
            except _q.Empty:
                continue
            t = time.perf_counter()
            try:
                code, log = submit(tx)
            except Exception as e:  # noqa: BLE001 - counted, not fatal
                code, log = -1, repr(e)[:120]
            run.record(code, (time.perf_counter() - t) * 1000, log)
            q.task_done()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(max(1, workers))]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    for k in range(count):
        target = t0 + k / rate
        lag = target - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        elif lag < -0.05:
            run.late += 1
        q.put((k, make_tx(k)))
    q.join()
    stop.set()
    return time.perf_counter() - t0


# --------------------------------------------------------------------------
# tx builders
# --------------------------------------------------------------------------


def make_tx_builder(signed: bool, size: int, tag: str = "lt"):
    if not signed:
        return lambda k: (b"%s-%d=" % (tag.encode(), k)).ljust(size, b"x")
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.mempool import sigtx

    priv = PrivKey.generate(b"loadtime-sigtx-key" + b"\x00" * 14)

    def build(k: int) -> bytes:
        payload = (b"%s-%d=" % (tag.encode(), k)).ljust(size, b"x")
        return sigtx.wrap(priv, payload)

    return build


# --------------------------------------------------------------------------
# --smoke: mempool + admission + host verify plane, no consensus, no jax
# --------------------------------------------------------------------------


def run_smoke(rate: float = 400.0, duration: float = 2.0,
              pool_size: int = 64) -> dict:
    """Host-only miniature: floods a Mempool (kvstore app, admission
    control, sigtx verification through a host-path verify plane) past
    its watermarks, so BOTH outcomes are exercised: accepted txs AND
    explicit OVERLOADED verdicts with retry hints. Asserts jax was
    never imported — this is the tier-1 guard's contract."""
    jax_loaded_before = "jax" in sys.modules

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config.config import MempoolConfig
    from cometbft_tpu.mempool.mempool import Mempool
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    mcfg = MempoolConfig(size=pool_size, high_watermark=0.5,
                         low_watermark=0.3, max_inflight_checktx=8,
                         retry_after_ms=100.0)
    mp = Mempool(KVStoreApplication(), max_txs=mcfg.size,
                 verify_sigs=True)
    mp.admission = mcfg.build_admission(fill_fn=mp.fill_fraction)
    plane = VerifyPlane(window_ms=0.5, use_device=False,
                        bulk_deadline_ms=100.0)
    plane.start()
    set_global_plane(plane)
    run = OpenLoopRun()
    try:
        wall = open_loop(rate, duration,
                         make_tx_builder(True, 32, tag="smoke"),
                         lambda tx: _submit_mempool(mp, tx), run,
                         workers=8)
    finally:
        set_global_plane(None)
        plane.stop()
    pstats = plane.stats()
    rep = run.report(wall, extra={
        "mode": "smoke (mempool+plane only, no consensus, no jax)",
        "plane": {"lane_rows": pstats["lane_rows"],
                  "sheds": pstats["sheds"],
                  "lane_waits": plane.lane_wait_stats()},
        "admission": mp.admission.stats(),
        # already-loaded jax (a test process that ran device suites
        # first) is not OUR import — the contract is that the smoke
        # path itself never pulls it in
        "jax_imported": "jax" in sys.modules and not jax_loaded_before,
    })
    # smoke contract: the flood must overfill the tiny pool, so the
    # overload path really ran — and jax must never load
    assert rep["accepted"] > 0, "smoke flood accepted nothing"
    assert rep["overloaded"] > 0, \
        "smoke flood never tripped admission/shedding"
    assert all("retry_after_ms=" in s for s in rep["overload_log_samples"])
    assert not rep["jax_imported"], "--smoke must not import jax"
    return rep


def _submit_mempool(mp, tx: bytes):
    resp = mp.check_tx(tx)
    return resp.code, resp.log


# --------------------------------------------------------------------------
# in-process full-node mode
# --------------------------------------------------------------------------


def run_inprocess(rate: float, duration: float, n_nodes: int = 4,
                  signed: bool = True, size: int = 32,
                  plane: bool = True) -> dict:
    """A real LocalNetwork committing blocks while node 0 is flooded
    through broadcast_tx — the sustained-consensus-throughput shape
    (ROADMAP item 5) without the TCP stack in the way."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config.config import MempoolConfig
    from cometbft_tpu.consensus.ticker import TimeoutParams
    from cometbft_tpu.crypto.keys import PrivKey
    from cometbft_tpu.node.node import LocalNetwork, Node
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.state.state import State
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.verifyplane import VerifyPlane, set_global_plane

    fast = TimeoutParams(propose=0.4, propose_delta=0.1,
                         prevote=0.2, prevote_delta=0.1,
                         precommit=0.2, precommit_delta=0.1,
                         commit=0.05)
    privs = [PrivKey.generate(bytes([i + 1]) * 32)
             for i in range(n_nodes)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    state = State.make_genesis("loadtime-chain", vals)
    net = LocalNetwork()
    nodes = []
    mcfg = MempoolConfig()
    for i, priv in enumerate(privs):
        node = Node(KVStoreApplication(), state.copy(),
                    privval=FilePV(priv), broadcast=net.broadcaster(i),
                    timeouts=fast, mempool_config=mcfg)
        net.add(node)
        nodes.append(node)
    vplane = None
    if plane:
        vplane = VerifyPlane(window_ms=1.0, use_device=False,
                             bulk_deadline_ms=250.0)
        vplane.start()
        set_global_plane(vplane)
    for n in nodes:
        n.start()
    run = OpenLoopRun()
    try:
        h0 = nodes[0].height()
        wall = open_loop(rate, duration,
                         make_tx_builder(signed, size),
                         lambda tx: _submit_mempool(nodes[0].mempool, tx),
                         run, workers=8)
        h1 = max(n.height() for n in nodes)
        commits = h1 - h0
    finally:
        if vplane is not None:
            set_global_plane(None)
        for n in nodes:
            n.stop()
        if vplane is not None:
            vplane.stop()
    extra = {
        "mode": f"in-process LocalNetwork x{n_nodes}",
        "commits": commits,
        "commits_per_s": round(commits / wall, 2) if wall else 0.0,
        "admission": nodes[0].mempool.admission.stats()
        if nodes[0].mempool.admission else None,
    }
    # per-height commit-latency attribution from node 0's always-on
    # height ledger (trimmed: the printed document must not carry
    # 512 full records), with the height_report table beside it
    try:
        from tools import height_report

        hd = nodes[0].consensus.height_ledger.dump()
        hd["heights"] = hd["heights"][-64:]
        rep = height_report.stage_report(hd)
        extra["height_dump"] = hd
        extra["height_stage_table"] = rep["stages"]
        extra["commit_p50_ms"] = rep["commit_p50_ms"]
        extra["commit_p99_ms"] = rep["commit_p99_ms"]
    except Exception as e:  # noqa: BLE001 - report, don't kill the run
        extra["height_dump_error"] = repr(e)[:200]
    if vplane is not None:
        ps = vplane.stats()
        extra["plane"] = {"lane_rows": ps["lane_rows"],
                          "sheds": ps["sheds"],
                          "lane_waits": vplane.lane_wait_stats()}
    return run.report(wall, extra=extra)


# --------------------------------------------------------------------------
# --rpc mode: flood a live node over JSON-RPC
# --------------------------------------------------------------------------


def run_rpc(url: str, rate: float, duration: float,
            signed: bool = False, size: int = 32) -> dict:
    import base64
    import urllib.request

    def submit(tx: bytes):
        body = json.dumps({
            "jsonrpc": "2.0", "id": 1, "method": "broadcast_tx_sync",
            "params": {"tx": base64.b64encode(tx).decode()},
        }).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            doc = json.loads(r.read().decode())
        res = doc.get("result") or {}
        log = res.get("log", "")
        if "retry_after_ms" in res and "retry_after_ms=" not in log:
            log += f" retry_after_ms={res['retry_after_ms']}"
        return res.get("code", -1), log

    run = OpenLoopRun()
    wall = open_loop(rate, duration, make_tx_builder(signed, size),
                     submit, run, workers=16)
    return run.report(wall, extra={"mode": f"rpc {url}"})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="open-loop tx load generator (test/loadtime analog)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="offered tx rate per second (open-loop)")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="seconds of sustained offered load")
    ap.add_argument("--nodes", type=int, default=4,
                    help="in-process mode: LocalNetwork size")
    ap.add_argument("--size", type=int, default=32,
                    help="tx payload bytes")
    ap.add_argument("--unsigned", action="store_true",
                    help="plain txs (skip the sigtx envelope)")
    ap.add_argument("--rpc", default="",
                    help="flood a live node's JSON-RPC URL instead of "
                         "an in-process net")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 mode: mempool+plane only, no "
                         "consensus, no jax import, ~3 s")
    args = ap.parse_args(argv)
    if args.smoke:
        rep = run_smoke()
    elif args.rpc:
        rep = run_rpc(args.rpc, args.rate, args.duration,
                      signed=not args.unsigned, size=args.size)
    else:
        rep = run_inprocess(args.rate, args.duration, args.nodes,
                            signed=not args.unsigned, size=args.size)
    print(json.dumps(rep, indent=1))
    return 0


if __name__ == "__main__":
    import os

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    raise SystemExit(main())
