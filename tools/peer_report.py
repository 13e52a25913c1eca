"""Turn a /dump_peers document into a per-peer traffic/health table —
and DIFF two of them.

The p2p-level sibling of tools/trace_report.py and
tools/height_report.py: where those decompose a FLUSH and a BLOCK,
this decomposes the GOSSIP PLANE — per peer: msgs/bytes each way, send
queue high-water, blocked puts, full-queue drops, throttle stalls,
link drops, injected-fault attribution, ping RTT, and duplicate-vote
receipts. Feed it a saved ``curl $NODE/dump_peers`` file or any JSON
holding a ``peers`` list.

Differencing mirrors trace_report --diff: health-counter delta rows
with REGRESSED/improved flags past BOTH a relative and an absolute
threshold, and ``--fail-on-regression`` for CI gates (requires --diff
— a gate wired without a comparison must error, not read permanently
green). Counters here are cumulative-by-construction, so the diff
compares the two windows' TOTALS: growth in drops/stalls/RTT between
two captures of the same node is a real health change.

Usage:
    python tools/peer_report.py dump.json [--json]
    python tools/peer_report.py --diff A.json B.json \
        [--json] [--threshold-pct 25] [--threshold-abs 8] \
        [--fail-on-regression]
"""
from __future__ import annotations

import os
import sys
from typing import List

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools._report_common import (  # noqa: E402 - after sys.path fix
    build_parser, flag_symmetric, load_dump, run_cli)

# aggregate health counters the diff flags on: bigger = sicker
HEALTH_KEYS = ("blocked_puts", "full_drops", "throttle_stalls",
               "link_drops", "inj_drops", "inj_delays", "dup_votes")

DEFAULT_THRESHOLD_PCT = 25.0
DEFAULT_THRESHOLD_ABS = 8.0


def load_peers(path: str) -> dict:
    """Load {summary, peers, events}: a /dump_peers document or a bare
    {"peers": [...]} object."""
    return load_dump(path, "/dump_peers", "peers")


def peer_report(dump: dict) -> dict:
    """Aggregate a peer dump into the table + totals the text report
    prints and the diff compares."""
    peers = list(dump.get("peers", []))
    summary = dict(dump.get("summary", {}))
    rows = []
    for p in peers:
        rows.append({
            "peer": p.get("peer", "?"),
            "dir": p.get("dir", "?"),
            "state": p.get("state", "?")
            + (f"({p['reason']})" if p.get("reason") else ""),
            "msgs_tx": p.get("msgs_tx", 0),
            "bytes_tx": p.get("bytes_tx", 0),
            "msgs_rx": p.get("msgs_rx", 0),
            "bytes_rx": p.get("bytes_rx", 0),
            "q_hiwater": p.get("q_hiwater", 0),
            "blocked_puts": p.get("blocked_puts", 0),
            "full_drops": p.get("full_drops", 0),
            "throttle_stalls": p.get("throttle_stalls", 0),
            "link_drops": p.get("link_drops", 0),
            "inj": p.get("inj_drops", 0) + p.get("inj_delays", 0),
            "rtt_ms": p.get("rtt_ms", 0.0),
            "dup_votes": p.get("dup_votes", 0),
        })
    # prefer the dump's summary totals: they fold in ring-evicted
    # records, so they stay monotone across captures (the per-peer
    # rows are only the retained window); fall back to summing rows
    # for bare {"peers": [...]} inputs
    totals = {k: int(summary.get(k, sum(p.get(k, 0) for p in peers)))
              for k in HEALTH_KEYS}
    rtts = sorted(p.get("rtt_ms", 0.0) for p in peers
                  if p.get("pings", 0))
    return {
        "peers": len(peers),
        "peers_live": summary.get("peers_live", 0),
        "peers_dropped": summary.get("peers_dropped", 0),
        "rows": rows,
        "totals": totals,
        "msgs_tx": summary.get("msgs_tx", 0),
        "msgs_rx": summary.get("msgs_rx", 0),
        "bytes_tx": summary.get("bytes_tx", 0),
        "bytes_rx": summary.get("bytes_rx", 0),
        "rtt_p50_ms": rtts[len(rtts) // 2] if rtts else 0.0,
        "rtt_max_ms": rtts[-1] if rtts else 0.0,
        "q_hiwater": max((p.get("q_hiwater", 0) for p in peers),
                         default=0),
        "votes": summary.get("votes", {}),
        "events": len(dump.get("events", [])),
    }


# --------------------------------------------------------------------------
# differencing (trace_report --diff's shape, over the health totals)
# --------------------------------------------------------------------------


def diff_report(rep_a: dict, rep_b: dict,
                threshold_pct: float = DEFAULT_THRESHOLD_PCT,
                threshold_abs: float = DEFAULT_THRESHOLD_ABS) -> dict:
    """Health-counter delta rows (A = before, B = after): a counter
    REGRESSED when it grew past BOTH the relative and absolute
    thresholds (relative guards big-but-stable counters, absolute
    guards noise on tiny ones); RTT p50 diffs as its own row."""

    def flag_of(a: float, b: float) -> str:
        return flag_symmetric(a, b, threshold_pct=threshold_pct,
                              abs_floor=threshold_abs)

    rows = []
    for key in HEALTH_KEYS:
        a = rep_a["totals"].get(key, 0)
        b = rep_b["totals"].get(key, 0)
        rows.append({"metric": key, "a": a, "b": b, "delta": b - a,
                     "flag": flag_of(a, b)})
    a_rtt, b_rtt = rep_a["rtt_p50_ms"], rep_b["rtt_p50_ms"]
    rows.append({"metric": "rtt_p50_ms", "a": a_rtt, "b": b_rtt,
                 "delta": round(b_rtt - a_rtt, 3),
                 "flag": flag_of(a_rtt, b_rtt)})
    a_q, b_q = rep_a["q_hiwater"], rep_b["q_hiwater"]
    rows.append({"metric": "q_hiwater", "a": a_q, "b": b_q,
                 "delta": b_q - a_q, "flag": flag_of(a_q, b_q)})

    notes = []
    if rep_b["peers_dropped"] > rep_a["peers_dropped"]:
        notes.append(
            f"peer churn grew: {rep_a['peers_dropped']} -> "
            f"{rep_b['peers_dropped']} dropped peers (check the "
            f"lifecycle events for the drop reasons)")
    dup_a = rep_a.get("votes", {}).get("dups", 0)
    dup_b = rep_b.get("votes", {}).get("dups", 0)
    if dup_b > max(2 * dup_a, dup_a + threshold_abs):
        notes.append(
            f"duplicate vote deliveries grew: {dup_a} -> {dup_b} "
            f"(lack-based gossip healing is lagging)")

    regressions = [r["metric"] for r in rows if r["flag"] == "REGRESSED"]
    return {"rows": rows, "regressions": regressions, "notes": notes,
            "peers_a": rep_a["peers"], "peers_b": rep_b["peers"]}


# --------------------------------------------------------------------------
# formatting
# --------------------------------------------------------------------------


def format_report(rep: dict) -> str:
    lines = [f"{rep['peers']} peers in the ledger window "
             f"({rep['peers_live']} live, {rep['peers_dropped']} "
             f"dropped, {rep['events']} lifecycle events)"]
    lines += ["", f"{'peer':<14}{'dir':>4}{'state':>18}"
                  f"{'tx msgs/B':>14}{'rx msgs/B':>14}{'q_hi':>6}"
                  f"{'blkd':>6}{'drop':>6}{'thr':>5}{'link':>6}"
                  f"{'inj':>5}{'rtt ms':>8}{'dupV':>6}"]
    for r in rep["rows"]:
        lines.append(
            f"{r['peer']:<14}{r['dir']:>4}{r['state']:>18}"
            f"{str(r['msgs_tx']) + '/' + str(r['bytes_tx']):>14}"
            f"{str(r['msgs_rx']) + '/' + str(r['bytes_rx']):>14}"
            f"{r['q_hiwater']:>6}{r['blocked_puts']:>6}"
            f"{r['full_drops']:>6}{r['throttle_stalls']:>5}"
            f"{r['link_drops']:>6}{r['inj']:>5}"
            f"{r['rtt_ms']:>8.3f}{r['dup_votes']:>6}")
    t = rep["totals"]
    lines += ["",
              f"totals: {rep['msgs_tx']} msgs/{rep['bytes_tx']} B out, "
              f"{rep['msgs_rx']} msgs/{rep['bytes_rx']} B in; "
              f"blocked={t['blocked_puts']} full_drops={t['full_drops']} "
              f"throttle={t['throttle_stalls']} "
              f"link_drops={t['link_drops']} "
              f"injected={t['inj_drops']}d/{t['inj_delays']}s "
              f"dup_votes={t['dup_votes']}"]
    if rep["rtt_p50_ms"] or rep["rtt_max_ms"]:
        lines.append(f"ping RTT p50/max: {rep['rtt_p50_ms']}/"
                     f"{rep['rtt_max_ms']} ms")
    v = rep.get("votes") or {}
    if v.get("seen"):
        lines.append(
            f"vote routes: {v['seen']} first-seen, {v['dups']} "
            f"duplicate receipts, {v['relayed']} relays "
            f"({v.get('tracked', 0)} tracked now)")
    if t["full_drops"] or t["blocked_puts"]:
        lines.append(
            f"STARVATION: {t['full_drops']} full-queue drops / "
            f"{t['blocked_puts']} blocked puts — check /dump_incidents "
            f"for a peer_starvation snapshot and the per-peer rows "
            f"above for WHICH queue")
    return "\n".join(lines)


def format_diff(diff: dict, path_a: str = "A", path_b: str = "B") -> str:
    lines = [f"peer-health delta: {path_a} ({diff['peers_a']} peers) "
             f"-> {path_b} ({diff['peers_b']} peers)"]
    lines += ["", f"{'metric':<18}{'A':>10}{'B':>10}{'Δ':>10}  flag"]
    for r in diff["rows"]:
        lines.append(f"{r['metric']:<18}{r['a']:>10}{r['b']:>10}"
                     f"{r['delta']:>+10}  {r['flag']}")
    for n in diff.get("notes", []):
        lines.append(f"NOTE: {n}")
    lines += ["", ("regressions: " + ", ".join(diff["regressions"])
                   if diff["regressions"] else "no regressions flagged")]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = build_parser(
        "per-peer traffic/health table from a /dump_peers document, "
        "or a health delta diff of two of them",
        operand_help="peer dump file(s); two files with --diff",
        diff_help="diff two dumps: health-counter delta table with "
                  "regression flags",
        default_pct=DEFAULT_THRESHOLD_PCT,
        default_abs=DEFAULT_THRESHOLD_ABS,
        abs_help="absolute regression floor (count / ms)")
    return run_cli(argv, parser=ap, load=load_peers,
                   report=peer_report, diff=diff_report,
                   fmt_report=format_report, fmt_diff=format_diff)


if __name__ == "__main__":
    raise SystemExit(main())
