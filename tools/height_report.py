"""Turn a /dump_heights document into a per-stage commit-latency table
with a late-signer section — and DIFF two of them.

The consensus-level sibling of tools/trace_report.py: where the trace
report decomposes a FLUSH, this decomposes a BLOCK — proposal
propagation vs prevote quorum vs precommit quorum vs persist vs apply,
per height, percentile-summarized, with the verify-plane join and the
chronically-late-signer table the DCN round reads. Feed it a saved
``curl $NODE/dump_heights`` file or any JSON holding a ``heights``
list.

Differencing mirrors trace_report --diff: stage-delta rows with
REGRESSED/improved/appeared/vanished flags on mean ms past BOTH a
relative and an absolute threshold, and ``--fail-on-regression`` for
CI gates (requires --diff — a gate wired without a comparison must
error, not read permanently green).

Usage:
    python tools/height_report.py dump.json [--json]
    python tools/height_report.py --diff A.json B.json \
        [--json] [--threshold-pct 10] [--threshold-ms 1.0] \
        [--fail-on-regression]
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools._report_common import (  # noqa: E402 - after sys.path fix
    build_parser, flag_symmetric, load_dump, run_cli)

# per-record STAGE DELTAS derived from the cumulative timeline: each
# row is "time spent inside this stage", so the table sums to the
# commit latency instead of repeating cumulative prefixes
STAGE_BOUNDS = [
    ("proposal", None, "proposal_ms"),
    ("prevote_quorum", "proposal_ms", "prevote_quorum_ms"),
    ("precommit_quorum", "prevote_quorum_ms", "precommit_quorum_ms"),
    ("commit_wait", "precommit_quorum_ms", "commit_ms"),
    ("persist_apply", "commit_ms", "apply_ms"),
]

DEFAULT_THRESHOLD_PCT = 10.0
DEFAULT_THRESHOLD_MS = 1.0


def load_heights(path: str) -> dict:
    """Load {heights, late_signers, summary}: a /dump_heights document
    or a bare {"heights": [...]} object."""
    return load_dump(path, "/dump_heights", "heights")


def _pct(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    s = sorted(xs)
    i = min(len(s) - 1, int(round(q * (len(s) - 1))))
    return s[i]


def _row(name: str, durs: List[float]) -> dict:
    n = len(durs)
    return {
        "stage": name,
        "count": n,
        "total_ms": round(sum(durs), 3),
        "mean_ms": round(sum(durs) / n, 4) if n else 0.0,
        "p50_ms": round(_pct(durs, 0.5), 4),
        "p99_ms": round(_pct(durs, 0.99), 4),
        "max_ms": round(max(durs), 4) if n else 0.0,
    }


def stage_report(dump: dict) -> dict:
    """Aggregate a height dump into the per-stage table + the
    late-signer and attribution extras the text report prints."""
    recs = [r for r in dump.get("heights", [])]
    # only heights with a complete monotone timeline contribute to the
    # per-stage deltas (catch-up pushes and clock-domain-swapped
    # heights carry zeros; their totals would poison the means)
    staged = []
    for r in recs:
        ts = [r.get(k, 0.0) for _, _, k in STAGE_BOUNDS]
        if r.get("via") == "consensus" and all(t > 0 for t in ts) \
                and ts == sorted(ts):
            staged.append(r)
    stage_durs: Dict[str, List[float]] = {}
    for name, lo_key, hi_key in STAGE_BOUNDS:
        durs = []
        for r in staged:
            lo = r.get(lo_key, 0.0) if lo_key else 0.0
            durs.append(max(0.0, r.get(hi_key, 0.0) - lo))
        stage_durs[name] = durs
    commit_lat = [r["apply_ms"] for r in staged]
    stages = [_row(name, stage_durs[name]) for name, _, _ in STAGE_BOUNDS]
    stages.append(_row("total_commit", commit_lat))

    plane_ms = [r.get("plane_ms", 0.0) for r in staged]
    fsync_ms = [r.get("wal_fsync_ms", 0.0) for r in staged]
    return {
        "heights": len(recs),
        "staged_heights": len(staged),
        "skipped_heights": len(recs) - len(staged),
        "stages": stages,
        "commit_p50_ms": round(_pct(commit_lat, 0.5), 3),
        "commit_p99_ms": round(_pct(commit_lat, 0.99), 3),
        "rounds_max": max((r.get("rounds", 0) for r in recs), default=0),
        "multi_round_heights": sum(
            1 for r in recs if r.get("rounds", 0) > 0),
        "plane_ms_mean": round(sum(plane_ms) / len(plane_ms), 3)
        if plane_ms else 0.0,
        "plane_flushes": int(sum(r.get("plane_flushes", 0)
                                 for r in recs)),
        "cold_table_heights": sum(
            1 for r in recs if r.get("cold_tables", 0)),
        "wal_fsync_ms_mean": round(sum(fsync_ms) / len(fsync_ms), 3)
        if fsync_ms else 0.0,
        "catchup_heights": sum(
            1 for r in recs if r.get("via") == "catchup"),
        "late_votes": int(sum(len(r.get("late", [])) for r in recs)),
        # the network-vs-crypto split over every late arrival (rows are
        # [vidx, off, net, sign, via]; pre-ISSUE-14 dumps carry 2-elem
        # rows and contribute zeros)
        "late_net_ms": round(sum(
            row[2] for r in recs for row in r.get("late", [])
            if len(row) >= 4), 3),
        "late_sign_ms": round(sum(
            row[3] for r in recs for row in r.get("late", [])
            if len(row) >= 4), 3),
        "absent_votes": int(sum(r.get("absent", 0) for r in recs)),
        "late_signers": list(dump.get("late_signers", []))[:16],
    }


# --------------------------------------------------------------------------
# differencing (trace_report --diff's shape, over stage mean ms)
# --------------------------------------------------------------------------


def diff_report(rep_a: dict, rep_b: dict,
                threshold_pct: float = DEFAULT_THRESHOLD_PCT,
                threshold_ms: float = DEFAULT_THRESHOLD_MS) -> dict:
    """Stage-delta rows (A = before, B = after) with REGRESSED/
    improved flags: a stage regressed when its mean grew past BOTH the
    relative and absolute thresholds (one guards noise on tiny stages,
    the other on huge-but-stable ones)."""
    a_by = {r["stage"]: r for r in rep_a.get("stages", [])}
    b_by = {r["stage"]: r for r in rep_b.get("stages", [])}
    order = [r["stage"] for r in rep_a.get("stages", [])]
    order += [s for s in b_by if s not in a_by]

    def flag_of(ma: float, mb: float) -> str:
        return flag_symmetric(ma, mb, threshold_pct=threshold_pct,
                              abs_floor=threshold_ms)

    rows = []
    for name in order:
        ra, rb = a_by.get(name), b_by.get(name)
        if ra is None or rb is None:
            rows.append({
                "stage": name,
                "flag": "appeared" if ra is None else "vanished",
                "count_a": ra["count"] if ra else 0,
                "count_b": rb["count"] if rb else 0,
                "mean_ms_a": ra["mean_ms"] if ra else 0.0,
                "mean_ms_b": rb["mean_ms"] if rb else 0.0,
                "p99_ms_a": ra["p99_ms"] if ra else 0.0,
                "p99_ms_b": rb["p99_ms"] if rb else 0.0,
                "delta_mean_ms": round(
                    (rb["mean_ms"] if rb else 0.0)
                    - (ra["mean_ms"] if ra else 0.0), 4),
                "delta_pct": None,
            })
            continue
        d = rb["mean_ms"] - ra["mean_ms"]
        rows.append({
            "stage": name,
            "flag": flag_of(ra["mean_ms"], rb["mean_ms"]),
            "count_a": ra["count"], "count_b": rb["count"],
            "mean_ms_a": ra["mean_ms"], "mean_ms_b": rb["mean_ms"],
            "p99_ms_a": ra["p99_ms"], "p99_ms_b": rb["p99_ms"],
            "delta_mean_ms": round(d, 4),
            "delta_pct": round(d / ra["mean_ms"] * 100.0, 1)
            if ra["mean_ms"] else None,
        })

    # attribution deltas worth a flag of their own: cold tables
    # appearing (the warmer stopped absorbing rotations) and round
    # escalation appearing (quorum health changed)
    notes = []
    if rep_b.get("cold_table_heights", 0) \
            > rep_a.get("cold_table_heights", 0):
        notes.append(
            f"cold tables grew: {rep_a.get('cold_table_heights', 0)} "
            f"-> {rep_b.get('cold_table_heights', 0)} heights paid an "
            f"inline valset table build (check the next-epoch warmer)")
    if rep_b.get("multi_round_heights", 0) \
            > rep_a.get("multi_round_heights", 0):
        notes.append(
            f"round escalation grew: "
            f"{rep_a.get('multi_round_heights', 0)} -> "
            f"{rep_b.get('multi_round_heights', 0)} multi-round "
            f"heights")

    regressions = [r["stage"] for r in rows
                   if r["flag"] == "REGRESSED"
                   or (r["flag"] == "appeared"
                       and r["mean_ms_b"] >= threshold_ms)]
    return {"stages": rows, "regressions": regressions, "notes": notes,
            "commit_p99_ms_a": rep_a.get("commit_p99_ms", 0.0),
            "commit_p99_ms_b": rep_b.get("commit_p99_ms", 0.0),
            "heights_a": rep_a.get("heights", 0),
            "heights_b": rep_b.get("heights", 0)}


# --------------------------------------------------------------------------
# formatting
# --------------------------------------------------------------------------


def format_report(rep: dict) -> str:
    lines = [f"{rep['heights']} heights in the ledger window "
             f"({rep['staged_heights']} with a full stage timeline"
             + (f", {rep['skipped_heights']} skipped: catch-up or "
                f"partial stamps" if rep["skipped_heights"] else "")
             + ")"]
    lines += ["", f"{'stage':<20}{'count':>7}{'mean ms':>10}"
                  f"{'p50 ms':>10}{'p99 ms':>10}{'max ms':>10}"]
    for r in rep["stages"]:
        lines.append(f"{r['stage']:<20}{r['count']:>7}"
                     f"{r['mean_ms']:>10.3f}{r['p50_ms']:>10.3f}"
                     f"{r['p99_ms']:>10.3f}{r['max_ms']:>10.3f}")
    lines += ["",
              f"commit latency p50/p99: {rep['commit_p50_ms']}/"
              f"{rep['commit_p99_ms']} ms; "
              f"verify-plane {rep['plane_ms_mean']} ms/height over "
              f"{rep['plane_flushes']} joined flushes; "
              f"WAL fsync {rep['wal_fsync_ms_mean']} ms/height"]
    if rep["multi_round_heights"]:
        lines.append(
            f"ROUND ESCALATION: {rep['multi_round_heights']} height(s) "
            f"needed extra rounds (max round {rep['rounds_max']})")
    if rep["cold_table_heights"]:
        lines.append(
            f"COLD TABLES: {rep['cold_table_heights']} height(s) "
            f"joined a flush that paid an inline valset table build "
            f"(post-rotation stall — check the next-epoch warmer)")
    if rep["catchup_heights"]:
        lines.append(f"{rep['catchup_heights']} height(s) arrived via "
                     f"catch-up push (no stage timeline)")
    if rep["late_signers"]:
        lines += ["", "chronically late signers (heights late after "
                      "quorum / absent from commit; net = in flight, "
                      "sign = signed late):"]
        lines.append(f"{'validator':>10}{'late':>7}{'absent':>8}"
                     f"{'total':>8}{'net ms':>10}{'sign ms':>10}")
        for row in rep["late_signers"]:
            lines.append(f"{row['val']:>10}{row['late_heights']:>7}"
                         f"{row['absent_heights']:>8}{row['total']:>8}"
                         f"{row.get('net_ms', 0.0):>10.3f}"
                         f"{row.get('sign_ms', 0.0):>10.3f}")
    elif rep["late_votes"] or rep["absent_votes"]:
        lines.append(f"late votes: {rep['late_votes']}, absent "
                     f"precommits: {rep['absent_votes']}")
    if rep.get("late_net_ms") or rep.get("late_sign_ms"):
        lines.append(
            f"late-vote decomposition: {rep['late_net_ms']} ms in "
            f"flight (network) vs {rep['late_sign_ms']} ms signed "
            f"late (crypto/host) — see /dump_peers for the hops")
    return "\n".join(lines)


def format_diff(diff: dict, path_a: str = "A", path_b: str = "B") -> str:
    lines = [f"height stage-delta: {path_a} ({diff['heights_a']} "
             f"heights) -> {path_b} ({diff['heights_b']} heights)"]
    lines += ["", f"{'stage':<20}{'cnt A':>6}{'cnt B':>6}"
                  f"{'mean A':>9}{'mean B':>9}{'Δ ms':>9}{'Δ %':>8}"
                  f"  {'flag'}"]
    for r in diff["stages"]:
        pct = f"{r['delta_pct']:+.1f}" if r["delta_pct"] is not None \
            else "-"
        lines.append(
            f"{r['stage']:<20}{r['count_a']:>6}{r['count_b']:>6}"
            f"{r['mean_ms_a']:>9.3f}{r['mean_ms_b']:>9.3f}"
            f"{r['delta_mean_ms']:>+9.3f}{pct:>8}  {r['flag']}")
    lines += ["", f"commit p99: {diff['commit_p99_ms_a']} -> "
                  f"{diff['commit_p99_ms_b']} ms"]
    for n in diff.get("notes", []):
        lines.append(f"NOTE: {n}")
    lines += ["", ("regressions: " + ", ".join(diff["regressions"])
                   if diff["regressions"] else "no regressions flagged")]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = build_parser(
        "per-stage commit-latency table from a /dump_heights "
        "document, or a stage-delta diff of two of them",
        operand_help="height dump file(s); two files with --diff",
        diff_help="diff two dumps: stage-delta table with regression "
                  "flags",
        default_pct=DEFAULT_THRESHOLD_PCT,
        default_abs=DEFAULT_THRESHOLD_MS,
        pct_help="relative regression floor (mean ms, %%)",
        abs_flag="--threshold-ms",
        abs_help="absolute regression floor (mean ms)")
    return run_cli(argv, parser=ap, load=load_heights,
                   report=stage_report, diff=diff_report,
                   fmt_report=format_report, fmt_diff=format_diff)


if __name__ == "__main__":
    raise SystemExit(main())
