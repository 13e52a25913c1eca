"""Turn a /dump_catchup document into a replay throughput report —
and DIFF two of them.

The bootstrap-plane sibling of tools/tenant_report.py and
controller_report.py: where those decompose the POD and the LOOP, this
decomposes a REPLAY — per fused flush: heights covered, signatures
verified, read/verify/apply time, valset-boundary and warm-ahead
flags, resume-skip counts — plus the run figures (blocks/sec,
sigs/sec, boundary count, warm requests, resumes, and the time split
between reading history, verifying commits, and applying blocks).
Feed it a saved ``curl $NODE/dump_catchup`` file.

Differencing mirrors tenant_report --diff: figure delta rows with
REGRESSED/improved flags past BOTH a relative and an absolute
threshold, and ``--fail-on-regression`` for CI gates (requires --diff
— a gate wired without a comparison must error, not read permanently
green). Flags: blocks/sec or sigs/sec decay (the firehose got
slower), verify-time growth (cold epoch tables — check the warm-ahead
column), and re-verified blocks appearing where a resume should have
skipped them.

Usage:
    python tools/catchup_report.py dump.json [--json]
    python tools/catchup_report.py --diff A.json B.json \
        [--json] [--threshold-pct 25] [--threshold-abs 4] \
        [--fail-on-regression]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools._report_common import (  # noqa: E402 - after sys.path fix
    build_parser, flag_directed, load_dump, run_cli)

DEFAULT_THRESHOLD_PCT = 25.0
DEFAULT_THRESHOLD_ABS = 4.0


def load_catchup(path: str) -> dict:
    """Load a /dump_catchup document (or a bare {"records": ...,
    "counters": ...} object)."""
    return load_dump(path, "/dump_catchup", "records", "counters")


def catchup_report(dump: dict) -> dict:
    """Aggregate a catch-up dump into the figures the text report
    prints and the diff compares."""
    recs = list(dump.get("records") or [])
    counters = dict(dump.get("counters") or {})
    summary = dict(dump.get("summary") or {})
    read_ms = sum(r.get("read_ms", 0.0) for r in recs)
    verify_ms = sum(r.get("verify_ms", 0.0) for r in recs)
    apply_ms = sum(r.get("apply_ms", 0.0) for r in recs)
    busy_ms = read_ms + verify_ms + apply_ms
    return {
        "flushes": counters.get("flushes", len(recs)),
        "blocks_applied": counters.get("blocks_applied", 0),
        "blocks_verified": counters.get("blocks_verified", 0),
        "blocks_skipped": counters.get("blocks_skipped", 0),
        "sigs_verified": counters.get("sigs_verified", 0),
        "boundaries": counters.get("boundaries", 0),
        "warm_requests": counters.get("warm_requests", 0),
        "resumes": counters.get("resumes", 0),
        "blocks_per_s": summary.get("blocks_per_s", 0.0),
        "sigs_per_s": summary.get("sigs_per_s", 0.0),
        "read_ms": round(read_ms, 3),
        "verify_ms": round(verify_ms, 3),
        "apply_ms": round(apply_ms, 3),
        "verify_frac": round(verify_ms / busy_ms, 3) if busy_ms else 0.0,
        "records": recs,
    }


# --------------------------------------------------------------------------
# differencing (tenant_report --diff's shape, over the replay figures)
# --------------------------------------------------------------------------


def diff_report(rep_a: dict, rep_b: dict,
                threshold_pct: float = DEFAULT_THRESHOLD_PCT,
                threshold_abs: float = DEFAULT_THRESHOLD_ABS) -> dict:
    """Replay-figure delta rows (A = before, B = after). DECAY is bad
    for the rate figures; GROWTH is bad for verify time and for
    re-verified blocks a resume should have skipped. A figure flags
    REGRESSED only past BOTH thresholds."""

    def flag(a: float, b: float, bad_when: str,
             abs_floor: float = threshold_abs) -> str:
        return flag_directed(a, b, bad_when=bad_when,
                             threshold_pct=threshold_pct,
                             abs_floor=abs_floor)

    def row(metric: str, bad_when: str,
            abs_floor: float = threshold_abs) -> dict:
        a, b = rep_a[metric], rep_b[metric]
        return {"metric": metric, "a": a, "b": b,
                "delta": round(b - a, 4),
                "flag": flag(a, b, bad_when, abs_floor)}

    rows = [
        row("blocks_per_s", bad_when="down"),
        row("sigs_per_s", bad_when="down"),
        row("verify_ms", bad_when="up",
            abs_floor=max(threshold_abs, 50.0)),
        row("blocks_verified", bad_when="up"),
        {"metric": "blocks_applied", "a": rep_a["blocks_applied"],
         "b": rep_b["blocks_applied"],
         "delta": rep_b["blocks_applied"] - rep_a["blocks_applied"],
         "flag": ""},
        {"metric": "boundaries", "a": rep_a["boundaries"],
         "b": rep_b["boundaries"],
         "delta": rep_b["boundaries"] - rep_a["boundaries"],
         "flag": ""},
    ]

    notes = []
    if rep_b["resumes"] > rep_a["resumes"] \
            and rep_b["blocks_skipped"] <= rep_a["blocks_skipped"]:
        notes.append(
            "B resumed from a cursor but skipped no additional "
            "blocks — the resume re-verified work the cursor should "
            "have covered; check the cursor file survived the restart")
    if rep_b["boundaries"] and not rep_b["warm_requests"]:
        notes.append(
            "B crossed valset boundaries with ZERO warm-ahead "
            "requests — every epoch paid a cold table build; check "
            "the warmer was mounted")

    regressions = [r["metric"] for r in rows
                   if r["flag"] == "REGRESSED"]
    return {"rows": rows, "regressions": regressions, "notes": notes}


# --------------------------------------------------------------------------
# formatting
# --------------------------------------------------------------------------


def format_report(rep: dict) -> str:
    lines = [
        f"catch-up: {rep['blocks_applied']} blocks applied in "
        f"{rep['flushes']} fused flushes ({rep['blocks_verified']} "
        f"verified, {rep['blocks_skipped']} resume-skipped, "
        f"{rep['sigs_verified']} sigs); "
        f"{rep['blocks_per_s']} blocks/s, {rep['sigs_per_s']} sigs/s",
        f"time split: read {rep['read_ms']}ms, verify "
        f"{rep['verify_ms']}ms ({rep['verify_frac']:.0%} of busy), "
        f"apply {rep['apply_ms']}ms; {rep['boundaries']} valset "
        f"boundaries, {rep['warm_requests']} warm-ahead requests, "
        f"{rep['resumes']} resumes"]
    if rep["records"]:
        lines += ["", f"{'seq':>5}{'first':>9}{'last':>9}{'blks':>6}"
                      f"{'sigs':>8}{'skip':>6}{'read':>8}{'vrfy':>8}"
                      f"{'appl':>8}  flags"]
        for r in rep["records"][-24:]:
            flags = ("B" if r.get("boundary") else "") \
                + ("W" if r.get("warmed") else "")
            lines.append(
                f"{r['seq']:>5}{r['first']:>9}{r['last']:>9}"
                f"{r['blocks']:>6}{r['sigs']:>8}{r['skipped']:>6}"
                f"{r['read_ms']:>8}{r['verify_ms']:>8}"
                f"{r['apply_ms']:>8}  {flags}")
    return "\n".join(lines)


def format_diff(diff: dict, path_a: str = "A",
                path_b: str = "B") -> str:
    lines = [f"catch-up delta: {path_a} -> {path_b}",
             "", f"{'metric':<20}{'A':>12}{'B':>12}{'Δ':>12}  flag"]
    for r in diff["rows"]:
        lines.append(f"{r['metric']:<20}{r['a']:>12}{r['b']:>12}"
                     f"{r['delta']:>+12}  {r['flag']}")
    for n in diff.get("notes", []):
        lines.append(f"NOTE: {n}")
    lines += ["", ("regressions: " + ", ".join(diff["regressions"])
                   if diff["regressions"]
                   else "no regressions flagged")]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = build_parser(
        "replay throughput report from a /dump_catchup document, or "
        "a replay-figure delta diff of two of them",
        operand_help="catch-up dump file(s); two with --diff",
        diff_help="diff two dumps: replay-figure delta table with "
                  "regression flags",
        default_pct=DEFAULT_THRESHOLD_PCT,
        default_abs=DEFAULT_THRESHOLD_ABS)
    return run_cli(argv, parser=ap, load=load_catchup,
                   report=catchup_report, diff=diff_report,
                   fmt_report=format_report, fmt_diff=format_diff)


if __name__ == "__main__":
    raise SystemExit(main())
