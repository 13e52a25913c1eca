"""Turn Chrome trace files (libs/tracing.py export) into per-stage
critical-path tables — and DIFF two of them.

The perf loop's before/after instrument: run a workload with tracing on
(``[tracing] enable``, then ``curl $NODE/dump_traces``), feed the
file here, and read where the wall time went per stage — pack vs
device flight vs collect vs settle for the verify plane, per-step time
for consensus, fsync cost for the WAL.

Differencing is the regression instrument (ISSUE 6 / ROADMAP open item
1): ``--diff A.trace.json B.trace.json`` aligns the two stage tables
and emits stage-delta and overlap-delta rows with regression flags, so
"where did the commit's 6.6 ms go" is one command instead of an
eyeballing exercise.

Traces with no verify-plane spans (blocksync-/consensus-only runs)
fall back to a consensus-step table derived from the ``consensus.step``
instants, and the report says so.

Usage:
    python tools/trace_report.py trace.json [--json]
    python tools/trace_report.py --diff A.trace.json B.trace.json \
        [--json] [--threshold-pct 10] [--threshold-ms 0.05] \
        [--fail-on-regression]
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools._report_common import (  # noqa: E402 - after sys.path fix
    build_parser, flag_symmetric, run_cli)

# verify-plane flush pipeline, in submission order: the critical-path
# section reports these stages first and computes pack/flight overlap
PLANE_STAGES = ("plane.pack", "plane.flight", "plane.collect",
                "plane.verify", "plane.settle")

# diff thresholds: a stage only flags when it moved by BOTH the
# relative and the absolute floor (one guards noise on tiny stages, the
# other on huge-but-stable ones)
DEFAULT_THRESHOLD_PCT = 10.0
DEFAULT_THRESHOLD_MS = 0.05


def load(path: str) -> List[dict]:
    with open(path) as f:
        doc = json.load(f)
    return doc["traceEvents"] if isinstance(doc, dict) else doc


def _pct(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    s = sorted(xs)
    i = min(len(s) - 1, int(round(q * (len(s) - 1))))
    return s[i]


def _flight_intervals(events: List[dict]) -> List[tuple]:
    """(ts_begin, ts_end) per async flight id, from b/e event pairs."""
    begun: Dict[str, float] = {}
    out = []
    for e in events:
        if e.get("ph") == "b":
            begun[e.get("id", "")] = e["ts"]
        elif e.get("ph") == "e":
            t0 = begun.pop(e.get("id", ""), None)
            if t0 is not None:
                out.append((t0, e["ts"]))
    return out


def _merge_intervals(intervals: List[tuple]) -> List[tuple]:
    """Union of (lo, hi) intervals as disjoint sorted intervals. The
    deck keeps several flights airborne at once, so overlap math MUST
    run against the union — summing raw per-flight overlaps counted
    the same pack microsecond once per concurrent flight (fractions
    over 1.0 with two flights airborne)."""
    out: List[list] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [tuple(p) for p in out]


def _overlap_us(span: tuple, intervals: List[tuple]) -> float:
    """Time `span` spends inside `intervals` — exact only when the
    intervals are disjoint (pass them through _merge_intervals)."""
    lo, hi = span
    return sum(max(0.0, min(hi, b) - max(lo, a))
               for a, b in intervals if b > lo and a < hi)


def _deck_occupancy(intervals: List[tuple]) -> dict:
    """Concurrency sweep over the flight intervals: how long >=1 and
    >=2 flights were airborne, and the deepest the deck got — the
    pipelined-halves instrument (one airborne flight at a time means
    the deck never overlapped; ge2 time is chips on BOTH halves busy)."""
    events = sorted([(lo, 1) for lo, hi in intervals]
                    + [(hi, -1) for lo, hi in intervals])
    depth = 0
    ge1 = ge2 = 0.0
    deepest = 0
    prev = None
    for t, d in events:
        if prev is not None and depth >= 1:
            ge1 += t - prev
            if depth >= 2:
                ge2 += t - prev
        depth += d
        deepest = max(deepest, depth)
        prev = t
    return {"ge1_us": ge1, "ge2_us": ge2, "max_airborne": deepest}


def _consensus_step_durations(events: List[dict]) -> Dict[str, List[float]]:
    """Per-step dwell times (us) reconstructed from ``consensus.step``
    instants: each instant marks ENTERING a step, so a step's duration
    is the gap to the next step instant on the same thread. The open
    tail (last instant per thread) has no end and is dropped."""
    by_tid: Dict[int, List[tuple]] = {}
    for e in events:
        if e.get("ph") == "i" and e.get("name") == "consensus.step":
            step = (e.get("args") or {}).get("step", "?")
            by_tid.setdefault(e.get("tid", 0), []).append(
                (e["ts"], str(step)))
    out: Dict[str, List[float]] = {}
    for seq in by_tid.values():
        seq.sort(key=lambda p: p[0])
        for (t0, step), (t1, _) in zip(seq, seq[1:]):
            out.setdefault(f"step.{step}", []).append(t1 - t0)
    return out


def _row(name: str, durs: List[float]) -> dict:
    return {
        "stage": name,
        "count": len(durs),
        "total_ms": round(sum(durs) / 1000.0, 3),
        "mean_ms": round(sum(durs) / len(durs) / 1000.0, 4)
        if durs else 0.0,
        "p50_ms": round(_pct(durs, 0.5) / 1000.0, 4),
        "max_ms": round(max(durs) / 1000.0, 4) if durs else 0.0,
    }


def stage_report(events: List[dict]) -> dict:
    """Aggregate a trace into {stages, instants, plane} — the table
    main() pretty-prints.

    stages: per span name, count + total/mean/p50/max ms.
    instants: per instant name, count.
    plane: flush-pipeline extras — flight count/total from the async
    b/e pairs, the fraction of pack time hidden behind an airborne
    flight (computed against the UNION of flight intervals, so several
    concurrent deck flights never double-count a pack microsecond),
    and the deck occupancy sweep: fraction of trace wall time with >=1
    and >=2 flights airborne (the pipelined-halves instrument — a
    healthy deck shows ge2 occupancy, not just a boolean overlap).
    fallback: set (with a human note) when the trace holds no
    verify-plane spans and the stage table was derived from the
    consensus-step instants instead.
    """
    spans: Dict[str, List[float]] = {}
    instants: Dict[str, int] = {}
    pack_spans = []
    t_lo = t_hi = None
    for e in events:
        ph = e.get("ph")
        ts = e.get("ts")
        if ts is not None:
            end = ts + e.get("dur", 0.0)
            t_lo = ts if t_lo is None else min(t_lo, ts)
            t_hi = end if t_hi is None else max(t_hi, end)
        if ph == "X":
            spans.setdefault(e["name"], []).append(e.get("dur", 0.0))
            if e["name"] == "plane.pack":
                pack_spans.append((e["ts"], e["ts"] + e.get("dur", 0.0)))
        elif ph == "i":
            instants[e["name"]] = instants.get(e["name"], 0) + 1
    flights = _flight_intervals(events)

    fallback = None
    if not any(n in spans for n in PLANE_STAGES):
        # consensus-/blocksync-only trace: no flush pipeline to report.
        # Fall back to the per-step dwell table so the report is never
        # empty on a trace that plainly recorded consensus activity.
        steps = _consensus_step_durations(events)
        if steps:
            for name, durs in steps.items():
                spans.setdefault(name, durs)
            fallback = ("no verify-plane spans in this trace; stage "
                        "table includes consensus-step dwell times "
                        "derived from consensus.step instants")

    # plane stages first (pipeline order), then everything else by
    # total time descending — the critical path reads top-down
    ordered = [n for n in PLANE_STAGES if n in spans]
    rest = sorted((n for n in spans if n not in PLANE_STAGES),
                  key=lambda n: -sum(spans[n]))
    stages = [_row(n, spans[n]) for n in ordered + rest]

    plane: Optional[dict] = None
    if flights or pack_spans:
        flight_total = sum(b - a for a, b in flights)
        pack_total = sum(b - a for a, b in pack_spans)
        # union first: with the deck, pack(k+2) can overlap TWO
        # airborne flights — per-flight sums would count it twice
        merged = _merge_intervals(flights)
        overlapped = sum(_overlap_us(p, merged) for p in pack_spans)
        occ = _deck_occupancy(flights)
        wall = (t_hi - t_lo) if (t_lo is not None and t_hi > t_lo) \
            else 0.0
        plane = {
            "flights": len(flights),
            "flight_total_ms": round(flight_total / 1000.0, 3),
            "pack_total_ms": round(pack_total / 1000.0, 3),
            "pack_overlapped_ms": round(overlapped / 1000.0, 3),
            "pack_overlap_frac": round(overlapped / pack_total, 3)
            if pack_total else 0.0,
            # fused flushes that paid a valset table build/patch inline
            # (plane.cold_table instants): a steady stream should show
            # 0 — nonzero localizes a post-rotation stall the next-
            # epoch warmer should have absorbed
            "cold_tables": instants.get("plane.cold_table", 0),
            "deck": {
                "max_airborne": occ["max_airborne"],
                "airborne_ge1_ms": round(occ["ge1_us"] / 1000.0, 3),
                "airborne_ge2_ms": round(occ["ge2_us"] / 1000.0, 3),
                "occupancy_ge1": round(occ["ge1_us"] / wall, 3)
                if wall else 0.0,
                "occupancy_ge2": round(occ["ge2_us"] / wall, 3)
                if wall else 0.0,
            },
        }
    return {"stages": stages, "instants": instants, "plane": plane,
            "events": len(events), "fallback": fallback}


# --------------------------------------------------------------------------
# differencing
# --------------------------------------------------------------------------


def diff_report(rep_a: dict, rep_b: dict,
                threshold_pct: float = DEFAULT_THRESHOLD_PCT,
                threshold_ms: float = DEFAULT_THRESHOLD_MS) -> dict:
    """Align two stage_report outputs (A = before, B = after) into
    stage-delta rows + an overlap-delta block with regression flags.

    A stage REGRESSED when its mean grew by more than BOTH thresholds
    (relative + absolute); it improved when it shrank by the same
    margin. Stages present on only one side are flagged too (appeared
    = new cost, vanished = cost removed or stage renamed)."""
    a_by = {r["stage"]: r for r in rep_a.get("stages", [])}
    b_by = {r["stage"]: r for r in rep_b.get("stages", [])}
    order = [r["stage"] for r in rep_a.get("stages", [])]
    order += [s for s in (r["stage"] for r in rep_b.get("stages", []))
              if s not in a_by]

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
                "total_ms_a": ra["total_ms"] if ra else 0.0,
                "total_ms_b": rb["total_ms"] if rb else 0.0,
                "delta_mean_ms": round(
                    (rb["mean_ms"] if rb else 0.0)
                    - (ra["mean_ms"] if ra else 0.0), 4),
                "delta_total_ms": round(
                    (rb["total_ms"] if rb else 0.0)
                    - (ra["total_ms"] if ra else 0.0), 3),
                "delta_pct": None,
            })
            continue
        d_mean = rb["mean_ms"] - ra["mean_ms"]
        rows.append({
            "stage": name,
            "flag": flag_of(ra["mean_ms"], rb["mean_ms"]),
            "count_a": ra["count"], "count_b": rb["count"],
            "mean_ms_a": ra["mean_ms"], "mean_ms_b": rb["mean_ms"],
            "total_ms_a": ra["total_ms"], "total_ms_b": rb["total_ms"],
            "delta_mean_ms": round(d_mean, 4),
            "delta_total_ms": round(rb["total_ms"] - ra["total_ms"], 3),
            "delta_pct": round(d_mean / ra["mean_ms"] * 100.0, 1)
            if ra["mean_ms"] else None,
        })

    overlap = None
    pa, pb = rep_a.get("plane"), rep_b.get("plane")
    if pa or pb:
        fa = (pa or {}).get("pack_overlap_frac", 0.0)
        fb = (pb or {}).get("pack_overlap_frac", 0.0)
        da = (pa or {}).get("deck") or {}
        db = (pb or {}).get("deck") or {}
        overlap = {
            "pack_overlap_frac_a": fa,
            "pack_overlap_frac_b": fb,
            "delta": round(fb - fa, 3),
            # deck occupancy deltas: losing ge2 time means the halves
            # stopped flying concurrently (informational — the flag
            # below still keys on pack overlap + flights vanishing)
            "occupancy_ge2_a": da.get("occupancy_ge2", 0.0),
            "occupancy_ge2_b": db.get("occupancy_ge2", 0.0),
            "max_airborne_a": da.get("max_airborne", 0),
            "max_airborne_b": db.get("max_airborne", 0),
            "flights_a": (pa or {}).get("flights", 0),
            "flights_b": (pb or {}).get("flights", 0),
            "flight_total_ms_a": (pa or {}).get("flight_total_ms", 0.0),
            "flight_total_ms_b": (pb or {}).get("flight_total_ms", 0.0),
            # losing overlap means pack time stopped hiding behind the
            # device — the double buffer stopped paying. Flights
            # vanishing entirely is the worst case of that (the plane
            # degraded to synchronous/host flushes).
            "flag": "REGRESSED"
            if (fb < fa - 0.05
                or ((pa or {}).get("flights", 0) > 0
                    and not (pb or {}).get("flights", 0)))
            else ("improved" if fb > fa + 0.05 else ""),
        }

    # an appeared stage is only a REGRESSION when its new cost clears
    # the absolute threshold — a trivial span the before-run happened
    # not to hit must not fail a --fail-on-regression CI gate
    regressions = [r["stage"] for r in rows
                   if r["flag"] == "REGRESSED"
                   or (r["flag"] == "appeared"
                       and r["mean_ms_b"] >= threshold_ms)]
    if overlap and overlap["flag"] == "REGRESSED":
        regressions.append("pack_overlap_frac")
    notes = [n for n in (rep_a.get("fallback"), rep_b.get("fallback"))
             if n]
    return {"stages": rows, "overlap": overlap,
            "regressions": regressions, "notes": notes,
            "events_a": rep_a.get("events", 0),
            "events_b": rep_b.get("events", 0)}


# --------------------------------------------------------------------------
# formatting
# --------------------------------------------------------------------------


def format_report(rep: dict) -> str:
    lines = [f"{rep['events']} trace events"]
    if rep.get("fallback"):
        lines.append(f"NOTE: {rep['fallback']}")
    lines += ["", f"{'stage':<26}{'count':>7}{'total ms':>11}"
                  f"{'mean ms':>10}{'p50 ms':>10}{'max ms':>10}"]
    for r in rep["stages"]:
        lines.append(f"{r['stage']:<26}{r['count']:>7}"
                     f"{r['total_ms']:>11.3f}{r['mean_ms']:>10.4f}"
                     f"{r['p50_ms']:>10.4f}{r['max_ms']:>10.4f}")
    if rep["plane"]:
        p = rep["plane"]
        lines += ["",
                  f"verify-plane flights: {p['flights']} "
                  f"({p['flight_total_ms']} ms airborne); "
                  f"pack {p['pack_total_ms']} ms, "
                  f"{p['pack_overlapped_ms']} ms "
                  f"({p['pack_overlap_frac']:.0%}) hidden behind flights"]
        if p.get("cold_tables"):
            lines.append(
                f"COLD TABLES: {p['cold_tables']} fused flush(es) paid "
                f"a valset table build inline (post-rotation stall — "
                f"check the next-epoch warmer)")
        d = p.get("deck")
        if d:
            lines.append(
                f"deck occupancy: >=1 flight {d['occupancy_ge1']:.0%} "
                f"of wall ({d['airborne_ge1_ms']} ms), >=2 flights "
                f"{d['occupancy_ge2']:.0%} ({d['airborne_ge2_ms']} ms),"
                f" max airborne {d['max_airborne']}")
    if rep["instants"]:
        lines += ["", "instants: " + ", ".join(
            f"{k}×{v}" for k, v in sorted(rep["instants"].items()))]
    return "\n".join(lines)


def format_diff(diff: dict, path_a: str = "A", path_b: str = "B") -> str:
    lines = [f"stage-delta: {path_a} ({diff['events_a']} events) -> "
             f"{path_b} ({diff['events_b']} events)"]
    for n in diff.get("notes", []):
        lines.append(f"NOTE: {n}")
    lines += ["", f"{'stage':<22}{'cnt A':>6}{'cnt B':>6}"
                  f"{'mean A':>9}{'mean B':>9}{'Δ ms':>9}{'Δ %':>8}"
                  f"  {'flag'}"]
    for r in diff["stages"]:
        pct = f"{r['delta_pct']:+.1f}" if r["delta_pct"] is not None \
            else "-"
        lines.append(
            f"{r['stage']:<22}{r['count_a']:>6}{r['count_b']:>6}"
            f"{r['mean_ms_a']:>9.4f}{r['mean_ms_b']:>9.4f}"
            f"{r['delta_mean_ms']:>+9.4f}{pct:>8}  {r['flag']}")
    if diff["overlap"]:
        o = diff["overlap"]
        lines += ["",
                  f"overlap-delta: pack_overlap_frac "
                  f"{o['pack_overlap_frac_a']:.3f} -> "
                  f"{o['pack_overlap_frac_b']:.3f} (Δ {o['delta']:+.3f})"
                  f" flights {o['flights_a']}->{o['flights_b']}"
                  f" deck-ge2 {o['occupancy_ge2_a']:.3f}->"
                  f"{o['occupancy_ge2_b']:.3f}"
                  + (f"  {o['flag']}" if o["flag"] else "")]
    lines += ["", ("regressions: " + ", ".join(diff["regressions"])
                   if diff["regressions"] else "no regressions flagged")]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = build_parser(
        "per-stage critical-path table from a Chrome trace, or a "
        "stage-delta diff of two traces",
        operand="traces",
        operand_help="trace file(s) (libs/tracing export); two files "
                     "with --diff",
        diff_help="diff two traces: stage-delta + overlap-delta "
                  "tables with regression flags",
        default_pct=DEFAULT_THRESHOLD_PCT,
        default_abs=DEFAULT_THRESHOLD_MS,
        pct_help="relative regression floor (mean ms, %%)",
        abs_flag="--threshold-ms",
        abs_help="absolute regression floor (mean ms)")
    return run_cli(argv, parser=ap, load=load, report=stage_report,
                   diff=diff_report, fmt_report=format_report,
                   fmt_diff=format_diff, operand="traces",
                   noun="trace")



if __name__ == "__main__":
    raise SystemExit(main())
