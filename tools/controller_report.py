"""Turn a /dump_controller document into a decision timeline and
per-actuator travel tables — and DIFF two of them.

The control-plane sibling of tools/device_report.py, trace_report.py,
height_report.py and peer_report.py: where those decompose the DEVICE,
a FLUSH, a BLOCK, and the GOSSIP, this decomposes the LOOP — per
actuator: configured base, clamp bounds, current value, displacement
from base, move count, tighten/relax split; plus the decision timeline
(who moved, which direction, what the trigger sensors read) and the
SLO-violation accrual. Feed it a saved ``curl $NODE/dump_controller``
file.

Differencing mirrors device_report --diff: figure delta rows with
REGRESSED/improved flags past BOTH a relative and an absolute
threshold, and ``--fail-on-regression`` for CI gates (requires --diff
— a gate wired without a comparison must error, not read permanently
green). Flags: SLO-violation growth (the loop stopped holding the
target), decision-count blowup (a flapping loop — hysteresis or
cooldown miswired), and residual displacement growth (actuators parked
off base at the trough means the loop stopped relaxing).

Usage:
    python tools/controller_report.py dump.json [--json]
    python tools/controller_report.py --diff A.json B.json \
        [--json] [--threshold-pct 25] [--threshold-abs 4] \
        [--fail-on-regression]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools._report_common import (  # noqa: E402 - after sys.path fix
    build_parser, flag_directional, load_dump, run_cli)

DEFAULT_THRESHOLD_PCT = 25.0
DEFAULT_THRESHOLD_ABS = 4.0


def load_controller(path: str) -> dict:
    """Load a /dump_controller document (or a bare {"decisions": ...,
    "actuators": ...} object)."""
    return load_dump(path, "/dump_controller", "decisions", "actuators")


def controller_report(dump: dict) -> dict:
    """Aggregate a controller dump into the tables the text report
    prints and the diff compares."""
    state = dict(dump.get("state", {}))
    decisions = list(dump.get("decisions", []))
    acts: dict = {}
    for name, a in (dump.get("actuators") or {}).items():
        acts[name] = {
            "actuator": name,
            "value": a.get("value", 0.0),
            "base": a.get("base", 0.0),
            "min": a.get("min", 0.0),
            "max": a.get("max", 0.0),
            "moves": a.get("moves", 0),
            # displacement from base, normalized by the clamp span —
            # the "how far off the configured static point is the loop
            # parked" figure the diff watches
            "displacement": round(
                abs(a.get("value", 0.0) - a.get("base", 0.0)), 4),
            "tightens": 0,
            "relaxes": 0,
        }
    timeline = []
    for d in decisions:
        row = acts.get(d.get("actuator"))
        if row is not None:
            if d.get("relax"):
                row["relaxes"] += 1
            else:
                row["tightens"] += 1
        timeline.append({
            "seq": d.get("seq"), "at_ms": d.get("at_ms"),
            "height": d.get("height"), "actuator": d.get("actuator"),
            "direction": d.get("direction"), "old": d.get("old"),
            "new": d.get("new"), "relax": bool(d.get("relax")),
            "trigger": d.get("trigger", {}),
        })
    displaced = sorted((r["actuator"] for r in acts.values()
                        if r["displacement"] > 0))
    return {
        "decisions_total": state.get("decisions_total", 0),
        "evals": state.get("evals", 0),
        "pokes": state.get("pokes", 0),
        "pressed": bool(state.get("pressed", False)),
        "slo": dict(dump.get("slo", {})),
        "slo_violation_s": state.get("slo_violation_s", 0.0),
        "actuators": sorted(acts.values(),
                            key=lambda r: (-r["moves"], r["actuator"])),
        "displacement_total": round(
            sum(r["displacement"] for r in acts.values()), 4),
        "displaced": displaced,
        "timeline": timeline,
    }


# --------------------------------------------------------------------------
# differencing (device_report --diff's shape, over the loop figures)
# --------------------------------------------------------------------------


def diff_report(rep_a: dict, rep_b: dict,
                threshold_pct: float = DEFAULT_THRESHOLD_PCT,
                threshold_abs: float = DEFAULT_THRESHOLD_ABS) -> dict:
    """Loop-figure delta rows (A = before, B = after). Growth is bad
    for violation seconds, decision count and residual displacement; a
    figure REGRESSED past BOTH thresholds — except slo_violation_s,
    where ANY growth flags (the loop exists to keep it at zero)."""

    def flag_of(a: float, b: float, abs_floor: float = threshold_abs,
                any_growth: bool = False) -> str:
        return flag_directional(a, b, threshold_pct=threshold_pct,
                                abs_floor=abs_floor,
                                any_growth=any_growth)

    rows = [
        # holding the SLO is the loop's one job: any violation growth
        # flags, no relative threshold can excuse it
        {"metric": "slo_violation_s", "a": rep_a["slo_violation_s"],
         "b": rep_b["slo_violation_s"],
         "flag": flag_of(rep_a["slo_violation_s"],
                         rep_b["slo_violation_s"], abs_floor=0.001,
                         any_growth=True)},
        {"metric": "decisions_total", "a": rep_a["decisions_total"],
         "b": rep_b["decisions_total"],
         "flag": flag_of(rep_a["decisions_total"],
                         rep_b["decisions_total"])},
        {"metric": "displacement_total",
         "a": rep_a["displacement_total"],
         "b": rep_b["displacement_total"],
         "flag": flag_of(rep_a["displacement_total"],
                         rep_b["displacement_total"],
                         abs_floor=0.01)},
        {"metric": "evals", "a": rep_a["evals"], "b": rep_b["evals"],
         "flag": ""},
    ]
    for r in rows:
        r["delta"] = round(r["b"] - r["a"], 4)

    notes = []
    acts_a = {r["actuator"]: r for r in rep_a["actuators"]}
    for row in rep_b["actuators"]:
        before = acts_a.get(row["actuator"],
                            {"moves": 0, "displacement": 0.0})
        if row["displacement"] > 0 and row["displacement"] \
                > before["displacement"]:
            notes.append(
                f"{row['actuator']} parked off base: "
                f"{row['value']} vs base {row['base']} "
                f"(was off by {before['displacement']}) — the loop "
                f"stopped relaxing; check the timeline's last relax "
                f"and the hysteresis thresholds")
        if before["moves"] and row["moves"] > 4 * before["moves"]:
            notes.append(
                f"{row['actuator']} move count blew up: "
                f"{before['moves']} -> {row['moves']} — a flapping "
                f"loop; check cooldown and the enter/exit spread")
    if rep_b["pressed"] and not rep_a["pressed"]:
        notes.append(
            "run B ended still PRESSED — pressure never released "
            "before the dump; trough assertions read tightened values")

    regressions = [r["metric"] for r in rows
                   if r["flag"] == "REGRESSED"]
    return {"rows": rows, "regressions": regressions, "notes": notes}


# --------------------------------------------------------------------------
# formatting
# --------------------------------------------------------------------------


def format_report(rep: dict) -> str:
    slo = rep["slo"]
    lines = [
        f"decisions: {rep['decisions_total']} over {rep['evals']} "
        f"evaluations ({rep['pokes']} pokes), "
        + ("PRESSED" if rep["pressed"] else "unpressed")
        + f"; SLO commit p99 {slo.get('commit_p99_ms', '?')} ms, "
          f"violation accrued {rep['slo_violation_s']} s"]
    if rep["actuators"]:
        lines += ["", f"{'actuator':<26}{'value':>10}{'base':>10}"
                      f"{'min':>9}{'max':>9}{'moves':>7}"
                      f"{'tight':>7}{'relax':>7}"]
        for r in rep["actuators"]:
            lines.append(
                f"{r['actuator']:<26}{r['value']:>10}{r['base']:>10}"
                f"{r['min']:>9}{r['max']:>9}{r['moves']:>7}"
                f"{r['tightens']:>7}{r['relaxes']:>7}")
        if rep["displaced"]:
            lines.append(
                f"off base: {', '.join(rep['displaced'])} "
                f"(total displacement {rep['displacement_total']})")
        else:
            lines.append("all actuators at their configured base")
    if rep["timeline"]:
        lines += ["", "decision timeline (oldest first):"]
        for d in rep["timeline"]:
            trig = d["trigger"]
            why = ", ".join(
                f"{k}={trig[k]}" for k in ("p99_ms", "fill",
                                           "shed_delta", "util_p50",
                                           "compile_storms")
                if k in trig and trig[k] not in (None, 0, 0.0))
            lines.append(
                f"  #{d['seq']:<4} h={d['height']:<6} "
                f"{d['actuator']:<26} {d['direction']:<5}"
                f"{d['old']} -> {d['new']}"
                + (" (relax)" if d["relax"] else "")
                + (f"  [{why}]" if why else ""))
    return "\n".join(lines)


def format_diff(diff: dict, path_a: str = "A",
                path_b: str = "B") -> str:
    lines = [f"control-plane delta: {path_a} -> {path_b}",
             "", f"{'metric':<22}{'A':>12}{'B':>12}{'Δ':>12}  flag"]
    for r in diff["rows"]:
        lines.append(f"{r['metric']:<22}{r['a']:>12}{r['b']:>12}"
                     f"{r['delta']:>+12}  {r['flag']}")
    for n in diff.get("notes", []):
        lines.append(f"NOTE: {n}")
    lines += ["", ("regressions: " + ", ".join(diff["regressions"])
                   if diff["regressions"]
                   else "no regressions flagged")]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = build_parser(
        "decision timeline and per-actuator travel tables from a "
        "/dump_controller document, or a loop-figure delta diff of "
        "two of them",
        operand_help="controller dump file(s); two with --diff",
        diff_help="diff two dumps: loop-figure delta table with "
                  "regression flags",
        default_pct=DEFAULT_THRESHOLD_PCT,
        default_abs=DEFAULT_THRESHOLD_ABS)
    return run_cli(argv, parser=ap, load=load_controller,
                   report=controller_report, diff=diff_report,
                   fmt_report=format_report, fmt_diff=format_diff)


if __name__ == "__main__":
    raise SystemExit(main())
