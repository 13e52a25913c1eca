"""Turn a /dump_devices document into compile/residency/utilization
tables — and DIFF two of them.

The device-plane sibling of tools/trace_report.py, height_report.py,
and peer_report.py: where those decompose a FLUSH, a BLOCK, and the
GOSSIP, this decomposes the DEVICE — per compile site: count, total
ms, steady-state recompiles (the round-5 regression class),
persistent-cache hits; per family x device: resident bytes, pinned
valset slots, headroom against the 65536-slot/chip budget; plus the
flush ledger's device-time split (comp/h2d/dev ms, utilization) when
the dump carries it. Feed it a saved ``curl $NODE/dump_devices`` file.

Differencing mirrors trace_report --diff: counter/figure delta rows
with REGRESSED/improved flags past BOTH a relative and an absolute
threshold, and ``--fail-on-regression`` for CI gates (requires --diff
— a gate wired without a comparison must error, not read permanently
green). Flags: compile-count and compile-seconds growth, ANY
steady-state recompile growth (absolute threshold 0 — one is a bug),
residency growth, headroom shrink, and utilization collapse.

Usage:
    python tools/device_report.py dump.json [--json]
    python tools/device_report.py --diff A.json B.json \
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
    build_parser, flag_directional, load_dump, run_cli)

DEFAULT_THRESHOLD_PCT = 25.0
DEFAULT_THRESHOLD_ABS = 8.0


def load_devices(path: str) -> dict:
    """Load a /dump_devices document (or a bare {"summary": ...,
    "compiles": ...} object)."""
    return load_dump(path, "/dump_devices", "compiles", "summary")


def device_report(dump: dict) -> dict:
    """Aggregate a device dump into the tables the text report prints
    and the diff compares."""
    summary = dict(dump.get("summary", {}))
    compiles = list(dump.get("compiles", []))
    sites: dict = {}
    for c in compiles:
        site = c.get("site") or "?"
        row = sites.setdefault(site, {"site": site, "compiles": 0,
                                      "ms": 0.0, "steady": 0,
                                      "pcache": 0})
        if c.get("pcache_hit"):
            row["pcache"] += 1
        else:
            row["compiles"] += 1
            row["ms"] = round(row["ms"] + c.get("dur_ms", 0.0), 3)
        if c.get("steady"):
            row["steady"] += 1
    res_rows = []
    for fam, devs in sorted((dump.get("residency") or {}).items()):
        for dev, slot in sorted(devs.items()):
            res_rows.append({"family": fam, "dev": dev,
                             "bytes": slot.get("bytes", 0),
                             "slots": slot.get("slots", 0)})
    head = {str(k): v
            for k, v in (dump.get("headroom_rows") or {}).items()}
    fl = dump.get("flushes") or {}
    return {
        "compiles": summary.get("compiles", 0),
        "compile_s": summary.get("compile_s", 0.0),
        "pcache_hits": summary.get("pcache_hits", 0),
        "steady_compiles": summary.get("steady_compiles", 0),
        "steady": summary.get("steady", False),
        "sites": sorted(sites.values(),
                        key=lambda r: -(r["ms"] + r["pcache"])),
        "resident_bytes": summary.get("resident_bytes", 0),
        "families": summary.get("families", {}),
        "residency_rows": res_rows,
        "headroom_min": min(head.values()) if head else None,
        "headroom": head,
        "util_p50": (fl.get("util") or {}).get("p50", 0.0),
        "dev_ms_p50": (fl.get("dev_ms") or {}).get("p50", 0.0),
        "flush_comp_ms": fl.get("comp_ms", 0.0),
        "reconcile": dump.get("reconcile", {}),
        # ISSUE 20 kernel cost surfaces (absent on dumps from builds
        # predating the recorder)
        "cost_surfaces": list(dump.get("cost_surfaces") or []),
        "cost_counters": dict(dump.get("cost_counters") or {}),
    }


# --------------------------------------------------------------------------
# differencing (trace_report --diff's shape, over the device figures)
# --------------------------------------------------------------------------


def diff_report(rep_a: dict, rep_b: dict,
                threshold_pct: float = DEFAULT_THRESHOLD_PCT,
                threshold_abs: float = DEFAULT_THRESHOLD_ABS) -> dict:
    """Device-figure delta rows (A = before, B = after). Growth is bad
    for compiles/residency, shrink is bad for headroom/util; a figure
    REGRESSED past BOTH thresholds — except steady_compiles, where ANY
    growth flags (one steady recompile is the round-5 bug class)."""

    def flag_of(a: float, b: float, bad_dir: int = +1,
                abs_floor: float = threshold_abs,
                any_growth: bool = False) -> str:
        # any_growth: the relative threshold is waived — one more
        # steady recompile flags no matter how big the baseline is
        return flag_directional(a, b, threshold_pct=threshold_pct,
                                abs_floor=abs_floor, bad_dir=bad_dir,
                                any_growth=any_growth)

    rows = [
        {"metric": "compiles", "a": rep_a["compiles"],
         "b": rep_b["compiles"],
         "flag": flag_of(rep_a["compiles"], rep_b["compiles"])},
        {"metric": "compile_s", "a": rep_a["compile_s"],
         "b": rep_b["compile_s"],
         "flag": flag_of(rep_a["compile_s"], rep_b["compile_s"],
                         abs_floor=1.0)},
        # one steady-state recompile is a bug: ANY growth flags — no
        # relative threshold can excuse the round-5 class
        {"metric": "steady_compiles", "a": rep_a["steady_compiles"],
         "b": rep_b["steady_compiles"],
         "flag": flag_of(rep_a["steady_compiles"],
                         rep_b["steady_compiles"], abs_floor=1.0,
                         any_growth=True)},
        {"metric": "resident_bytes", "a": rep_a["resident_bytes"],
         "b": rep_b["resident_bytes"],
         "flag": flag_of(rep_a["resident_bytes"],
                         rep_b["resident_bytes"],
                         abs_floor=max(threshold_abs, 1 << 16))},
    ]
    for r in rows:
        r["delta"] = round(r["b"] - r["a"], 3)
    ha, hb = rep_a["headroom_min"], rep_b["headroom_min"]
    if ha is not None or hb is not None:
        ha = 0 if ha is None else ha
        hb = 0 if hb is None else hb
        rows.append({"metric": "headroom_rows_min", "a": ha, "b": hb,
                     "delta": hb - ha,
                     "flag": flag_of(ha, hb, bad_dir=-1,
                                     abs_floor=128)})
    ua, ub = rep_a["util_p50"], rep_b["util_p50"]
    if ua or ub:
        rows.append({"metric": "util_p50", "a": ua, "b": ub,
                     "delta": round(ub - ua, 4),
                     "flag": flag_of(ua, ub, bad_dir=-1,
                                     abs_floor=0.05)})
    # kernel cost surfaces: a cell whose marginal ms-per-row grew past
    # both thresholds is a MARGINAL-COST REGRESSION — the same jit
    # family at the same shape charging more per row than it used to
    cs_a = {(r["family"], r["rows_bucket"], r["n_dev"]): r
            for r in rep_a["cost_surfaces"]}
    for r in rep_b["cost_surfaces"]:
        key = (r["family"], r["rows_bucket"], r["n_dev"])
        before = cs_a.get(key)
        if before is None:
            continue
        ma = before.get("marginal_ms_per_row")
        mb = r.get("marginal_ms_per_row")
        if ma is None or mb is None:
            continue
        fl = flag_of(ma, mb, abs_floor=0.001)
        if fl:
            fam, bucket, n_dev = key
            rows.append({
                "metric": f"marginal_ms_per_row"
                          f"[{fam}@{bucket}x{n_dev}]",
                "a": ma, "b": mb, "delta": round(mb - ma, 6),
                "flag": fl})

    notes = []
    sites_b = {r["site"]: r for r in rep_b["sites"]}
    sites_a = {r["site"]: r for r in rep_a["sites"]}
    for site, row in sites_b.items():
        grew = row["compiles"] - sites_a.get(
            site, {"compiles": 0})["compiles"]
        if row["steady"] and grew > 0:
            notes.append(
                f"steady-state recompiles at {site}: "
                f"{row['steady']} steady / {grew} new compiles — the "
                f"round-5 class; pull /dump_incidents for a "
                f"compile_storm snapshot and /dump_flushes comp_ms "
                f"for the flushes that paid")
    da, db = rep_a["reconcile"], rep_b["reconcile"]
    if db.get("table_drift") or da.get("table_drift"):
        notes.append(
            f"residency accounting drift: "
            f"{da.get('table_drift', 0)} -> {db.get('table_drift', 0)} "
            f"bytes (the per-device split and the cache truth "
            f"disagree — neither number is trustworthy)")

    regressions = [r["metric"] for r in rows if r["flag"] == "REGRESSED"]
    return {"rows": rows, "regressions": regressions, "notes": notes}


# --------------------------------------------------------------------------
# formatting
# --------------------------------------------------------------------------


def format_report(rep: dict) -> str:
    lines = [
        f"compiles: {rep['compiles']} backend "
        f"({rep['compile_s']} s), {rep['pcache_hits']} pcache hits, "
        f"{rep['steady_compiles']} STEADY-STATE"
        + (" (steady declared)" if rep["steady"] else
           " (steady never declared)")]
    if rep["sites"]:
        lines += ["", f"{'site':<26}{'compiles':>9}{'ms':>10}"
                      f"{'steady':>7}{'pcache':>7}"]
        for r in rep["sites"]:
            lines.append(f"{r['site']:<26}{r['compiles']:>9}"
                         f"{r['ms']:>10.1f}{r['steady']:>7}"
                         f"{r['pcache']:>7}")
    if rep["residency_rows"]:
        lines += ["", f"{'family':<16}{'dev':>6}{'bytes':>14}"
                      f"{'slots':>9}"]
        for r in rep["residency_rows"]:
            lines.append(f"{r['family']:<16}{r['dev']:>6}"
                         f"{r['bytes']:>14}{r['slots']:>9}")
        lines.append(
            f"resident total: {rep['resident_bytes']} B; per-chip "
            f"headroom min {rep['headroom_min']} of 65536 valset "
            f"slots")
    if rep["util_p50"] or rep["dev_ms_p50"]:
        lines.append(
            f"flush device split: util p50 {rep['util_p50']}, dev_ms "
            f"p50 {rep['dev_ms_p50']}, compile ms charged to flushes "
            f"{rep['flush_comp_ms']}")
    if rep["cost_surfaces"]:
        cc = rep["cost_counters"]
        lines += ["", f"cost surfaces ({cc.get('observed', 0)} flush "
                      f"observations, {cc.get('cells', 0)} cells):",
                  f"{'family':<22}{'rows<=':>8}{'ndev':>5}{'n':>5}"
                  f"{'dev p50':>9}{'dev p95':>9}{'h2d p50':>9}"
                  f"{'ms/row':>10}"]
        for r in rep["cost_surfaces"]:
            marg = r.get("marginal_ms_per_row")
            lines.append(
                f"{r['family']:<22}{r['rows_bucket']:>8}"
                f"{r['n_dev']:>5}{r['n']:>5}{r['dev_ms_p50']:>9}"
                f"{r['dev_ms_p95']:>9}{r['h2d_ms_p50']:>9}"
                f"{marg if marg is not None else '-':>10}")
    rc = rep["reconcile"]
    if rc:
        drift = rc.get("table_drift", 0)
        lines.append(
            f"accounting cross-check: split {rc.get('table_bytes_split')}"
            f" vs cache {rc.get('table_bytes_cache')} "
            + ("(exact)" if not drift else f"DRIFT {drift} B"))
    if rep["steady_compiles"]:
        lines.append(
            f"STEADY-STATE RECOMPILES: {rep['steady_compiles']} — the "
            f"round-5 regression class; check /dump_incidents for a "
            f"compile_storm snapshot and the site table above for WHO")
    return "\n".join(lines)


def format_diff(diff: dict, path_a: str = "A", path_b: str = "B") -> str:
    lines = [f"device-plane delta: {path_a} -> {path_b}",
             "", f"{'metric':<20}{'A':>12}{'B':>12}{'Δ':>12}  flag"]
    for r in diff["rows"]:
        lines.append(f"{r['metric']:<20}{r['a']:>12}{r['b']:>12}"
                     f"{r['delta']:>+12}  {r['flag']}")
    for n in diff.get("notes", []):
        lines.append(f"NOTE: {n}")
    lines += ["", ("regressions: " + ", ".join(diff["regressions"])
                   if diff["regressions"] else "no regressions flagged")]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = build_parser(
        "compile/residency/utilization tables from a /dump_devices "
        "document, or a device-figure delta diff of two of them",
        operand_help="device dump file(s); two files with --diff",
        diff_help="diff two dumps: device-figure delta table with "
                  "regression flags",
        default_pct=DEFAULT_THRESHOLD_PCT,
        default_abs=DEFAULT_THRESHOLD_ABS,
        abs_help="absolute regression floor (count / bytes)")
    return run_cli(argv, parser=ap, load=load_devices,
                   report=device_report, diff=diff_report,
                   fmt_report=format_report, fmt_diff=format_diff)


if __name__ == "__main__":
    raise SystemExit(main())
