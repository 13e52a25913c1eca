"""Turn a /dump_tenants document into per-tenant occupancy and QoS
tables — and DIFF two of them.

The multi-tenant sibling of tools/controller_report.py, device_report,
height_report and peer_report: where those decompose the LOOP, the
DEVICE, a BLOCK and the GOSSIP, this decomposes the POD — per tenant:
verified rows (per lane), quota sheds, warm skips, cold-table
evictions, HBM residency (bytes + tables), verify-wait percentiles,
and the configured quotas; plus the registry-level figures (size,
evictions, the retired-totals accumulator). Feed it a saved
``curl $NODE/dump_tenants`` file.

Differencing mirrors controller_report --diff: figure delta rows with
REGRESSED/improved flags past BOTH a relative and an absolute
threshold, and ``--fail-on-regression`` for CI gates (requires --diff
— a gate wired without a comparison must error, not read permanently
green). Flags: shed growth (quotas started biting — or a neighbor got
noisy), warm-skip growth (residency budgets rejecting prefetches),
cold-eviction churn, and per-tenant verify-wait p99 growth (the
fair-share drain stopped being fair).

Usage:
    python tools/tenant_report.py dump.json [--json]
    python tools/tenant_report.py --diff A.json B.json \
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


def load_tenants(path: str) -> dict:
    """Load a /dump_tenants document (or a bare {"tenants": ...,
    "registry_size": ...} object)."""
    return load_dump(path, "/dump_tenants", "tenants", "registry_size")


def tenant_report(dump: dict) -> dict:
    """Aggregate a tenant dump into the tables the text report prints
    and the diff compares."""
    tenants = []
    for name, t in (dump.get("tenants") or {}).items():
        res = t.get("residency") or {}
        wait = t.get("wait_ms") or {}
        tenants.append({
            "tenant": name,
            "rows": t.get("rows", 0),
            "lane_rows": dict(t.get("lane_rows", {})),
            "sheds": t.get("sheds", 0),
            "warm_skips": t.get("warm_skips", 0),
            "cold_evictions": t.get("cold_evictions", 0),
            "row_quota": t.get("row_quota", 0),
            "residency_budget": t.get("residency_budget", 0),
            "resident_bytes": res.get("bytes", 0),
            "resident_tables": res.get("tables", 0),
            "wait_p99_ms": wait.get("p99_ms", 0.0),
            "wait_n": wait.get("n", 0),
            # ISSUE 20 device chargeback columns (0.0 on dumps from
            # builds predating the split — the report stays readable)
            "device_ms": t.get("device_ms", 0.0),
            "comp_ms": t.get("comp_ms", 0.0),
            "h2d_ms": t.get("h2d_ms", 0.0),
            "delta_bytes": t.get("delta_bytes", 0),
        })
    tenants.sort(key=lambda r: (-r["rows"], r["tenant"]))
    retired = dict(dump.get("retired", {}))
    return {
        "registry_size": dump.get("registry_size", 0),
        "evicted": dump.get("evicted", 0),
        "owner_keys": dump.get("owner_keys", 0),
        "retired": retired,
        "tenants": tenants,
        "rows_total": sum(r["rows"] for r in tenants)
        + retired.get("rows", 0),
        "sheds_total": sum(r["sheds"] for r in tenants)
        + retired.get("sheds", 0),
        "warm_skips_total": sum(r["warm_skips"] for r in tenants)
        + retired.get("warm_skips", 0),
        "cold_evictions_total": sum(r["cold_evictions"]
                                    for r in tenants)
        + retired.get("cold_evictions", 0),
        "resident_bytes_total": sum(r["resident_bytes"]
                                    for r in tenants),
        "wait_p99_worst_ms": max(
            (r["wait_p99_ms"] for r in tenants), default=0.0),
        "device_ms_total": round(
            sum(r["device_ms"] for r in tenants)
            + retired.get("device_us", 0) / 1000.0, 3),
        "comp_ms_total": round(
            sum(r["comp_ms"] for r in tenants)
            + retired.get("comp_us", 0) / 1000.0, 3),
    }


# --------------------------------------------------------------------------
# differencing (controller_report --diff's shape, over the pod figures)
# --------------------------------------------------------------------------


def diff_report(rep_a: dict, rep_b: dict,
                threshold_pct: float = DEFAULT_THRESHOLD_PCT,
                threshold_abs: float = DEFAULT_THRESHOLD_ABS) -> dict:
    """Pod-figure delta rows (A = before, B = after). Growth is bad
    for sheds, warm skips, cold-eviction churn and the worst per-
    tenant wait p99; a figure REGRESSED past BOTH thresholds."""

    def flag_of(a: float, b: float,
                abs_floor: float = threshold_abs) -> str:
        return flag_directional(a, b, threshold_pct=threshold_pct,
                                abs_floor=abs_floor)

    def row(metric: str, abs_floor: float = threshold_abs) -> dict:
        a, b = rep_a[metric], rep_b[metric]
        return {"metric": metric, "a": a, "b": b,
                "delta": round(b - a, 4),
                "flag": flag_of(a, b, abs_floor)}

    rows = [
        row("sheds_total"),
        row("warm_skips_total"),
        row("cold_evictions_total"),
        row("wait_p99_worst_ms", abs_floor=max(threshold_abs, 10.0)),
        # compile ms charged to tenants growing means the pod started
        # paying recompiles for someone — a regression signal
        row("comp_ms_total", abs_floor=max(threshold_abs, 10.0)),
        # total device ms is workload-following, informational only
        {"metric": "device_ms_total", "a": rep_a["device_ms_total"],
         "b": rep_b["device_ms_total"],
         "delta": round(rep_b["device_ms_total"]
                        - rep_a["device_ms_total"], 4),
         "flag": ""},
        {"metric": "rows_total", "a": rep_a["rows_total"],
         "b": rep_b["rows_total"],
         "delta": round(rep_b["rows_total"] - rep_a["rows_total"], 4),
         "flag": ""},
        {"metric": "registry_size", "a": rep_a["registry_size"],
         "b": rep_b["registry_size"],
         "delta": rep_b["registry_size"] - rep_a["registry_size"],
         "flag": ""},
    ]

    notes = []
    by_a = {r["tenant"]: r for r in rep_a["tenants"]}
    # device-share growth: a tenant taking a materially bigger slice
    # of the pod's device time than before (>= 10 percentage points
    # on a non-trivial total) is the noisy-neighbor chargeback signal
    tot_a = max(rep_a["device_ms_total"], 1e-9)
    tot_b = max(rep_b["device_ms_total"], 1e-9)
    if rep_b["device_ms_total"] >= 1.0:
        for r in rep_b["tenants"]:
            share_b = r["device_ms"] / tot_b
            before = by_a.get(r["tenant"])
            share_a = (before["device_ms"] / tot_a) if before else 0.0
            if share_b - share_a >= 0.10:
                notes.append(
                    f"tenant {r['tenant']!r} device-share growth: "
                    f"{share_a * 100.0:.1f}% -> {share_b * 100.0:.1f}% "
                    f"of pod device time ({r['device_ms']} ms) — pull "
                    f"/dump_devices cost_surfaces for its flush "
                    f"family and /dump_flushes for WHO queued the "
                    f"rows")
    for r in rep_b["tenants"]:
        before = by_a.get(r["tenant"])
        if before is None:
            notes.append(f"tenant {r['tenant']!r} is new in B "
                         f"({r['rows']} rows)")
            continue
        d = r["sheds"] - before["sheds"]
        if d >= threshold_abs and (before["sheds"] == 0 or
                                   d / before["sheds"] * 100.0
                                   >= threshold_pct):
            notes.append(
                f"tenant {r['tenant']!r} shed growth: "
                f"{before['sheds']} -> {r['sheds']} — its quota "
                f"started biting; check row_quota sizing and whether "
                f"a neighbor's drain share starved it")
    for name in by_a:
        if name not in {r["tenant"] for r in rep_b["tenants"]}:
            notes.append(f"tenant {name!r} gone in B (evicted or "
                         f"retired into the _retired accumulator)")

    regressions = [r["metric"] for r in rows
                   if r["flag"] == "REGRESSED"]
    return {"rows": rows, "regressions": regressions, "notes": notes}


# --------------------------------------------------------------------------
# formatting
# --------------------------------------------------------------------------


def format_report(rep: dict) -> str:
    ret = rep["retired"]
    lines = [
        f"registry: {rep['registry_size']} tenants "
        f"({rep['evicted']} evicted, retired rows "
        f"{ret.get('rows', 0)}), {rep['owner_keys']} owned table "
        f"keys; {rep['rows_total']} rows verified, "
        f"{rep['sheds_total']} quota sheds, "
        f"{rep['resident_bytes_total']} resident bytes"]
    if rep["tenants"]:
        lines += ["", f"{'tenant':<22}{'rows':>10}{'sheds':>7}"
                      f"{'wskip':>7}{'cevict':>7}{'resKB':>8}"
                      f"{'tables':>7}{'p99ms':>9}{'quota':>7}"
                      f"{'dev_ms':>10}{'comp_ms':>9}"]
        for r in rep["tenants"]:
            lines.append(
                f"{r['tenant']:<22}{r['rows']:>10}{r['sheds']:>7}"
                f"{r['warm_skips']:>7}{r['cold_evictions']:>7}"
                f"{r['resident_bytes'] // 1024:>8}"
                f"{r['resident_tables']:>7}{r['wait_p99_ms']:>9}"
                f"{r['row_quota'] or '-':>7}"
                f"{r['device_ms']:>10}{r['comp_ms']:>9}")
        lines.append(
            f"device time charged: {rep['device_ms_total']} ms "
            f"(compile {rep['comp_ms_total']} ms), retired included")
    return "\n".join(lines)


def format_diff(diff: dict, path_a: str = "A",
                path_b: str = "B") -> str:
    lines = [f"tenant-plane delta: {path_a} -> {path_b}",
             "", f"{'metric':<24}{'A':>12}{'B':>12}{'Δ':>12}  flag"]
    for r in diff["rows"]:
        lines.append(f"{r['metric']:<24}{r['a']:>12}{r['b']:>12}"
                     f"{r['delta']:>+12}  {r['flag']}")
    for n in diff.get("notes", []):
        lines.append(f"NOTE: {n}")
    lines += ["", ("regressions: " + ", ".join(diff["regressions"])
                   if diff["regressions"]
                   else "no regressions flagged")]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = build_parser(
        "per-tenant occupancy and QoS tables from a /dump_tenants "
        "document, or a pod-figure delta diff of two of them",
        operand_help="tenant dump file(s); two with --diff",
        diff_help="diff two dumps: pod-figure delta table with "
                  "regression flags",
        default_pct=DEFAULT_THRESHOLD_PCT,
        default_abs=DEFAULT_THRESHOLD_ABS)
    return run_cli(argv, parser=ap, load=load_tenants,
                   report=tenant_report, diff=diff_report,
                   fmt_report=format_report, fmt_diff=format_diff)


if __name__ == "__main__":
    raise SystemExit(main())
