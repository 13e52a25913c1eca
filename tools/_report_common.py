"""Shared --diff/--fail-on-regression plumbing for the report tools.

Seven report tools (trace, height, peer, device, controller, catchup,
tenant) grew the same CLI shape one PR at a time: positional dump
file(s), ``--diff`` for an A->B delta table, a relative + absolute
threshold pair, ``--json``, and a ``--fail-on-regression`` CI gate
that must ERROR when wired without ``--diff`` (a gate without a
comparison reads permanently green). This module is that shape, once —
the per-tool files keep what is genuinely theirs (which keys make a
dump theirs, figure aggregation, which metrics flag in which direction,
table rendering).

Three flag styles exist in the fleet and all three live here:

  * :func:`flag_directional` — growth (or shrink, ``bad_dir=-1``) is
    the bad direction; improvement needs only the absolute floor while
    a regression needs BOTH floors, and ``any_growth=True`` waives the
    relative floor (the steady-recompile / SLO-violation rule: one is
    a bug no matter the baseline). Used by tenant/controller/device.
  * :func:`flag_symmetric` — both directions flag past both floors:
    bigger is REGRESSED, smaller is improved. Used by the ms-based
    stage tables (height/trace) and the peer health counters.
  * :func:`flag_directed` — symmetric thresholds but an explicit
    ``bad_when`` ("up"/"down") names the bad direction, so a drop in
    blocks/s flags REGRESSED while a drop in verify_ms flags improved.
    Used by catchup's throughput-vs-latency mix.

Behavior-identical by construction: each function is the verbatim
closure it replaced, with the thresholds as keyword arguments instead
of captured cells; the argparse error strings are unchanged (the
synthetic-regression smokes in tests/test_z*_smoke.py pin them).
"""
from __future__ import annotations

import argparse
import json


def load_dump(path: str, route: str, *keys: str) -> dict:
    """A saved ``curl $NODE<route>`` document: a JSON object that holds
    every one of ``keys``. Anything else is refused by name, so a file
    handed to the wrong tool never renders as an empty report."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and all(k in doc for k in keys):
        return doc
    raise ValueError(
        f"{path}: not a {route} document (no {' / '.join(keys)})")


def flag_directional(a: float, b: float, *, threshold_pct: float,
                     abs_floor: float, bad_dir: int = 1,
                     any_growth: bool = False) -> str:
    """One-sided flag: movement in ``bad_dir`` is bad. A regression
    must clear the absolute floor AND (unless ``any_growth``) the
    relative floor; an improvement needs only the absolute floor."""
    d = (b - a) * bad_dir
    if d <= 0:
        return "improved" if d < 0 and abs(d) >= abs_floor else ""
    if d < abs_floor:
        return ""
    if not any_growth and a > 0 and d / abs(a) * 100.0 < threshold_pct:
        return ""
    return "REGRESSED"


def flag_directed(a: float, b: float, *, bad_when: str,
                  threshold_pct: float, abs_floor: float) -> str:
    """Two-sided flag with an explicit bad direction: past both
    floors, movement toward ``bad_when`` ("up"/"down") is REGRESSED
    and the opposite movement is improved."""
    d = b - a
    bad = d > 0 if bad_when == "up" else d < 0
    if abs(d) < abs_floor:
        return ""
    if a > 0 and abs(d) / abs(a) * 100.0 < threshold_pct:
        return ""
    return "REGRESSED" if bad else "improved"


def flag_symmetric(a: float, b: float, *, threshold_pct: float,
                   abs_floor: float) -> str:
    """Two-sided flag where growth is bad: past both floors, up is
    REGRESSED and down is improved."""
    return flag_directed(a, b, bad_when="up",
                         threshold_pct=threshold_pct,
                         abs_floor=abs_floor)


def build_parser(description: str, *, operand: str = "dumps",
                 operand_help: str, diff_help: str,
                 default_pct: float, default_abs: float,
                 pct_help: str = "relative regression floor (%%)",
                 abs_flag: str = "--threshold-abs",
                 abs_help: str = "absolute regression floor "
                                 "(count / value)"
                 ) -> argparse.ArgumentParser:
    """The shared CLI surface. ``abs_flag`` lets the ms-based tools
    keep their ``--threshold-ms`` spelling; either way the value parses
    into ``args.threshold_abs`` so run_cli passes one tuple shape."""
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument(operand, nargs="+", help=operand_help)
    ap.add_argument("--diff", action="store_true", help=diff_help)
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of a table")
    ap.add_argument("--threshold-pct", type=float, default=default_pct,
                    help=pct_help)
    ap.add_argument(abs_flag, type=float, default=default_abs,
                    dest="threshold_abs", help=abs_help)
    ap.add_argument("--fail-on-regression", action="store_true",
                    help="exit 1 when the diff flags any regression")
    return ap


def run_cli(argv, *, parser: argparse.ArgumentParser, load, report,
            diff, fmt_report, fmt_diff, operand: str = "dumps",
            noun: str = "dump") -> int:
    """The shared main() body: parse, gate-check, then either the
    single-file report or the two-file diff (exit 1 when the gate is
    armed and the diff flags regressions)."""
    args = parser.parse_args(argv)
    paths = getattr(args, operand)
    if args.fail_on_regression and not args.diff:
        # only a diff can flag regressions; a gate wired without --diff
        # would be permanently green
        parser.error("--fail-on-regression requires --diff")
    if args.diff:
        if len(paths) != 2:
            parser.error(f"--diff needs exactly two {noun} files")
        rep_a = report(load(paths[0]))
        rep_b = report(load(paths[1]))
        d = diff(rep_a, rep_b, args.threshold_pct, args.threshold_abs)
        print(json.dumps(d) if args.json
              else fmt_diff(d, paths[0], paths[1]))
        return 1 if args.fail_on_regression and d["regressions"] else 0
    if len(paths) != 1:
        parser.error(f"exactly one {noun} file (or use --diff A B)")
    rep = report(load(paths[0]))
    print(json.dumps(rep) if args.json else fmt_report(rep))
    return 0
