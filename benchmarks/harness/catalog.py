"""Finds everything that belongs to one cell by the names in
BENCHMARK.json: the configuration's file, the traffic mix's file, the
driver the mix names, and one reader per metric. A later PR adds a cell
by adding files and entries; nothing here names a cell."""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class CatalogError(Exception):
    pass


def _name(s: str) -> str:
    if not isinstance(s, str) or not NAME_RE.match(s):
        raise CatalogError(f"not a name: {s!r}")
    return s


def _json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except OSError as e:
        raise CatalogError(f"cannot read {path}: {e}") from e


def _module(kind: str, name: str, bench_dir: str):
    path = os.path.join(bench_dir, kind, _name(name) + ".py")
    if not os.path.isfile(path):
        raise CatalogError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of BENCHMARK.json's workloads with its files loaded."""

    def __init__(self, workload: str, bench_dir: str = BENCH_DIR):
        self.bench_dir = bench_dir
        self.spec = _json(os.path.join(os.path.dirname(bench_dir),
                                       "BENCHMARK.json"))
        rows = [w for w in self.spec["workloads"] if w["name"] == workload]
        if len(rows) != 1:
            raise CatalogError(
                f"workload {workload!r} is not in BENCHMARK.json "
                f"(has: {[w['name'] for w in self.spec['workloads']]})")
        self.workload = rows[0]
        self.name = workload
        self.chips = int(self.workload["chips"])
        self.config_name = _name(self.workload["config"])
        self.traffic_name = _name(self.workload["traffic"])
        cfg_row = [c for c in self.spec["configs"]
                   if c["name"] == self.config_name]
        if len(cfg_row) != 1:
            raise CatalogError(f"no config entry {self.config_name!r}")
        self.config_path = os.path.join(os.path.dirname(bench_dir),
                                        cfg_row[0]["file"])
        self.config = _json(self.config_path)
        self.traffic_path = os.path.join(
            bench_dir, "traffic", self.traffic_name + ".json")
        self.traffic = _json(self.traffic_path)
        self.driver = _module("drivers", self.traffic["driver"], bench_dir)

    def metrics(self, group: str):
        """[(entry, reader module)] of the cell's metrics in `group`
        ('end_to_end' or 'per_layer'), in BENCHMARK.json's order."""
        kind = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[
            group]
        out = []
        for m in self.spec[group]:
            cells = m.get("workloads")
            if cells is not None and self.name not in cells:
                continue
            out.append((m, _module(kind, m["name"], self.bench_dir)))
        return out
