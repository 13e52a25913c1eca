"""The harness is driven by data: a configuration, a traffic mix and a
per-layer metric added as NEW FILES and new entries are found, with no
edit to a file that was there; and BENCHMARK.json agrees with the
files it names."""
import json
import os
import shutil

import pytest

from harness import catalog


def _spec():
    with open(os.path.join(catalog.REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_added_files_are_found(tmp_path):
    bench = tmp_path / "benchmarks"
    shutil.copytree(catalog.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    # a later PR's files: one configuration, one mix, one metric reader
    cfg = json.loads((bench / "configs" / "valset-1k.json").read_text())
    cfg.update(name="qa175", validators=175)
    (bench / "configs" / "qa175.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "votes-serial.json").read_text())
    mix.update(name="votes-bursts", rate_per_s=40)
    (bench / "traffic" / "votes-bursts.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "flushes_per_vote.py").write_text(
        'LAYER = "verify plane"\n'
        'UNIT, BETTER, SOURCE, MOVES = "count", "lower", '
        '"program_counter", "vote_p50_ms"\n\n\n'
        "def read(obs):\n"
        "    return obs.get('flushes_per_vote')\n")
    # ... and its entries
    spec = _spec()
    spec["configs"].append({"name": "qa175", "source": "upstream QA",
                            "file": "benchmarks/configs/qa175.json",
                            "reduced": [], "why": "realistic size"})
    spec["workloads"].append({"name": "qa175.bursts", "config": "qa175",
                              "traffic": "votes-bursts", "chips": 1,
                              "why": "bursts"})
    for m in spec["end_to_end"]:
        if m["name"].startswith("vote_"):
            m["workloads"].append("qa175.bursts")
    spec["per_layer"].append({
        "name": "flushes_per_vote", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "verify plane",
        "moves": "vote_p50_ms", "workloads": ["qa175.bursts"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = catalog.Cell("qa175.bursts", bench_dir=str(bench))
    assert cell.config["validators"] == 175
    assert cell.traffic["rate_per_s"] == 40
    assert cell.driver.__file__ == str(bench / "drivers" / "votes_serial.py")
    layer = {e["name"]: r for e, r in cell.metrics("per_layer")}
    assert layer["flushes_per_vote"].read({"flushes_per_vote": 2}) == 2
    assert "compiles_in_window" in layer          # reported in every cell
    assert "plane_fill" not in layer              # lists other cells only
    assert [e["name"] for e, _ in cell.metrics("end_to_end")] == [
        "vote_p50_ms", "setup_s"]
    # a reader that finds nothing to read returns nothing
    assert layer["flushes_per_vote"].read({}) is None


def test_unknown_names_are_refused():
    with pytest.raises(catalog.CatalogError):
        catalog.Cell("no-such.cell")
    with pytest.raises(catalog.CatalogError):
        catalog._module("drivers", "../run", catalog.BENCH_DIR)


@pytest.mark.parametrize(
    "cell_name", [w["name"] for w in _spec()["workloads"]])
def test_benchmark_json_agrees_with_its_files(cell_name):
    spec = _spec()
    cell = catalog.Cell(cell_name)
    assert cell.chips == 1
    cfg_entry = next(c for c in spec["configs"]
                     if c["name"] == cell.config_name)
    assert cell.config["name"] == cfg_entry["name"]
    assert cell.config["source"] == cfg_entry["source"]
    assert cell.config["reduced"] == cfg_entry["reduced"]
    assert len(cfg_entry["source"]) <= 200
    assert {"guarantees", "assumed", "reduced"} <= set(cell.config)
    assert cell.traffic["name"] == cell.traffic_name
    for fn in ("prepare", "warm", "window", "verify", "abandon", "close"):
        assert callable(getattr(cell.driver, fn))
    e2e = cell.metrics("end_to_end")
    assert "setup_s" in [e["name"] for e, _ in e2e] and len(e2e) >= 2
    reported = {e["name"] for e, _ in e2e}
    for entry, reader in e2e:
        assert (reader.UNIT, reader.BETTER, reader.SOURCE) == (
            entry["unit"], entry["better"], entry["source"])
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.25
    layer = cell.metrics("per_layer")
    assert layer
    for entry, reader in layer:
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.MOVES) == (entry["unit"], entry["better"],
                                  entry["source"], entry["layer"],
                                  entry["moves"])
        # a per-layer metric is reported only where the metric it moves is
        assert entry["moves"] in reported
        assert reader.read({}) is None
