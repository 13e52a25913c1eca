"""The last line carries exactly the contract's keys."""
import json

import pytest

from harness import lastline

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 123}


def test_untraced_line_has_the_contract_keys_only():
    line = json.loads(lastline.last_line(
        correct=True, attempted=10, failed=0,
        metrics={"commit_p50_ms": (71.25, "ms"), "setup_s": (30.5, "s")},
        device=DEVICE))
    assert sorted(line) == sorted(lastline.KEYS)
    assert line["metrics"]["commit_p50_ms"] == {"value": 71.25, "unit": "ms"}
    assert sorted(line["device"]) == sorted(lastline.DEVICE_KEYS)


def test_traced_line_adds_busy_window_and_breakdown():
    ops = [[f"op{i}", 1.0 / (i + 1)] for i in range(14)]
    line = json.loads(lastline.last_line(
        correct=True, attempted=10, failed=0,
        metrics={"commit_host_ms": (30.0, "ms")},
        device={**DEVICE, "busy_s": 1.5, "window_s": 4.0},
        breakdown={"device_ops": ops, "idle_gaps": [["bench.add_vote", 2.0]],
                   "window_s": 4.0, "lines": {}}))
    assert sorted(line) == sorted(lastline.KEYS + ("breakdown",))
    assert sorted(line["breakdown"]) == ["device_ops", "idle_gaps"]
    assert len(line["breakdown"]["device_ops"]) == 10
    assert line["device"]["busy_s"] == 1.5


def test_a_stray_device_key_is_refused():
    with pytest.raises(ValueError):
        lastline.last_line(correct=True, attempted=1, failed=0, metrics={},
                           device={**DEVICE, "hostname": "x"})
    with pytest.raises(ValueError):
        lastline.last_line(correct=True, attempted=1, failed=0, metrics={},
                           device={"platform": "tpu"})
