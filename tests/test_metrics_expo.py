"""/metrics exposition coverage (ISSUE 5 satellites).

A promtext-parser round-trip over a fully-populated NodeMetrics
(HELP/TYPE pairing, label escaping, histogram bucket monotonicity),
the idle-histogram zero-row fix, the scrape-time sampling of the
previously-invisible internals (failpoint trigger counts, WAL fsync
latency, staging pool, breaker transitions), and the metric naming
lint wired as a fast tier-1 gate.
"""
import re

import pytest

from cometbft_tpu.libs.metrics import Histogram, NodeMetrics, Registry

# ---------------------------------------------------------------------------
# a small prometheus text-format 0.0.4 parser (the round-trip oracle)
# ---------------------------------------------------------------------------

_SAMPLE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})? (?P<value>[^ ]+)$"
)
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(v: str) -> str:
    return (v.replace("\\n", "\n").replace('\\"', '"')
            .replace("\\\\", "\\"))


def parse_promtext(text: str):
    """Parse an exposition into {family: {type, help, samples}} and
    VALIDATE structure: every sample belongs to a family whose HELP and
    TYPE were declared first, label blocks parse completely, values are
    floats."""
    families = {}
    current = None
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_ = rest.partition(" ")
            families[name] = {"help": help_, "type": None, "samples": []}
            current = name
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, typ = rest.partition(" ")
            assert name in families, f"TYPE before HELP: {line!r}"
            assert name == current, f"TYPE not paired with HELP: {line!r}"
            families[name]["type"] = typ
            continue
        assert not line.startswith("#"), f"unknown comment {line!r}"
        m = _SAMPLE.match(line)
        assert m, f"unparseable sample line {line!r}"
        sname = m.group("name")
        base = sname
        for suffix in ("_bucket", "_sum", "_count"):
            if sname.endswith(suffix) and sname[: -len(suffix)] in families:
                base = sname[: -len(suffix)]
        assert base in families, f"sample {sname} has no HELP/TYPE"
        assert families[base]["type"] is not None, f"{base} missing TYPE"
        labels = {}
        raw = m.group("labels")
        if raw:
            consumed = 0
            for lm in _LABEL.finditer(raw):
                labels[lm.group(1)] = _unescape(lm.group(2))
                consumed = lm.end()
            rest = raw[consumed:].strip(", ")
            assert not rest, f"unparsed label residue {rest!r} in {line!r}"
        value = float(m.group("value")) if m.group("value") != "+Inf" \
            else float("inf")
        families[base]["samples"].append((sname, labels, value))
    return families


def _check_histogram(fam_name: str, fam: dict) -> None:
    """Bucket monotonicity + _sum/_count presence per label set."""
    by_key = {}
    for sname, labels, value in fam["samples"]:
        key = tuple(sorted((k, v) for k, v in labels.items()
                           if k != "le"))
        slot = by_key.setdefault(key, {"buckets": [], "sum": None,
                                       "count": None})
        if sname.endswith("_bucket"):
            slot["buckets"].append((float(labels["le"]), value))
        elif sname.endswith("_sum"):
            slot["sum"] = value
        elif sname.endswith("_count"):
            slot["count"] = value
    assert by_key, f"{fam_name}: histogram family exposed no samples"
    for key, slot in by_key.items():
        assert slot["sum"] is not None, f"{fam_name}{key}: no _sum"
        assert slot["count"] is not None, f"{fam_name}{key}: no _count"
        buckets = sorted(slot["buckets"])
        assert buckets, f"{fam_name}{key}: no buckets"
        cum = [v for _, v in buckets]
        assert cum == sorted(cum), \
            f"{fam_name}{key}: buckets not monotonic: {buckets}"
        assert buckets[-1][0] == float("inf"), \
            f"{fam_name}{key}: missing +Inf bucket"
        assert buckets[-1][1] == slot["count"], \
            f"{fam_name}{key}: +Inf bucket != _count"


# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------


def _populated_node_metrics() -> NodeMetrics:
    m = NodeMetrics()
    m.height.set(7)
    m.rounds.set(1)
    m.validators.set(4)
    m.block_interval.observe(0.8)
    m.num_txs.set(3)
    m.total_txs.inc(3)
    m.block_size.set(512)
    m.step_duration.observe(0.01, step="propose")
    m.step_duration.observe(0.002, step="prevote")
    m.verify_batches.inc()
    m.verify_sigs.inc(128)
    m.verify_seconds.observe(0.02)
    m.plane_queue_depth.set(2)
    m.plane_batch_size.observe(64)
    m.plane_wait_seconds.observe(0.003)
    m.plane_padding_waste.inc(4)
    m.plane_pack_seconds.observe(0.0004)
    # split by path since the device-stamping PR: "device" = per-row
    # delta buffers, "host" = full packed rows
    m.plane_h2d_bytes.inc(4096, path="host")
    m.plane_h2d_bytes.inc(80, path="device")
    m.mempool_size.set(9)
    m.peers.set(3)
    m.blocksync_syncing.set(0)
    return m


def test_full_nodemetrics_promtext_roundtrip():
    text = _populated_node_metrics().expose_text()
    fams = parse_promtext(text)
    # every registered family made it out with HELP+TYPE
    for name in ("cometbft_consensus_height",
                 "cometbft_consensus_txs_total",
                 "cometbft_consensus_step_duration_seconds",
                 "cometbft_verifyplane_batch_rows",
                 "cometbft_verifyplane_shard_flushes_total",
                 "cometbft_verifyplane_shard_rows_total",
                 "cometbft_verifyplane_shard_devices",
                 "cometbft_crypto_valset_table_cache_total",
                 "cometbft_parallel_mesh_step_cache_total",
                 "cometbft_crypto_staging_pool_total",
                 "cometbft_crypto_breaker_transitions_total",
                 "cometbft_failpoints_fires_total",
                 "cometbft_wal_fsync_total",
                 "cometbft_wal_fsync_seconds_total"):
        assert name in fams, f"{name} missing from exposition"
    for name, fam in fams.items():
        assert fam["type"] in ("counter", "gauge", "histogram"), name
        assert fam["samples"], f"{name}: no sample rows at all"
        if fam["type"] == "histogram":
            _check_histogram(name, fam)
    # labeled histogram kept its label through the round trip
    steps = {s[1].get("step") for s in
             fams["cometbft_consensus_step_duration_seconds"]["samples"]}
    assert {"propose", "prevote"} <= steps
    # the h2d counter's path split (device stamping PR) survives the
    # round trip with both series intact
    h2d = {s[1].get("path"): s[2] for s in
           fams["cometbft_verifyplane_h2d_bytes_total"]["samples"]}
    assert h2d == {"host": 4096, "device": 80}


def test_idle_histograms_expose_zero_rows():
    """Satellite fix: a registered-but-never-observed histogram must
    still scrape with zero buckets/_sum/_count (previously the family
    vanished entirely — an idle plane had NO latency metrics)."""
    text = NodeMetrics().expose_text()
    fams = parse_promtext(text)
    fam = fams["cometbft_verifyplane_submit_to_result_seconds"]
    assert fam["type"] == "histogram"
    _check_histogram("cometbft_verifyplane_submit_to_result_seconds", fam)
    names = dict((s[0], s[2]) for s in fam["samples"])
    assert names["cometbft_verifyplane_submit_to_result_seconds_sum"] == 0
    assert names["cometbft_verifyplane_submit_to_result_seconds_count"] == 0


def test_label_escaping_roundtrip():
    r = Registry()
    c = r.counter("test", "weird_total", "label escaping")
    hostile = 'a"b\\c\nd'
    c.inc(3, reason=hostile)
    fams = parse_promtext(r.expose_text())
    samples = fams["cometbft_test_weird_total"]["samples"]
    labeled = [s for s in samples if s[1]]
    assert labeled and labeled[0][1]["reason"] == hostile
    assert labeled[0][2] == 3.0


def test_histogram_zero_rows_direct():
    h = Histogram("x_seconds", "h", buckets=(0.1, 1))
    lines = h.expose()
    assert "x_seconds_count 0" in lines
    assert "x_seconds_sum 0" in lines
    assert any("_bucket" in ln and ln.endswith(" 0") for ln in lines)


def test_scrape_samples_failpoints_and_wal(tmp_path):
    """The previously-unreachable internals land on /metrics: per-point
    failpoint trigger counts and WAL fsync latency, sampled at scrape
    time."""
    from cometbft_tpu.consensus import wal as walmod
    from cometbft_tpu.libs import failpoints as fp

    fp.reset()
    fp.register("expo.test.point", "test seam")
    fp.arm("expo.test.point", "raise", count=1)
    with pytest.raises(fp.FailpointError):
        fp.fail_point("expo.test.point")

    w = walmod.WAL(str(tmp_path / "t.wal"))
    before = walmod.fsync_stats()["count"]
    w.write_sync(walmod.MSG_INFO, b"hello")
    w.close()

    try:
        text = NodeMetrics().expose_text()
        fams = parse_promtext(text)
        fires = {s[1].get("point"): s[2]
                 for s in fams["cometbft_failpoints_fires_total"]["samples"]
                 if s[1]}
        assert fires.get("expo.test.point") == 1.0
        wal_count = fams["cometbft_wal_fsync_total"]["samples"][0][2]
        assert wal_count >= before + 1
        secs = fams["cometbft_wal_fsync_seconds_total"]["samples"][0][2]
        assert secs >= 0.0
    finally:
        fp.reset()


def test_scrape_samples_breaker_and_staging():
    from cometbft_tpu.crypto import batch as cbatch

    brk = cbatch.device_breaker()
    pool = cbatch.staging_pool()
    pool.get("expo.test", (4,), "int32")
    pool.get("expo.test", (4,), "int32")
    pool.get("expo.test", (4,), "int32")  # 2 misses (slots) + 1 hit
    text = NodeMetrics().expose_text()
    fams = parse_promtext(text)
    kinds = {s[1].get("kind"): s[2] for s in
             fams["cometbft_crypto_staging_pool_total"]["samples"] if s[1]}
    assert kinds.get("misses", 0) >= 2
    assert kinds.get("hits", 0) >= 1
    trans = {s[1].get("kind"): s[2] for s in
             fams["cometbft_crypto_breaker_transitions_total"]["samples"]
             if s[1]}
    assert trans.get("open", -1) == float(brk.trips)
    assert trans.get("close", -1) == float(brk.closes)
    res = fams["cometbft_crypto_staging_pool_resident_bytes"]["samples"]
    assert res[0][2] >= 16  # the 4x int32 test buffers are resident


def test_scrape_staging_stats_move_under_flush_traffic():
    """ISSUE 6 satellite: the scrape-time pool stats (hits/misses/
    resident bytes) MOVE correctly as flush traffic rotates buffers —
    including the verify plane's PRIVATE pool, which only the scrape
    aggregation can see."""
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.verifyplane import (
        VerifyPlane,
        clear_global_plane,
        set_global_plane,
    )

    def pool_kinds(text):
        fams = parse_promtext(text)
        kinds = {s[1].get("kind"): s[2] for s in
                 fams["cometbft_crypto_staging_pool_total"]["samples"]
                 if s[1]}
        res = fams["cometbft_crypto_staging_pool_resident_bytes"]
        return kinds, res["samples"][0][2]

    m = NodeMetrics()
    plane = VerifyPlane(window_ms=0.5, use_device=False)
    plane.start()
    set_global_plane(plane)
    try:
        before, res_before = pool_kinds(m.expose_text())
        # rotate the plane's PRIVATE pool like concurrent device
        # flushes would: slots misses to warm a fresh shape, then hits
        for _ in range(5):
            plane._staging.get("expo.flush", (8, 4), "int32")
        # and the process-global pool (blocksync path)
        cbatch.staging_pool().get("expo.flush2", (2, 2), "int32")
        after, res_after = pool_kinds(m.expose_text())
        # the private pool's 2 slots were allocation misses, the other
        # 3 gets were rotation hits; the global pool added 1 miss
        assert after.get("misses", 0) >= before.get("misses", 0) + 3
        assert after.get("hits", 0) >= before.get("hits", 0) + 3
        # resident bytes grew by exactly the new buffers: 2 slots of
        # 8x4 int32 (private pool) + the single allocated 2x2 int32
        # slot (global pool lazily allocates per get)
        assert res_after - res_before == 2 * 8 * 4 * 4 + 1 * 2 * 2 * 4
    finally:
        clear_global_plane(plane)
        plane.stop()


def test_metrics_lint_nodemetrics_clean():
    """CI gate: the full node metric set obeys the naming conventions
    (counters _total, histograms seconds/bytes/rows, no dupes)."""
    from tools.metrics_lint import lint_node_metrics

    assert lint_node_metrics() == []


def test_metrics_lint_sample_coverage_detects_undeclared():
    """The registry cross-check (ISSUE 13 satellite): a _sample body
    writing into a family never declared in NodeMetrics.__init__ must
    be flagged — its AttributeError would otherwise be swallowed by
    the sampler's fault isolation and the family would silently never
    scrape. The real _sample must pass clean (covered by the
    lint_node_metrics test above, which now includes this check)."""
    from tools.metrics_lint import _sample_coverage

    out = _sample_coverage(
        "self.ghost_family.set(1.0)\nself.height_stage.set(0.0)")
    assert any("ghost_family" in v for v in out), out
    assert not any("height_stage" in v for v in out), out


def test_metrics_lint_catches_violations():
    from tools.metrics_lint import lint_registry

    r = Registry()
    r.counter("bad", "requests", "counter missing _total")
    r.gauge("bad", "depth_total", "gauge with counter suffix")
    r.histogram("bad", "latency_ms", "histogram off base unit")
    r.counter("bad", "dup_total", "first")
    r.counter("bad", "dup_total", "second")
    r.gauge("bad", "nohelp")
    out = lint_registry(r)
    assert any("must end _total" in v for v in out)
    assert any("must not end _total" in v for v in out)
    assert any("base unit" in v for v in out)
    assert any("duplicate" in v for v in out)
    assert any("empty HELP" in v for v in out)
