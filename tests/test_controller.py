"""The self-tuning control plane (libs/controller): hysteresis,
cooldowns, clamp bounds, the structurally-off-limits CONSENSUS lane,
the bounded decision ledger, and the module-global dump surface.

All host-only: the controller is driven against fakes here, once
against the real plane and admission setters, and on a live network in
the simnet scenarios of test_soak."""
import pytest

from cometbft_tpu.libs import controller as cp


class FakeLedger:
    """Height ledger stand-in: len + the commit-latency summary."""

    def __init__(self):
        self.p99 = 0.0

    def __len__(self):
        return 1

    def summary(self):
        return {"commit_latency_ms": {"p99": self.p99}}


class FakeFlushLedger:
    def __init__(self):
        self.device = {}

    def summary(self):
        return {"device": self.device} if self.device else {}


class FakePlane:
    def __init__(self, bulk_ms=8.0, gw_ms=4.0, deadline_ms=400.0,
                 flights=1, flights_max=4):
        self.bulk_window = bulk_ms / 1000.0
        self.gateway_window = gw_ms / 1000.0
        self.bulk_deadline = deadline_ms / 1000.0
        self.flights = flights
        self.flights_max = flights_max
        self.sheds = {"consensus": 0, "gateway": 0, "bulk": 0}
        self.ledger = FakeFlushLedger()
        self.applied = []

    def set_lane_window_ms(self, lane, ms):
        assert lane in ("gateway", "bulk")
        self.applied.append(("window", lane, ms))
        if lane == "bulk":
            self.bulk_window = ms / 1000.0
        else:
            self.gateway_window = ms / 1000.0
        return ms

    def set_lane_deadline_ms(self, lane, ms):
        assert lane in ("gateway", "bulk")
        self.applied.append(("deadline", lane, ms))
        self.bulk_deadline = ms / 1000.0
        return ms

    def set_flights(self, n):
        self.applied.append(("flights", n))
        self.flights = min(self.flights_max, max(1, int(n)))
        return self.flights


class FakeAdmission:
    def __init__(self, high=0.9, low=0.7):
        self.high_watermark = high
        self.low_watermark = low
        self.fill = 0.0
        self._fill_fn = lambda: self.fill

    def set_watermarks(self, high, low):
        self.high_watermark = min(1.0, max(0.01, float(high)))
        self.low_watermark = min(max(0.0, float(low)),
                                 self.high_watermark)
        return (self.high_watermark, self.low_watermark)


BOUNDS = {
    cp.ACT_BULK_WINDOW: (8.0, 24.0),
    cp.ACT_GATEWAY_WINDOW: (4.0, 12.0),
    cp.ACT_BULK_DEADLINE: (50.0, 400.0),
    cp.ACT_ADMISSION: (0.2, 0.9),
}


def make(plane=None, admission=None, ledger=None, **kw):
    kw.setdefault("decision_interval", 1)
    kw.setdefault("cooldown", 0)
    c = cp.Controller(**kw)
    c.attach(plane=plane, admission=admission, height_ledger=ledger,
             bounds=BOUNDS)
    return c


def test_attach_builds_only_sheddable_actuators():
    plane, adm = FakePlane(), FakeAdmission()
    c = make(plane, adm, FakeLedger())
    names = set(c.actuator_values())
    assert names == {cp.ACT_BULK_WINDOW, cp.ACT_GATEWAY_WINDOW,
                     cp.ACT_BULK_DEADLINE, cp.ACT_ADMISSION,
                     cp.ACT_FLIGHTS}
    # no CONSENSUS knob exists anywhere in the table
    assert not any("consensus" in n for n in names)


def test_consensus_lane_setters_rejected():
    """The plane-side half of the structural guarantee: the CONSENSUS
    lane has no controller-reachable setter path."""
    from cometbft_tpu.verifyplane.plane import VerifyPlane

    p = VerifyPlane(use_device=False)
    try:
        with pytest.raises(ValueError):
            p.set_lane_window_ms("consensus", 10.0)
        with pytest.raises(ValueError):
            p.set_lane_deadline_ms("consensus", 10.0)
    finally:
        p.stop()  # a live dispatcher thread would drag the whole suite


def test_pressure_latch_tightens_then_relaxes_to_base():
    plane, adm, led = FakePlane(), FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, slo_commit_p99_ms=100.0)
    base = c.actuator_values()
    # SLO violated: the latch presses and every pressure actuator
    # takes one step in its tighten direction
    led.p99 = 250.0
    c.poke(1, 0)
    vals = c.actuator_values()
    assert vals[cp.ACT_ADMISSION] < base[cp.ACT_ADMISSION]
    assert vals[cp.ACT_BULK_WINDOW] > base[cp.ACT_BULK_WINDOW]
    assert vals[cp.ACT_GATEWAY_WINDOW] > base[cp.ACT_GATEWAY_WINDOW]
    assert vals[cp.ACT_BULK_DEADLINE] < base[cp.ACT_BULK_DEADLINE]
    # the admission spread is preserved by the apply
    assert adm.high_watermark == pytest.approx(
        vals[cp.ACT_ADMISSION])
    assert adm.high_watermark - adm.low_watermark == pytest.approx(
        0.2)
    # p99 back to mid-range but above pressure_low * slo: the latch
    # HOLDS (hysteresis — no flap at the boundary)
    led.p99 = 80.0
    c.poke(2, 0)
    assert c.dump()["state"]["pressed"]
    # full headroom: latch releases and actuators walk back to base
    led.p99 = 10.0
    for h in range(3, 20):
        c.poke(h, 0)
    vals = c.actuator_values()
    for name, v in vals.items():
        assert v == pytest.approx(base[name]), name
    assert not c.dump()["state"]["pressed"]


def test_relax_never_passes_base():
    plane, adm, led = FakePlane(), FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, slo_commit_p99_ms=100.0)
    base = c.actuator_values()
    led.p99 = 0.0
    for h in range(40):  # headroom forever: nothing may drift past base
        c.poke(h, 0)
    assert c.actuator_values() == pytest.approx(base)
    assert c.dump()["state"]["decisions_total"] == 0


def test_closed_loop_on_real_actuators_spares_the_consensus_window():
    """The same loop against the real setters (a host VerifyPlane and
    the mempool's AdmissionController): three peak -> trough cycles
    widen the bulk window and lower the watermark at each peak, walk
    both back to base at each trough, keep every decision inside its
    bounds, and never move the CONSENSUS lane's window."""
    from cometbft_tpu.mempool.admission import AdmissionController
    from cometbft_tpu.verifyplane.plane import VerifyPlane

    led = FakeLedger()
    fill = [0.1]
    plane = VerifyPlane(window_ms=0.5, use_device=False)
    adm = AdmissionController(high_watermark=0.9, low_watermark=0.7,
                              fill_fn=lambda: fill[0])
    c = cp.Controller(slo_commit_p99_ms=100.0, decision_interval=1,
                      cooldown=0)
    old_global, old_last = cp._GLOBAL, cp._LAST
    try:
        c.attach(plane=plane, admission=adm, height_ledger=led,
                 bounds={cp.ACT_BULK_WINDOW: (1.0, 8.0),
                         cp.ACT_GATEWAY_WINDOW: (0.5, 4.0),
                         cp.ACT_ADMISSION: (0.3, 0.9)})
        consensus_window, base_bulk = plane.window, plane.bulk_window
        height = 0
        for _ in range(3):
            led.p99, fill[0] = 500.0, 0.8    # peak: 5x over the SLO
            for _ in range(8):
                height += 1
                c.poke(height, 0)
            assert plane.bulk_window > base_bulk
            assert adm.high_watermark < 0.9
            led.p99, fill[0] = 10.0, 0.1     # trough: headroom
            for _ in range(16):
                height += 1
                c.poke(height, 0)
            assert plane.bulk_window == pytest.approx(base_bulk)
            assert adm.high_watermark == 0.9
        assert plane.window == consensus_window
        dump = c.dump()
        assert dump["state"]["decisions_total"] >= 6
        for d in dump["decisions"]:
            act = dump["actuators"][d["actuator"]]
            assert act["min"] - 1e-9 <= d["new"] <= act["max"] + 1e-9, d
    finally:
        cp._GLOBAL, cp._LAST = old_global, old_last
        plane.stop()


def test_fill_pressure_triggers_before_shed_storm():
    """Mempool fill climbing toward the watermark presses the latch
    even with commit p99 healthy — the pre-shed_storm trigger."""
    plane, adm, led = FakePlane(), FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, fill_high=0.6, fill_low=0.3)
    adm.fill = 0.7
    c.poke(1, 0)
    assert c.dump()["state"]["pressed"]
    assert c.actuator_values()[cp.ACT_ADMISSION] < 0.9


def test_cooldown_gates_repeat_moves():
    plane, adm, led = FakePlane(), FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, slo_commit_p99_ms=100.0, cooldown=3)
    led.p99 = 500.0
    c.poke(1, 0)
    n0 = c.dump()["state"]["decisions_total"]
    assert n0 > 0
    for h in range(2, 5):  # within the cooldown: no further moves
        c.poke(h, 0)
    assert c.dump()["state"]["decisions_total"] == n0
    c.poke(5, 0)  # cooldown elapsed: the next step lands
    assert c.dump()["state"]["decisions_total"] > n0


def test_runaway_loop_clamps_at_bounds():
    """Sustained pressure walks every actuator to its config bound and
    STOPS — a runaway loop degrades to the clamp, never past it."""
    plane, adm, led = FakePlane(), FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, slo_commit_p99_ms=100.0)
    led.p99 = 10_000.0
    for h in range(60):
        c.poke(h, 0)
    vals = c.actuator_values()
    assert vals[cp.ACT_ADMISSION] == pytest.approx(0.2)
    assert vals[cp.ACT_BULK_DEADLINE] == pytest.approx(50.0)
    # the window ceiling is the TIGHTER of the config bound and half
    # the lane's wait SLO (a window IS added latency on its lane)
    assert vals[cp.ACT_BULK_WINDOW] <= 24.0
    assert vals[cp.ACT_GATEWAY_WINDOW] <= 12.0
    # and the plane/admission saw only clamped values
    assert all(0.2 <= ms[2] or ms[0] != "window"
               for ms in plane.applied)
    assert adm.high_watermark >= 0.2


def test_window_ceiling_capped_by_wait_slo():
    plane, adm, led = FakePlane(), FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, slo_commit_p99_ms=100.0,
             slo_bulk_wait_ms=20.0, slo_gateway_wait_ms=10.0)
    led.p99 = 10_000.0
    for h in range(60):
        c.poke(h, 0)
    vals = c.actuator_values()
    assert vals[cp.ACT_BULK_WINDOW] <= 10.0   # 20/2, not the 24 bound
    assert vals[cp.ACT_GATEWAY_WINDOW] <= 5.0


def test_decision_interval_gates_evaluation():
    plane, adm, led = FakePlane(), FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, decision_interval=4,
             slo_commit_p99_ms=100.0)
    led.p99 = 500.0
    for h in range(3):
        c.poke(h, 0)
    assert c.dump()["state"]["evals"] == 0
    c.poke(3, 0)
    assert c.dump()["state"]["evals"] == 1


def test_deck_grows_on_low_util_h2d_bound():
    from cometbft_tpu.libs import incidents

    plane, adm, led = FakePlane(flights=1, flights_max=4), \
        FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, deck_min_flushes=4)
    # storms fired earlier in the test session are history, not signal
    c._last_storms = int(
        incidents.recorder().fired.get("compile_storm", 0))
    plane.ledger.device = {
        "fused_flushes": 10,
        "util": {"p50": 0.2}, "h2d_ms": {"p50": 3.0},
        "dev_ms": {"p50": 1.0},
    }
    c.poke(1, 0)
    assert plane.flights == 2
    # no FRESH fused evidence since the grow: no further move
    c.poke(2, 0)
    assert plane.flights == 2
    plane.ledger.device["fused_flushes"] = 20
    c.poke(3, 0)
    assert plane.flights == 3
    # the ceiling: flights_max, never past
    plane.ledger.device["fused_flushes"] = 99
    for h in range(4, 10):
        plane.ledger.device["fused_flushes"] += 10
        c.poke(h, 0)
    assert plane.flights <= plane.flights_max


def test_deck_shrinks_on_compile_storm():
    from cometbft_tpu.libs import incidents

    plane = FakePlane(flights=3, flights_max=4)
    c = make(plane, FakeAdmission(), FakeLedger())
    rec = incidents.recorder()
    # pre-existing storm counts must NOT shrink a fresh controller:
    # only a NEW storm (delta) is a signal
    c._last_storms = int(rec.fired.get("compile_storm", 0))
    rec.fired["compile_storm"] = c._last_storms + 1
    try:
        c.poke(1, 0)
        assert plane.flights == 2
    finally:
        rec.fired["compile_storm"] = max(
            0, rec.fired.get("compile_storm", 1) - 1)


def test_decision_ring_bounded_and_dump_shape():
    plane, adm, led = FakePlane(), FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, slo_commit_p99_ms=100.0, capacity=8)
    led.p99 = 500.0
    for h in range(200):
        led.p99 = 500.0 if h % 2 else 1.0  # thrash to generate moves
        c.poke(h, 0)
    d = c.dump()
    assert len(d["decisions"]) <= 8
    assert set(d["decisions"][-1]) >= {
        "seq", "at_ms", "height", "actuator", "direction", "old",
        "new", "relax", "trigger", "cooldowns"}
    for name, a in d["actuators"].items():
        assert a["min"] <= a["value"] <= a["max"], name
    assert d["slo"]["commit_p99_ms"] == 100.0
    assert d["state"]["decisions_total"] >= len(d["decisions"])
    # decision_counts agree with the total
    assert sum(c.decision_counts.values()) == \
        d["state"]["decisions_total"]


def test_refused_apply_is_a_non_decision():
    class RefusingAdmission(FakeAdmission):
        def set_watermarks(self, high, low):
            raise RuntimeError("refused")

    adm = RefusingAdmission()
    led = FakeLedger()
    c = make(None, adm, led, slo_commit_p99_ms=100.0)
    led.p99 = 500.0
    c.poke(1, 0)
    assert c.dump()["state"]["decisions_total"] == 0
    assert adm.high_watermark == 0.9  # untouched


def test_module_globals_and_dump_survive_clear():
    plane, adm, led = FakePlane(), FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, slo_commit_p99_ms=100.0)
    old_global, old_last = cp._GLOBAL, cp._LAST
    try:
        cp.set_global_controller(c)
        assert cp.global_controller() is c
        led.p99 = 500.0
        cp.poke(1, 0)  # the module seam drives the registered one
        assert c.dump()["state"]["pokes"] == 1
        mark = cp.controller_mark()
        assert not cp.controller_advanced(mark)
        cp.clear_global_controller(c)
        assert cp.global_controller() is None
        # _LAST serves post-mortem dumps after stop
        assert cp.dump_controller()["state"]["pokes"] == 1
        assert cp.controller_tail(4) != [] or \
            cp.dump_controller()["state"]["decisions_total"] == 0
        # pokes after clear are no-ops
        cp.poke(2, 0)
        assert c.dump()["state"]["pokes"] == 1
    finally:
        cp._GLOBAL, cp._LAST = old_global, old_last


def test_empty_dump_shape():
    old_global, old_last = cp._GLOBAL, cp._LAST
    try:
        cp._GLOBAL = cp._LAST = None
        d = cp.dump_controller()
        assert d["decisions"] == [] and d["actuators"] == {}
        assert d["state"]["decisions_total"] == 0
        assert cp.controller_mark() == (None, -1)
        assert cp.controller_tail() == []
    finally:
        cp._GLOBAL, cp._LAST = old_global, old_last


def test_metrics_families_sampled():
    """The controller_* families land in /metrics from the registered
    controller, and survive its clearing via _LAST."""
    from cometbft_tpu.libs.metrics import NodeMetrics

    plane, adm, led = FakePlane(), FakeAdmission(), FakeLedger()
    c = make(plane, adm, led, slo_commit_p99_ms=100.0)
    old_global, old_last = cp._GLOBAL, cp._LAST
    try:
        cp.set_global_controller(c)
        led.p99 = 500.0
        c.poke(1, 0)
        text = NodeMetrics().expose_text()
        assert "cometbft_controller_decisions_total{" in text
        assert 'actuator="admission_high_watermark"' in text
        assert "cometbft_controller_actuator_value{" in text
        assert "cometbft_controller_slo_violation_seconds_total" \
            in text
        cp.clear_global_controller(c)
        assert "cometbft_controller_decisions_total{" in \
            NodeMetrics().expose_text()
    finally:
        cp._GLOBAL, cp._LAST = old_global, old_last


def test_config_section_build_bounds_and_roundtrip(tmp_path):
    from cometbft_tpu.config.config import (
        Config,
        ConfigError,
        load_config,
        save_config,
    )

    cfg = Config()
    assert cfg.controller.build() is None  # off by default
    cfg.controller.enable = True
    ctl = cfg.controller.build()
    assert ctl is not None
    b = cfg.controller.bounds(cfg.verify_plane, cfg.mempool)
    assert set(b) == {cp.ACT_BULK_WINDOW, cp.ACT_GATEWAY_WINDOW,
                      cp.ACT_BULK_DEADLINE, cp.ACT_ADMISSION}
    for lo, hi in b.values():
        assert lo <= hi
    # the admission floor never exceeds the configured watermark
    assert b[cp.ACT_ADMISSION][1] == cfg.mempool.high_watermark
    # TOML round-trip preserves the section
    cfg.controller.slo_commit_p99_ms = 321.0
    path = str(tmp_path / "config.toml")
    save_config(cfg, path)
    cfg2 = load_config(path)
    assert cfg2.controller.enable is True
    assert cfg2.controller.slo_commit_p99_ms == 321.0
    # validation: a deadline floor under one flush window is the
    # shed-everything misconfiguration and must be refused
    cfg2.controller.bulk_deadline_min_ms = 0.1
    with pytest.raises(ConfigError):
        cfg2.validate_basic()
    cfg2 = load_config(path)
    cfg2.controller.fill_low = 0.9  # must stay < fill_high
    with pytest.raises(ConfigError):
        cfg2.validate_basic()
    cfg2 = load_config(path)
    cfg2.controller.admission_floor = 0.99  # above mempool high mark
    with pytest.raises(ConfigError):
        cfg2.validate_basic()


def test_flights_max_config_validation():
    from cometbft_tpu.config.config import Config, ConfigError

    cfg = Config()
    cfg.verify_plane.pipeline_flights = 2
    cfg.verify_plane.pipeline_flights_max = 1  # below the static value
    with pytest.raises(ConfigError):
        cfg.validate_basic()


def test_node_controller_attr():
    """Every Node exposes .controller (None when the section is off) —
    the rpc dump route's lookup contract."""
    import inspect as _inspect

    from cometbft_tpu.node.node import Node

    assert "controller" in _inspect.signature(Node.__init__).parameters
