"""Low-overhead span/event tracer with Chrome trace-event export.

The Prometheus surface (libs/metrics.py) answers "how much, on
average"; it cannot answer "where did THIS flush's 4 ms go" or "why did
this simnet schedule wedge". This module is the missing axis: named
spans and instants recorded into a bounded in-memory ring buffer, and
exported as Chrome trace-event JSON (load the file straight into
https://ui.perfetto.dev). Committee-consensus measurement work (arXiv:
2302.00418) and the FPGA verification-engine paper (arXiv:2112.02229)
both attribute their wins via per-stage latency decomposition — this is
that instrument, built into the node.

Design rules:

  * OFF BY DEFAULT, and near-free while off: every hook is a module
    function that loads one global and returns a shared no-op context
    manager when no tracer is installed. Call sites fire per flush /
    per step / per fsync — never per signature. The one exception is
    :func:`stage`: always on, bounded, a couple of microseconds.
  * Clock is ``time.perf_counter_ns`` by default. The simnet installs
    ``Timestamp.now().to_ns()`` (its virtual clock) via
    :func:`set_clock`, so the same (seed, schedule) produces an
    IDENTICAL trace — a wedged schedule's trace is replayable evidence,
    not a heisen-log. ``deterministic=True`` additionally pins tid/pid
    so two runs export byte-identical JSON.
  * Bounded: the ring buffer (``capacity`` events, deque) makes the
    tracer safe to leave enabled on a long-lived node; ``/dump_traces``
    on the RPC surface serves whatever the ring currently holds.

Event vocabulary (Chrome trace-event phases):

  span(name)            -> one "X" (complete) event, ts+dur
  instant(name)         -> one "i" event
  flight_begin/end(id)  -> "b"/"e" async events correlated by id; used
                           for verify-plane flights so pack(k+1)
                           VISIBLY overlaps device-flight(k) in the UI

  stage(name)           -> ALWAYS ON: one record in the bounded stage
                           ring, one event of any running jax.profiler
                           capture's host plane (the shared clock with
                           the device plane), and the same "X" event a
                           span gives when the tracer is on. For the few
                           stages of a served operation; see
                           :func:`stage`.
"""
from __future__ import annotations

import json
import sys
import threading
import time
from collections import deque
from typing import Callable, List, Optional

DEFAULT_CAPACITY = 16384
STAGE_CAPACITY = 16384


class _NullSpan:
    """Shared no-op context manager: the disabled-path cost of a span
    is one global load + one `with` on this singleton."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("tr", "name", "cat", "args", "t0")

    def __init__(self, tr: "Tracer", name: str, cat: str, args: dict):
        self.tr = tr
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self.t0 = self.tr._clock()
        return self

    def __exit__(self, *exc):
        self.tr._complete(self.name, self.cat, self.t0,
                          self.tr._clock() - self.t0, self.args)
        return False


class Tracer:
    """A bounded ring of Chrome trace events."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 clock: Optional[Callable[[], int]] = None,
                 deterministic: bool = False):
        self.capacity = max(16, int(capacity))
        self.deterministic = bool(deterministic)
        self._events: deque = deque(maxlen=self.capacity)
        self._clock = clock or _CLOCK or time.perf_counter_ns
        self.dropped = 0  # events pushed past a full ring

    # -- clock -------------------------------------------------------------

    def set_clock(self, fn: Optional[Callable[[], int]]) -> None:
        """Install a ns clock (None restores perf_counter_ns)."""
        self._clock = fn or time.perf_counter_ns

    def _tid(self) -> int:
        return 0 if self.deterministic else threading.get_ident()

    # -- recording ---------------------------------------------------------

    def _push(self, ev: dict) -> None:
        if len(self._events) == self.capacity:
            self.dropped += 1
        self._events.append(ev)

    def _complete(self, name: str, cat: str, t0_ns: int, dur_ns: int,
                  args: dict) -> None:
        ev = {"ph": "X", "name": name, "cat": cat or "app",
              "ts": t0_ns / 1000.0, "dur": dur_ns / 1000.0,
              "pid": 1, "tid": self._tid()}
        if args:
            ev["args"] = args
        self._push(ev)

    def span(self, name: str, cat: str = "", **args) -> _Span:
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "", **args) -> None:
        ev = {"ph": "i", "name": name, "cat": cat or "app",
              "ts": self._clock() / 1000.0, "s": "t",
              "pid": 1, "tid": self._tid()}
        if args:
            ev["args"] = args
        self._push(ev)

    def flight_begin(self, name: str, fid, cat: str = "", **args) -> None:
        ev = {"ph": "b", "name": name, "cat": cat or "app",
              "id": str(fid), "ts": self._clock() / 1000.0,
              "pid": 1, "tid": self._tid()}
        if args:
            ev["args"] = args
        self._push(ev)

    def flight_end(self, name: str, fid, cat: str = "", **args) -> None:
        ev = {"ph": "e", "name": name, "cat": cat or "app",
              "id": str(fid), "ts": self._clock() / 1000.0,
              "pid": 1, "tid": self._tid()}
        if args:
            ev["args"] = args
        self._push(ev)

    # -- export ------------------------------------------------------------

    def events(self) -> List[dict]:
        # list(deque) is one C-level call that holds the GIL end to
        # end (deque iteration never calls back into Python), so the
        # snapshot is atomic against concurrent _push appends — no
        # lock on the hot path. Anything fancier than list() here
        # (e.g. a comprehension over self._events) would break that.
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()
        self.dropped = 0

    def tail(self, n: int = 40) -> List[str]:
        """The last n event names (with phase), newest last — compact
        enough to ride a simnet replay blob."""
        evs = list(self._events)[-n:]
        return [f"{e['name']}({e['ph']})" for e in evs]

    def chrome_trace(self) -> dict:
        """Perfetto/chrome://tracing-loadable document."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)


# --------------------------------------------------------------------------
# the process-global tracer (None = tracing disabled)
# --------------------------------------------------------------------------

_TRACER: Optional[Tracer] = None
# module-default clock: installed by the simnet BEFORE/while a tracer
# exists so deterministic runs never see a wall-clock timestamp
_CLOCK: Optional[Callable[[], int]] = None
# bumped whenever the clock monotonic_ns() resolves to can change
# domain (set_clock / enable / disable): two monotonic_ns() readings
# are only comparable when taken under the same generation
_CLOCK_GEN: int = 0


def _clock_changed() -> None:
    """monotonic_ns() may now resolve to another clock domain: stamps
    taken before and after do not compare, so the generation moves on
    and the stage ring (whose records carry such stamps) starts over."""
    global _CLOCK_GEN, _STAGES_DROPPED
    _CLOCK_GEN += 1
    _STAGES.clear()
    _STAGES_DROPPED = 0


def enable(capacity: int = DEFAULT_CAPACITY,
           clock: Optional[Callable[[], int]] = None,
           deterministic: bool = False) -> Tracer:
    """Install (and return) a fresh global tracer."""
    global _TRACER
    _TRACER = Tracer(capacity, clock, deterministic)
    _clock_changed()
    return _TRACER


def disable() -> None:
    global _TRACER
    _TRACER = None
    _clock_changed()


def enabled() -> bool:
    return _TRACER is not None


def tracer() -> Optional[Tracer]:
    return _TRACER


def set_clock(fn: Optional[Callable[[], int]]) -> None:
    """Install a ns clock for the current AND any future tracer. The
    simnet passes ``lambda: Timestamp.now().to_ns()`` so traces run on
    the virtual clock; None restores perf_counter_ns. Clears the stage
    ring: its stamps are of the clock that was."""
    global _CLOCK
    _CLOCK = fn
    _clock_changed()
    t = _TRACER
    if t is not None:
        t.set_clock(fn)


def clock_ns() -> Optional[int]:
    """The installed tracer's clock reading, or None when tracing is
    off. Callers that stamp their own correlation timestamps (e.g. the
    verify plane's submit-to-pack queue wait) MUST use this instead of
    a wall clock so the stamps stay on the trace timeline — and stay
    deterministic under the simnet's virtual clock."""
    t = _TRACER
    return None if t is None else t._clock()


def monotonic_ns() -> int:
    """Always-available ns clock for ALWAYS-ON accounting (the verify
    plane's flush ledger): the tracer's clock when one is enabled (so
    ledger stamps share the trace timeline), else the module clock when
    installed (virtual under simnet — ledgers of the same (seed,
    schedule) replay identically), else time.perf_counter_ns. Unlike
    :func:`clock_ns` this never returns None: the ledger records every
    flush whether or not tracing is on."""
    t = _TRACER
    if t is not None:
        return t._clock()
    c = _CLOCK
    return c() if c is not None else time.perf_counter_ns()


def module_clock_installed() -> bool:
    """True when a module-default clock is installed (the simnet's
    virtual clock). Real-clock background pollers (the incident
    watchdog ticker) gate on this: a wall-clock poke evaluated against
    virtual-clock stamps would fire garbage incidents AND break simnet
    replay determinism."""
    return _CLOCK is not None


def clock_gen() -> int:
    """Generation counter for :func:`monotonic_ns`'s clock domain.
    Holders of a stored stamp (the verify plane's submit-time
    queued_ms anchor) compare generations before differencing two
    readings: a simnet clock install/restore between stamp and use
    would otherwise difference a virtual-epoch ns against a
    perf_counter ns and produce a garbage duration."""
    return _CLOCK_GEN


def span(name: str, cat: str = "", **args):
    t = _TRACER
    if t is None:
        return _NULL_SPAN
    return t.span(name, cat, **args)


def instant(name: str, cat: str = "", **args) -> None:
    t = _TRACER
    if t is not None:
        t.instant(name, cat, **args)


def flight_begin(name: str, fid, cat: str = "", **args) -> None:
    t = _TRACER
    if t is not None:
        t.flight_begin(name, fid, cat, **args)


def flight_end(name: str, fid, cat: str = "", **args) -> None:
    t = _TRACER
    if t is not None:
        t.flight_end(name, fid, cat, **args)


def export_chrome() -> dict:
    t = _TRACER
    if t is None:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    return t.chrome_trace()


def write(path: str) -> None:
    t = _TRACER
    if t is not None:
        t.write(path)


def tail(n: int = 40) -> List[str]:
    t = _TRACER
    return [] if t is None else t.tail(n)


# --------------------------------------------------------------------------
# stages: the always-on spans of a served operation
# --------------------------------------------------------------------------

# (name, t0_ns, dur_ns, tid) per closed stage, oldest first by END time
# (a nested stage lands before the stage around it)
_STAGES: deque = deque(maxlen=STAGE_CAPACITY)
_STAGES_DROPPED = 0
# jax.profiler.TraceAnnotation once jax was found imported. Looked up
# in sys.modules and never imported here: host-only and simnet
# processes stay jax-free, and without jax there is no profiler whose
# clock a stage could share.
_ANNOTATION = None


def _annotation():
    global _ANNOTATION
    jax = sys.modules.get("jax")
    # a jax still half-way through its own import has no .profiler yet
    prof = getattr(jax, "profiler", None)
    _ANNOTATION = getattr(prof, "TraceAnnotation", None)
    return _ANNOTATION


class _Stage:
    __slots__ = ("name", "args", "t0", "ms", "_ann", "_event")

    def __init__(self, name: str, args: dict, event: bool = True):
        self.name = name
        self.args = args
        self.ms = 0.0
        self._event = event

    def __enter__(self):
        cls = _ANNOTATION or _annotation()
        if cls is not None:
            self._ann = cls(self.name, **self.args)
            self._ann.__enter__()
        else:
            self._ann = None
        self.t0 = monotonic_ns()
        return self

    def __exit__(self, *exc):
        global _STAGES_DROPPED
        dur = monotonic_ns() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.ms = dur / 1e6
        if len(_STAGES) == STAGE_CAPACITY:
            _STAGES_DROPPED += 1
        _STAGES.append((self.name, self.t0, dur, threading.get_ident(),
                        self.args))
        t = _TRACER
        if t is not None and self._event:
            t._complete(self.name, "", self.t0, dur, self.args)
        return False


def stage(name: str, **args) -> _Stage:
    """An ALWAYS-ON span around one stage of a served operation (a
    catch-up step's verify, a commit check's host pack). Unlike
    :func:`span` it needs no tracer:

      * leaving it appends ``(name, t0_ns, dur_ns, tid)`` and its
        args to one process-global ring of STAGE_CAPACITY records on
        :func:`monotonic_ns` (read with :func:`stages` or
        :func:`stage_records`; overflow drops
        the oldest and counts in :func:`stages_dropped`), and the
        object then holds ``.ms``: a caller that keeps a ledger column
        reads that instead of timing the region a second time;
      * while jax is imported it is also a
        ``jax.profiler.TraceAnnotation(name, **args)``: nothing without
        a capture, and with one an event of the profile's host plane
        under this name with its args as stats, on the device plane's
        timeline. Any capture of a running process shows the program's
        stages beside the device operations, with no switch to set;
      * with the tracer on it pushes the "X" event ``span(name,
        **args)`` would.

    Call sites fire per stage of an operation or per block, never per
    signature."""
    return _Stage(name, args)


def stage_untraced(name: str, **args) -> _Stage:
    """:func:`stage` without the tracer's "X" event, for a region whose
    stamps the simnet's virtual clock cannot make repeat: a thread's
    idle wait (the verify plane's ``plane.wait``) begins whenever that
    thread gets there, while the event loop moves the clock on. The
    ring and any profiler capture still get it; the exported trace of
    one (seed, schedule) stays identical."""
    return _Stage(name, args, event=False)


def stages() -> List[tuple]:
    """The stage ring's records as ``(name, t0_ns, dur_ns, tid)``,
    oldest first (atomic snapshot: see Tracer.events)."""
    return [r[:4] for r in list(_STAGES)]


def stage_records() -> List[tuple]:
    """:func:`stages` with each record's args as a fifth field: the
    dict its stage was entered with (``votes.intake``'s ``n``)."""
    return list(_STAGES)


def stages_dropped() -> int:
    """Records pushed out of the full stage ring since it was last
    cleared (a change of clock domain clears it)."""
    return _STAGES_DROPPED
