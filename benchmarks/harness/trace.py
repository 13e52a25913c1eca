"""From the profiler's `.xplane.pb` to numbers: device busy time (the
union of the intervals in which an operation ran on a chip), the idle
share, the operations that took most time, and the idle gaps by what
the host was doing in them.

The traced window is the benchmark's own `bench.window` annotation:
the tracer opens it right after `start_trace` and closes it before
`stop_trace`, so the window is defined on the profile's own clock and
every interval is clipped to it. Host activity is read from the
benchmark's other `bench.*` annotations (`jax.profiler.TraceAnnotation`
around the calls into each layer); a part of a gap that no such span
covers is reported as `host.other`.

Read with `jax.profiler.ProfileData`, which needs nothing but JAX.
`tests/test_trace.py` checks this reduction against a small trace
recorded on the chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the line of a device plane that holds one event per executed
# operation; "XLA Modules" (one event per program) is the fallback
OPS_LINES = ("XLA Ops", "XLA Modules")
Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of a sorted disjoint `busy` inside [lo, hi]."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def attribute(idle: Sequence[Interval],
              spans: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Total length of `idle` by name of the innermost span (the covering
    one that started last) over each piece; uncovered pieces go to
    'host.other'."""
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    longest = max((b - a for a, b, _ in spans), default=0.0)
    out: Dict[str, float] = {}
    for lo, hi in idle:
        i0 = bisect.bisect_left(starts, lo - longest)
        i1 = bisect.bisect_left(starts, hi)
        over = [s for s in spans[i0:i1] if s[1] > lo]
        cuts = sorted({lo, hi, *(max(lo, a) for a, _, _ in over),
                       *(min(hi, b) for _, b, _ in over)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [s for s in over if s[0] <= mid < s[1]]
            name = max(cover)[2] if cover else "host.other"
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_xplane(path: str, n_chips: int) -> Optional[dict]:
    """The reduction. Times in seconds. None when the profile holds no
    `bench.window` span (nothing to read)."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    window = None
    spans: List[Tuple[float, float, str]] = []
    device_events: Dict[int, list] = {}
    lines_seen: Dict[str, List[str]] = {}
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = list(plane.lines)
        if m:
            lines_seen[plane.name] = [ln.name for ln in lines]
            by_name = {ln.name: ln for ln in lines}
            line = next((by_name[n] for n in OPS_LINES if n in by_name),
                        None)
            if line is not None:
                device_events[int(m.group(1))] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
            continue
        if not plane.name.startswith("/host:"):
            continue
        for ln in lines:
            for e in ln.events:
                if e.name == WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(SPAN_PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    if window is None:
        return None
    lo, hi = window
    busy_ns, ops, idle_by = [], {}, {}
    # a chip without a single event was idle throughout
    for dev in range(max(n_chips, 1)):
        device_events.setdefault(dev, [])
    for dev, events in sorted(device_events.items()):
        busy = union(clip([(a, b) for a, b, _ in events], lo, hi))
        busy_ns.append(sum(b - a for a, b in busy))
        for a, b, name in events:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                # the event's name is the whole HLO instruction: keep
                # its own name ("%fusion.3"). Operations nest (a while
                # and its body), so these can sum to more than busy.
                name = name.split(" = ")[0][:80]
                ops[name] = ops.get(name, 0.0) + (b - a)
        for name, ns in attribute(gaps(busy, lo, hi), spans).items():
            idle_by[name] = idle_by.get(name, 0.0) + ns
    chips = len(busy_ns)  # averaged over the chips
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    idle = sorted(idle_by.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / chips / 1e9,
        "device_ops": [[n, ns / chips / 1e9] for n, ns in top[:10]],
        "idle_gaps": [[n, ns / chips / 1e9] for n, ns in idle[:10]],
        "n_device_events": sum(len(v) for v in device_events.values()),
        "n_host_spans": len(spans),
        "lines": lines_seen,
    }


def options():
    """No Python tracer: it records every Python call, which slows the
    host it is measuring and makes the trace large. TraceAnnotation
    spans are host-tracer events and stay."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


class Tracer:
    """Profiles `length` seconds of a window from a thread of its own,
    starting `delay` seconds after `start()`. The cell's code runs
    undisturbed on its own threads; `span(name)` is what it wraps its
    calls in."""

    def __init__(self, trace_dir: str, delay: float, length: float):
        self.dir, self.delay, self.length = trace_dir, delay, length
        self.t_on = self.t_off = None  # time.monotonic() of the window
        self._thread = threading.Thread(target=self._run, daemon=True)
        self.error: Optional[BaseException] = None

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.delay)
            jax.profiler.start_trace(self.dir, profiler_options=options())
            try:
                self.t_on = time.monotonic()
                with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                    time.sleep(self.length)
                self.t_off = time.monotonic()
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # re-raised by finish(), on its thread
            self.error = e

    def finish(self, n_chips: int) -> Optional[dict]:
        self._thread.join()
        if self.error is not None:
            raise self.error
        return reduce_xplane(find_xplane(self.dir), n_chips)
