"""How long a flush's verdicts lay ready on the chip before the verify
plane's dispatcher came for them, from a traced run's profile.

The plane's dispatcher stages (`plane.wait`, `.pack`, `.dispatch`,
`.land`, `.collect`, `.settle`) are `jax.profiler.TraceAnnotation`s, so
the `.xplane.pb` of a `--trace 1` run holds them on the dispatcher's
line of the host plane beside the device plane's operations.
`plane.land` is the readiness wait with a flight airborne; the device
operations of that flight are those between the collect before it and
its own end. A land that ends 4 ms after the last of them did has
slept 4 ms over verdicts that were ready; a land that ends while one
still runs waited for the device.

The two planes are NOT on one clock to the millisecond: on a v5e the
device plane's stamps run ahead of the host plane's (PR 37: a flush's
first operation is stamped 1.7 ms BEFORE the host enqueued its
program). `lead_ns` bounds that from below by causality: no operation
of a flush started before the flush's first program was launched
(jax's own `PjitFunction(...)` event inside `plane.dispatch`; the
dispatch's start where the profile has none), so the largest "launch
minus first operation" over the profile's flushes is how far the
device plane leads, at least. `lags_ns` moves the device's stamps back
by it. What stays in is the launch's own latency (some 0.25 ms): the
lag is an upper estimate by that much.

`lags_ns` and `lead_ns` are pure functions over event tuples
`(start_ns, end_ns, name)`. `land_lag_ms` finds the profile this
process wrote (`harness/main.py` traces into
`<tempfile.mkdtemp(prefix="tpu-bft-bench-")>/trace`, which still exists
when the readers run), reads the host plane's `plane.*` and launch
events and the device planes' operations (the lines `harness/trace.py`
reads) and gives the median. Nothing (None, the metric left out of the
line) where the run was not traced, no such profile is found, or it
holds no `plane.land` event in its window: a parent of the PR that
added the stages.
"""
from __future__ import annotations

import bisect
import glob
import os
import tempfile
from typing import Iterator, List, Optional, Sequence, Tuple

from harness import readings, stats, trace

LAND, DISPATCH, COLLECT = "plane.land", "plane.dispatch", "plane.collect"
STAGE_PREFIX = "plane."
LAUNCH_PREFIX = "PjitFunction("  # jax's event around a jitted call
TMP_PREFIX = "tpu-bft-bench-"  # harness/main.Ctx.tmpdir
Event = Tuple[int, int, str]


def _flights(host: Sequence[Event], device: Sequence[Event]
             ) -> Iterator[Tuple[Event, int, List[Tuple[int, int]]]]:
    """(land, launch_ns, operations) for each `plane.land` event that
    has a `plane.dispatch` before it: the earliest moment an operation
    of its flight can have started (the first launch inside that
    dispatch, else the dispatch's start), and the device operations
    that started, by the device plane's stamps, between the end of the
    collect before that dispatch (the flight before is over by then on
    any clock; the dispatch's start where there is none) and the
    land's end. Flights without an operation are left out."""
    dispatches = sorted((a, b) for a, b, name in host if name == DISPATCH)
    d_starts = [a for a, _ in dispatches]
    c_ends = sorted(b for _, b, name in host if name == COLLECT)
    launches = sorted(a for a, _, name in host
                      if name.startswith(LAUNCH_PREFIX))
    ops = sorted((a, b) for a, b, _ in device)
    starts = [a for a, _ in ops]
    for land in sorted(e for e in host if e[2] == LAND):
        k = bisect.bisect_right(d_starts, land[0])
        if not k:
            continue
        d0, d1 = dispatches[k - 1]
        i = bisect.bisect_left(launches, d0)
        launch = launches[i] if i < len(launches) and launches[i] <= d1 \
            else d0
        j = bisect.bisect_right(c_ends, d0)
        since = c_ends[j - 1] if j else d0
        mine = ops[bisect.bisect_left(starts, since):
                   bisect.bisect_left(starts, land[1])]
        if mine:
            yield land, launch, mine


def lead_ns(host: Sequence[Event], device: Sequence[Event]) -> int:
    """How far the device plane's stamps run ahead of the host
    plane's, at least: the largest launch minus first operation over
    the profile's flights, 0 where no operation is stamped before its
    launch. One number a profile: a capture is seconds long and the
    planes do not drift in it."""
    return max([launch - mine[0][0] for _, launch, mine
                in _flights(host, device)] + [0])


def lags_ns(host: Sequence[Event], device: Sequence[Event],
            window: Tuple[int, int]) -> List[int]:
    """For each `plane.land` event of `host` that lies in `window`: its
    end minus the end of the last operation of its flight, the device's
    stamps moved back by `lead_ns`; 0 where an operation is still
    running at the land's end; the land is left out where its flight
    has no operation (nothing was dispatched, or the profile lost
    it)."""
    lo, hi = window
    lead = lead_ns(host, device)
    return [max(land[1] - (max(end for _, end in mine) + lead), 0)
            for land, _, mine in _flights(host, device)
            if lo <= land[0] and land[1] <= hi]


def find_profile() -> Optional[str]:
    """The newest `.xplane.pb` under a harness temp directory: the one
    this run's tracer has just written (an older run's directory is
    removed when its process ends)."""
    found = []
    for d in glob.glob(os.path.join(tempfile.gettempdir(),
                                    TMP_PREFIX + "*", "trace")):
        try:
            path = trace.find_xplane(d)
            found.append((os.path.getmtime(path), path))
        except OSError:  # no profile there, or gone meanwhile
            continue
    return max(found)[1] if found else None


def read_events(path: str):
    """(host `plane.*` and launch events, device operations, the
    `bench.window` span or None) of a profile, as
    `(start_ns, end_ns, name)`."""
    from jax.profiler import ProfileData

    host: List[Event] = []
    device: List[Event] = []
    window = None
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        if trace.DEVICE_PLANE.match(plane.name):
            by_name = {ln.name: ln for ln in lines}
            line = next((by_name[n] for n in trace.OPS_LINES
                         if n in by_name), None)
            if line is not None:
                device += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name == trace.WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith((STAGE_PREFIX, LAUNCH_PREFIX)):
                        host.append((e.start_ns,
                                     e.start_ns + e.duration_ns, e.name))
    return host, device, window


def land_lag_ms(obs) -> Optional[float]:
    """Median of `lags_ns` over the traced window, in ms."""
    if readings.traced(obs) is None:
        return None
    path = find_profile()
    if path is None:
        return None
    host, device, window = read_events(path)
    if window is None:
        return None
    lags = lags_ns(host, device, window)
    return stats.median(lags) / 1e6 if lags else None
