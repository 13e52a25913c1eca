"""One run of one cell: load, warm the cell's own shapes, measure for
`--seconds`, check the outputs against the plain reference, print.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--rehearse]

Everything that belongs to the cell is found by name (harness/catalog):
its configuration, its traffic mix, the driver the mix names and one
reader per metric. A driver is a module with these functions:

    prepare(ctx) -> fx         host only, before JAX is touched: seeded
                               fixtures (from the cache, or signed in
                               worker processes that `fx` may still
                               hold as futures)
    warm(ctx, fx) -> state     build the system under test as a node
                               does and run every shape the window uses
    window(ctx, state) -> obs  the measured window: readings only
    verify(ctx, state, obs) -> {"attempted", "failed", "correct"}
                               against the plain reference, outside
                               the window
    abandon(fx), close(state)  stop what prepare and warm started

`--trace 0` prints the cell's end-to-end metrics; `--trace 1` profiles
a few seconds inside the window and prints its per-layer metrics, the
device's busy time and the breakdown. `--rehearse` is the CPU rehearsal
at tiny sizes: it walks the same control flow and prints counts, never
a time, a rate or a share.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import time

from harness import catalog, lastline, trace

TRACE_DELAY_S = 2.0   # into the window, past its first operations
TRACE_LENGTH_S = 4.0  # "a few seconds": traces are large


class Ctx:
    """What a driver is given: the cell's data files, the run's
    arguments, and `span(name)` to wrap its calls into each layer."""

    def __init__(self, cell, args, t_start):
        self.cell = cell
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.tracing = bool(args.trace)
        self.rehearse = args.rehearse
        self.t_start = t_start
        self.tmpdir = tempfile.mkdtemp(prefix="tpu-bft-bench-")
        self.config = self._sized(cell.config)
        self.traffic = self._sized(cell.traffic)
        self.info = {"marks_s": {}}  # facts for the line before the last

    def mark(self, name: str) -> None:
        """Seconds since process start at which set-up reached `name`."""
        self.info["marks_s"][name] = time.monotonic() - self.t_start

    def _sized(self, doc: dict) -> dict:
        """The file as it is run: in a rehearsal, with its own
        `rehearsal` entries laid over it."""
        out = {k: v for k, v in doc.items() if k != "rehearsal"}
        if self.rehearse:
            out.update(doc.get("rehearsal", {}))
        return out

    def span(self, name: str):
        """A host span on the profiler's clock (traced runs only)."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)


def _device(info: dict) -> dict:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": info["platform"], "kind": info["device_kind"],
            "count": info["n_devices"], "memory_peak_bytes": max(peaks)}


def _dispatch_floor_ms() -> list:
    """Copy of bench.measure_dispatch_floor: 50 trivial jitted calls,
    each fetched back to the host. The readings, in ms."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def trivial(x):
        return x + 1

    x = jnp.zeros((8, 128), jnp.int32)
    np.asarray(trivial(x))
    out = []
    for _ in range(50):
        t = time.perf_counter()
        np.asarray(trivial(x))
        out.append((time.perf_counter() - t) * 1e3)
    return out


def _counts_only(doc):
    """`doc` without its floats (times, rates, shares), at any depth."""
    if isinstance(doc, dict):
        return {k: _counts_only(v) for k, v in doc.items()
                if not isinstance(v, float)}
    if isinstance(doc, (list, tuple)):
        return [_counts_only(v) for v in doc if not isinstance(v, float)]
    return doc


def boot(rehearse: bool, chips: int) -> dict:
    """Start JAX as every entry point of the program does (persistent
    compile cache, compile listener) and name the device. Raises
    NoAcceleratorError where a measurement has no TPU to run on."""
    from cometbft_tpu.libs import deviceledger
    from cometbft_tpu.libs.jax_cache import enable_persistent_compile_cache

    cache_dir = enable_persistent_compile_cache()
    deviceledger.arm_compile_listener()
    if rehearse:
        info = deviceledger.probe_device()
    else:
        info = deviceledger.require_accelerator()
        if info["platform"] != "tpu" or info["n_devices"] < chips:
            raise deviceledger.NoAcceleratorError(
                f"cell needs {chips} TPU chip(s); JAX reports "
                f"{info['n_devices']} x {info['platform']!r}")
    deviceledger.install(deviceledger.CompileLedger(capacity=8192))
    return {**info, "compile_cache": cache_dir}


def measure(cell, ctx, info: dict, fx):
    """Warm, measure, check. Returns (head, last): the facts for the
    line before the last, and the last line's fields."""
    from cometbft_tpu.libs import deviceledger

    drv = cell.driver
    ctx.mark("jax_up")
    state = drv.warm(ctx, fx)
    ctx.mark("warm")
    try:
        # the fixtures are millions of long-lived objects of the
        # benchmark's own: keep the collector from walking them (a pause
        # of tenths of a second) at moments of its choosing in the window
        gc.collect()
        gc.freeze()
        floor = _dispatch_floor_ms() if ctx.tracing else None
        c0 = deviceledger.counters()
        tracer = None
        if ctx.tracing and not ctx.rehearse:
            # a mix whose device programs are many small operations
            # names a shorter trace (`trace_seconds`): the profiler
            # takes about a minute to write a million events
            length = min(ctx.traffic.get("trace_seconds", TRACE_LENGTH_S),
                         max(0.5, ctx.seconds / 3))
            tracer = trace.Tracer(
                os.path.join(ctx.tmpdir, "trace"),
                min(TRACE_DELAY_S, ctx.seconds / 4), length)
        setup_s = time.monotonic() - ctx.t_start
        if tracer is not None:
            tracer.start()
        obs = drv.window(ctx, state)
        c1 = deviceledger.counters()
        ctx.mark("window_end")
        reduced = tracer.finish(cell.chips) if tracer is not None else None
        ctx.mark("trace_reduced")
        verdict = drv.verify(ctx, state, obs)
    finally:
        drv.close(state)  # every thread the cell started has ended

    obs.update(
        setup_s=setup_s, seconds=ctx.seconds, trace=reduced,
        device_kind=info["device_kind"],
        trace_window=(tracer.t_on, tracer.t_off) if tracer else None,
        dispatch_floor_ms=floor,
        compile={k: c1[k] - c0[k] for k in ("compiles", "compile_s",
                                            "pcache_hits")})
    group = "per_layer" if ctx.tracing else "end_to_end"
    metrics = {}
    for entry, reader in cell.metrics(group):
        value = reader.read(obs)
        if value is not None:  # nothing to read: left out of the line
            metrics[entry["name"]] = (float(value), entry["unit"])

    compiled = [r for r in deviceledger.ledger().records()
                if not r["pcache_hit"] and r["dur_ms"] >= 1e3]
    head = {
        "cell": cell.name, "seed": ctx.seed, "seconds": ctx.seconds,
        "trace": int(ctx.tracing),
        "device": {k: info[k] for k in ("platform", "device_kind",
                                        "n_devices")},
        "compile_cache": info["compile_cache"],
        "setup": {**ctx.info, "setup_s": setup_s,
                  "compiles_over_1s": len(compiled),
                  "compiled": sorted({r["fun"] for r in compiled}),
                  **{k: c0[k] for k in ("compiles", "compile_s",
                                        "pcache_hits")}},
        "samples": {k: len(v) for k, v in obs.get("samples", {}).items()},
        "counters": obs.get("counters", {}),
        **verdict,
    }
    if reduced is not None:
        head["trace_lines"] = reduced["lines"]
        head["trace_events"] = {"device": reduced["n_device_events"],
                                "host_spans": reduced["n_host_spans"]}
    return head, {**verdict, "metrics": metrics, "breakdown": reduced}


def run(args, t_start: float) -> int:
    from cometbft_tpu.libs import deviceledger

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    cell = catalog.Cell(args.workload)
    ctx = Ctx(cell, args, t_start)
    try:
        fx = cell.driver.prepare(ctx)  # workers sign while JAX starts
        ctx.mark("prepared")
        try:
            info = boot(args.rehearse, cell.chips)
        except deviceledger.NoAcceleratorError as e:
            cell.driver.abandon(fx)
            print(f"benchmark: nothing measured: {e}", file=sys.stderr)
            return 2
        head, last = measure(cell, ctx, info, fx)
    finally:
        shutil.rmtree(ctx.tmpdir, ignore_errors=True)
    if args.rehearse:
        # counts only: a CPU run gives no time, rate or share
        head = _counts_only(head)
        head["rehearsal"] = True
        head["metrics_readable"] = sorted(last["metrics"])
        print(json.dumps(head), flush=True)
        return 0 if last["correct"] else 1
    print(json.dumps(head), flush=True)
    device = _device(info)
    if last["breakdown"] is not None:
        device["busy_s"] = last["breakdown"]["busy_s"]
        device["window_s"] = last["breakdown"]["window_s"]
    print(lastline.last_line(
        correct=last["correct"], attempted=last["attempted"],
        failed=last["failed"], metrics=last["metrics"], device=device,
        breakdown=last["breakdown"]), flush=True)
    return 0


def main(t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; prints counts only")
    args = ap.parse_args()
    try:
        return run(args, t_start)
    except catalog.CatalogError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
