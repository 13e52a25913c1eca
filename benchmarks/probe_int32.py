#!/usr/bin/env python3
"""probe_int32.py: the int32 multiply-add rate of one chip's vector
unit, the one row of peaks.json that no vendor publishes.

    python3 benchmarks/probe_int32.py        (on the TPU machine)

Run by hand, once per device kind; never inside a cell. The verify
kernels are int32 limb arithmetic on the VPU, so the published MXU
peaks are not their ceiling. The probe runs one fused elementwise
program: `unroll` dependent steps of x = x * a + b on int32, over
`chains` independent accumulators, across an array of `n` elements,
and reads its duration from the profiler's device plane. Each element
is read and written once, so at a few hundred steps the program is
bound by arithmetic, not by HBM. It prints the best rate over a few
(unroll, chains) points; the result is copied into peaks.json with
this command beside it.

With --trace-out DIR it also records a small profiler trace of a few
such calls under the benchmark's own annotations: the recorded trace
that benchmarks/tests/test_trace.py checks the reduction against.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def rate(n: int, unroll: int, chains: int, trace_dir: str) -> float:
    """Multiply-adds per second of DEVICE time: five calls under the
    profiler, the median duration of the program's events on the
    device plane ("XLA Modules"). The host clock would add the dispatch
    floor, which is as long as the smaller programs run."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from harness import stats, trace

    @jax.jit
    def f(xs, a, b):
        xs = list(xs)
        for _ in range(unroll):
            xs = [x * a + b for x in xs]
        return tuple(xs)

    key = jax.random.PRNGKey(0)
    a = jax.random.randint(key, (n,), 1, 1 << 13, jnp.int32)
    b = a[::-1]
    xs = tuple(a + i for i in range(chains))
    jax.block_until_ready(f(xs, a, b))
    jax.profiler.start_trace(trace_dir, profiler_options=trace.options())
    for _ in range(5):
        jax.block_until_ready(f(xs, a, b))
    jax.profiler.stop_trace()
    prof = ProfileData.from_file(trace.find_xplane(trace_dir))
    durs = [e.duration_ns for pl in prof.planes
            if trace.DEVICE_PLANE.match(pl.name)
            for ln in pl.lines if ln.name == "XLA Modules"
            for e in ln.events if e.name.startswith("jit_f")]
    return n * unroll * chains / (stats.median(durs) / 1e9)


def record_trace(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, a):
        for _ in range(64):
            x = x * a + a
        return x

    a = jnp.arange(1 << 20, dtype=jnp.int32)
    f(a, a).block_until_ready()
    from harness import trace

    jax.profiler.start_trace(out_dir, profiler_options=trace.options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(a, a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.pause"):
                time.sleep(0.002)
    jax.profiler.stop_trace()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-out")
    args = ap.parse_args()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"probe_int32: no TPU ({dev.platform})", file=sys.stderr)
        return 2
    points = []
    tmp = tempfile.mkdtemp(prefix="probe-int32-")
    for unroll, chains in ((64, 1), (256, 1), (1024, 1), (256, 2),
                           (512, 4)):
        r = rate(1 << 20, unroll, chains,
                 os.path.join(tmp, f"u{unroll}c{chains}"))
        points.append({"unroll": unroll, "chains": chains,
                       "int32_mac_per_s": r})
        print(json.dumps(points[-1]), flush=True)
    shutil.rmtree(tmp, ignore_errors=True)
    if args.trace_out:
        record_trace(args.trace_out)
    print(json.dumps({"device_kind": dev.device_kind,
                      "int32_mac_per_s": max(p["int32_mac_per_s"]
                                             for p in points)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
