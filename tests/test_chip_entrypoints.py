"""The chip entry points refuse a CPU, and the compile cache is placed
from outside.

chip_smoke.py proves the TPU and benchmarks/run.py measures it; with no
accelerator they must fail before doing anything, never degrade to a
run that passes without touching the chip. The persistent compile cache
follows JAX_COMPILATION_CACHE_DIR where it is set and otherwise sits at
one fixed path inside the checkout (libs/jax_cache.py).
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_PROBE = (
    "import jax\n"
    "from cometbft_tpu.libs.jax_cache import "
    "enable_persistent_compile_cache as enable\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "print(repr((before, enable(), "
    "jax.config.jax_compilation_cache_dir)))\n"
)


def _run(args, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable] + args, cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=300)


def test_cache_dir_follows_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX itself reads it and the
    helper sets no other directory."""
    want = str(tmp_path / "placed-by-the-driver")
    r = _run(["-c", _CACHE_PROBE], JAX_COMPILATION_CACHE_DIR=want)
    assert r.returncode == 0, r.stderr
    before, returned, after = eval(r.stdout.strip())
    assert before == returned == after == want


def test_cache_dir_default_is_fixed_inside_the_checkout():
    """Unset, the cache is <checkout>/.jax_cache: the same path in every
    process, with no /tmp, pid or time in it."""
    r = _run(["-c", _CACHE_PROBE])
    assert r.returncode == 0, r.stderr
    before, returned, after = eval(r.stdout.strip())
    assert before is None
    assert returned == after == os.path.join(REPO, ".jax_cache")
    from cometbft_tpu.libs import jax_cache

    assert jax_cache.DEFAULT_CACHE_DIR == returned  # not per process
    assert os.path.relpath(returned, REPO) == ".jax_cache"


def test_chip_smoke_refuses_a_cpu():
    """JAX_PLATFORMS=cpu: exit 2 at the gate, the missing device named
    on stderr, and no result on stdout."""
    r = _run(["chip_smoke.py"])
    assert r.returncode == 2
    assert r.stdout == ""
    assert "no TPU" in r.stderr and "platform is 'cpu'" in r.stderr


def test_benchmark_run_refuses_a_cpu():
    """JAX_PLATFORMS=cpu without --rehearse: the cell prepares, finds no
    TPU, exits 2 with nothing on stdout (no line a reader could take
    for a measurement) and says so on stderr. qa200.bursts is the
    cheapest cell to prepare (175 validators)."""
    r = _run(["benchmarks/run.py", "--workload", "qa200.bursts",
              "--seed", "0", "--seconds", "1"])
    assert r.returncode == 2
    assert r.stdout == ""
    assert "nothing measured" in r.stderr
    assert "platform is 'cpu'" in r.stderr


def test_chip_smoke_last_line_has_the_contract_keys_only():
    """The driver reads the last line of stdout: {"ok", "device"} with
    {"platform", "kind", "count"} and no other key (the run's totals go
    on the line before it)."""
    import json

    import chip_smoke

    line = chip_smoke.verdict_line(
        {"platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
