"""Test configuration: JAX on a virtual 8-device CPU mesh.

Tests run with JAX_PLATFORMS=cpu (the tier-1 command sets it, and so
does this file): they must neither depend on nor take the chip.
Multi-chip sharding is checked on 8 virtual CPU devices, which XLA_FLAGS
must ask for before jax initializes its CPU client.

Set CBT_TEST_ON_TPU=1 to run on the chip instead, through the builder's
chip tool: that is the only place the cached ZIP-215 kernel's own tests
(tests/test_ed25519_cached.py and friends) are not skipped.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

if not os.environ.get("CBT_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent compilation cache: the interpret-mode Pallas kernels take
# minutes to compile on a CPU; every entry point shares one cache
# (libs/jax_cache.py says where it lives).
from cometbft_tpu.libs.jax_cache import (  # noqa: E402
    enable_persistent_compile_cache,
)

enable_persistent_compile_cache()

# ---------------------------------------------------------------------------
# Tier-1 duration report: the suite runs under a hard 870 s timeout
# (ROADMAP "Tier-1 verify"), so any NON-slow-marked test that takes more
# than 60 s is a budget hazard — flag it loudly in the terminal summary
# so it gets a `slow` marker (with a fast sibling) before it breaks the
# quick gate.
import pytest  # noqa: E402

_DURATION_FLAG_SECS = 60.0
_over_budget = []


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    outcome.get_result()
    if (call.when == "call"
            and call.duration is not None
            and call.duration > _DURATION_FLAG_SECS
            and item.get_closest_marker("slow") is None):
        _over_budget.append((item.nodeid, call.duration))


def pytest_terminal_summary(terminalreporter):
    for nodeid, dur in _over_budget:
        terminalreporter.write_line(
            f"[tier1-duration] non-slow test over {_DURATION_FLAG_SECS:.0f}s:"
            f" {nodeid} took {dur:.1f}s — mark it slow (keep a fast"
            " sibling) or shrink it"
        )


# ---------------------------------------------------------------------------
# ValidatorSet.hash() remembers its root (ISSUE 26) and a stale root is
# a consensus fault. This wrapper lives on the test side only (no hook
# in the program): under it every root the program is handed is checked
# against a fresh merkle root of the leaves as they are.


@pytest.fixture
def checked_valset_roots(monkeypatch):
    """Wraps ValidatorSet.hash for the test; yields the list of roots
    handed out, one per call. How many of them were computed is the
    count of `valset.hash` stages (tracing.stages())."""
    from cometbft_tpu.crypto import merkle
    from cometbft_tpu.types.validator import ValidatorSet

    real = ValidatorSet.hash
    roots = []

    def checked(self):
        root = real(self)
        fresh = merkle.hash_from_byte_slices(
            [v.bytes() for v in self.validators])
        assert root == fresh, (
            f"stale validator-set root: {root.hex()} != {fresh.hex()}")
        roots.append(root)
        return root

    monkeypatch.setattr(ValidatorSet, "hash", checked)
    return roots


# ---------------------------------------------------------------------------
# The benchmark's plain reference (benchmarks/reference/: OpenSSL and
# plain integers, nothing of the program) also referees tier-1 tests of
# what it referees on the chip. Imported as benchmarks/run.py imports
# it, from its own directory, when a test first asks.


@pytest.fixture(scope="session")
def plain_reference():
    import sys
    from types import SimpleNamespace

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import bisection, ecdsa, plain, schnorrkel

    return SimpleNamespace(plain=plain, schnorrkel=schnorrkel, ecdsa=ecdsa,
                           bisection=bisection)
