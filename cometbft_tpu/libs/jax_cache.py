"""Where the persistent JAX compilation cache lives.

Every entry point that compiles (chip_smoke.py, benchmarks/run.py,
`python -m cometbft_tpu start`, tools/tpu_differential.py,
tests/conftest.py)
calls this one helper before its first jit, so they all share one
cache and none runs without it: a cold Mosaic compile of one verify
kernel costs tens of seconds, and a fresh process would otherwise pay
it for every kernel family again.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
helper sets no directory. Where it is not, the cache is a fixed path
inside the checkout: the directory is part of what a later process
must find again, so it never depends on /tmp, a pid or a time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_compile_cache() -> str:
    """Make sure this process compiles through the persistent cache;
    returns the directory in use. Safe to call repeatedly."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
