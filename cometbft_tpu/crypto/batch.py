"""Batch-verifier dispatch: pick the device kernel by key type.

Reference: crypto/batch/batch.go:12-32 (CreateBatchVerifier switches on
key type; SupportsBatchVerifier gates the batch path). The TPU build goes
further than the reference in two ways:
- secp256k1 IS batchable here (the reference has no ECDSA batch path at
  all — batch.go:12-21 only dispatches ed25519/sr25519);
- one mixed-key commit verifies in a single call: rows are grouped by key
  type and each group goes to its kernel. A kernel may hand back its
  verdicts not yet fetched (PendingVerdicts): every group is dispatched
  before the first is fetched, so the device works on one key type's
  rows while the host packs the other's.

The batch_fn signature used across validation.py: fn(pubs, msgs, sigs)
with pubs a sequence of crypto.keys.PubKey; returns (n,) bool validity —
the per-signature slice the blame path needs (types/validation.go:243).

Degraded mode: every kernel dispatch runs under a circuit breaker. A
device fault (XLA or Mosaic error, a lost device, an injected
`crypto.device_dispatch` failpoint), raised at dispatch or when the
verdicts are fetched, is caught, logged, counted in the breaker's
`faults`, and that key type's rows re-verified on the host
single-signature path — a sick TPU costs throughput, never consensus
liveness. After `failure_threshold` consecutive faults the breaker
OPENS and batches go straight to the host path; every `cooldown`
seconds one batch probes the device again (half-open), and a success
closes the breaker. Measurements on committee-based consensus (arXiv:
2302.00418) put verification squarely on the liveness-critical path,
which is why the fallback is tested, not assumed.
"""
from __future__ import annotations

import logging
import threading
import time
from collections import defaultdict
from typing import Callable, List, Sequence

import numpy as np

from cometbft_tpu.crypto.keys import (
    ED25519_KEY_TYPE,
    SECP256K1_KEY_TYPE,
    SR25519_KEY_TYPE,
    PubKey,
)
from cometbft_tpu.libs import failpoints as fp
from cometbft_tpu.libs import tracing
from cometbft_tpu.libs.staging import StagingPool
from cometbft_tpu.types.canonical import TemplateRows

_log = logging.getLogger(__name__)

_BATCHABLE = {ED25519_KEY_TYPE, SECP256K1_KEY_TYPE, SR25519_KEY_TYPE}

fp.register("crypto.device_dispatch",
            "device kernel about to run (raise = device fault; the "
            "breaker + host fallback must keep verdicts correct)")


def supports_batch_verifier(key_type: str) -> bool:
    """crypto/batch/batch.go:24-32 analog (plus secp256k1)."""
    return key_type in _BATCHABLE


class CircuitBreaker:
    """Consecutive-failure breaker with timed half-open probes.

    closed  — device healthy, every batch dispatches to it.
    open    — device sick: batches take the host path; once per
              `cooldown` seconds a single batch is let through as a
              probe (half-open). Probe success -> closed; probe
              failure -> stay open, restart the cooldown clock.
    """

    def __init__(self, failure_threshold: int = 2,
                 cooldown: float = 30.0, name: str = "device"):
        self.failure_threshold = max(1, failure_threshold)
        self.cooldown = cooldown
        self.name = name
        self._lock = threading.Lock()
        self._failures = 0
        self._open_until = 0.0
        self._is_open = False
        self.trips = 0        # times the breaker opened (ops counter)
        self.faults = 0       # every recorded device fault, monotone: a
        # single fault between two successes never trips the breaker,
        # and without this count it would leave no trace at all
        self.closes = 0       # open -> closed recoveries
        self.probes = 0       # half-open probes attempted

    @property
    def state(self) -> str:
        with self._lock:
            return "open" if self._is_open else "closed"

    def allow(self) -> bool:
        """True -> caller may try the device (normal or probe)."""
        with self._lock:
            if not self._is_open:
                return True
            now = time.monotonic()
            if now >= self._open_until:
                # claim the probe slot; concurrent callers keep falling
                # back until this probe resolves or the clock lapses
                self._open_until = now + self.cooldown
                self.probes += 1
                return True
            return False

    def record_success(self) -> None:
        with self._lock:
            was_open = self._is_open
            self._failures = 0
            self._is_open = False
            if was_open:
                self.closes += 1
        if was_open:
            _log.warning("circuit breaker %s: device recovered, "
                         "breaker CLOSED", self.name)
            tracing.instant("breaker.close", cat="crypto",
                            breaker=self.name)

    def record_failure(self) -> None:
        with self._lock:
            self._failures += 1
            self.faults += 1
            now_tripping = (not self._is_open
                            and self._failures >= self.failure_threshold)
            if now_tripping:
                self._is_open = True
                self.trips += 1
            if self._is_open:
                self._open_until = time.monotonic() + self.cooldown
        if now_tripping:
            _log.error(
                "circuit breaker %s: OPEN after %d consecutive device "
                "faults; verifying on the host path, re-probing every "
                "%.1fs", self.name, self._failures, self.cooldown,
            )
            tracing.instant("breaker.open", cat="crypto",
                            breaker=self.name)

    def reset(self) -> None:
        with self._lock:
            self._failures = 0
            self._is_open = False
            self._open_until = 0.0


# One breaker for THE device: all kernels share the accelerator, so a
# sick device moves every key type to the host path at once.
_DEVICE_BREAKER = CircuitBreaker(name="verify-device")


def device_breaker() -> CircuitBreaker:
    return _DEVICE_BREAKER


# One staging pool for THE device, mirroring the breaker: every caller
# that packs rows for upload (verify plane flushes, blocksync chunks)
# rotates through the same two persistent host buffers per
# bucket shape, so the dispatcher can pack flush k+1 while the device
# still verifies flush k (libs/staging.py). Device-resident caches
# (valset/window tables) never ride this pool — donation-safe.
_STAGING = StagingPool(slots=2)


def staging_pool() -> StagingPool:
    return _STAGING


def configure_breaker(failure_threshold: int, cooldown: float) -> None:
    """Apply [crypto] breaker knobs (config.py) to the global breaker."""
    _DEVICE_BREAKER.failure_threshold = max(1, failure_threshold)
    _DEVICE_BREAKER.cooldown = cooldown


_BACKEND_FAULT_LOGGED = False


def _accel_backend() -> bool:
    """True when an accelerator backend is actually usable. Never raises:
    a backend that fails to initialize (a chip another process holds, a
    misconfigured JAX_PLATFORMS) must degrade to the CPU path, not take
    signature verification down with it. The failure is logged once at
    error level, and the probe's answer is what /dump_devices reports."""
    global _BACKEND_FAULT_LOGGED
    from cometbft_tpu.libs import deviceledger

    try:
        return deviceledger.probe_device()["platform"] != "cpu"
    except Exception:  # noqa: BLE001 - backend init failure
        if not _BACKEND_FAULT_LOGGED:
            _BACKEND_FAULT_LOGGED = True
            _log.exception(
                "JAX backend failed to initialize; this process "
                "verifies signatures WITHOUT the accelerator")
        return False


def _kernel_for(key_type: str) -> Callable:
    if key_type == ED25519_KEY_TYPE:
        from cometbft_tpu.ops import ed25519_kernel

        return ed25519_kernel.verify_batch
    if key_type == SECP256K1_KEY_TYPE:
        if _accel_backend():
            from cometbft_tpu.ops import ecdsa_pallas

            return ecdsa_pallas.verify_batch
        # CPU: the XLA-composed kernel beats interpret-mode Pallas
        from cometbft_tpu.ops import ecdsa_kernel

        return ecdsa_kernel.verify_batch
    if key_type == SR25519_KEY_TYPE:
        from cometbft_tpu.ops import sr25519_kernel

        return sr25519_kernel.verify_batch
    raise ValueError(f"no batch verifier for key type {key_type!r}")


class PendingVerdicts:
    """What a kernel returns in place of (n,) bool verdicts when it has
    dispatched its rows and not waited for them: the device arrays of
    its passes, in row order. `fetch()` waits, copies back and returns
    the first `n` verdicts under the always-on stage `stage`;
    verify_batch_direct calls it once, after the call's last dispatch."""

    __slots__ = ("outs", "n", "stage")

    def __init__(self, outs: Sequence, n: int, stage: str):
        self.outs, self.n, self.stage = list(outs), n, stage

    def fetch(self) -> np.ndarray:
        with tracing.stage(self.stage):
            return np.concatenate(
                [np.asarray(o) for o in self.outs])[: self.n]


def _host_verify_rows(pubs, msgs, sigs, idxs, valid) -> None:
    """Host fallback: per-row single verify via the reference-path
    PubKey.verify_signature (ed25519_ref and friends). Fills `valid`
    in place for the given indices."""
    for i in idxs:
        try:
            valid[i] = pubs[i].verify_signature(msgs[i], sigs[i])
        except ValueError:
            valid[i] = False


def verify_batch(
    pubs: Sequence[PubKey],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    kernels: dict = None,
    breaker: CircuitBreaker = None,
) -> np.ndarray:
    """Verify a (possibly mixed-key-type) batch; (n,) bool validity.

    When the verify plane is running (node-lifecycle scheduler,
    cometbft_tpu.verifyplane), a default-configured call becomes a
    submit-and-wait over the plane so independent callers coalesce into
    shared device passes. Calls that pin kernels/breaker (tests, the
    plane's own dispatcher) keep the direct path.
    """
    if kernels is None and breaker is None:
        from cometbft_tpu.verifyplane import plane as _vp

        p = _vp.global_plane()
        if p is not None:
            try:
                return p.submit_and_wait(pubs, msgs, sigs)
            except _vp.PlaneError:
                pass  # plane stopped/overflowed mid-call: go direct
    return verify_batch_direct(pubs, msgs, sigs, kernels, breaker)


def verify_batch_direct(
    pubs: Sequence[PubKey],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    kernels: dict = None,
    breaker: CircuitBreaker = None,
) -> np.ndarray:
    """The direct (non-plane) batch verify: group rows by key type and
    dispatch each group to its kernel under the circuit breaker.

    Every group is dispatched before any verdict is waited for: a kernel
    that returns PendingVerdicts is fetched after the call's last
    dispatch, in group order; one that returns the verdicts themselves
    (numpy) is done when it returns. A fault at dispatch or at fetch
    records one breaker failure and re-verifies THAT group on the host;
    a success is recorded only with a group's verdicts in hand.

    kernels overrides the per-type kernel (e.g. the Pallas ed25519 path).
    breaker overrides the global device circuit breaker (tests)."""
    n = len(pubs)
    valid = np.zeros((n,), np.bool_)
    brk = breaker if breaker is not None else _DEVICE_BREAKER
    groups: dict = defaultdict(list)
    for i, p in enumerate(pubs):
        groups[p.key_type].append(i)

    def on_host(kt, idxs):
        with tracing.span("crypto.batch.host", cat="crypto",
                          key_type=kt, rows=len(idxs)):
            _host_verify_rows(pubs, msgs, sigs, idxs, valid)

    def faulted(kt, idxs):
        brk.record_failure()
        _log.exception(
            "device batch verify failed for %s (%d sigs); "
            "falling back to the host path", kt, len(idxs),
        )
        on_host(kt, idxs)

    flying = []  # (key type, its rows' indices, PendingVerdicts)
    for kt, idxs in groups.items():
        if kt not in _BATCHABLE:
            # unknown type: per-row single verify; a type with no verifier
            # at all marks the row invalid instead of raising mid-batch
            _host_verify_rows(pubs, msgs, sigs, idxs, valid)
            continue
        if not brk.allow():
            on_host(kt, idxs)
            continue
        kernel = (kernels or {}).get(kt) or _kernel_for(kt)
        try:
            fp.fail_point("crypto.device_dispatch")
            with tracing.span("crypto.batch.device", cat="crypto",
                              key_type=kt, rows=len(idxs)):
                sub = kernel(
                    [pubs[i].data for i in idxs],
                    # a commit's lazy rows stay lazy: no bytes here
                    msgs.take(idxs) if isinstance(msgs, TemplateRows)
                    else [msgs[i] for i in idxs],
                    [sigs[i] for i in idxs],
                )
            if isinstance(sub, PendingVerdicts):
                flying.append((kt, idxs, sub))
                continue
            valid[np.asarray(idxs)] = np.asarray(sub)
            brk.record_success()
        except Exception:  # noqa: BLE001 - device fault, not verdict
            faulted(kt, idxs)
    for kt, idxs, pending in flying:
        try:
            valid[np.asarray(idxs)] = pending.fetch()
            brk.record_success()
        except Exception:  # noqa: BLE001 - the fault surfaced at the wait
            faulted(kt, idxs)
    return valid


def batch_fn() -> Callable:
    """The batch_fn validation.py consumes (CreateBatchVerifier analog)."""
    return verify_batch
