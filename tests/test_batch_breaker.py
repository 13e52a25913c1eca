"""Device-fault circuit breaker (crypto/batch.py): with the
`crypto.device_dispatch` failpoint armed, batch verification must trip
the breaker, return verdicts identical to the ed25519_ref host oracle,
and recover once the fault clears (ISSUE acceptance criterion)."""
import numpy as np
import pytest

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import ed25519_ref as ed
from cometbft_tpu.crypto.keys import PrivKey
from cometbft_tpu.libs import failpoints as fp


@pytest.fixture(autouse=True)
def clean():
    fp.reset()
    cbatch.device_breaker().reset()
    yield
    fp.reset()
    cbatch.device_breaker().reset()
    cbatch.configure_breaker(2, 30.0)  # restore defaults


def make_batch(n=6):
    """Mixed valid/invalid ed25519 rows + the host-oracle expectation."""
    seeds = [bytes([i + 10]) * 32 for i in range(n)]
    privs = [PrivKey.generate(s) for s in seeds]
    pubs = [p.pub_key() for p in privs]
    msgs = [b"breaker-%d" % i for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    sigs[2] = b"\x01" * 64                      # garbage signature
    msgs_t = list(msgs)
    msgs_t[4] = msgs[4] + b"tampered"           # sig/msg mismatch
    exp = [ed.verify(p.data, m, s)
           for p, m, s in zip(pubs, msgs_t, sigs)]
    assert exp == [True, True, False, True, False, True]
    return pubs, msgs_t, sigs, exp


def oracle_kernel(pub_bytes, msgs, sigs):
    """Stand-in 'device' kernel: oracle semantics, zero compile cost.

    The breaker tests exercise dispatch/trip/probe/fallback control
    flow, which is independent of which kernel runs; using the real
    XLA kernel here would spend minutes of 1-core compile inside the
    alphabetically-early part of the tier-1 run. Kernel correctness
    itself is covered by the differential tests."""
    return np.asarray(
        [ed.verify(p, m, s) for p, m, s in zip(pub_bytes, msgs, sigs)]
    )


KERNELS = {"ed25519": oracle_kernel}


def test_device_fault_trips_breaker_host_path_correct():
    pubs, msgs, sigs, exp = make_batch()
    brk = cbatch.CircuitBreaker(failure_threshold=2, cooldown=0.2)

    fp.arm("crypto.device_dispatch", "raise")  # device is sick
    # 1st faulted batch: breaker still closed (threshold 2), host path
    got = cbatch.verify_batch(pubs, msgs, sigs, kernels=KERNELS, breaker=brk)
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert brk.state == "closed"
    # 2nd faulted batch: breaker trips
    got = cbatch.verify_batch(pubs, msgs, sigs, kernels=KERNELS, breaker=brk)
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert brk.state == "open" and brk.trips == 1 and brk.faults == 2

    # while open (cooldown not lapsed) the device is NOT dispatched:
    # the armed failpoint would raise, so correct results prove the
    # host path served the batch without even probing
    fires_before = fp.registry().stats("crypto.device_dispatch")["fires"]
    got = cbatch.verify_batch(pubs, msgs, sigs, kernels=KERNELS, breaker=brk)
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert fp.registry().stats("crypto.device_dispatch")["fires"] == \
        fires_before


def test_breaker_reprobes_and_recovers():
    pubs, msgs, sigs, exp = make_batch()
    brk = cbatch.CircuitBreaker(failure_threshold=1, cooldown=0.05)

    fp.arm("crypto.device_dispatch", "raise")
    got = cbatch.verify_batch(pubs, msgs, sigs, kernels=KERNELS, breaker=brk)
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert brk.state == "open"

    # fault clears; after the cooldown the next batch probes the device
    # and the breaker closes
    fp.reset()
    import time

    time.sleep(0.06)
    got = cbatch.verify_batch(pubs, msgs, sigs, kernels=KERNELS, breaker=brk)
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert brk.state == "closed" and brk.probes >= 1


def test_probe_failure_keeps_breaker_open():
    pubs, msgs, sigs, exp = make_batch()
    brk = cbatch.CircuitBreaker(failure_threshold=1, cooldown=0.05)
    fp.arm("crypto.device_dispatch", "raise")
    cbatch.verify_batch(pubs, msgs, sigs, kernels=KERNELS, breaker=brk)
    assert brk.state == "open"
    import time

    time.sleep(0.06)
    # still faulted: the probe fails and the breaker stays open
    got = cbatch.verify_batch(pubs, msgs, sigs, kernels=KERNELS, breaker=brk)
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert brk.state == "open" and brk.probes >= 1


def test_flake_action_degrades_not_halts():
    """A flaky device (every 2nd dispatch faults) still returns correct
    verdicts on every call — consensus sees slowdown, never error."""
    pubs, msgs, sigs, exp = make_batch()
    brk = cbatch.CircuitBreaker(failure_threshold=10, cooldown=0.01)
    fp.arm("crypto.device_dispatch", "flake", arg=2)
    for _ in range(4):
        got = cbatch.verify_batch(pubs, msgs, sigs, kernels=KERNELS, breaker=brk)
        np.testing.assert_array_equal(got, np.asarray(exp))
    # single faults between successes never trip the breaker; `faults`
    # is the only trace they leave, and reset() does not erase it
    assert brk.state == "closed" and brk.trips == 0
    assert brk.faults == 2
    brk.reset()
    assert brk.faults == 2


def test_device_batch_fn_covered_by_breaker():
    """The TPU verify path (validation.device_batch_fn) dispatches
    through the same breaker-guarded chokepoint."""
    from cometbft_tpu.types import validation

    pubs, msgs, sigs, exp = make_batch()
    cbatch.configure_breaker(1, 30.0)
    fn = validation.device_batch_fn(use_pallas=False)
    fp.arm("crypto.device_dispatch", "raise")
    got = np.asarray(fn(pubs, msgs, sigs))
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert cbatch.device_breaker().state == "open"


def test_fault_in_a_later_chunk_is_one_fault_and_host_verdicts(
        monkeypatch):
    """A batch over validation.COMMIT_CHUNK_ROWS goes to the device in
    chunks; a kernel that raises on the second leaves the whole call to
    the breaker, once, and the host serves every row."""
    import jax.numpy as jnp

    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.types import validation

    pubs, msgs, sigs, exp = make_batch()
    calls = []

    def sick(ay, asign, ry, rsign, sdig, hdig, precheck):
        calls.append(len(precheck))
        if len(calls) == 2:
            raise RuntimeError("device lost")
        return jnp.asarray(precheck)  # wrong for rows 2 and 4: unused

    monkeypatch.setattr(ek, "verify_kernel", sick)
    monkeypatch.setattr(validation, "COMMIT_CHUNK_ROWS", 4)
    cbatch.configure_breaker(1, 30.0)
    brk = cbatch.device_breaker()
    trips0, faults0 = brk.trips, brk.faults  # monotone across reset()
    got = validation.device_batch_fn(use_pallas=False)(pubs, msgs, sigs)
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert calls == [4, 4]
    assert (brk.state, brk.trips - trips0, brk.faults - faults0) == (
        "open", 1, 1)


class _Verdicts:
    """A pass's verdicts as verify_batch_direct sees a device array:
    fetched by np.asarray, which is where a fault of the wait shows."""

    def __init__(self, valid, log, tag, sick=False):
        self.valid, self.log, self.tag, self.sick = valid, log, tag, sick

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.tag))
        if self.sick:
            raise RuntimeError("device lost")
        return np.asarray(self.valid)


@pytest.mark.parametrize("where", ["dispatch", "fetch"])
@pytest.mark.parametrize("sick", ["ed25519", "sr25519"])
def test_a_pending_groups_fault_is_its_own(monkeypatch, sick, where):
    """Kernels that return their verdicts not yet fetched
    (PendingVerdicts): both groups are dispatched before either is
    fetched; a fault at dispatch or at the fetch re-verifies only THAT
    group on the host and counts one breaker fault, and the other
    group's verdicts are the device's."""
    from cometbft_tpu.crypto import sr25519_ref as sr
    from cometbft_tpu.crypto.keys import Sr25519PrivKey

    pubs, msgs, sigs, exp = make_batch()
    sk = Sr25519PrivKey.generate(b"\x31" * 32)
    for i in range(3):
        pubs.append(sk.pub_key())
        msgs.append(b"pending-%d" % i)
        sigs.append(sk.sign(b"pending-%d" % (i if i != 1 else 9)))
        exp.append(i != 1)
    order = [6, 0, 1, 7, 2, 3, 8, 4, 5]  # sr25519 first, interleaved
    pubs, msgs, sigs, exp = ([x[i] for i in order]
                             for x in (pubs, msgs, sigs, exp))
    log = []

    def kernel(kt, oracle):
        def run(pub_bytes, ms, ss):
            log.append(("dispatch", kt))
            if kt == sick and where == "dispatch":
                raise RuntimeError("device lost")
            valid = [oracle(p, m, s) for p, m, s in zip(pub_bytes, ms, ss)]
            half = len(valid) // 2  # two passes, in row order
            return cbatch.PendingVerdicts(
                [_Verdicts(valid[:half], log, kt),
                 _Verdicts(valid[half:], log, kt,
                           kt == sick and where == "fetch")],
                len(valid), kt + ".fetch")
        return run

    on_host = []
    real = cbatch._host_verify_rows
    monkeypatch.setattr(
        cbatch, "_host_verify_rows",
        lambda p, m, s, idxs, valid: (on_host.append(list(idxs)),
                                      real(p, m, s, idxs, valid))[1])
    brk = cbatch.CircuitBreaker(failure_threshold=5)
    got = cbatch.verify_batch(
        pubs, msgs, sigs, breaker=brk,
        kernels={"ed25519": kernel("ed25519", ed.verify),
                 "sr25519": kernel("sr25519", sr.verify)})
    np.testing.assert_array_equal(got, np.asarray(exp))
    assert brk.faults == 1 and brk.state == "closed"
    assert on_host == [[i for i, p in enumerate(pubs)
                        if p.key_type == sick]]
    # every dispatch of the call, then the fetches in group order
    assert log[:2] == [("dispatch", "sr25519"), ("dispatch", "ed25519")]
    fetched = [kt for kind, kt in log[2:] if kind == "fetch"]
    assert len(log) == 2 + len(fetched)
    healthy = "ed25519" if sick == "sr25519" else "sr25519"
    assert fetched == {
        "dispatch": [healthy] * 2,
        # the sick group's first pass came back, its second raised
        "fetch": ["sr25519"] * 2 + ["ed25519"] * 2}[where]


def test_breaker_config_knobs():
    from cometbft_tpu.config.config import Config, ConfigError

    cfg = Config()
    cfg.crypto.breaker_failure_threshold = 7
    cfg.crypto.breaker_cooldown = 1.5
    cfg.validate_basic()
    cfg.crypto.batch_fn()  # applies the knobs to the global breaker
    assert cbatch.device_breaker().failure_threshold == 7
    assert cbatch.device_breaker().cooldown == 1.5
    cfg.crypto.breaker_failure_threshold = 0
    with pytest.raises(ConfigError):
        cfg.validate_basic()


@pytest.mark.parametrize("where", ["dispatch", "fetch"])
def test_a_fault_re_verifies_a_commits_lazy_rows_from_their_bytes(
        monkeypatch, where):
    """The served commit check hands its batch_fn lazy sign-bytes
    (canonical.TemplateRows: templates and a timestamp a row). A
    device fault at dispatch or at the fetch re-verifies the group on
    the host from the REAL bytes of its rows, so the verdict and the
    blame are the oracle's."""
    from cometbft_tpu.types import canonical, validation
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (BLOCK_ID_FLAG_COMMIT, Commit,
                                           CommitSig)
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    chain, height = "breaker-chain", 7
    privs = {p.pub_key().address(): p for p in
             (PrivKey.generate(bytes([i + 40]) * 32) for i in range(9))}
    vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs.values()])
    bid = BlockID(b"\x42" * 32, PartSetHeader(1, b"\x43" * 32))
    sigs = []
    for idx, v in enumerate(vs.validators):
        ts = Timestamp(1_700_000_000 + idx, 1000 * idx)
        sig = privs[v.address].sign(canonical.canonical_vote_bytes(
            chain, canonical.PRECOMMIT_TYPE, height, 0, bid, ts))
        if idx == 3:
            sig = sig[:7] + bytes([sig[7] ^ 1]) + sig[8:]
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts, sig))
    commit = Commit(height, 0, bid, sigs)
    handed, on_host = [], []

    def kernel(pub_bytes, msgs, sigs):
        handed.append(msgs)
        if where == "dispatch":
            raise RuntimeError("device lost")
        return cbatch.PendingVerdicts(
            [_Verdicts([True] * len(sigs), [], "ed25519", sick=True)],
            len(sigs), "ed25519.fetch")

    real = cbatch._host_verify_rows

    def host(pubs, msgs, sigs, idxs, valid):
        on_host.append((msgs, [msgs[i] for i in idxs]))
        return real(pubs, msgs, sigs, idxs, valid)

    monkeypatch.setattr(cbatch, "_host_verify_rows", host)
    brk = cbatch.CircuitBreaker(failure_threshold=5)
    with pytest.raises(validation.InvalidSignatureError) as ei:
        validation.verify_commit_light(
            chain, vs, bid, height, commit,
            lambda p, m, s: cbatch.verify_batch(
                p, m, s, kernels={"ed25519": kernel}, breaker=brk))
    assert ei.value.idx == 3 and brk.faults == 1
    # the kernel was handed the group's rows still lazy; the host read
    # the bytes those rows stand for
    assert [type(m) for m in handed] == [canonical.TemplateRows]
    (msgs, read), = on_host
    assert type(msgs) is canonical.TemplateRows
    assert read == [commit.vote_sign_bytes(chain, i) for i in range(7)]
