"""Commit verification: the VerifyCommit family over the device verifier.

Reference: types/validation.go — VerifyCommit (:26), VerifyCommitLight
(:60), VerifyCommitLightTrusting (:95), shouldBatchVerify gate (:13-17),
verifyCommitBatch (:153-257) with fused tally + per-sig blame fallback
(:243-250), verifyCommitSingle (:266-333).

TPU-first restructuring: the reference interleaves sign-bytes
reconstruction, BatchVerifier.Add and the power tally in one Go loop with
an early 2/3 break. Here the whole commit is packed once (vectorized host
staging), verified in one fused device pass that also computes the quorum
bit, and the early-break becomes "don't fetch what you don't need" — the
device always verifies every signature (data-parallel work is free until
the batch is full), matching the reference's countAllSignatures=true path
bit-for-bit and its early-break path in outcome.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence

import numpy as np

from cometbft_tpu.libs import tracing
from cometbft_tpu.types import canonical
from cometbft_tpu.types.commit import (
    BLOCK_ID_FLAG_COMMIT,
    Commit,
)
from cometbft_tpu.types.validator import ValidatorSet


class VerificationError(Exception):
    pass


class InvalidSignatureError(VerificationError):
    def __init__(self, idx: int, msg: str = ""):
        self.idx = idx
        super().__init__(msg or f"wrong signature (#{idx})")


class NotEnoughPowerError(VerificationError):
    def __init__(self, got: int, needed: int):
        self.got = got
        self.needed = needed
        super().__init__(
            f"invalid commit -- insufficient voting power: got {got}, "
            f"needed more than {needed}"
        )


# Batch path gate (types/validation.go:13-17): >=2 sigs and a batch-capable
# key type. The device adds its own economics: below this many signatures
# the H2D+dispatch overhead exceeds the pure-Python single verify cost.
BATCH_VERIFY_THRESHOLD = 2


def _should_batch_verify(commit: Commit) -> bool:
    return len(commit.signatures) >= BATCH_VERIFY_THRESHOLD


# Template packing (the zero-copy hot path): batch verification hands
# its batch_fn the commit's sign-bytes as Commit.sign_rows (the lazy
# canonical.TemplateRows: two templates and a timestamp a row) instead
# of the per-vote encode loop's list. The toggle exists for the
# legacy/differential path only — bytes are identical either way
# (tests/test_sign_template.py property fuzz + the simnet determinism
# scenario), so flipping it must never change behavior.
_TEMPLATE_PACK = True


def set_template_packing(on: bool) -> bool:
    """Enable/disable the vectorized template-packing path; returns the
    previous setting (tests and the simnet determinism guard)."""
    global _TEMPLATE_PACK
    prev = _TEMPLATE_PACK
    _TEMPLATE_PACK = bool(on)
    return prev


def template_packing_enabled() -> bool:
    return _TEMPLATE_PACK


def _commit_msgs(chain_id: str, commit: Commit, idxs) -> Sequence[bytes]:
    """Sign-bytes for the collected signature indices: the commit's
    templates and each row's timestamp as a lazy Sequence[bytes] that
    builds no bytes until something iterates it (what knows the type
    hashes them in C: ops/ed25519_kernel.pack_templated), or the list
    of the legacy per-vote encode loop."""
    if _TEMPLATE_PACK:
        return commit.sign_rows(chain_id, idxs)
    return [commit.vote_sign_bytes(chain_id, i) for i in idxs]


def verify_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id,
    height: int,
    commit: Commit,
    batch_fn: Optional[Callable] = None,
) -> None:
    """Full verification (types/validation.go:26): 2/3+ of the total power
    of `vals` must have signed block_id; all signatures are checked."""
    _verify_basic(vals, block_id, height, commit)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    _verify(
        chain_id, vals, commit, voting_power_needed,
        ignore_sig=lambda cs: cs.is_absent(),
        count_sig=lambda cs: cs.for_block(),
        count_all=True,
        lookup_by_address=False,
        batch_fn=batch_fn,
    )


def verify_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id,
    height: int,
    commit: Commit,
    batch_fn: Optional[Callable] = None,
) -> None:
    """Light verification (types/validation.go:60): stop at 2/3+, only
    commit-flag signatures checked."""
    _verify_basic(vals, block_id, height, commit)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    _verify(
        chain_id, vals, commit, voting_power_needed,
        ignore_sig=lambda cs: not cs.for_block(),
        count_sig=lambda cs: cs.for_block(),
        count_all=False,
        lookup_by_address=False,
        batch_fn=batch_fn,
    )


def verify_commit_light_trusting(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    trust_level=(1, 3),
    batch_fn: Optional[Callable] = None,
) -> None:
    """Trusting verification (types/validation.go:95): trust_level (default
    1/3) of the OLD validator set must have signed; validators are looked
    up by address (indices differ between sets)."""
    if commit is None:
        raise VerificationError("nil commit")
    num, denom = trust_level
    if denom == 0:
        # reference panics on zero denominator before any math
        # (validation.go:101-103); no further range check is applied here
        # (the light client validates [1/3, 1] separately)
        raise VerificationError("trustLevel has zero Denominator")
    total = vals.total_voting_power()
    voting_power_needed = total * num // denom
    _verify(
        chain_id, vals, commit, voting_power_needed,
        ignore_sig=lambda cs: not cs.for_block(),
        count_sig=lambda cs: cs.for_block(),
        count_all=False,
        lookup_by_address=True,
        batch_fn=batch_fn,
    )


def _verify_basic(vals, block_id, height, commit) -> None:
    """Shared header checks (types/validation.go verifyBasicValsAndCommit)."""
    if vals is None or vals.is_nil_or_empty():
        raise VerificationError("nil or empty validator set")
    if commit is None:
        raise VerificationError("nil commit")
    if len(vals) != len(commit.signatures):
        raise VerificationError(
            f"invalid commit -- wrong set size: {len(vals)} vs "
            f"{len(commit.signatures)}"
        )
    if height != commit.height:
        raise VerificationError(
            f"invalid commit -- wrong height: {height} vs {commit.height}"
        )
    if block_id != commit.block_id:
        raise VerificationError(
            f"invalid commit -- wrong block ID: want {block_id}, "
            f"got {commit.block_id}"
        )


def _verify(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable,
    count_sig: Callable,
    count_all: bool,
    lookup_by_address: bool,
    batch_fn: Optional[Callable],
) -> None:
    # the body all three VerifyCommit variants share, as one always-on
    # stage; the batch path's own stages nest in it
    with tracing.stage("commit.verify", sigs=len(commit.signatures)):
        if _should_batch_verify(commit) and batch_fn is not None:
            _verify_batch(
                chain_id, vals, commit, voting_power_needed,
                ignore_sig, count_sig, count_all, lookup_by_address,
                batch_fn,
            )
        else:
            _verify_single(
                chain_id, vals, commit, voting_power_needed,
                ignore_sig, count_sig, count_all, lookup_by_address,
            )


def _row(chain_id, vals, commit, idx, cs, lookup_by_address):
    """Resolve (pubkey, power) for a commit sig, or None to skip.

    By-index for same-set verification, by-address for trusting mode
    (types/validation.go:176-199)."""
    if lookup_by_address:
        vi, val = vals.get_by_address(cs.validator_address)
        if val is None:
            return None
        return val.pub_key, val.voting_power
    val = vals.get_by_index(idx)
    if val is None:
        return None
    return val.pub_key, val.voting_power


def _verify_batch(
    chain_id, vals, commit, voting_power_needed,
    ignore_sig, count_sig, count_all, lookup_by_address, batch_fn,
) -> None:
    """Device path: one fused pack+verify+tally pass, blame on failure
    (types/validation.go:153-257).

    Outcome-equivalence with the reference's collection loop:
    - signatures are collected in commit order; with count_all=False the
      collection STOPS once the optimistic tally crosses the threshold
      (validation.go:223-225 early break) — later signatures, valid or
      not, are never examined;
    - the power threshold is checked on the optimistic tally BEFORE any
      cryptographic verification (validation.go:230-233);
    - on batch failure the reference re-verifies one-by-one for blame
      (:243-250); the device returns per-signature validity, so blame is
      the first invalid collected index, which is exactly where the
      single-verify fallback would stop.
    """
    pubs: List = []  # crypto.keys.PubKey — batch_fn groups by key_type
    sigs: List[bytes] = []
    idxs: List[int] = []
    tallied = 0
    seen = set()
    with tracing.stage("commit.collect"):
        for idx, cs in enumerate(commit.signatures):
            if ignore_sig(cs):
                continue
            resolved = _row(chain_id, vals, commit, idx, cs,
                            lookup_by_address)
            if resolved is None:
                continue
            if lookup_by_address:
                # duplicate check only for resolved validators
                # (validation.go:188-198: skip-unknown precedes seenVals)
                if cs.validator_address in seen:
                    raise VerificationError(
                        f"double vote from {cs.validator_address.hex()}"
                    )
                seen.add(cs.validator_address)
            pub_key, power = resolved
            pubs.append(pub_key)
            sigs.append(cs.signature)
            idxs.append(idx)
            if count_sig(cs):
                tallied += power
                if not count_all and tallied > voting_power_needed:
                    break

    if tallied <= voting_power_needed:
        raise NotEnoughPowerError(tallied, voting_power_needed)

    # what the collected rows signed, gathered AFTER collection: their
    # timestamps and nil flags beside the commit's templates (template
    # packing: the bytes are built where they are hashed, if the
    # batch_fn knows the type), or the legacy loop's bytes
    with tracing.stage("commit.sign_bytes", rows=len(idxs)):
        msgs = _commit_msgs(chain_id, commit, idxs)
    with tracing.stage("commit.batch_fn", rows=len(pubs)):
        valid = np.asarray(batch_fn(pubs, msgs, sigs))[: len(pubs)]
    if not valid.all():
        bad = int(np.flatnonzero(~valid)[0])
        raise InvalidSignatureError(idxs[bad])


def _verify_single(
    chain_id, vals, commit, voting_power_needed,
    ignore_sig, count_sig, count_all, lookup_by_address,
) -> None:
    """CPU fallback loop (types/validation.go:266-333). By-index lookups
    trust the index↔validator correspondence without an address compare,
    exactly like the reference (verifyCommitSingle lookUpByIndex arm)."""
    tallied = 0
    seen = set()
    for idx, cs in enumerate(commit.signatures):
        if ignore_sig(cs):
            continue
        resolved = _row(chain_id, vals, commit, idx, cs, lookup_by_address)
        if resolved is None:
            continue
        if lookup_by_address:
            if cs.validator_address in seen:
                raise VerificationError(
                    f"double vote from {cs.validator_address.hex()}"
                )
            seen.add(cs.validator_address)
        pub_key, power = resolved
        if not pub_key.verify_signature(
            commit.vote_sign_bytes(chain_id, idx), cs.signature
        ):
            raise InvalidSignatureError(idx)
        if count_sig(cs):
            tallied += power
            if not count_all and tallied > voting_power_needed:
                return
    if tallied <= voting_power_needed:
        raise NotEnoughPowerError(tallied, voting_power_needed)


# --------------------------------------------------------------------------
# Device batch_fn factories
# --------------------------------------------------------------------------

# Rows of one device pass of a batch too large for one (device_batch_fn),
# whatever its key type. A larger batch is cut into chunks of exactly
# this shape, each dispatched as soon as it is packed: the kernel sweeps
# ceil(n / T) * T rows where the bucket ladder's next rung may be 16,384
# for 6,667, and every pack but the first runs while the device
# verifies. Smaller shortens what nothing hides (the first pack, and
# the last chunk's pass, which the host waits out) and the padded tail;
# larger means fewer packs and dispatches, each with a fixed cost (0.3
# ms a dispatch). Swept 512 to 4,096 on a v5e over the 6,667 ed25519
# rows of a 10,000-validator commit (PERF.md section 6, PR 31).
COMMIT_CHUNK_ROWS = 1024


def _rows_matrix(msgs):
    """A commit's lazy rows as the ONE matrix they expand to (the
    `prepare` of a kind whose pack hashes the message bytes themselves);
    anything else as it is."""
    if isinstance(msgs, canonical.TemplateRows):
        return msgs.expand()
    return msgs


def _verify_chunked(kind, queue: list, pub_bytes, msgs, sigs):
    """The crypto/batch kernel of one key type, `kind` = (name, pack,
    run, one_pass, prepare): reads the group's messages once
    (`prepare(msgs)`: what every chunk's pack will be handed runs of),
    cuts its rows into chunks, packs each on the host
    (`pack(pubs, msgs, sigs, pad)` -> the packed rows, and the pack
    stage's args that are known only once it is done: `templated`, 1
    where the chunk's sign-bytes never existed as Python objects, and
    for secp256k1 `native`, 1 where the pack was the one C call) and
    hands it to the device
    (`run(packed)`, which returns while the device works), and returns
    the verdicts NOT YET FETCHED (cbatch.PendingVerdicts).

    Up to COMMIT_CHUNK_ROWS rows it is ONE pass padded to `one_pass(n)`
    rows, as ever; above, chunks of exactly COMMIT_CHUNK_ROWS, the
    tail's too, each on the device while the host packs the next.
    `queue` holds every pass this CALL of the batch_fn has dispatched,
    of any key type: a mixed commit's second group packs while the
    first group's chunks run. Stages `<name>.pack` / `.dispatch` per
    chunk, `<name>.fetch` once (PendingVerdicts.fetch)."""
    from cometbft_tpu.crypto import batch as cbatch

    name, pack, run, one_pass, prepare = kind
    n = len(pub_bytes)
    msgs = prepare(msgs)
    pad = COMMIT_CHUNK_ROWS if n > COMMIT_CHUNK_ROWS else one_pass(n)
    chunks = max(1, -(-n // pad))
    outs = []
    for k in range(chunks):
        lo = k * pad
        # flying: passes of this call the device has not finished as
        # this one's pack starts (0 past the first: it ran dry)
        at = {"rows": min(n - lo, pad), "chunk": k, "chunks": chunks,
              "flying": sum(not o.is_ready() for o in queue)}
        with tracing.stage(name + ".pack", padded=pad, **at) as st:
            packed, done = pack(pub_bytes[lo:lo + pad], msgs[lo:lo + pad],
                                sigs[lo:lo + pad], pad)
            st.args.update(done)
        # returns while the device runs
        with tracing.stage(name + ".dispatch", **at):
            outs.append(run(packed))
        queue.append(outs[-1])
    # what the host still waits for once nothing is left to dispatch,
    # and the copy back, are verify_batch_direct's to ask for
    return cbatch.PendingVerdicts(outs, n, name + ".fetch")


def device_batch_fn(use_pallas: Optional[bool] = None,
                    cached: bool = False) -> Callable:
    """Build a batch_fn backed by the batched TPU verifiers.

    Returns fn(pubs: [PubKey], msgs, sigs) -> (n,) bool validity, with
    rows grouped by key type (crypto/batch.py dispatch): ed25519 via the
    Pallas kernel on TPU backends / XLA-composed kernel elsewhere
    (interpret-mode Pallas on CPU is far slower than the XLA path),
    sr25519 via its Pallas kernel and secp256k1 via the ECDSA kernel
    (Pallas on TPU backends, XLA-composed elsewhere), all three fed as
    fixed-shape chunks through one in-flight queue a call
    (_verify_chunked: every chunk of every key type is dispatched
    before the first verdict is waited for). The voting-power
    tally stays host-side here because VerifyCommit's early-break
    collection is inherently sequential; the fused device tally serves
    the streaming paths (blocksync replay) where whole commits are
    verified unconditionally.
    """
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.ops import ed25519_kernel as ek

    if use_pallas is None:
        use_pallas = cbatch._accel_backend()
    if use_pallas:
        from cometbft_tpu.ops import ed25519_pallas as kp

        rows_of, one_pass = kp.pack_rows, kp.pad_to_tile
        run_ed = lambda rows: kp.verify_rows(rows)  # noqa: E731
    else:
        rows_of = lambda pb: pb  # noqa: E731
        run_ed = lambda pb: ek.verify_kernel(  # noqa: E731
            pb.ay, pb.asign, pb.ry, pb.rsign, pb.sdig, pb.hdig,
            pb.precheck)
        one_pass = lambda n: ek.bucket_size(max(n, 1))  # noqa: E731

    def pack_ed(p, m, s, pad):
        pb, templated = ek.pack_templated(p, m, s, pad_to=pad)
        return rows_of(pb), {"templated": int(templated)}

    # its pack builds a commit's lazy rows where it hashes them: as is
    ed_kind = ("ed25519", pack_ed, run_ed, one_pass, lambda m: m)

    def srk():  # first used by a batch that holds an sr25519 row
        from cometbft_tpu.ops import sr25519_kernel

        return sr25519_kernel

    # sr25519 has the one kernel: Pallas, interpreted on a CPU backend
    sr_kind = ("sr25519",
               lambda p, m, s, pad: (
                   srk().pack_batch_sr(p, m, s, pad_to=pad),
                   {"templated": int(isinstance(m, canonical.SignRows))}),
               lambda rows: srk().verify_rows(rows),
               lambda n: srk().kp.pad_to_tile(n),
               # merlin hashes the message bytes themselves: a commit's
               # lazy rows become ONE matrix before the group's first
               # chunk, and each chunk hashes its rows of it (a
               # patch_rows a chunk costs 0.35 ms more a chunk). Here and
               # not in a closure around _verify_chunked: that form read
               # 4.7 s more in a process's first sr25519 dispatch
               # (PERF.md section 6, PR 34)
               _rows_matrix)

    def eck():  # first used by a batch that holds a secp256k1 row
        from cometbft_tpu.ops import ecdsa_kernel

        return ecdsa_kernel

    if use_pallas:
        def ecp():
            from cometbft_tpu.ops import ecdsa_pallas

            return ecdsa_pallas

        secp_rows_of = lambda pb: ecp().pack_rows(pb)  # noqa: E731
        run_secp = lambda rows: ecp().verify_rows(rows)  # noqa: E731
        secp_one_pass = lambda n: ecp().pad_to_tile(n)  # noqa: E731
    else:
        secp_rows_of = rows_of
        run_secp = lambda pb: eck().verify_kernel(  # noqa: E731
            pb.qx, pb.qparity, pb.u1dig, pb.u2dig, pb.xr1, pb.xr2,
            pb.precheck)
        secp_one_pass = one_pass

    def pack_secp(p, m, s, pad):
        pb = eck().pack_batch(p, m, s, pad_to=pad)
        return secp_rows_of(pb), {
            "templated": int(isinstance(m, canonical.SignRows)),
            "native": int(pb.native)}

    # SHA-256 reads a message's bytes where they lie in the group's
    # one matrix, as merlin does
    secp_kind = ("secp256k1", pack_secp, run_secp, secp_one_pass,
                 _rows_matrix)

    def ed25519_cached(pub_bytes, msgs, sigs):
        # Cached-valset kernel (opt-in): ~3x the general kernel's
        # steady-state throughput, but the window table is keyed on
        # the EXACT pubkey list — callers must present a stable
        # list (the full valset in order) or every call pays a
        # table rebuild. The batch paths that guarantee stability
        # (blocksync StreamVerifier) use it; the
        # per-commit subset lists verify_commit_light produces
        # would thrash the LRU, so the default stays general.
        from cometbft_tpu.ops import ed25519_cached as ec

        # packs, runs and fetches inside the one call
        with tracing.stage("ed25519.dispatch", rows=len(pub_bytes)):
            return ec.verify_batch_cached(pub_bytes, list(msgs), sigs)

    def fn(pubs, msgs, sigs):
        queue = []  # this call's passes, of any key type

        def ed25519_verify(pub_bytes, msgs, sigs):
            if use_pallas and cached and len(pub_bytes) >= 128:
                return ed25519_cached(pub_bytes, msgs, sigs)
            return _verify_chunked(ed_kind, queue, pub_bytes, msgs, sigs)

        return cbatch.verify_batch(pubs, msgs, sigs, kernels={
            "ed25519": ed25519_verify,
            "sr25519": functools.partial(_verify_chunked, sr_kind, queue),
            "secp256k1": functools.partial(_verify_chunked, secp_kind,
                                           queue)})

    return fn


def oracle_batch_fn() -> Callable:
    """Pure-Python batch_fn (differential-test reference, no device)."""

    def fn(pubs, msgs, sigs):
        return np.asarray(
            [p.verify_signature(m, s) for p, m, s in zip(pubs, msgs, sigs)]
        )

    return fn


def commit_packed_batch(chain_id: str, commit: Commit, keys, idxs=None,
                        pad_to: Optional[int] = None):
    """Zero-copy staging of a commit's signatures for the device
    verifier: commit -> PackedBatch without ever materializing per-row
    Python sign-bytes: the served path's own pack
    (ops/ed25519_kernel.pack_templated over _commit_msgs) for one
    commit in one pass.

    keys[i] is validator i's 32-byte ed25519 pubkey (valset order). The
    native path assembles sign-bytes in C from the commit's (pre, suf)
    templates + per-row timestamps (ed25519_pack_commits); the fallback
    patches the numpy templates and feeds pack_batch. Both are
    byte-identical to the legacy per-vote path.

    Returns (PackedBatch, row_idxs) with row k of the batch holding
    commit-signature row_idxs[k]."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    sigs_all = commit.signatures
    if idxs is None:
        idxs = [i for i, cs in enumerate(sigs_all)
                if cs.for_block() and i < len(keys)]
    packed, _ = ek.pack_templated(
        [keys[i] for i in idxs], _commit_msgs(chain_id, commit, idxs),
        [sigs_all[i].signature for i in idxs], pad_to=pad_to)
    return packed, idxs
