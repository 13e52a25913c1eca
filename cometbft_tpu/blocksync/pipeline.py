"""Streaming multi-commit verification pipeline — the TPU blocksync core.

Reference shape: blocksync/reactor.go:463 verifies each streamed block's
commit serially (`state.Validators.VerifyCommitLight(...)` once per
block, ~1k sigs each). The TPU restructuring packs MANY consecutive
commits into one fused device pass: every signature row carries a
commit_id, the kernel verifies all rows in parallel and computes each
commit's voting-power quorum bit with a segmented one-hot tally
(ed25519_kernel.tally_core), so an 8k-signature pass retires 8 blocks
of 1k validators at once.

A verify() call is cut into chunks of at most CHUNK_ROWS device rows: a
64-block run of 1k validators is 8 such passes, overlapped. Double
buffering comes free from JAX async dispatch: the kernel call for chunk
k returns immediately, so the host packs chunk k+1 while the device
works; fetching chunk k's results overlaps the next dispatch
(SURVEY.md §7 stage 2's H2D-hiding requirement). Every verdict of the
call is in hand before verify() returns.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from cometbft_tpu.libs import tracing
from cometbft_tpu.ops import ed25519_kernel as ek
from cometbft_tpu.types.commit import Commit
from cometbft_tpu.types.validation import (
    InvalidSignatureError,
    NotEnoughPowerError,
    VerificationError,
    _verify_basic,
)
from cometbft_tpu.types.validator import ValidatorSet

# Fixed commit-axis padding: keeps the kernel's static n_commits constant
# across runs (one compile per signature bucket, not per run length).
MAX_COMMITS_PER_CHUNK = 64

# Device rows a chunk holds at most. Small enough that a full run at 1k
# validators (blocksync MAX_RUN, catchup.MAX_RUN: 64 blocks) is several
# chunks, so verify()'s two-in-flight loop has a next chunk to pack while
# the device verifies the last: a run that is one chunk packs and
# verifies in turn. The first pack of a call is the part nothing hides,
# so smaller is better until the host's per-chunk costs set the pace:
# swept 4,096 to 65,536 on a v5e (PERF.md section 6, PR 28).
CHUNK_ROWS = 8192

# Device-side sign-bytes stamping for the cached chunk path (ISSUE 19):
# ship per-row (sig, ts, flags) deltas plus ONE resident template per
# commit height instead of full packed rows — the catch-up firehose is
# exactly the cross-height shape the template cache amortizes. Flip off
# to force the legacy full-row pack (the bit-live differential oracle).
DEVICE_STAMP = True


@dataclass
class CommitJob:
    """One block's commit to verify (the VerifyCommitLight arguments)."""

    vals: ValidatorSet
    block_id: object
    height: int
    commit: Commit
    chain_id: str


@dataclass
class _Chunk:
    jobs: List  # [(global_idx, CommitJob)]
    row_job: np.ndarray   # (n,) job index per signature row
    row_idx: np.ndarray   # (n,) commit-signature index per row (blame)
    pending: tuple        # device arrays in flight
    row_pos: Optional[np.ndarray] = None  # device row per packed sig
    # (None = rows are dense 0..n-1; cached-table chunks stride commits
    # to the valset table period so row b mod M == validator index)


class StreamVerifier:
    """Packs CommitJobs into fused multi-commit device passes.

    verify(jobs) returns a list of Optional[VerificationError] — None for
    a commit that verified with quorum, the failure otherwise (bad sig
    rows get InvalidSignatureError with the exact commit-sig index, like
    the reference's per-sig blame fallback, types/validation.go:243-250).
    """

    def __init__(self, max_sigs: int = CHUNK_ROWS, use_pallas: bool = False,
                 min_device_sigs: int = 129):
        from cometbft_tpu.libs.staging import StagingPool

        self.max_sigs = max_sigs
        self.use_pallas = use_pallas
        self._vs_cache = {}
        # below this many rows the device pass loses to a host verify
        # loop (dispatch + compile economics — the shouldBatchVerify gate,
        # types/validation.go:13-17, applied to the streaming path)
        self.min_device_sigs = min_device_sigs
        # private staging pool, 3 deep: up to 2 chunks fly while a 3rd
        # packs (the double-buffer window below), and a chunk's buffers
        # come round again only after that chunk was collected
        self._staging = StagingPool(slots=3)
        # which path each dispatched chunk took: cached table with the
        # rows stamped on device, cached table with host-packed rows,
        # or the dense general kernel (mixed valsets, malformed rows).
        # The fallbacks between them are silent by design; the counts
        # are how a caller sees which one it got.
        self.chunks = {"stamped": 0, "host_packed": 0, "dense": 0}

    # -- packing -----------------------------------------------------------

    @staticmethod
    def _template_msgs(jobs, job_idxs):
        """No-native fallback: vectorized template patching per commit
        (Commit.sign_bytes_rows via validation's toggle) — byte-equal
        to the legacy per-row vote_sign_bytes loop, shared by both
        pack paths."""
        from cometbft_tpu.types import validation as tv

        msgs = []
        for j, idxs in job_idxs:
            job = jobs[j][1]
            msgs += tv._commit_msgs(job.chain_id, job.commit, idxs)
        return msgs

    def _valset_arrays(self, vs):
        """(pub_bytes_list, power_list, all_32B) per ValidatorSet,
        cached by identity — the streaming loop re-reads one set for
        hundreds of consecutive commits."""
        cached = self._vs_cache.get(id(vs))
        if cached is not None and cached[3] is vs:
            return cached[:3]
        # tuples, not lists: immutable key columns hit the identity-
        # memoized content key in ed25519_cached.table_for_pubs
        keys = tuple(v.pub_key.data for v in vs.validators)
        powers = tuple(v.voting_power for v in vs.validators)
        keys_ok = all(len(k) == 32 for k in keys)
        if len(self._vs_cache) > 8:
            self._vs_cache.clear()
        # the valset itself rides in the entry so an id() collision with
        # a garbage-collected set can never alias
        self._vs_cache[id(vs)] = (keys, powers, keys_ok, vs)
        return keys, powers, keys_ok

    def _cached_table(self, jobs):
        """The valset window table when every job in the chunk shares one
        ed25519 valset (the dominant blocksync shape) — else None."""
        if not self.use_pallas:
            return None
        vs0 = jobs[0][1].vals
        if any(job.vals is not vs0 for _, job in jobs[1:]):
            return None
        keys, vpowers, keys_ok = self._valset_arrays(vs0)
        if not keys_ok or len(keys) < 2:
            return None
        from cometbft_tpu.ops import ed25519_cached as ec

        # device-resident per-valset cache: the steady sync stream hits
        # the identity memo and never re-hashes (or re-uploads) the set
        return ec.table_for_valset(vs0)

    def _pack_chunk_cached(self, jobs, table) -> Optional[_Chunk]:
        """Strided pack for the cached-table kernel: commit c occupies
        device rows [c*M, (c+1)*M) with validator i's signature at row
        c*M + i (the kernel derives the table key as row mod M). Rows
        with no countable signature stay dead (precheck=0, counted=0).
        """
        from cometbft_tpu import native
        from cometbft_tpu.ops import ed25519_cached as ec
        from cometbft_tpu.ops.ed25519_pallas import _PB
        from cometbft_tpu.types import canonical

        M = table.n_vals
        # static jobs-per-chunk — MUST match _split_for_tables or small
        # valsets would inflate B to max_sigs rows of mostly-dead work
        cap = min(MAX_COMMITS_PER_CHUNK, max(1, self.max_sigs // M))
        assert len(jobs) <= cap
        B = cap * M

        pubs: List[bytes] = []
        sigs: List[bytes] = []
        row_job: List[int] = []
        row_idx: List[int] = []
        row_pos: List[int] = []
        row_ts: List[tuple] = []
        job_idxs: List[tuple] = []  # (j, idxs) for the template fallback
        keys, _, _ = self._valset_arrays(jobs[0][1].vals)
        nvals = len(keys)
        for j, (_, job) in enumerate(jobs):
            css = job.commit.signatures
            idxs = [i for i, cs in enumerate(css)
                    if cs.for_block() and i < nvals]
            if not idxs:
                continue
            pubs += [keys[i] for i in idxs]
            sigs += [css[i].signature for i in idxs]
            row_ts += [(css[i].timestamp.seconds, css[i].timestamp.nanos)
                       for i in idxs]
            row_job += [j] * len(idxs)
            row_idx += idxs
            row_pos += [j * M + i for i in idxs]
            job_idxs.append((j, idxs))
        if not pubs:
            return None
        n = len(pubs)
        if any(len(s) != 64 for s in sigs):
            return None  # malformed rows: dense screen path handles
        pos = np.asarray(row_pos, np.int64)
        thresh = np.zeros((cap, ek.TALLY_LIMBS), np.int32)
        thresh[:, -1] = ek.POWER_MASK  # unreachable for padded job slots
        for j, (_, job) in enumerate(jobs):
            thresh[j] = ek.threshold_limbs(
                job.vals.total_voting_power() * 2 // 3
            )[0]
        # delta staging first: when every job stamps, the whole host
        # pack below (SHA-512 + mod-L per row) never runs
        pending = self._stamp_chunk(jobs, sigs, row_ts, row_job, pos,
                                    B, cap, table, thresh)
        if pending is not None:
            self.chunks["stamped"] += 1
            return _Chunk(list(jobs), np.asarray(row_job),
                          np.asarray(row_idx), pending, row_pos=pos)
        # dense native/numpy pack, then scatter to the strided layout
        packed = None
        if native.available():
            templates = []
            for _, job in jobs:
                enc = canonical.CanonicalVoteEncoder(
                    job.chain_id, canonical.PRECOMMIT_TYPE,
                    job.commit.height, job.commit.round,
                    job.commit.block_id,
                )
                templates.append(enc.template)
            packed = native.ed25519_pack_commits(
                b"".join(pubs), b"".join(sigs), templates,
                np.asarray(row_job, np.int32),
                np.asarray([s for s, _ in row_ts], np.int64),
                np.asarray([nn for _, nn in row_ts], np.int64), n,
            )
        if packed is not None:
            _, _, ry_d, rsign_d, sdig_d, hdig_d, pre_d = packed
        else:
            msgs = self._template_msgs(jobs, job_idxs)
            pbd = ek.pack_batch(pubs, msgs, sigs, pad_to=n)
            ry_d, rsign_d = pbd.ry, pbd.rsign
            sdig_d, hdig_d, pre_d = pbd.sdig, pbd.hdig, pbd.precheck
        # pinned staging: chunk arrays rotate through the verifier's
        # persistent pool so packing chunk k+1 reuses chunk k-2's memory
        pool = self._staging
        ry = pool.get("chunk.ry", (B, ry_d.shape[1]), ry_d.dtype)
        ry[pos] = ry_d[:n]
        rsign = pool.get("chunk.rsign", (B,), np.int32)
        rsign[pos] = np.asarray(rsign_d[:n], np.int32)
        sdig = pool.get("chunk.sdig", (B, sdig_d.shape[1]), sdig_d.dtype)
        sdig[pos] = sdig_d[:n]
        hdig = pool.get("chunk.hdig", (B, hdig_d.shape[1]), hdig_d.dtype)
        hdig[pos] = hdig_d[:n]
        precheck = pool.get("chunk.precheck", (B,), np.bool_)
        precheck[pos] = np.asarray(pre_d[:n], np.bool_)
        counted = pool.get("chunk.counted", (B,), np.bool_)
        counted[pos] = True
        commit_ids = pool.get("chunk.cid", (B,), np.int32)
        for j in range(cap):
            commit_ids[j * M:(j + 1) * M] = j
        pb = _PB(None, None, ry, rsign, sdig, hdig, precheck)
        out = pool.get("chunk.rows", ec.packed_rows_shape(B, cap),
                       np.int32)
        rows = ec.pack_rows_cached(pb, counted, commit_ids, thresh,
                                   out=out)
        with tracing.stage("stream.dispatch", path="host_packed"):
            pending = ec.verify_tally_rows_cached(rows, table, cap)
        self.chunks["host_packed"] += 1
        return _Chunk(list(jobs), np.asarray(row_job),
                      np.asarray(row_idx), pending, row_pos=pos)

    def _stamp_chunk(self, jobs, sigs, row_ts, row_job, pos, B, cap,
                     table, thresh):
        """Delta staging for the cached chunk (ISSUE 19): stage only
        (sig, ts_words, flags) per row — 80 B instead of the full
        packed column set — and let the device stamping prologue
        expand each row against its height's resident template
        (tmpl_id == commit_id == the job index). Returns the pending
        device arrays, or None when the chunk must host-pack: stamping
        disabled, a pre-pub_raw table, more heights than the template
        matrix holds, or timestamp words outside the staged int32
        layout."""
        if not DEVICE_STAMP or getattr(table, "pub_raw", None) is None:
            return None
        from cometbft_tpu.ops import ed25519_cached as ec
        from cometbft_tpu.types import canonical

        if len(jobs) > ec.MAX_TEMPLATE_SITES:
            return None
        if any(not (-2**63 <= s < 2**63 and -2**31 <= nn < 2**31)
               for s, nn in row_ts):
            return None
        sites = []
        for _, job in jobs:
            tpl = canonical.VoteRowTemplate(
                job.chain_id, canonical.PRECOMMIT_TYPE,
                job.commit.height, job.commit.round,
                job.commit.block_id)
            sites.append(tpl.stamp_site())
        sec_a = np.fromiter((s for s, _ in row_ts), np.int64,
                            count=len(row_ts))
        nan_a = np.fromiter((nn for _, nn in row_ts), np.int64,
                            count=len(row_ts))
        try:
            # padded to the chunk's job capacity: one stamp program per
            # validator-set size, however many blocks a run carries
            ent = ec.template_entry(sites, pad_to=cap)
        except ValueError:  # site count over the template matrix
            return None
        pool = self._staging
        dsig = pool.get("chunk.dsig", (B, 64), np.uint8)
        dsig[pos] = np.frombuffer(b"".join(sigs),
                                  np.uint8).reshape(-1, 64)
        dts = pool.get("chunk.dts", (B, 3), np.int32)
        dts[pos] = canonical.split_ts_words(sec_a, nan_a)
        dfl = pool.get("chunk.dflags", (B,), np.int32)
        rj = np.asarray(row_job, np.int64)
        # live | counted | tmpl_id<<2 | cid<<10 — every packed chunk
        # row is countable (the for_block filter already ran); dead
        # lanes keep the pool's zero fill (live=0 -> zero row)
        dfl[pos] = (3 | (rj << 2) | (rj << 10)).astype(np.int32)
        with tracing.stage("stream.dispatch", path="stamped"):
            return ec.verify_tally_delta_cached(dsig, dts, dfl, ent,
                                                table, cap, thresh)

    def _pack_chunk(self, jobs) -> Optional[_Chunk]:
        """jobs: [(global_idx, CommitJob)] for this chunk."""
        from cometbft_tpu import native
        from cometbft_tpu.types import canonical

        pubs: List[bytes] = []
        sigs: List[bytes] = []
        row_job: List[int] = []
        row_idx: List[int] = []
        powers: List[int] = []
        row_ts: List[tuple] = []
        job_idxs: List[tuple] = []  # (j, idxs) for the template fallback
        well_formed = True
        native_possible = native.available()
        for j, (_, job) in enumerate(jobs):
            # per-valset key/power staging is cached (sync streams reuse
            # one set across hundreds of commits); the per-commit work is
            # a handful of comprehensions, not a 6-append row loop
            keys, vpowers, keys_ok = self._valset_arrays(job.vals)
            css = job.commit.signatures
            nvals = len(keys)
            idxs = [i for i, cs in enumerate(css)
                    if cs.for_block() and i < nvals]
            if not idxs:
                continue
            pubs += [keys[i] for i in idxs]
            sigs += [css[i].signature for i in idxs]
            if native_possible:  # consumed only by the native fast path
                row_ts += [
                    (css[i].timestamp.seconds, css[i].timestamp.nanos)
                    for i in idxs
                ]
            row_job += [j] * len(idxs)
            row_idx += idxs
            powers += [vpowers[i] for i in idxs]
            job_idxs.append((j, idxs))
            if not keys_ok or any(len(css[i].signature) != 64
                                  for i in idxs):
                well_formed = False  # numpy path screens bad rows
        if not pubs:
            return None
        n = len(pubs)
        if self.use_pallas:
            from cometbft_tpu.ops import ed25519_pallas as kp

            pad = kp.pad_to_tile(n)
        else:
            pad = ek.bucket_size(n)
        # native fast path: sign-bytes are assembled in C from one
        # (pre, suf) template per commit + per-row timestamps — the
        # hottest host loop of streaming verification never builds
        # Python message objects at all
        packed = None
        if well_formed and native_possible:
            templates = []
            for _, job in jobs:
                enc = canonical.CanonicalVoteEncoder(
                    job.chain_id, canonical.PRECOMMIT_TYPE,
                    job.commit.height, job.commit.round,
                    job.commit.block_id,
                )
                templates.append(enc.template)
            packed = native.ed25519_pack_commits(
                b"".join(pubs), b"".join(sigs), templates,
                np.asarray(row_job, np.int32),
                np.asarray([s for s, _ in row_ts], np.int64),
                np.asarray([nn for _, nn in row_ts], np.int64), pad,
            )
        if packed is not None:
            pb = ek.PackedBatch(n, pad, *packed)
        else:
            msgs = self._template_msgs(jobs, job_idxs)
            pb = ek.pack_batch(pubs, msgs, sigs, pad_to=pad)
        power5 = np.zeros((pad, ek.POWER_LIMBS), np.int32)
        power5[:n] = ek.power_limbs(np.asarray(powers, np.int64))
        counted = np.zeros((pad,), np.bool_)
        counted[:n] = True
        # the commit dimension is PADDED to a fixed size: n_commits is a
        # static arg of the jit'd kernel, so a varying count would force a
        # recompile (minutes on CPU) for every distinct run length
        c_pad = MAX_COMMITS_PER_CHUNK + 1
        commit_ids = np.zeros((pad,), np.int32)
        commit_ids[:n] = np.asarray(row_job, np.int32)
        # padding rows tally into the sink commit id so they can't pollute
        # job 0's quorum
        commit_ids[n:] = c_pad - 1
        thresh = np.zeros((c_pad, ek.TALLY_LIMBS), np.int32)
        thresh[:, -1] = ek.POWER_MASK  # unused/sink: unreachable threshold
        for j, (_, job) in enumerate(jobs):
            thresh[j] = ek.threshold_limbs(
                job.vals.total_voting_power() * 2 // 3
            )[0]

        with tracing.stage("stream.dispatch", path="dense"):
            pending = self._dispatch(pb, power5, counted, commit_ids,
                                     thresh, c_pad)
        self.chunks["dense"] += 1
        return _Chunk(jobs, np.asarray(row_job), np.asarray(row_idx),
                      pending)

    def _dispatch(self, pb, power5, counted, commit_ids, thresh, n_commits):
        if self.use_pallas:
            from cometbft_tpu.ops import ed25519_pallas as kp

            # single fused H2D transfer per chunk (see kp.pack_rows)
            rows = kp.pack_rows(pb, power5, counted, commit_ids, thresh)
            return kp.verify_tally_rows(rows, thresh.shape[0])
        return ek.verify_tally_kernel(
            pb.ay, pb.asign, pb.ry, pb.rsign, pb.sdig, pb.hdig, pb.precheck,
            power5, counted, commit_ids, thresh, n_commits,
        )

    # -- the streaming loop ------------------------------------------------

    def _chunk_indexed(self, indexed):
        """Split [(global_idx, job)] into chunks of <= max_sigs rows."""
        cur, cur_sigs = [], 0
        for gi, job in indexed:
            n = len(job.commit.signatures)
            if cur and (cur_sigs + n > self.max_sigs
                        or len(cur) >= MAX_COMMITS_PER_CHUNK):
                yield cur
                cur, cur_sigs = [], 0
            cur.append((gi, job))
            cur_sigs += n
        if cur:
            yield cur

    def verify(
        self, jobs: Sequence[CommitJob]
    ) -> List[Optional[VerificationError]]:
        results: List[Optional[VerificationError]] = [None] * len(jobs)
        with tracing.stage("stream.prechecks", jobs=len(jobs)):
            indexed = self._prechecked(jobs, results)
            total_rows = sum(
                len(j.commit.signatures) for _, j in indexed
            )
        if total_rows < self.min_device_sigs:
            from cometbft_tpu.types import validation as tv

            for gi, job in indexed:
                try:
                    tv.verify_commit_light(
                        job.chain_id, job.vals, job.block_id, job.height,
                        job.commit, batch_fn=None,
                    )
                except VerificationError as e:
                    results[gi] = e
            return results

        in_flight: List[_Chunk] = []
        for chunk_pairs in self._split_for_tables(indexed):
            # host pack or delta staging, template entry and (nested,
            # stream.dispatch) the device call; flying = chunks the
            # device still has while the host packs this one (0: it idles)
            with tracing.stage("stream.pack", jobs=len(chunk_pairs),
                               rows=sum(len(j.commit.signatures)
                                        for _, j in chunk_pairs),
                               flying=len(in_flight)):
                chunk = self._pack_any(chunk_pairs)
            if chunk is None:
                # zero packable rows (e.g. every signature ABSENT): fail
                # CLOSED — these commits tallied no power at all
                for gi, job in chunk_pairs:
                    results[gi] = NotEnoughPowerError(
                        0, job.vals.total_voting_power() * 2 // 3
                    )
            else:
                in_flight.append(chunk)
            # keep at most 2 chunks in flight: fetch the oldest while the
            # newest computes (double buffering)
            if len(in_flight) > 2:
                self._collect(in_flight.pop(0), results)
        for chunk in in_flight:
            self._collect(chunk, results)
        return results

    def _prechecked(self, jobs, results):
        """The structural prechecks and the routing of what the fused
        pass cannot take (their verdicts go into `results`); returns
        [(index, job)] of what it can."""
        done = set()
        # structural prechecks stay host-side (cheap, no device round trip)
        for i, job in enumerate(jobs):
            try:
                _verify_basic(job.vals, job.block_id, job.height, job.commit)
            except VerificationError as e:
                results[i] = e
                done.add(i)

        # commits with non-ed25519 validators route to the grouped batch
        # dispatch (crypto/batch.py handles mixed key types); the fused
        # multi-commit pass below assumes uniform ed25519 rows
        for i, job in enumerate(jobs):
            if i in done:
                continue
            if any(
                v.pub_key.key_type != "ed25519" for v in job.vals.validators
            ):
                from cometbft_tpu.types import validation as tv

                try:
                    tv.verify_commit_light(
                        job.chain_id, job.vals, job.block_id, job.height,
                        job.commit, tv.device_batch_fn(),
                    )
                except VerificationError as e:
                    results[i] = e
                done.add(i)
        return [(i, j) for i, j in enumerate(jobs) if i not in done]

    def _split_for_tables(self, indexed):
        """Chunk, then sub-split cached-table chunks to the static
        jobs-per-chunk capacity the strided layout compiles for."""
        for chunk_pairs in self._chunk_indexed(indexed):
            table = self._cached_table(chunk_pairs)
            if table is None:
                yield chunk_pairs
                continue
            cap = min(MAX_COMMITS_PER_CHUNK,
                      max(1, self.max_sigs // table.n_vals))
            for k in range(0, len(chunk_pairs), cap):
                yield chunk_pairs[k:k + cap]

    def _pack_any(self, jobs) -> Optional[_Chunk]:
        table = self._cached_table(jobs)
        if table is not None:
            chunk = self._pack_chunk_cached(jobs, table)
            if chunk is not None:
                return chunk  # malformed rows fall through to the screen
        return self._pack_chunk(jobs)

    def _collect(self, chunk: _Chunk, results) -> None:
        # waits for the device, then blames
        with tracing.stage("stream.collect", jobs=len(chunk.jobs)):
            valid, tally, quorum = chunk.pending
            valid = np.asarray(valid)
            quorum = np.asarray(quorum)
            for j, (gi, job) in enumerate(chunk.jobs):
                rows = chunk.row_job == j
                if chunk.row_pos is not None:
                    row_valid = valid[chunk.row_pos[rows]]
                else:
                    row_valid = valid[: len(chunk.row_job)][rows]
                if not row_valid.all():
                    bad = chunk.row_idx[rows][~row_valid][0]
                    results[gi] = InvalidSignatureError(int(bad))
                elif not bool(quorum[j]):
                    needed = job.vals.total_voting_power() * 2 // 3
                    results[gi] = NotEnoughPowerError(-1, needed)


def make_stream_verifier(use_pallas: Optional[bool] = None,
                         max_sigs: int = CHUNK_ROWS) -> StreamVerifier:
    if use_pallas is None:
        from cometbft_tpu.crypto.batch import _accel_backend

        use_pallas = _accel_backend()
    return StreamVerifier(max_sigs=max_sigs, use_pallas=use_pallas)
