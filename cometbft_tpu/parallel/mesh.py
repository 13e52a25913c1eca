"""Device-mesh sharding for the verification pipeline.

CometBFT's scale dimensions are validator-set size (up to 10k sigs per
commit, types/vote_set.go:18 MaxVotesCount) x commits in flight (blocksync
window 600, blocksync/pool.go:32). Both map to pure data parallelism: the
signature batch shards across a 1-D `batch` mesh axis, each device verifies
its slice and computes a partial voting-power tally, and one `psum` over ICI
reduces the per-commit tallies (the TPU analog of the reference's
gossip-aggregated `libs/bits` bitarrays + tally loop, SURVEY.md §2.6).

Multi-host: the same code runs over a DCN-spanning mesh — XLA routes the
psum hierarchically (ICI within pod slice, DCN across hosts).

Sub-meshes: every step below is memoized by the EXACT device tuple
(_mesh_key), so the verify plane's pipelined halves (fused.half_meshes
— two disjoint sub-meshes flying alternating flushes) each compile
their own program exactly once and hit the memo steady-state; a half
and the full mesh never collide in the cache. The psum in each step
reduces over its own mesh's axis only, which is what makes a flush
complete within its half — its rows, table shards, and thresholds all
live there (the deck's disjointness invariant).
"""
from __future__ import annotations

import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cometbft_tpu.ops import ed25519_kernel as ek

def _smap(fn, mesh, in_specs, out_specs, unchecked: bool = False):
    kw = {"check_vma": False} if unchecked else {}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def make_mesh(devices=None, axis: str = "batch") -> Mesh:
    devices = jax.devices() if devices is None else devices
    return Mesh(np.asarray(devices), (axis,))


# Compiled-step memo (round-5 regression fix): every builder below used
# to return a FRESH jax.jit(shard_map(...)) closure per call, so two
# calls with the same mesh re-traced — and on CPU interpret-compiled the
# Pallas kernel again, minutes each. Steps are cached by
# (builder, mesh identity, n_commits); jit's own cache handles row-shape
# specialization within a step.
_STEP_CACHE: dict = {}

# Memoization regression guard (the round-5 MULTICHIP timeout was
# per-call shard_map rebuilds): every builder counts its probe, so
# tests can assert steady-state calls HIT instead of silently
# re-tracing. The counters are mutated from the verify plane's
# dispatcher thread AND from test/scrape probes concurrently, so
# increments ride one module lock — an
# unguarded += loses counts exactly when several threads flush at once
# (the same race the plane's sheds counter fixed in PR 7).
_CACHE_STATS = {"hits": 0, "misses": 0}
_STATS_LOCK = threading.Lock()


def cache_stats() -> dict:
    with _STATS_LOCK:
        return dict(_CACHE_STATS)


def _cache_get(key):
    fn = _STEP_CACHE.get(key)
    with _STATS_LOCK:
        if fn is not None:
            _CACHE_STATS["hits"] += 1
        else:
            _CACHE_STATS["misses"] += 1
    return fn


def _cache_put(key, fn):
    """Memoize a freshly-built step, wrapped so its FIRST invocation
    attributes the lazy jit trace/compile to this builder in the
    device observatory's compile ledger (libs/deviceledger) — unless
    a richer frame (the verify plane's per-flush attribution, a
    caller's own) is already active on the calling thread, in which case
    that frame keeps the credit. After the first call the wrapper is
    a list check: steady-state dispatch cost is untouched."""
    from cometbft_tpu.libs import deviceledger

    site = f"mesh.step:{key[0]}"
    done: list = []

    def wrapped(*args):
        if done:
            return fn(*args)
        fr = deviceledger.attr_begin_fallback(site)
        try:
            return fn(*args)
        finally:
            done.append(1)
            if fr is not None:
                deviceledger.attr_end(fr)

    _STEP_CACHE[key] = wrapped
    return wrapped


def _mesh_key(mesh: Mesh):
    return (tuple(mesh.axis_names), tuple(mesh.devices.flat))


def _carry_tally(t):
    """Re-canonicalize tally limbs after a psum (limbs < ndev * 2^13)."""
    for i in range(ek.TALLY_LIMBS - 1):
        c = t[..., i] >> ek.POWER_LIMB_BITS
        t = t.at[..., i].add(-(c << ek.POWER_LIMB_BITS)).at[..., i + 1].add(c)
    return t


def sharded_verify_tally(mesh: Mesh, n_commits: int):
    """Build the sharded fused verify+tally step for a given mesh.

    Returns a jitted fn with the same signature as
    ed25519_kernel.verify_tally_kernel (minus n_commits). Batch dims shard
    over the mesh axis; tallies are psum-reduced; threshold/quorum are
    replicated. Memoized per (mesh, n_commits).
    """
    key = ("xla", _mesh_key(mesh), int(n_commits))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    axis = mesh.axis_names[0]
    bspec = P(axis)
    rspec = P()

    def step(ay, asign, ry, rsign, sdig, hdig, precheck, power5, counted,
             commit_ids, threshold):
        valid = ek.verify_core(ay, asign, ry, rsign, sdig, hdig, precheck)
        local = ek.tally_core(valid, power5, counted, commit_ids, n_commits)
        total = jax.lax.psum(local, axis)
        total = _carry_tally(total)
        quorum = ek.quorum_core(total, threshold)
        return valid, total, quorum

    sharded = _smap(
        step,
        mesh=mesh,
        in_specs=(bspec,) * 7 + (bspec, bspec, bspec, rspec),
        out_specs=(bspec, rspec, rspec),
    )
    fn = jax.jit(sharded)
    return _cache_put(key, fn)


def _sharded_verify_rows_step(mesh: Mesh):
    """The EXPENSIVE half of the rows path: the Mosaic/Pallas verify
    kernel (plus cheap per-row column extraction) under shard_map.
    Independent of n_commits, so every tally width shares this one
    compiled program — the round-5 multichip regression was exactly this
    program compiling once per (call, n_commits)."""
    key = ("pallas-verify", _mesh_key(mesh))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    from cometbft_tpu.ops import ed25519_pallas as kp

    axis = mesh.axis_names[0]

    def vstep(rows, base):
        valid = kp._verify_rows.__wrapped__(rows, base)
        pw = rows[kp.C_POW:kp.C_POW + 3]
        power5 = jax.numpy.stack(
            [pw[0] & kp._M13, pw[0] >> 13, pw[1] & kp._M13,
             pw[1] >> 13, pw[2]], axis=1)
        counted = (rows[kp.C_FLAGS] >> 3) & 1 != 0
        commit_ids = rows[kp.C_CID]
        return valid, power5, counted, commit_ids

    sharded = _smap(
        vstep,
        mesh=mesh,
        in_specs=(P(None, axis), P()),
        out_specs=(P(axis), P(axis, None), P(axis), P(axis)),
        # pallas_call's out_shape carries no varying-mesh-axes annotation;
        # the specs above pin the sharding explicitly
        unchecked=True,
    )
    fn = jax.jit(sharded)
    return _cache_put(key, fn)


def _sharded_tally_step(mesh: Mesh, n_commits: int):
    """The CHEAP half: per-device tally einsum + psum + quorum. A fresh
    trace per n_commits costs seconds, not the Pallas kernel's minutes."""
    key = ("pallas-tally", _mesh_key(mesh), int(n_commits))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    axis = mesh.axis_names[0]

    def tstep(valid, power5, counted, commit_ids, threshold):
        local = ek.tally_core(valid, power5, counted, commit_ids, n_commits)
        total = _carry_tally(jax.lax.psum(local, axis))
        quorum = ek.quorum_core(total, threshold)
        return total, quorum

    sharded = _smap(
        tstep,
        mesh=mesh,
        in_specs=(P(axis), P(axis, None), P(axis), P(axis), P()),
        out_specs=(P(), P()),
        unchecked=True,
    )
    fn = jax.jit(sharded)
    return _cache_put(key, fn)


def sharded_verify_tally_rows(mesh: Mesh, n_commits: int):
    """The FLAGSHIP (Pallas) kernel under shard_map.

    The compact packed array (R, B) shards on its lane axis (axis 1): each
    device runs the Mosaic kernel on its B/n_dev slice (which must be a
    multiple of ed25519_pallas.B_TILE), computes its partial power tally,
    and one psum over the mesh reduces per-commit tallies. Thresholds ride
    as a separate replicated argument (they are per-commit, not per-row,
    so they must not be lane-sharded with the rows).

    Two compiled programs compose the step: the n_commits-independent
    Pallas verify (shared by ALL tally widths on a mesh) and a tiny
    per-n_commits tally+psum jit. Both are memoized, so repeated calls —
    the round-5 multichip regression — reuse the compiled closures
    instead of re-tracing."""
    key = ("rows", _mesh_key(mesh), int(n_commits))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    verify = _sharded_verify_rows_step(mesh)
    tally = _sharded_tally_step(mesh, n_commits)

    def fn(rows, base, threshold):
        valid, power5, counted, commit_ids = verify(rows, base)
        total, quorum = tally(valid, power5, counted, commit_ids,
                              threshold)
        return valid, total, quorum

    return _cache_put(key, fn)


def shard_batch_arrays(mesh: Mesh, pb: ek.PackedBatch, power5, counted,
                       commit_ids):
    """Pad batch arrays to a multiple of the mesh size and device_put them
    with the batch sharding (so the jitted step does no host resharding).

    Padding rows necessarily carry commit_id=0 (there is no "no commit"
    id); they are kept out of every tally by construction: counted is
    cast to bool and the padding region is set False EXPLICITLY (not
    left to zero-fill), and precheck pads False so the verify core
    rejects the rows independently. tests/test_mesh.py's padded-vs-
    unpadded tally regression guards commit 0's sum bit-for-bit."""
    axis = mesh.axis_names[0]
    n_dev = mesh.devices.size
    padded = pb.padded
    counted = np.asarray(counted, np.bool_)
    if padded % n_dev:
        extra = n_dev - padded % n_dev
        pad1 = lambda a: np.pad(a, [(0, extra)] + [(0, 0)] * (a.ndim - 1))
        pb = pb._replace(
            padded=padded + extra, ay=pad1(pb.ay), asign=pad1(pb.asign),
            ry=pad1(pb.ry), rsign=pad1(pb.rsign), sdig=pad1(pb.sdig),
            hdig=pad1(pb.hdig), precheck=pad1(pb.precheck),
        )
        power5 = pad1(np.asarray(power5))
        counted = pad1(counted)
        counted[padded:] = False  # padding rows are never counted
        commit_ids = pad1(np.asarray(commit_ids))
    sh = NamedSharding(mesh, P(axis))
    put = lambda a: jax.device_put(a, sh)
    return pb, (
        put(pb.ay), put(pb.asign), put(pb.ry), put(pb.rsign), put(pb.sdig),
        put(pb.hdig), put(pb.precheck), put(power5), put(counted),
        put(commit_ids),
    )


def sharded_stream_verify(mesh: Mesh, n_commits: int):
    """The blocksync STREAMING path (cached-valset kernel) under
    shard_map: a multi-commit chunk shards at COMMIT granularity.

    Layout contract (blocksync/pipeline.py _pack_chunk_cached): commit c
    occupies rows [c*M, (c+1)*M) with validator i at row c*M + i. The
    rows array (R, C*M) shards on its lane axis so each device holds
    C/n_dev whole commits — the per-device slice width stays a multiple
    of M, which keeps the kernel's `row mod M -> validator` and
    `tile mod M/128 -> table block` maps intact without any index
    plumbing. The valset table replicates (it is the same valset for
    every commit — the streaming shape, blocksync/reactor.go:463); rows
    carry GLOBAL commit ids, so each device's partial tally lands in
    the right commit slot and one psum over the mesh finishes every
    commit's quorum at once.
    """
    from cometbft_tpu.ops import ed25519_cached as ec

    key = ("stream", _mesh_key(mesh), int(n_commits))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    axis = mesh.axis_names[0]

    def step(rows, tab, ok, power5, base, threshold):
        valid, local, _ = ec._verify_tally_cached.__wrapped__(
            rows, tab, ok, power5, base, n_commits
        )
        total = _carry_tally(jax.lax.psum(local, axis))
        quorum = ek.quorum_core(total, threshold)
        return valid, total, quorum

    sharded = _smap(
        step,
        mesh=mesh,
        in_specs=(P(None, axis), P(), P(), P(), P(), P()),
        out_specs=(P(axis), P(), P()),
        unchecked=True,
    )
    fn = jax.jit(sharded)
    return _cache_put(key, fn)


def sharded_fused_verify(mesh: Mesh, n_commits: int):
    """The verify PLANE's fused flush under shard_map: the cached-table
    kernel with the VALSET sharded across the mesh.

    Where sharded_stream_verify replicates one table and shards at
    commit granularity (the blocksync shape: many commits, modest
    valset), this shards the validator set itself — the 100k-validator
    commit shape, where ONE commit's valset exceeds a single chip's
    table budget (table_pad caps at 65536 slots/device). Device d holds
    the window-table shard for validators [d*M_s, (d+1)*M_s)
    (ed25519_cached.sharded_table_for_pubs) and its rows slice carries
    exactly those validators' signatures (fused.shard_positions lays
    commits out so row `d*B_loc + s*M_s + (v mod M_s)` is validator v's
    stride-s slot — the in-kernel `row mod M -> validator` map then
    resolves LOCAL indices with no plumbing). Rows carry GLOBAL commit
    ids, so each device's partial voting-power tally lands in the right
    commit slot; one psum over the mesh + a limb re-carry + quorum_core
    finish every commit's quorum bit ON DEVICE — the fused quorum
    output generalizes across chips.

    Thresholds ride as a separate replicated argument (the in-rows
    threshold rows are per-device slices and meaningless sharded; the
    kernel's own quorum output is discarded). Memoized per
    (mesh, n_commits); the expensive Pallas program recompiles per
    (mesh, local-batch-shape) under jit's own cache, exactly like the
    single-device path's bucket shapes."""
    from cometbft_tpu.ops import ed25519_cached as ec

    key = ("fused", _mesh_key(mesh), int(n_commits))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    axis = mesh.axis_names[0]

    def step(rows, tab, ok, power5, base, threshold):
        valid, local, _ = ec._verify_tally_cached.__wrapped__(
            rows, tab, ok, power5, base, n_commits
        )
        total = _carry_tally(jax.lax.psum(local, axis))
        quorum = ek.quorum_core(total, threshold)
        return valid, total, quorum

    sharded = _smap(
        step,
        mesh=mesh,
        in_specs=(P(None, axis), P(axis, None), P(axis), P(axis, None),
                  P(), P()),
        out_specs=(P(axis), P(), P()),
        unchecked=True,
    )
    fn = jax.jit(sharded)
    return _cache_put(key, fn)


def sharded_stamped_verify(mesh: Mesh, n_commits: int, msg_max: int):
    """sharded_fused_verify's DELTA twin: each device stamps its own
    rows slice from the per-row deltas before the cached kernel runs.

    The staged deltas shard exactly like the rows they expand into —
    sig/ts shard on the row axis, flags on its only axis — because
    fused.shard_positions already laid row `d*B_loc + s*M_s + v_loc`
    out as device d's stride-s slot for local validator v_loc: the
    stamping prologue's `row mod pub_raw_len -> validator` gather then
    resolves against the device's OWN (M_s, 32) pub_raw shard with no
    index plumbing, and the expanded slice is bit-identical to the
    single-device oracle's slice (the shardplane prog's stamped
    phase). Template matrices replicate (a few hundred bytes, one
    family per flush); thresholds ride the replicated `threshold` arg
    as ever — the in-rows threshold rows are zeros here (t_rows=1),
    matching the sharded fused path's discard of the in-kernel quorum.

    Memoized per (mesh, n_commits, msg_max): msg_max is a static of
    the stamp trace; the template matrices' bucketed shapes retrace
    under jit's own cache like any other arg shape."""
    from cometbft_tpu.ops import ed25519_cached as ec

    key = ("stamped", _mesh_key(mesh), int(n_commits), int(msg_max))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    axis = mesh.axis_names[0]

    def step(sig, ts, flags, pre_mat, pre_len, suf_mat, suf_len,
             ts_tag, pub_raw, tab, ok, power5, base, threshold):
        thr0 = jax.numpy.zeros((1, ek.TALLY_LIMBS), jax.numpy.int32)
        rows = ec._stamp_rows_core(
            sig, ts, flags, pre_mat, pre_len, suf_mat, suf_len,
            ts_tag, pub_raw, thr0, msg_max=msg_max, t_rows=1)
        valid, local, _ = ec._verify_tally_cached.__wrapped__(
            rows, tab, ok, power5, base, n_commits
        )
        total = _carry_tally(jax.lax.psum(local, axis))
        quorum = ek.quorum_core(total, threshold)
        return valid, total, quorum

    sharded = _smap(
        step,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis),
                  P(), P(), P(), P(), P(),
                  P(axis, None), P(axis, None), P(axis), P(axis, None),
                  P(), P()),
        out_specs=(P(axis), P(), P()),
        unchecked=True,
    )
    fn = jax.jit(sharded)
    return _cache_put(key, fn)


def sharded_stamp_rows(mesh: Mesh, msg_max: int):
    """Test/oracle step: ONLY the per-shard stamping prologue, rows
    gathered back lane-sharded — so the shardplane prog can assert the
    per-device stamped slices bit-match the single-device expansion
    without running the verify kernel."""
    from cometbft_tpu.ops import ed25519_cached as ec

    key = ("stamp-rows", _mesh_key(mesh), int(msg_max))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    axis = mesh.axis_names[0]

    def step(sig, ts, flags, pre_mat, pre_len, suf_mat, suf_len,
             ts_tag, pub_raw):
        thr0 = jax.numpy.zeros((1, ek.TALLY_LIMBS), jax.numpy.int32)
        return ec._stamp_rows_core(
            sig, ts, flags, pre_mat, pre_len, suf_mat, suf_len,
            ts_tag, pub_raw, thr0, msg_max=msg_max, t_rows=1)

    sharded = _smap(
        step,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis),
                  P(), P(), P(), P(), P(), P(axis, None)),
        out_specs=P(None, axis),
        unchecked=True,
    )
    fn = jax.jit(sharded)
    return _cache_put(key, fn)
