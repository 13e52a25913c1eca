"""Device-fused flush: cached valset table + in-pass quorum tally.

When a flush's submissions all come from quorum groups backed by one
shared validator set (the gossiped-vote burst shape: many validators'
precommits for the same height, grouped per candidate block), the plane
skips the generic grouped dispatch and reuses the cached-valset window
table (ops.ed25519_cached): each signature is scattered to device row
``stride*M + validator_index`` so the kernel's static BlockSpec table
fetch applies, and the per-group voting-power tally is computed by the
SAME device pass (ed25519_kernel.tally_core) that verifies the
signatures — the quorum bit a VoteSet waits on is a kernel output, not
a host reduction.

Multichip ([verify_plane] mesh knobs): when the plane is configured
with a >1-device mesh, plan_fused lays the scattered rows out in
per-device blocks (validator v of stride s lands at
``d*B_loc + s*M_s + (v mod M_s)`` with d = v // M_s — shard_positions
is the one home of that math), the valset window table is
device-resident PER SHARD (ed25519_cached.sharded_table_for_pubs), and
dispatch_fused launches parallel/mesh.sharded_fused_verify: each chip
verifies its validators' signatures against its local table shard and
the voting-power tally psum-reduces ON DEVICE, so the quorum bit is
still a kernel output — one cross-chip pass for a 100k-validator
commit (a single chip's table budget caps at 65536 validator slots).

Pipelined mesh halves ([verify_plane] pipeline_flights): the plane's
flight deck keeps up to K flushes airborne at once on DISJOINT
sub-meshes. half_meshes splits the flush mesh into two halves on the
same device-prefix seam effective_mesh clamps through, and plan_fused
carries the size-aware fan-out policy: a small flush rides the free
half (its psum reduces over that half alone — every one of its rows
and its whole table shard set live there, so the quorum bit is exact),
while a flush past the half's per-device budget (or over the
half_mesh_rows knob) takes the full mesh and sets ``drain_first`` so
the dispatcher lands the airborne deck before dispatching it.
plan_ready is the non-blocking landing probe that lets the deck settle
flights out of order; plan_wait is its blocking twin, on which the
plane's lander thread sits so that the dispatcher is woken when a
flight's results are ready rather than a poll slice later.

This is the plane's TPU specialization; it is bypassed on CPU backends
(the interpret-mode cached kernel costs minutes of compile) where the
generic host path in plane._verify_rows serves the same semantics.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from cometbft_tpu.libs.deviceledger import rows_bucket

MAX_FUSED_ROWS = 65536  # per-device rows budget (B_loc when sharded)

# Shape buckets. The stride count and the group count are shapes of the
# jitted flush (B = n_dev * strides * M rows, a (groups, limbs)
# threshold matrix), and every new shape costs a trace, a Mosaic
# lowering and a compile of tens of seconds on the dispatcher thread —
# longer than a vote waits for its verdict. Rounding both up keeps a
# node to the few programs its start-up compile (VerifyPlane.prime)
# covers: padded strides are dead rows, padded groups have a threshold
# no tally reaches.
MIN_FUSED_COMMITS = 4

# Test seam: tier-1 has no accelerator, so the sharded plumbing is
# proven on a forced multi-device CPU host with the expensive kernels
# stubbed (tests/test_zshardplane_smoke.py flips this in a subprocess).
# Production CPU backends stay on the host path — interpret-mode Pallas
# costs minutes per compile.
ALLOW_CPU_FUSED = False

# Device-side sign-bytes stamping (ISSUE 19): template-eligible flushes
# ship (device-resident template, per-row deltas) and the stamping
# prologue rebuilds the packed rows on device. Module-level toggle +
# setter (the validation._TEMPLATE_PACK pattern) so the config plumbs
# it and the differential tests force either path.
DEVICE_STAMP = True


def set_device_stamping(on: bool) -> None:
    global DEVICE_STAMP
    DEVICE_STAMP = bool(on)


class _Plan:
    """A fully host-side staged fused flush: everything up to (but not
    including) the device dispatch. Splitting plan from execution lets
    the plane consume a circuit-breaker probe slot only when a device
    attempt actually happens (an ineligible flush must not burn the
    breaker's half-open probe). dispatch_fused() then launches the
    kernel WITHOUT fetching (pending holds the in-flight device
    arrays), and collect_fused() blocks for the verdicts — the split
    that lets the plane pack flush k+1 while flush k flies."""

    __slots__ = ("rows", "pos", "batch", "groups", "sub_gid",
                 "counted_pos", "n_commits", "pubs_v", "powers_v",
                 "pending", "mesh", "n_dev", "thresh", "devs",
                 "drain_first", "warm", "util",
                 # device-stamped delta staging: `stamped` selects the
                 # path, `delta` holds the (sig, ts, flags) staging
                 # buffers, `sites` the StampSites in template-id
                 # order, `delta_bytes` the staged delta footprint
                 # (rows is None on this path)
                 "stamped", "delta", "sites", "delta_bytes")


def _eligible(batch):
    """All submissions carry validator indices, ed25519 keys only, and
    share ONE valset-backed group family; returns (valset_pubs,
    valset_powers) or None."""
    pubs0 = powers0 = None
    for sub in batch:
        g = sub.group
        if g is None or sub.vidx is None or g.valset_pubs is None:
            return None
        if len(sub.vidx) != len(sub.rows):
            return None
        # the cached window table is ed25519-only; secp/sr valsets take
        # the generic grouped dispatch
        if any(r[0].key_type != "ed25519" or len(r[0].data) != 32
               for r in sub.rows):
            return None
        if pubs0 is None:
            pubs0, powers0 = g.valset_pubs, g.valset_powers
        elif g.valset_pubs is not pubs0 and g.valset_pubs != pubs0:
            return None
    if pubs0 is None:
        return None
    return pubs0, powers0


def _stamp_sites(stamp_meta, row_gid, max_sites: int):
    """Template-id assignment + device-stamp eligibility for a flush.

    Returns (StampSites in template-id order, per-row template ids) or
    None when the flush must fall back to host packing: a row without
    stamp metadata (non-vote rows — e.g. extension rows), timestamp
    words outside the staged int32 layout, more than the
    for-block/for-nil template pair among one commit's rows, or more
    template families than the staged flags' 8-bit id field."""
    ids: List[int] = []
    sites: List[object] = []
    idx_of: Dict[object, int] = {}
    per_gid: Dict[int, set] = {}
    for st, gid in zip(stamp_meta, row_gid):
        if st is None:
            return None
        tpl, secs, nanos = st
        if not (-2**31 <= nanos < 2**31 and -2**63 <= secs < 2**63):
            return None
        site = tpl.stamp_site()
        key = site.key
        tid = idx_of.get(key)
        if tid is None:
            if len(sites) >= max_sites:
                return None
            tid = idx_of[key] = len(sites)
            sites.append(site)
        gset = per_gid.setdefault(gid, set())
        gset.add(key)
        if len(gset) > 2:
            return None  # mixed block_ids past the for-block/nil pair
        ids.append(tid)
    return tuple(sites), ids


def shard_positions(vidx, strides, m_shard: int,
                    n_strides: int) -> np.ndarray:
    """Row positions for the fused flush layout, single- or multi-chip.

    Validator v of stride s lands at ``d*B_loc + s*m_shard +
    (v mod m_shard)`` where d = v // m_shard owns the validator's table
    shard and B_loc = n_strides*m_shard is one device's slice width.
    With one device m_shard is the whole padded valset and this
    degenerates to the classic ``s*M + v``. Pure numpy, no jax needed."""
    v = np.asarray(vidx, np.int64)
    s = np.asarray(strides, np.int64)
    b_loc = n_strides * m_shard
    return (v // m_shard) * b_loc + s * m_shard + (v % m_shard)


# the plane's flush mesh, memoized per requested device count (mesh
# identity feeds the step/table memos downstream — a fresh Mesh per
# flush would defeat them)
_MESH_MEMO: dict = {}


def plane_mesh(devices: int):
    """Resolve the verify plane's flush mesh: 0 = all local devices,
    N caps at the first N. Returns None when fewer than 2 devices are
    usable — single-device dispatch is strictly better then."""
    import jax

    from cometbft_tpu.parallel import mesh as pm

    devs = jax.devices()
    n = len(devs) if not devices else min(int(devices), len(devs))
    if n < 2:
        return None
    m = _MESH_MEMO.get(n)
    if m is None:
        m = _MESH_MEMO[n] = pm.make_mesh(devs[:n])
    return m


# sub-meshes over a mesh's devices, memoized by the exact device tuple
# (effective_mesh clamps through prefixes; half_meshes slices the same
# memo into the deck's disjoint halves)
_SUBMESH_MEMO: dict = {}


def _sub_mesh_devs(devs: tuple):
    from cometbft_tpu.parallel import mesh as pm

    m = _SUBMESH_MEMO.get(devs)
    if m is None:
        m = _SUBMESH_MEMO[devs] = pm.make_mesh(list(devs))
    return m


def _sub_mesh(mesh, n_eff: int):
    return _sub_mesh_devs(tuple(mesh.devices.flat)[:n_eff])


def half_meshes(mesh) -> list:
    """The flush mesh split into two DISJOINT halves for the pipelined
    flight deck: lower half = device prefix, upper half = the rest.
    Each half needs >= 2 devices to run the sharded fused program
    pinned to its own chips, so meshes under 4 devices return [] and
    the deck degrades to classic single-flight dispatch."""
    if mesh is None or mesh.devices.size < 4:
        return []
    devs = tuple(mesh.devices.flat)
    mid = len(devs) // 2
    return [_sub_mesh_devs(devs[:mid]), _sub_mesh_devs(devs[mid:])]


def effective_mesh(mesh, nvals: int):
    """Clamp a flush mesh to the devices this valset actually fills.

    shard_stride rounds the per-shard slice up to a table_pad bucket,
    and the coarse buckets can leave trailing shards EMPTY — e.g. 10k
    validators over 8 devices takes a 4096-slot stride, so devices 3-7
    would stage, transfer, and verify pure padding on every flush with
    no correctness benefit. Shrinks the fan-out until every shard
    holds validators (fixpoint of n_eff = ceil(nvals / m_s)).

    Returns (mesh-or-None, n_dev, m_shard); None means single-device
    dispatch is strictly better (the whole valset fits one stride).
    Raises ValueError when the valset exceeds even the full mesh's
    table budget."""
    from cometbft_tpu.ops import ed25519_cached as ec

    if mesh is None:
        return None, 1, ec.shard_stride(nvals, 1)
    n_eff = int(mesh.devices.size)
    while True:
        m_s = ec.shard_stride(nvals, n_eff)
        need = -(-max(nvals, 1) // m_s)
        if need >= n_eff:
            break
        n_eff = need
    if n_eff < 2:
        return None, 1, ec.shard_stride(nvals, 1)
    if n_eff < mesh.devices.size:
        mesh = _sub_mesh(mesh, n_eff)
    return mesh, n_eff, m_s


def plan_fused(batch, pool=None, mesh=None, half=None,
               half_max_rows: int = 0) -> Optional[_Plan]:
    """Host-side staging of the fused cached-table dispatch for a
    flush. Returns a _Plan, or None when the flush shape is ineligible
    — the caller then runs the generic grouped path. No device work
    happens here (dispatch_fused/collect_fused do that, under the
    breaker). `mesh` (a >1-device parallel.mesh Mesh) selects the
    sharded cross-chip layout; None is the single-device path.

    `half` is the flight deck's fan-out offer: a free sub-mesh half
    the flush should prefer so it can fly while the other half carries
    an airborne flight. The size-aware policy lives here because only
    the plan knows the flush's true shape: the half is taken when the
    valset and stride count fit its per-device budget AND the flush is
    under `half_max_rows` (0 = budget-only); otherwise the flush takes
    the full `mesh` and the plan's ``drain_first`` flag tells the
    dispatcher to land the airborne deck before dispatching it."""
    import jax

    if jax.default_backend() == "cpu" and not ALLOW_CPU_FUSED:
        return None
    valset = _eligible(batch)
    if valset is None:
        return None
    pubs_v, powers_v = valset
    nvals = len(pubs_v)

    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.ops.ed25519_pallas import _PB
    from cometbft_tpu.types import canonical

    # slot assignment: first free stride wins (a validator's vote and
    # its extension land in different strides); positions are computed
    # AFTER the walk — the per-device slice width depends on the final
    # stride count when the valset is sharded
    pubs: List[bytes] = []
    msgs: List[bytes] = []
    sigs: List[bytes] = []
    row_v: List[int] = []
    row_s: List[int] = []
    row_gid: List[int] = []
    stamp_meta: List[Optional[tuple]] = []  # (template, secs, nanos)
    counted_ridx: List[Optional[int]] = []  # per submission: row index
    occupied: List[set] = []
    groups: List[object] = []
    gid_of: Dict[int, int] = {}
    sub_gid: List[int] = []
    for sub in batch:
        g = sub.group
        gid = gid_of.get(id(g))
        if gid is None:
            gid = gid_of[id(g)] = len(groups)
            groups.append(g)
        sub_gid.append(gid)
        cidx = None
        stamps = getattr(sub, "stamp", None)
        for k, ((pub, msg, sig), v) in enumerate(zip(sub.rows, sub.vidx)):
            if not (0 <= v < nvals) or pub.data != pubs_v[v] \
                    or len(sig) != 64:
                return None  # wrong key/slot claim: generic path decides
            s = 0
            while s < len(occupied) and v in occupied[s]:
                s += 1
            if s == len(occupied):
                occupied.append(set())
            occupied[s].add(v)
            pubs.append(pub.data)
            msgs.append(msg)
            sigs.append(sig)
            row_v.append(v)
            row_s.append(s)
            row_gid.append(gid)
            stamp_meta.append(stamps[k] if stamps is not None
                              and k < len(stamps) else None)
            if k == 0 and sub.counted:
                if sub.power != powers_v[v]:
                    return None  # tally rides the table's power column
                cidx = len(row_v) - 1
        counted_ridx.append(cidx)
    n = len(pubs)
    n_strides = len(occupied)
    if n == 0:
        return None

    # fan-out policy. The rows budget is PER DEVICE: each chip runs
    # the kernel on its B/n_dev slice, so a sharded flush scales the
    # cap with the mesh — a half offers half the budget at half the
    # dispatch footprint. effective_mesh clamps either choice to the
    # devices the valset actually fills.
    def _fit(m):
        m2, nd, ms = effective_mesh(m, nvals)
        if n_strides * ms > MAX_FUSED_ROWS:
            raise ValueError("flush over the per-device rows budget")
        return m2, nd, ms

    chosen = None
    took_full = False
    if half is not None and (not half_max_rows or n <= half_max_rows):
        try:
            chosen = _fit(half)
        except ValueError:
            chosen = None  # giant flush: the full mesh decides below
    if chosen is None:
        took_full = half is not None
        try:
            chosen = _fit(mesh)
        except ValueError:
            return None  # over even the full mesh's table budget
    mesh, n_dev, M = chosen
    # shape buckets (MIN_FUSED_COMMITS): strides round up to a power of
    # two where the rows budget allows it, else stay exact
    if rows_bucket(n_strides) * M <= MAX_FUSED_ROWS:
        n_strides = rows_bucket(n_strides)
    B = n_dev * n_strides * M

    n_commits = max(MIN_FUSED_COMMITS, rows_bucket(len(groups)))
    pos = shard_positions(row_v, row_s, M, n_strides)
    counted_pos = [None if ci is None else int(pos[ci])
                   for ci in counted_ridx]
    # pinned double-buffered staging: the scatter targets and the final
    # packed rows rotate through persistent host buffers per shape (the
    # CALLER's pool — one writer per key; the plane passes its private
    # pool), so packing flush k+1 never touches the memory flush k is
    # still uploading from
    if pool is None:
        from cometbft_tpu.crypto.batch import staging_pool

        pool = staging_pool()
    thresh = np.zeros((n_commits, ek.TALLY_LIMBS), np.int32)
    thresh[:, -1] = ek.POWER_MASK  # padded group slots: unreachable
    for gid, g in enumerate(groups):
        thresh[gid] = ek.threshold_limbs(max(g.threshold - 1, 0))[0]

    plan = _Plan()
    stamp = (_stamp_sites(stamp_meta, row_gid, ec.MAX_TEMPLATE_SITES)
             if DEVICE_STAMP else None)
    if stamp is not None:
        # device-stamped delta staging: ship 80 B/row — raw signature,
        # (secs_lo, secs_hi, nanos) words, packed flags — and let the
        # device prologue rebuild the packed rows next to the resident
        # template. Three slots (fused.dsig / .dts / .dflags); the pool's
        # zero fill makes unoccupied lanes live=0, which the prologue
        # expands to the same all-zero columns host packing pads with.
        sites, site_ids = stamp
        sec_a = np.fromiter((st[1] for st in stamp_meta), np.int64,
                            count=n)
        nan_a = np.fromiter((st[2] for st in stamp_meta), np.int64,
                            count=n)
        ts_rows = canonical.split_ts_words(sec_a, nan_a)
        fl_rows = np.ones((n,), np.int32)
        fl_rows |= np.asarray(site_ids, np.int32) << 2
        fl_rows |= np.asarray(row_gid, np.int32) << 10
        for ci in counted_ridx:
            if ci is not None:
                fl_rows[ci] |= 2
        dsig = pool.get("fused.dsig", (B, 64), np.uint8)
        dsig[pos] = np.frombuffer(b"".join(sigs), np.uint8) \
            .reshape(n, 64)
        dts = pool.get("fused.dts", (B, 3), np.int32)
        dts[pos] = ts_rows
        dfl = pool.get("fused.dflags", (B,), np.int32)
        dfl[pos] = fl_rows
        plan.rows = None
        plan.stamped = True
        plan.delta = (dsig, dts, dfl)
        plan.sites = sites
        plan.delta_bytes = int(dsig.nbytes + dts.nbytes + dfl.nbytes)
    else:
        # legacy full-row host pack — bit-live as the differential
        # oracle and the fallback for non-template-eligible flushes
        pbd = ek.pack_batch(pubs, msgs, sigs, pad_to=n)
        ry = pool.get("fused.ry", (B, pbd.ry.shape[1]), pbd.ry.dtype)
        ry[pos] = pbd.ry[:n]
        rsign = pool.get("fused.rsign", (B,), np.int32)
        rsign[pos] = np.asarray(pbd.rsign[:n], np.int32)
        sdig = pool.get("fused.sdig", (B, pbd.sdig.shape[1]),
                        pbd.sdig.dtype)
        sdig[pos] = pbd.sdig[:n]
        hdig = pool.get("fused.hdig", (B, pbd.hdig.shape[1]),
                        pbd.hdig.dtype)
        hdig[pos] = pbd.hdig[:n]
        precheck = pool.get("fused.precheck", (B,), np.bool_)
        precheck[pos] = np.asarray(pbd.precheck[:n], np.bool_)
        counted = pool.get("fused.counted", (B,), np.bool_)
        commit_ids = pool.get("fused.cid", (B,), np.int32)
        cur = 0
        for sub, gid, cpos in zip(batch, sub_gid, counted_pos):
            for p in pos[cur:cur + len(sub.rows)]:
                commit_ids[p] = gid
            cur += len(sub.rows)
            if cpos is not None:
                counted[cpos] = True

        pb = _PB(None, None, ry, rsign, sdig, hdig, precheck)
        # sharded: thresholds ride as a separate REPLICATED kernel
        # argument (the in-rows threshold rows would shard into
        # per-device fragments) so the packed rows carry a zero
        # threshold row; single-device keeps packing them into the
        # rows as before
        pack_thresh = None if mesh is not None else thresh
        out = pool.get(
            "fused.rows",
            ec.packed_rows_shape(B, 1 if mesh is not None else n_commits),
            np.int32)
        plan.rows = ec.pack_rows_cached(pb, counted, commit_ids,
                                        pack_thresh, out=out)
        plan.stamped = False
        plan.delta = None
        plan.sites = None
        plan.delta_bytes = 0
    plan.pos = pos
    plan.batch = batch
    plan.groups = groups
    plan.sub_gid = sub_gid
    plan.counted_pos = counted_pos
    plan.n_commits = n_commits
    plan.pubs_v = pubs_v
    plan.powers_v = powers_v
    plan.pending = None
    plan.mesh = mesh
    plan.n_dev = n_dev
    plan.thresh = thresh
    # device ids this flush will occupy (None = single-device): the
    # deck's disjointness bookkeeping and the ledger's dev0 column
    plan.devs = (None if mesh is None
                 else tuple(int(d.id) for d in mesh.devices.flat))
    plan.drain_first = took_full
    # did the dispatch find its valset table cached? (set by
    # dispatch_fused; the plane stamps it into the ledger's warm
    # column so post-rotation cold builds are attributable)
    plan.warm = False
    # rows-x-cost utilization: the fraction of the staged device pass
    # doing real work (n live rows over the B padded slots the kernel
    # sweeps across the whole fan-out) — the ledger's util column: how
    # much of the mesh a flush actually used
    plan.util = round(n / B, 4) if B else 0.0
    return plan


def plan_ready(plan: _Plan) -> bool:
    """Non-blocking landing probe for a dispatched plan: True when
    every in-flight output array is ready to fetch. The deck lands
    ready flights out of order (no head-of-line blocking when flight
    k+1 finishes before flight k); False — including when the runtime
    offers no probe — means the caller falls back to FIFO landing."""
    p = plan.pending
    if p is None:
        return True
    try:
        return all(bool(a.is_ready()) for a in p)
    except Exception:  # noqa: BLE001 - no readiness probe: FIFO lands
        return False


def plan_wait(plan: _Plan) -> None:
    """Blocking twin of plan_ready: returns once every in-flight output
    array of a dispatched plan is ready to fetch (at once where nothing
    is pending). It fetches nothing, and the wait releases the
    interpreter lock. An in-flight device fault may raise out of it;
    the plane's lander thread swallows that, and the fault surfaces in
    collect_fused under the breaker, where it always did."""
    p = plan.pending
    if p is None:
        return
    import jax

    jax.block_until_ready(p)


def plan_h2d_bytes(plan: _Plan) -> int:
    """Bytes this flush stages to the device (the packed rows, or the
    per-row delta buffers when device-stamped; the valset table and
    template are device-resident and upload once per valset/family)."""
    if plan.stamped:
        return int(plan.delta_bytes)
    return int(plan.rows.nbytes)


def dispatch_fused(plan: _Plan) -> None:
    """Launch a staged plan on the device WITHOUT fetching: fetch the
    (device-resident, valset-keyed) window table and enqueue the fused
    verify+tally kernel. Returns as soon as the dispatch is in flight
    (JAX async dispatch) so the caller can pack the next flush. Raises
    on dispatch-time device faults (the caller's breaker handles
    those). The rows buffer is dead once the kernel has read it, and
    the staging pool rotation guarantees the host copy isn't reused
    until this flight lands.

    With a mesh plan, the rows stage straight to the batch
    NamedSharding (one device_put, no host resharding inside the
    jitted step), the table comes from the per-shard device-resident
    cache, and the tally psums across the mesh — the quorum bit is
    still a kernel output."""
    from cometbft_tpu.ops import ed25519_cached as ec

    if plan.mesh is None:
        # pubs_v/powers_v are the QuorumGroup's immutable tuples, so the
        # content-key digest is identity-memoized (no per-flush O(valset)
        # hashing) and a steady-state flush never re-uploads the valset
        table, plan.warm = ec.table_for_pubs_info(plan.pubs_v,
                                                  plan.powers_v)
        if plan.stamped:
            ent = ec.template_entry(plan.sites)
            dsig, dts, dfl = plan.delta
            plan.pending = ec.verify_tally_delta_cached(
                dsig, dts, dfl, ent, table, plan.n_commits, plan.thresh
            )
        else:
            plan.pending = ec.verify_tally_rows_cached(
                plan.rows, table, plan.n_commits
            )
        return
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cometbft_tpu.parallel import mesh as pm

    table, plan.warm = ec.sharded_table_for_pubs_info(
        plan.pubs_v, plan.powers_v, plan.mesh)
    axis = plan.mesh.axis_names[0]
    thresh_d = jax.device_put(
        plan.thresh, NamedSharding(plan.mesh, P(None, None)))
    if plan.stamped:
        # per-shard stamping: each device expands ITS rows slice from
        # its delta slice + the replicated template + its own pub_raw
        # shard — shard_positions already placed every row on the
        # device owning its validator, so the stamped slices bit-match
        # the single-device oracle's slices
        ent = ec.template_entry(plan.sites)
        step = pm.sharded_stamped_verify(plan.mesh, plan.n_commits,
                                         ent.msg_max)
        dsig, dts, dfl = plan.delta
        row_sh = NamedSharding(plan.mesh, P(axis, None))
        lane_sh = NamedSharding(plan.mesh, P(axis))
        repl = NamedSharding(plan.mesh, P())
        plan.pending = step(
            jax.device_put(dsig, row_sh), jax.device_put(dts, row_sh),
            jax.device_put(dfl, lane_sh),
            jax.device_put(ent.pre_mat, repl),
            jax.device_put(ent.pre_len, repl),
            jax.device_put(ent.suf_mat, repl),
            jax.device_put(ent.suf_len, repl),
            jax.device_put(ent.ts_tag, repl),
            table.pub_raw, table.tab, table.ok, table.power5,
            ec.base60_repl(plan.mesh), thresh_d)
        return
    step = pm.sharded_fused_verify(plan.mesh, plan.n_commits)
    rows_d = jax.device_put(
        plan.rows, NamedSharding(plan.mesh, P(None, axis)))
    plan.pending = step(rows_d, table.tab, table.ok, table.power5,
                        ec.base60_repl(plan.mesh), thresh_d)


def collect_fused(plan: _Plan) -> Tuple[List[bool], Dict[object, int]]:
    """Block for a dispatched plan's results and gate the tallies per
    submission. Raises on in-flight device faults.

    Returns (per-row verdicts in flush order, {group: verified power
    tallied by the device this flush})."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    valid, tally, _quorum = plan.pending
    valid = np.asarray(valid)
    tallies_raw = ek.tally_to_int(np.asarray(tally))

    verdicts = [bool(v) for v in valid[plan.pos]]
    tallies: Dict[object, int] = {
        g: int(tallies_raw[gid]) for gid, g in enumerate(plan.groups)
    }
    # submission gating: power counts only when EVERY row of a counted
    # submission verified (a valid vote with a forged extension is
    # rejected by the caller, so its power must not stand in the tally)
    off = 0
    for sub, gid, cpos in zip(plan.batch, plan.sub_gid,
                              plan.counted_pos):
        sl = verdicts[off:off + len(sub.rows)]
        off += len(sub.rows)
        if cpos is not None and sl[0] and not all(sl):
            tallies[plan.groups[gid]] -= sub.power
    return verdicts, tallies
