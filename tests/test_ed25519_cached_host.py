"""Host-side bookkeeping of the cached-valset path (runs on CPU).

The kernel itself is TPU-gated (see test_ed25519_cached.py); everything
here exercises the table-cache logic WITHOUT invoking the Pallas
kernel: cache-key injectivity, near-miss digest deltas, packed-row
layout, power bookkeeping, and the churn budget fallback.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_ref as ed
from cometbft_tpu.ops import ed25519_cached as ec
from cometbft_tpu.ops import ed25519_kernel as ek


def pubs_n(n, tag=1):
    return [ed.pubkey_from_seed(bytes([tag, i % 251]) + b"\x13" * 30)
            for i in range(n)]


def test_cache_key_injective_over_lengths():
    a = ec._cache_key([b"", b"\x00" * 32], None)
    b = ec._cache_key([b"\x00" * 32, b""], None)
    assert a != b
    assert ec._cache_key([b"k"], [5]) != ec._cache_key([b"k"], [6])


def test_pubs_host_delta_detection():
    """The near-miss delta scan compares FULL pubkey bytes (the digest
    comparison it replaced was birthday-collidable at 2^32 work —
    round-5 advisory high)."""
    pubs = pubs_n(130)
    h1 = ec._pubs_host(pubs, 256)
    pubs2 = list(pubs)
    pubs2[77] = ed.pubkey_from_seed(b"\x99" * 32)
    h2 = ec._pubs_host(pubs2, 256)
    assert [i for i in range(256) if h1[i] != h2[i]] == [77]
    # padding slots are empty and equal
    assert h1[255] == b"" and len(h1) == 256


def test_pack_rows_layout():
    """The compact row layout round-trips: R limbs, s bytes, h nibbles,
    flags, thresholds land where the kernel expects them."""
    rng = np.random.default_rng(3)
    n, pad = 5, 128
    pubs = pubs_n(n)
    msgs = [b"m%d" % i for i in range(n)]
    seeds = [bytes([1, i % 251]) + b"\x13" * 30 for i in range(n)]
    sigs = [ed.sign(s, m) for s, m in zip(seeds, msgs)]
    pb = ek.pack_batch(pubs, msgs, sigs, pad_to=pad)
    counted = np.zeros(pad, np.bool_)
    counted[:n] = True
    cids = np.zeros(pad, np.int32)
    thresh = ek.threshold_limbs(1234567890123, 1)
    rows = ec.pack_rows_cached(pb, counted, cids, thresh)
    assert rows.shape[0] == ec.V_THRESH + 1
    # R y limbs round-trip from the packed pairs
    ry = np.asarray(pb.ry, np.int64)
    packed = rows[ec.V_RY:ec.V_RY + 10]
    lo, hi = packed & ((1 << 13) - 1), packed >> 13
    np.testing.assert_array_equal(lo.T, ry[:, :10])
    np.testing.assert_array_equal(hi.T, ry[:, 10:])
    # flags: precheck bit set only for real rows; counted bit matches
    flags = rows[ec.V_FLAGS]
    assert ((flags[:n] >> 1) & 1).all()
    assert not ((flags[n:] >> 1) & 1).any()
    assert (((flags >> 2) & 1) == counted.astype(np.int32)).all()
    # threshold limbs recoverable
    tv = rows[ec.V_THRESH:].reshape(-1)[: ek.TALLY_LIMBS]
    assert ek.tally_to_int(tv) == 1234567890123


def test_update_table_budget_errors():
    """Deltas beyond UPDATE_PAD raise ValueError (table_for_pubs turns
    that into a full rebuild) and out-of-range indices are rejected."""
    t = ec.ValsetTable(None, None, None, 256,
                       ec._pubs_host([], 256),
                       np.zeros(256, np.int64))
    with pytest.raises(ValueError):
        ec.update_table(t, [(300, b"\x00" * 32)])
    too_many = [(i, b"\x00" * 32) for i in range(ec.UPDATE_PAD + 1)]
    with pytest.raises(ValueError):
        ec.update_table(t, too_many)
    # power-only deltas on top of key changes count against the budget
    changes = [(i, b"\x00" * 32) for i in range(ec.UPDATE_PAD)]
    with pytest.raises(ValueError):
        ec.update_table(t, changes, {ec.UPDATE_PAD + 1: 5})
    # no-op delta returns the same table object
    assert ec.update_table(t, [], None) is t


def test_pad_rows_buckets():
    assert ec.pad_rows(1) == 128
    assert ec.pad_rows(129) == 256
    assert ec.pad_rows(5000) == 6144
    assert ec.pad_rows(10000) == 10240
    with pytest.raises(ValueError):
        ec.pad_rows(70000)


# -- incremental warming (warm_incremental) --------------------------------
# Each test swaps in a private table cache so the shared process-global
# one (other test files may have populated it) can never donate or
# receive a near-miss base.

def _fake_table(pubs, padded=128):
    return ec.ValsetTable(None, None, None, padded,
                          ec._pubs_host(pubs, padded),
                          np.zeros(padded, np.int64))


def _private_cache(monkeypatch):
    from cometbft_tpu.ops import table_cache as tc

    cache = tc.BoundedLRU("tables", 8, size_fn=tc.default_size)
    monkeypatch.setattr(ec, "_TABLE_CACHE", cache)
    return cache


def test_warm_incremental_no_base_returns_false(monkeypatch):
    cache = _private_cache(monkeypatch)
    calls = []
    monkeypatch.setattr(ec, "update_table",
                        lambda *a, **k: calls.append(a))
    assert ec.warm_incremental(tuple(pubs_n(4, tag=101))) is False
    assert calls == [] and len(cache) == 0
    # a base of a DIFFERENT padded size is not eligible either
    with ec._TABLE_LOCK:
        cache.put(b"base256", _fake_table(pubs_n(200, tag=102), 256))
    assert ec.warm_incremental(tuple(pubs_n(4, tag=101))) is False
    assert calls == []


def test_warm_incremental_patches_eligible_base(monkeypatch):
    cache = _private_cache(monkeypatch)
    base_pubs = pubs_n(4, tag=103)
    target_pubs = tuple(pubs_n(4, tag=104))
    with ec._TABLE_LOCK:
        cache.put(b"base", _fake_table(base_pubs))
        h0 = dict(ec._TABLE_STATS)
    marker = _fake_table(target_pubs)
    seen = []

    def fake_update(cand, changes, pw_map=None):
        seen.append((len(changes), dict(pw_map or {})))
        return marker

    monkeypatch.setattr(ec, "update_table", fake_update)
    assert ec.warm_incremental(target_pubs) is True
    # the 4 changed slots (padding rows identical) rode the update,
    # with no power rewrites
    assert seen == [(4, {})]
    key = ec._memo_cache_key(target_pubs, None)
    with ec._TABLE_LOCK:
        assert cache.peek(key) is marker
        h1 = dict(ec._TABLE_STATS)
    # a warm is neither a hit nor a miss, but IS an incremental patch
    assert h1["hits"] == h0["hits"]
    assert h1["misses"] == h0["misses"]
    assert h1["incremental_patches"] == h0["incremental_patches"] + 1
    # second warm: already cached, no second update_table call
    assert ec.warm_incremental(target_pubs) is True
    assert len(seen) == 1


def test_warm_incremental_budget_overflow_returns_false(monkeypatch):
    cache = _private_cache(monkeypatch)
    with ec._TABLE_LOCK:
        cache.put(b"base", _fake_table(pubs_n(4, tag=105)))

    def refuse(*a, **k):
        raise ValueError("delta over budget")

    monkeypatch.setattr(ec, "update_table", refuse)
    with ec._TABLE_LOCK:
        h0 = dict(ec._TABLE_STATS)
    assert ec.warm_incremental(tuple(pubs_n(4, tag=106))) is False
    with ec._TABLE_LOCK:
        h1 = dict(ec._TABLE_STATS)
    assert h1["incremental_patches"] == h0["incremental_patches"]


# -- a set of cached keys, re-powered or re-sorted (the rekey path) --------
# A stub _build_core gives each key fake columns computed from its bytes
# alone (as the real build's are), so a slot served from the wrong base
# slot shows, and the real build's minutes of CPU compile are not paid.

def _stub_core(ay, asign):
    ay = np.asarray(ay, np.int64)
    seed = (ay * np.arange(1, ay.shape[1] + 1)).sum(1) + 7 * np.asarray(
        asign, np.int64)
    width = ec.NJ * ec.NENT * ec.ROWS_PER_ENT
    tbl = (seed[:, None] * 31 + np.arange(width)[None, :]) % 8191
    ok = seed % 5 != 0
    return (jnp.asarray(tbl.reshape(-1, ec.ROWS_PER_ENT), jnp.int32),
            jnp.asarray(ok))


def _forbid(*a, **k):
    raise AssertionError("a curve column was computed")


def _indexed_cache(monkeypatch, capacity=8):
    """A fresh indexed table cache over the stub builder, with the churn
    programs of 256-slot tables counted as run (their warm has a test of
    its own: the stub cannot be traced into the patch program)."""
    from cometbft_tpu.ops import table_cache as tc

    cache = tc.BoundedLRU("tables", capacity, size_fn=tc.default_size,
                          index_fn=tc.table_key_set)
    monkeypatch.setattr(ec, "_TABLE_CACHE", cache)
    monkeypatch.setattr(ec, "_build_core", _stub_core)
    monkeypatch.setattr(ec, "_CHURN_WARMED", {256})
    return cache


def _stats():
    with ec._TABLE_LOCK:
        return dict(ec._TABLE_STATS)


# 130 validators (a 256-slot table) in upstream's order: power down
BASE_POWERS = [1000 - 3 * i for i in range(130)]


def _churned(case, pubs):
    """(keys, powers) of the next set: powers moved with the order kept,
    or moved so that upstream's sort by power re-seats validators."""
    powers = list(BASE_POWERS)
    if case == "same_order":
        powers[4] += 1
        powers[90] -= 2
        return list(pubs), powers
    powers[120] = 2000   # to the top
    powers[3] = 1        # to the bottom
    powers[60], powers[61] = powers[61], powers[60]
    order = sorted(range(len(pubs)), key=lambda i: -powers[i])
    return [pubs[i] for i in order], [powers[i] for i in order]


@pytest.fixture
def rekeyed(monkeypatch, request):
    """A cached base table, then the churned set's lookup with every
    curve-column path forbidden: (case, base, served, keys, powers,
    stats before, stats after)."""
    cache = _indexed_cache(monkeypatch)
    pubs = pubs_n(130, tag=107)
    base, warm = ec.table_for_pubs_info(list(pubs), BASE_POWERS)
    assert not warm and len(cache) == 1
    keys, powers = _churned(request.param, pubs)
    paths = ("build_table", "update_table", "_update_core")
    kept = {name: getattr(ec, name) for name in paths}
    for name in paths:
        monkeypatch.setattr(ec, name, _forbid)
    s0 = _stats()
    served, warm = ec.table_for_pubs_info(keys, powers)
    assert not warm  # not a straight hit: powers were uploaded
    for name, fn in kept.items():
        monkeypatch.setattr(ec, name, fn)
    return request.param, base, served, keys, powers, s0, _stats()


CASES = ["same_order", "resorted"]


@pytest.mark.parametrize("rekeyed", CASES, indirect=True)
def test_rekey_serves_a_set_of_cached_keys_without_columns(rekeyed):
    _, base, served, keys, powers, _, _ = rekeyed
    assert served is not base
    assert served.pubs_host[:130] == tuple(keys)
    assert list(served.powers_host[:130]) == powers


@pytest.mark.parametrize("rekeyed", CASES, indirect=True)
def test_rekey_counts_rekeyed_not_misses(rekeyed):
    _, _, served, keys, powers, s0, s1 = rekeyed
    assert s1["rekeyed"] == s0["rekeyed"] + 1
    assert s1["misses"] == s0["misses"]
    assert s1["incremental_patches"] == s0["incremental_patches"]
    # cached under the exact content key: the next lookup is a hit
    t, warm = ec.table_for_pubs_info(keys, powers)
    assert warm and t is served
    assert _stats()["hits"] == s1["hits"] + 1


@pytest.mark.parametrize("rekeyed", CASES, indirect=True)
def test_rekey_is_byte_identical_to_a_cold_build(rekeyed):
    _, _, served, keys, powers, _, _ = rekeyed
    cold = ec.build_table(keys, powers)
    assert served.n_vals == cold.n_vals == 256
    for name in ("tab", "ok", "power5", "pub_raw"):
        a, b = np.asarray(getattr(served, name)), np.asarray(
            getattr(cold, name))
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert served.pubs_host == cold.pubs_host
    np.testing.assert_array_equal(served.powers_host, cold.powers_host)
    assert served.powers_host.dtype == cold.powers_host.dtype


@pytest.mark.parametrize("rekeyed", ["same_order"], indirect=True)
def test_rekey_in_the_same_order_shares_the_base_columns(rekeyed):
    _, base, served, _, _, _, _ = rekeyed
    assert served.tab is base.tab
    assert served.ok is base.ok and served.pub_raw is base.pub_raw
    assert served.power5 is not base.power5


def test_rekey_not_taken_where_a_key_was_replaced(monkeypatch):
    _indexed_cache(monkeypatch)
    pubs = pubs_n(130, tag=108)
    ec.table_for_pubs_info(list(pubs), BASE_POWERS)
    monkeypatch.setattr(ec, "_rekey", _forbid)
    keys = list(pubs)
    keys[77] = ed.pubkey_from_seed(b"\x98" * 32)
    seen = []
    marker = _fake_table(keys, 256)

    def fake_update(cand, changes, pw_map=None):
        seen.append(changes)
        return marker

    monkeypatch.setattr(ec, "update_table", fake_update)
    s0 = _stats()
    t, _ = ec.table_for_pubs_info(keys, BASE_POWERS)
    s1 = _stats()
    assert s1["rekeyed"] == s0["rekeyed"]
    assert s1["misses"] == s0["misses"] + 1
    assert s1["incremental_patches"] == s0["incremental_patches"] + 1
    assert t is marker and seen == [[(77, keys[77])]]


def test_rekey_base_evicted_is_no_longer_found(monkeypatch):
    from cometbft_tpu.ops import table_cache as tc

    cache = _indexed_cache(monkeypatch, capacity=2)
    pubs = pubs_n(130, tag=109)
    ec.table_for_pubs_info(list(pubs), BASE_POWERS)
    for tag in (110, 111):  # two other key sets push the base out
        ec.table_for_pubs_info(pubs_n(130, tag=tag), BASE_POWERS)
    keys, powers = _churned("resorted", pubs)
    assert cache.find(tc.key_set(ec._pubs_host(keys, 256))) is None
    monkeypatch.setattr(ec, "_rekey", _forbid)
    s0 = _stats()
    ec.table_for_pubs_info(keys, powers)
    s1 = _stats()
    assert s1["rekeyed"] == s0["rekeyed"]
    assert s1["misses"] == s0["misses"] + 1


def test_rekey_lookup_records_its_path(monkeypatch):
    """Each table the cache lacks is one `table.make` stage whose `path`
    says how it was made; a hit records nothing."""
    from cometbft_tpu.libs import tracing

    _indexed_cache(monkeypatch)
    pubs = pubs_n(130, tag=112)
    n0 = len(tracing.stage_records())
    ec.table_for_pubs_info(list(pubs), BASE_POWERS)
    keys, powers = _churned("resorted", pubs)
    ec.table_for_pubs_info(keys, powers)
    ec.table_for_pubs_info(keys, powers)  # a hit
    recs = [r for r in tracing.stage_records()[n0:]
            if r[0] == "table.make"]
    assert [(r[4]["n"], r[4]["path"]) for r in recs] == [
        (256, "built"), (256, "rekeyed")]


def test_warm_incremental_rekeys_a_set_of_cached_keys(monkeypatch):
    _indexed_cache(monkeypatch)
    pubs = pubs_n(130, tag=113)
    ec.table_for_pubs_info(list(pubs), BASE_POWERS)
    keys, powers = _churned("resorted", pubs)
    for name in ("build_table", "update_table", "_update_core"):
        monkeypatch.setattr(ec, name, _forbid)
    s0 = _stats()
    assert ec.warm_incremental(tuple(keys), tuple(powers)) is True
    s1 = _stats()
    assert s1["rekeyed"] == s0["rekeyed"] + 1
    assert s1["misses"] == s0["misses"]
    assert ec.table_for_pubs_info(tuple(keys), tuple(powers))[1]


def test_rekey_warms_the_churn_programs_once_a_size(monkeypatch):
    """The first set a padded size serves by rekey runs the programs the
    chain's later sets need, the re-sort's gather and a seat change's
    patch, so neither compiles deep into the stream; a later rekey at
    that size runs only its own gather."""
    _indexed_cache(monkeypatch)
    monkeypatch.setattr(ec, "_CHURN_WARMED", set())
    calls = []
    gather = ec._permute_slots

    def spy_gather(*args):
        calls.append(("gather", args[3].shape))
        return gather(*args)

    monkeypatch.setattr(ec, "_permute_slots", spy_gather)
    monkeypatch.setattr(ec, "update_table", lambda table, changes, pw=None:
                        calls.append(("patch", table, changes)))
    pubs = pubs_n(130, tag=117)
    base, _ = ec.table_for_pubs_info(list(pubs), BASE_POWERS)
    assert calls == []  # a build warms nothing
    ec.table_for_pubs_info(*_churned("same_order", pubs))
    assert calls == [("gather", (256,)), ("patch", base, [(0, pubs[0])])]
    del calls[:]
    ec.table_for_pubs_info(*_churned("resorted", pubs))
    assert calls == [("gather", (256,))]
    assert _stats()["rekeyed"] >= 2


@pytest.mark.parametrize("drop", ["pop", "evict"])
def test_key_set_index_passes_to_the_older_table_of_one_key_set(drop):
    """The index names the NEWEST table inserted under a key set; when
    that one leaves, an older table of the same keys takes its place,
    and the index empties with the last of them."""
    from cometbft_tpu.ops import table_cache as tc

    cache = tc.BoundedLRU("tables", 3, index_fn=tc.table_key_set)
    keys = tuple(pubs_n(3, tag=114))
    old, new = _fake_table(keys), _fake_table(keys[::-1])
    other = _fake_table(pubs_n(3, tag=115))
    ks = tc.key_set(old.pubs_host)
    assert ks == tc.key_set(new.pubs_host)
    cache.put(b"old", old)
    cache.put(b"new", new)
    assert cache.find(ks) is new
    cache.get(b"old")  # used since: "new" is now the least recent
    if drop == "pop":
        cache.pop(b"new")
    else:
        cache.put(b"other", other)
        cache.put(b"other2", _fake_table(pubs_n(3, tag=116)))
        assert b"new" not in cache
    assert cache.find(ks) is old
    cache.pop(b"old")
    assert cache.find(ks) is None
    assert cache.find(tc.key_set(other.pubs_host)) is (
        other if drop == "evict" else None)
