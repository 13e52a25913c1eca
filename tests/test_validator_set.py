"""ValidatorSet: sorting, lookup, proposer rotation, updates, hashing.

Mirrors types/validator_set_test.go case structure (proposer rotation
frequency proportional to power, update semantics, power cap).
"""
from dataclasses import replace

import pytest

from cometbft_tpu.crypto.keys import PrivKey, PubKey
from cometbft_tpu.types.validator import (
    MAX_TOTAL_VOTING_POWER,
    Validator,
    ValidatorSet,
    ValidatorSetError,
)


def mkvals(powers):
    out = []
    for i, p in enumerate(powers):
        priv = PrivKey.generate(bytes([i + 1]) * 32)
        out.append(Validator(priv.pub_key(), p))
    return out


def test_sorted_by_power_desc_then_address():
    """ValidatorsByVotingPower order (validator_set.go:752-763): power
    desc, address asc tiebreak — fixes the hash and index mapping."""
    vs = ValidatorSet(mkvals([10, 30, 20, 30]))
    powers = [v.voting_power for v in vs.validators]
    assert powers == [30, 30, 20, 10]
    tied = [v.address for v in vs.validators if v.voting_power == 30]
    assert tied == sorted(tied)
    for i, v in enumerate(vs.validators):
        j, got = vs.get_by_address(v.address)
        assert j == i and got is v
    assert vs.get_by_address(b"\x00" * 20) == (-1, None)
    assert vs.get_by_index(99) is None
    assert vs.total_voting_power() == 90


def test_duplicate_address_rejected():
    v = mkvals([5])[0]
    with pytest.raises(ValidatorSetError):
        ValidatorSet([v, Validator(v.pub_key, 7)])


def test_proposer_rotation_proportional():
    """Proposer frequency tracks voting power (validator_set.go docstring:
    priority-queue rotation)."""
    vs = ValidatorSet(mkvals([1, 2, 7]))
    by_addr = {v.address: 0 for v in vs.validators}
    power = {v.address: v.voting_power for v in vs.validators}
    for _ in range(1000):
        p = vs.get_proposer()
        by_addr[p.address] += 1
        vs.increment_proposer_priority(1)
    for a, count in by_addr.items():
        assert abs(count - 100 * power[a]) <= 10, (count, power[a])


def test_total_power_cap():
    with pytest.raises(ValidatorSetError):
        ValidatorSet(mkvals([MAX_TOTAL_VOTING_POWER, 1]))


def test_hash_changes_with_set():
    a = ValidatorSet(mkvals([10, 20]))
    b = ValidatorSet(mkvals([10, 21]))
    assert a.hash() != b.hash()
    assert a.hash() == ValidatorSet(mkvals([10, 20])).hash()
    assert len(a.hash()) == 32


def test_update_with_change_set():
    vals = mkvals([10, 20, 30])
    vs = ValidatorSet(vals)
    h0 = vs.hash()
    # update power of one, remove one, add one
    newv = mkvals([1, 1, 1, 40])[3]
    changes = [
        Validator(vals[0].pub_key, 15),   # update
        Validator(vals[1].pub_key, 0),    # remove
        newv,                              # add
    ]
    vs.update_with_change_set(changes)
    assert vs.total_voting_power() == 15 + 30 + 40
    assert not vs.has_address(vals[1].address)
    assert vs.has_address(newv.address)
    assert vs.hash() != h0
    # removing a non-member fails
    ghost = mkvals([1, 1, 1, 1, 9])[4]
    with pytest.raises(ValidatorSetError):
        vs.update_with_change_set([Validator(ghost.pub_key, 0)])


def test_copy_isolated():
    vs = ValidatorSet(mkvals([5, 5]))
    cp = vs.copy()
    before = [v.proposer_priority for v in cp.validators]
    vs.increment_proposer_priority(3)
    assert [v.proposer_priority for v in cp.validators] == before
    assert [v.proposer_priority for v in vs.validators] != before


def test_state_store_roundtrip_preserves_proposer(tmp_path):
    """ISSUE 3 (found by the simnet kill/restart schedules): the
    persisted valset must carry the SELECTED proposer. Selection
    decrements the winner's priority by the total power, so a reload
    that re-derives "max priority" elects a different validator than
    every live peer — the restarted node then signs proposals its peers
    reject as forged (and would disconnect it for, over real p2p)."""
    from cometbft_tpu.state.state import State, StateStore

    vs = ValidatorSet(mkvals([10, 10, 10, 10]))
    # a few rotation steps so the memoized proposer is NOT the
    # max-priority row
    vs.increment_proposer_priority(1)
    want = vs.get_proposer().address
    assert vs._find_proposer().address != want  # re-derivation differs

    state = State.make_genesis("prop-chain", ValidatorSet(mkvals([10] * 4)))
    from dataclasses import replace

    state = replace(state, validators=vs, next_validators=vs.copy())
    store = StateStore(str(tmp_path / "state.db"))
    store.save(state)
    loaded = store.load()
    assert loaded.validators.get_proposer().address == want
    # the per-height validator history restores it too
    hist = store.load_validators(state.last_block_height + 1)
    assert hist.get_proposer().address == want
    store.close()


# ---------------------------------------------------------------------------
# Epoch-rotation edges (ISSUE 12): the churn path's interaction with
# the proposer memo and the valset-table identity memo.
# ---------------------------------------------------------------------------


def test_proposer_persists_across_rotation_and_restart(tmp_path):
    """The PR 3 proposer-persistence fix, extended through a ROTATION:
    a committee re-election (update_with_change_set) immediately before
    a restart must reload the same selected proposer — rotation clears
    the proposer memo, selection re-runs, and the persisted row must
    carry the NEW selection, not a re-derivation."""
    from dataclasses import replace

    from cometbft_tpu.state.state import State, StateStore

    vals = mkvals([10, 10, 10, 10])
    vs = ValidatorSet(vals)
    # the rotation: one member out, one in, one repowered
    newv = mkvals([1, 1, 1, 1, 25])[4]
    vs.update_with_change_set([
        Validator(vals[2].pub_key, 0),
        Validator(vals[0].pub_key, 14),
        newv,
    ])
    vs.increment_proposer_priority(1)  # select post-rotation proposer
    want = vs.get_proposer().address
    assert vs.has_address(want)  # the selection is a current member

    state = State.make_genesis("rot-chain", ValidatorSet(mkvals([10] * 4)))
    state = replace(state, validators=vs, next_validators=vs.copy())
    store = StateStore(str(tmp_path / "state.db"))
    store.save(state)
    loaded = store.load()
    assert loaded.validators.get_proposer().address == want
    assert sorted(v.address for v in loaded.validators.validators) == \
        sorted(v.address for v in vs.validators)
    store.close()


def test_rotation_invalidates_table_identity_memo(monkeypatch):
    """table_for_valset memoizes by (set identity, validators-list
    identity). BOTH rotation shapes must invalidate it: a
    membership change AND a power-only change (each replaces the
    validators list wholesale in update_with_change_set) — a stale
    table would verify against retired keys or tally stale powers."""
    from cometbft_tpu.ops import ed25519_cached as ec

    tables = []

    def fake_table_for_pubs(pubs, powers=None):
        tables.append((pubs, powers))
        return object()

    monkeypatch.setattr(ec, "table_for_pubs", fake_table_for_pubs)
    ec._VALSET_MEMO.clear()

    vals = mkvals([10, 20, 30])
    vs = ValidatorSet(vals)
    t1 = ec.table_for_valset(vs)
    assert ec.table_for_valset(vs) is t1  # steady state: memo hit

    # power-only change: same membership, new power
    vs.update_with_change_set([Validator(vals[0].pub_key, 11)])
    t2 = ec.table_for_valset(vs)
    assert t2 is not t1
    assert tables[-1][1] != tables[0][1]  # the new powers reached it

    # membership change: one out, one in
    newv = mkvals([1, 1, 1, 40])[3]
    vs.update_with_change_set([Validator(vals[1].pub_key, 0), newv])
    t3 = ec.table_for_valset(vs)
    assert t3 is not t2
    assert newv.pub_key.data in tables[-1][0]


def test_rotated_out_valset_memo_entry_evictable(monkeypatch):
    """A retired epoch's table must be GC-able once the bounded caches
    evict it: neither the valset memo nor any QuorumGroup-tuple memo
    may keep a strong ref past eviction."""
    import gc
    import weakref

    from cometbft_tpu.ops import ed25519_cached as ec
    from cometbft_tpu.ops import table_cache as tc

    class _T:  # weakref-able stand-in (object() is not)
        pass

    monkeypatch.setattr(ec, "table_for_pubs",
                        lambda pubs, powers=None: _T())
    ec._VALSET_MEMO.clear()
    saved = tc.capacities()
    tc.set_capacities(valset_memo=2)
    try:
        vs = ValidatorSet(mkvals([10, 20]))
        old = ec.table_for_valset(vs)
        ref = weakref.ref(old)
        del old
        # two epochs of churn push the retired entry out of the memo
        for power in (11, 12):
            vs2 = ValidatorSet(mkvals([10, 20]))
            vs2.update_with_change_set(
                [Validator(vs2.validators[0].pub_key, power)])
            ec.table_for_valset(vs2)
        ec.table_for_valset(ValidatorSet(mkvals([5, 5, 5])))
        gc.collect()
        assert ref() is None, \
            "rotated-out epoch's table still strongly referenced"
    finally:
        tc.set_capacities(**saved)


# ---------------------------------------------------------------------------
# The merkle-root memo (ISSUE 26): hash() computes once per membership.
# A stale root is a consensus fault, so every root handed out, on a set
# and on its copies, after any operation, equals a fresh one.
# ---------------------------------------------------------------------------


def _fresh_root(vs):
    from cometbft_tpu.crypto import merkle

    return merkle.hash_from_byte_slices([v.bytes() for v in vs.validators])


def _hash_computes():
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.types.validator import HASH_STAGE

    return sum(1 for rec in tracing.stages() if rec[0] == HASH_STAGE)


@pytest.mark.parametrize("seed", range(8))
def test_hash_equals_a_fresh_root_after_any_sequence(seed, tmp_path):
    """Seeded random walks over everything that touches a set: copies,
    proposer rotation, change sets of every shape (power-only, add,
    remove, empty), a state-store round trip. hash() is asked at random
    moments (so memos of every age are in play) and checked on every
    live set after every operation."""
    import random
    from dataclasses import replace

    from cometbft_tpu.state.state import State, StateStore

    rnd = random.Random(f"valset-root/{seed}")
    pool = mkvals([1] * 24)  # the keys members are drawn from
    members = rnd.sample(range(len(pool)), 6)
    live = [ValidatorSet([Validator(pool[i].pub_key, rnd.randint(1, 50))
                          for i in members])]
    store = StateStore(str(tmp_path / "state.db"))
    genesis = State.make_genesis("root-chain", ValidatorSet(mkvals([1])))

    def power_only(vs):
        v = rnd.choice(vs.validators)
        vs.update_with_change_set(
            [Validator(v.pub_key, v.voting_power + rnd.randint(1, 9))])

    def add(vs):
        out = [p for p in pool if not vs.has_address(p.address)]
        if out:
            vs.update_with_change_set(
                [Validator(rnd.choice(out).pub_key, rnd.randint(1, 50))])

    def remove(vs):
        if len(vs) > 2:
            vs.update_with_change_set(
                [Validator(rnd.choice(vs.validators).pub_key, 0)])

    def mixed(vs):
        out = [p for p in pool if not vs.has_address(p.address)]
        a, b = rnd.sample(vs.validators, 2)
        changes = [Validator(a.pub_key, a.voting_power + 3)]
        if len(vs) > 2:
            changes.append(Validator(b.pub_key, 0))
        if out:
            changes.append(Validator(out[0].pub_key, rnd.randint(1, 50)))
        vs.update_with_change_set(changes)

    def round_trip(vs):
        st = replace(genesis, validators=vs, next_validators=vs.copy())
        store.save(st)
        loaded = store.load()
        live.extend([loaded.validators, loaded.next_validators])

    ops = [
        lambda vs: live.append(vs.copy()),
        lambda vs: vs.increment_proposer_priority(rnd.randint(1, 4)),
        lambda vs: live.append(
            vs.copy_increment_proposer_priority(rnd.randint(1, 3))),
        power_only, add, remove, mixed,
        lambda vs: vs.update_with_change_set([]),
        round_trip,
    ]
    for _ in range(60):
        vs = rnd.choice(live)
        if rnd.random() < 0.6:
            vs.hash()  # a memo taken before the operation
        rnd.choice(ops)(vs)
        for s in live:
            assert s.hash() == _fresh_root(s)
        del live[:-6]  # keep the walk cheap: the six youngest sets
    store.close()


def test_hash_computes_once_per_membership():
    """The `valset.hash` stage fires on a computed root only: any
    number of calls on an unchanged set and on its copies compute once,
    a change set once more; a hit records nothing."""
    from cometbft_tpu.libs import tracing

    vals = mkvals([10, 20, 30])
    vs = ValidatorSet(vals)
    tracing.set_clock(None)  # an empty stage ring
    c0 = _hash_computes()
    root = vs.hash()
    assert _hash_computes() == c0 + 1
    cp = vs.copy()
    rot = vs.copy_increment_proposer_priority(3)
    vs.increment_proposer_priority(2)
    vs.update_with_change_set([])
    for s in (vs, cp, rot, cp.copy(), rot.copy()):
        for _ in range(5):
            assert s.hash() == root
    assert _hash_computes() == c0 + 1
    # a copy taken BEFORE the first hash() has nothing to carry
    cold = ValidatorSet(vals).copy()
    assert cold.hash() == root and _hash_computes() == c0 + 2
    # a change set: one more on the changed set, none on the others
    vs.update_with_change_set([Validator(vals[0].pub_key, 11)])
    changed = vs.hash()
    assert changed != root and changed == _fresh_root(vs)
    assert vs.hash() == vs.copy().hash() == changed
    assert cp.hash() == rot.hash() == root
    assert _hash_computes() == c0 + 3
    # whoever replaces the list by hand drops the memo with it
    vs.validators = list(vs.validators)
    assert vs.hash() == changed and _hash_computes() == c0 + 4


def test_a_native_root_is_remembered_and_dropped_as_before():
    """A root the C call built (`native` 1 on its `valset.hash` record)
    answers every repeated hash() from the memo, and a change set drops
    it: one more record, a root equal to the Python tree's."""
    from cometbft_tpu import native
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.types.validator import HASH_STAGE

    c = int(native.available())  # no compiler: the Python tree, 0

    def records():
        return [r[4] for r in tracing.stage_records() if r[0] == HASH_STAGE]

    vals = mkvals([10, 20, 30, 40])
    vs = ValidatorSet(vals)
    tracing.set_clock(None)  # an empty stage ring
    root = vs.hash()
    for _ in range(3):
        assert vs.hash() == root and vs.copy().hash() == root
    assert records() == [{"n": 4, "native": c}]
    assert root == _fresh_root(vs)
    vs.update_with_change_set([Validator(vals[1].pub_key, 0)])
    assert vs.hash() != root and vs.hash() == _fresh_root(vs)
    assert records() == [{"n": 4, "native": c}, {"n": 3, "native": c}]


# ---------------------------------------------------------------------------
# A set is built column-wise (sort, index, total, proposer rounds over
# int64 arrays); the per-member loops stay as the fallback for values
# int64 cannot hold. Both give the same set on every input.
# ---------------------------------------------------------------------------

_I64_MAX, _I64_MIN = 2**63 - 1, -(2**63)


def _by_loops(validators):
    """The set as the per-member loops build it: `sorted`, the index and
    total over the members, one round of `_rotate_loops`."""
    from cometbft_tpu.types.validator import _power_sort_key

    vs = ValidatorSet.__new__(ValidatorSet)
    vs.validators = sorted(validators, key=_power_sort_key)
    vs._reindex()
    vs._total_power = None
    vs._update_total_voting_power()
    vs.proposer = None
    vs._rotate_loops(1)
    return vs


def _loop_total(validators):
    """The reference's running total in set order, and its error."""
    from cometbft_tpu.types.validator import _power_sort_key

    total = 0
    for v in sorted(validators, key=_power_sort_key):
        total += v.voting_power
        if total > MAX_TOTAL_VOTING_POWER:
            raise ValidatorSetError(
                "total voting power exceeds MaxTotalVotingPower")
    return total


def _built(validators):
    """ValidatorSet(validators) and its `valset.build` record's args."""
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.types.validator import BUILD_STAGE

    tracing.set_clock(None)  # an empty stage ring
    vs = ValidatorSet(validators)
    rec, = [r for r in tracing.stage_records() if r[0] == BUILD_STAGE]
    return vs, rec[4]


def _fresh(validators):
    return [replace(v) for v in validators]


def _state(vs):
    return ([(v.address, v.voting_power, v.proposer_priority)
             for v in vs.validators], vs._index, vs.total_voting_power(),
            vs.get_proposer().address)


def _members(case, rnd):
    """(validators, rounds on the built set) of one equivalence case."""
    def key(kind=None):
        kind = kind or rnd.choice(["ed25519", "secp256k1"])
        return PubKey(rnd.randbytes(32 if kind == "ed25519" else 33), kind)

    n, times = {"members-1": (1, 0), "members-4": (4, 0),
                "members-10000": (10000, 0), "rounds-1": (175, 1),
                "rounds-7": (175, 7)}.get(case, (175, 0))
    kind = {"secp256k1-keys": "secp256k1",
            "mixed-key-lengths": None}.get(case, "ed25519")
    vals = []
    for _ in range(n):
        power = 10 if case == "equal-powers" else rnd.randint(500, 1500)
        prio = (rnd.randint(-10**12, 10**12)
                if case in ("incoming-priorities", "rounds-7") else 0)
        vals.append(Validator(key(kind), power, b"", prio))
    return vals, times


@pytest.mark.parametrize("case", [
    "members-1", "members-4", "members-175", "members-10000",
    "equal-powers", "incoming-priorities", "secp256k1-keys",
    "mixed-key-lengths", "rounds-1", "rounds-7"])
def test_a_columnar_set_is_the_loops_set(case):
    """Order, index, total, every priority, the proposer, the root, a
    copy's root and a change set's root: the columns' set (`columnar` 1)
    equals the loops' on every case, and `increment_proposer_priority`
    equals `_rotate_loops` on a built set."""
    import random

    from cometbft_tpu.crypto import merkle

    vals, times = _members(case, random.Random(f"columnar/{case}"))
    got, args = _built(_fresh(vals))
    want = _by_loops(_fresh(vals))
    assert args == {"n": len(vals), "columnar": 1}
    assert _state(got) == _state(want)
    assert got.total_voting_power() == _loop_total(vals)
    if times:
        got.increment_proposer_priority(times)
        want._rotate_loops(times)
        assert _state(got) == _state(want)
        cp = got.copy_increment_proposer_priority(times)
        ref = want.copy()
        ref._rotate_loops(times)
        assert _state(cp) == _state(ref)
    root = merkle.hash_from_byte_slices([v.bytes() for v in want.validators])
    assert got.hash() == want.hash() == root
    cp = got.copy()  # carries the columns as it carries the root
    assert cp._cols[0] is cp.validators and cp._cols[1] is got._cols[1]
    assert cp.hash() == root
    # a change set replaces the list: the columns go with the root
    members = got.validators
    changes = [Validator(members[0].pub_key, 0),
               Validator(members[-1].pub_key, 777)] if len(members) > 1 \
        else [Validator(members[0].pub_key, 778)]
    changes.append(Validator(PubKey(b"\x07" * 32), 5))
    for vs in (got, want):
        vs.update_with_change_set(_fresh(changes))
    assert _state(got) == _state(want)
    assert got._cols is None or got._cols[0] is got.validators
    assert got.hash() == merkle.hash_from_byte_slices(
        [v.bytes() for v in got.validators]) == want.hash()


def _edge(case):
    """(powers, priorities[, addresses]) of one error or fallback case."""
    top = MAX_TOTAL_VOTING_POWER
    return {
        # the duplicate is found before the total, as by the loops
        "duplicate-address": ([5, 7], [0, 0]),
        "total-above-cap": ([top, 1], [0, 0]),
        "running-total-passes-cap": ([top, 5, -10], [0, 0, 0]),
        "negative-powers-under-cap": ([5, -3, 10], [0, 0, 0]),
        # the loops' arithmetic leaves int64: they clip or carry it
        "priority-past-int64": ([10, 20, 30], [2**63, -(2**63) - 1, 0]),
        "centring-clips": ([0, 0, 0], [_I64_MAX, _I64_MAX, _I64_MIN]),
        "ratio-past-int64": ([1, 0], [_I64_MAX, _I64_MIN]),
        "power-near-int64-clips": ([_I64_MIN + 1, 3], [-5, 0]),
        "addition-wraps": ([10, -10], [_I64_MAX - 3, 3 - _I64_MAX]),
        # no int64 limit near: the columns, bytes order of addresses
        # where a null-padded column would tie them
        "addresses-of-unequal-length": (
            [7, 7, 7, 7], [0, 0, 0, 0],
            [b"\x05\x00\x00", b"\x05\x00", b"\x04\xff", b"\x05"]),
    }[case]


@pytest.mark.parametrize("case", [
    "duplicate-address", "total-above-cap", "running-total-passes-cap",
    "negative-powers-under-cap", "update-passes-cap", "priority-past-int64",
    "centring-clips", "ratio-past-int64", "power-near-int64-clips",
    "addition-wraps", "addresses-of-unequal-length"])
def test_errors_and_int64_limits_are_the_loops(case):
    """The errors are the loops' and come at their point; where a value
    the loops compute leaves int64 the build takes the loops
    (`columnar` 0) and gives their clipped or unbounded priorities."""
    if case == "update-passes-cap":
        # the change set's running total passes the cap where its sum
        # does not: the error, and the total stays unknown after it
        vals = mkvals([5, -10])
        newcomer = mkvals([1, 1, MAX_TOTAL_VOTING_POWER])[2]
        for vs in (ValidatorSet(_fresh(vals)), _by_loops(_fresh(vals))):
            for _ in range(2):
                with pytest.raises(ValidatorSetError, match="exceeds Max"):
                    vs.update_with_change_set([replace(newcomer)])
                with pytest.raises(ValidatorSetError, match="exceeds Max"):
                    vs.total_voting_power()
        return
    powers, prios, *addresses = _edge(case)
    addresses = addresses[0] if addresses else [b""] * len(powers)
    vals = [Validator(k.pub_key(), p, a, q) for k, p, q, a in
            zip((PrivKey.generate(bytes([i + 1]) * 32)
                 for i in range(len(powers))), powers, prios, addresses)]
    if case == "duplicate-address":
        vals.append(Validator(vals[0].pub_key, MAX_TOTAL_VOTING_POWER))
    try:
        want = _by_loops(_fresh(vals))
    except ValidatorSetError as e:
        with pytest.raises(ValidatorSetError, match=str(e)):
            ValidatorSet(_fresh(vals))
        if case != "duplicate-address":
            with pytest.raises(ValidatorSetError, match=str(e)):
                _loop_total(vals)
        return
    got, args = _built(_fresh(vals))
    assert _state(got) == _state(want)
    assert got.total_voting_power() == _loop_total(vals)
    assert got.hash() == want.hash()
    clipped = {v.proposer_priority for v in want.validators}
    if case in ("negative-powers-under-cap", "addresses-of-unequal-length"):
        assert args["columnar"] == 1
        return
    assert args["columnar"] == 0
    if case in ("centring-clips", "power-near-int64-clips",
                "addition-wraps"):
        assert clipped & {_I64_MIN, _I64_MAX}  # the loops clipped
    for times in (1, 7):  # a built set's rounds take the loops too
        cp = got.copy()
        cp.increment_proposer_priority(times)
        ref = want.copy()
        ref._rotate_loops(times)
        assert _state(cp) == _state(ref)
