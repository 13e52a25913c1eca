"""Validator and ValidatorSet: power-sorted set, proposer rotation, hashing.

Reference: types/validator.go (Validator, Bytes :119 SimpleValidator
proto), types/validator_set.go — NewValidatorSet (:70: update +
IncrementProposerPriority(1)), sort order ValidatorsByVotingPower
(:752-763: voting power DESC, address ASC tiebreak — consensus-critical:
it fixes both the merkle hash and the commit-signature index mapping),
GetByAddress (:latest, linear scan — a dict here), TotalVotingPower memo
w/ MaxTotalVotingPower = MaxInt64/8 cap (:25), IncrementProposerPriority
(:116-141) + RescalePriorities (:143), Hash (:347), updateWithChangeSet
(:589-639: compute priorities -> apply -> rescale -> center -> sort).

A set is built column-wise: the members' powers, addresses and
priorities are read once into int64 and byte columns, and the sort, the
total and the proposer rounds are array work whose results equal the
per-member loops' on every input. The loops are kept for the inputs
whose arithmetic int64 cannot hold exactly (a priority or power near its
limits, where the reference clips), and run there in full.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from itertools import accumulate
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from cometbft_tpu import native
from cometbft_tpu.crypto import merkle
from cometbft_tpu.crypto.keys import PubKey
from cometbft_tpu.libs import protoenc as pe
from cometbft_tpu.libs import tracing

# the always-on stage around every merkle root actually built (a miss
# of the root memo); stage names are a contract (README span table)
HASH_STAGE = "valset.hash"
# the always-on stage around every set the constructor builds: sort,
# index, total power and the first proposer round (ring and profiler
# capture only: no tracer event)
BUILD_STAGE = "valset.build"

MAX_TOTAL_VOTING_POWER = (2**63 - 1) // 8  # validator_set.go:25
PRIORITY_WINDOW_SIZE_FACTOR = 2  # validator_set.go:31


class ValidatorSetError(Exception):
    pass


@dataclass
class Validator:
    pub_key: PubKey
    voting_power: int
    address: bytes = b""
    proposer_priority: int = 0

    def __post_init__(self):
        if not self.address:
            self.address = self.pub_key.address()

    def bytes(self) -> bytes:
        """SimpleValidator proto bytes — the merkle leaf for valset Hash
        (types/validator.go:119)."""
        pk_body = pe.f_bytes(_key_field(self.pub_key.key_type),
                             self.pub_key.data)
        return pe.f_msg(1, pk_body) + pe.f_varint(2, self.voting_power)

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """Higher priority wins; ties break by lower address
        (validator.go:83 CompareProposerPriority)."""
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        return self if self.address < other.address else other


def _key_field(key_type: str) -> int:
    """The PublicKey oneof field a key is written under in a leaf:
    ed25519 = 1, secp256k1 = 2 (proto/tendermint/crypto/keys.proto)."""
    return 1 if key_type == "ed25519" else 2


@dataclass(frozen=True)
class _Leaves:
    """What a set's merkle leaves are written from, one row a member: the
    keys joined (None where their lengths differ), each key's PublicKey
    field number, and the voting powers as int64 (None where one is
    outside int64)."""
    keys: Optional[bytes]
    klen: int
    fields: np.ndarray
    powers: Optional[np.ndarray]

    @classmethod
    def of(cls, vals: Sequence[Validator]) -> "_Leaves":
        pubs = [v.pub_key for v in vals]
        data = [k.data for k in pubs]
        lens = set(map(len, data))
        klen = lens.pop() if len(lens) == 1 else 0
        types = [k.key_type for k in pubs]
        kinds = set(types)
        fields = (np.full(len(types), _key_field(kinds.pop()), np.uint8)
                  if len(kinds) == 1 else
                  np.fromiter(map(_key_field, types), np.uint8, len(types)))
        return cls(b"".join(data) if klen else None, klen, fields,
                   _int64([v.voting_power for v in vals]))

    def take(self, order: np.ndarray) -> "_Leaves":
        """The rows in `order`."""
        keys = self.keys
        if keys is not None:
            keys = np.frombuffer(keys, np.uint8).reshape(
                -1, self.klen)[order].tobytes()
        return _Leaves(keys, self.klen, self.fields[order],
                       None if self.powers is None else self.powers[order])


def _int64(values: Sequence[int]) -> Optional[np.ndarray]:
    """`values` as an int64 column, or None where one is not an integer
    inside int64 (numpy then infers another dtype)."""
    col = np.array(values) if values else np.zeros(0, np.int64)
    return col if col.dtype == np.int64 else None


def _native_root(leaves: _Leaves) -> Optional[bytes]:
    """hash()'s root in ONE native call over the members' keys and
    powers (native.valset_root), or None where there is no member, the
    library did not build, a key's length differs from the first's or a
    power is outside int64: then the leaves are built in Python."""
    if not leaves.keys or leaves.powers is None:
        return None
    return native.valset_root(leaves.keys, leaves.klen, leaves.fields,
                              leaves.powers)


def _power_order(power: np.ndarray,
                 addresses: Sequence[bytes]) -> np.ndarray:
    """The permutation that sorts members by voting power desc, address
    asc (ValidatorsByVotingPower). Members are unique by address, so any
    sort gives the one order `sorted(key=_power_sort_key)` gives."""
    lens = set(map(len, addresses))
    if len(lens) == 1:
        return np.lexsort((np.frombuffer(b"".join(addresses),
                                         f"S{lens.pop()}"), ~power))
    # a null-padded column ties b"a" with b"a\0", which bytes order
    # sets shorter first: the length breaks the tie
    return np.lexsort((np.fromiter(map(len, addresses), np.intp,
                                   len(addresses)),
                       np.array(addresses, object).astype(bytes), ~power))


def _power_sort_key(v: Validator):
    """ValidatorsByVotingPower Less: power desc, address asc."""
    return (-v.voting_power, v.address)


class ValidatorSet:
    """Power-sorted validator list with memoized total power.

    NOT thread-safe (mirrors the reference; callers hold their own locks).
    """

    # hash()'s memo: (the `validators` list the root was computed from,
    # the root). A class default, so a set put together by hand through
    # __new__ (copy, state._valset_from_j) starts with an empty memo.
    _root: Optional[Tuple[List[Validator], bytes]] = None
    # the leaf columns (keys, key fields, powers) in set order, held
    # against the `validators` list they were read from, as the root is
    _cols: Optional[Tuple[List[Validator], _Leaves]] = None

    def __init__(self, validators: Sequence[Validator]):
        # NewValidatorSet semantics (validator_set.go:70-79): genesis
        # validators all receive the same initial priority (equal after
        # centering -> 0), then one priority increment seats the proposer.
        vals = list(validators)
        # the stage's `columnar`: 1 where the columns sorted the set and
        # ran its round, 0 where a loop did either. No tracer event: a
        # set built before the simnet installs its virtual clock (the
        # genesis sets) is stamped on the real one, which cannot repeat
        with tracing.stage_untraced(BUILD_STAGE, n=len(vals)) as st:
            self.proposer: Optional[Validator] = None
            leaves = _Leaves.of(vals)
            columnar = self._seat(vals, leaves)
            if vals:
                columnar = self._rotate(1, vals, leaves.powers) and columnar
            st.args["columnar"] = int(columnar)

    def _seat(self, vals: List[Validator], leaves: _Leaves) -> bool:
        """Make `vals`, sorted by power desc and address asc, the set:
        its index, total power and leaf columns (`leaves`: `vals`'
        own, in their order). True where the columns gave the order,
        False where a power outside int64 left it to `sorted`."""
        if leaves.powers is None:
            self.validators: List[Validator] = sorted(
                vals, key=_power_sort_key)
            self._reindex()
            self._total_power = None
            self._update_total_voting_power()
            return False
        addresses = [v.address for v in vals]
        order = _power_order(leaves.powers, addresses)
        self.validators = list(map(vals.__getitem__, order.tolist()))
        self._cols = (self.validators, leaves.take(order))
        at = np.empty_like(order)
        at[order] = np.arange(len(order))
        self._reindex(addresses, at.tolist())
        self._total_power: Optional[int] = None  # until the check passes
        self._total_power = _checked_total(self._cols[1].powers.tolist())
        return True

    def _reindex(self, addresses: Optional[Sequence[bytes]] = None,
                 at: Optional[Sequence[int]] = None) -> None:
        """Index the members by address: `addresses` and their places in
        the set where the caller has them, else the set's own."""
        if addresses is None:
            addresses = [v.address for v in self.validators]
            at = range(len(addresses))
        idx = dict(zip(addresses, at))
        if len(idx) != len(self.validators):
            raise ValidatorSetError("duplicate validator address")
        self._index: Dict[bytes, int] = idx

    def _leaf_columns(self) -> _Leaves:
        memo = self._cols
        if memo is None or memo[0] is not self.validators:
            memo = self._cols = (self.validators,
                                 _Leaves.of(self.validators))
        return memo[1]

    # -- queries -------------------------------------------------------------

    def __len__(self) -> int:
        return len(self.validators)

    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def get_by_address(
        self, address: bytes
    ) -> Tuple[int, Optional[Validator]]:
        i = self._index.get(address, -1)
        return (i, self.validators[i]) if i >= 0 else (-1, None)

    def get_by_index(self, idx: int) -> Optional[Validator]:
        if 0 <= idx < len(self.validators):
            return self.validators[idx]
        return None

    def has_address(self, address: bytes) -> bool:
        return address in self._index

    def total_voting_power(self) -> int:
        if self._total_power is None:
            self._update_total_voting_power()
        return self._total_power

    def _update_total_voting_power(self) -> None:
        self._total_power = _checked_total(
            [v.voting_power for v in self.validators])

    def hash(self) -> bytes:
        """Merkle root of SimpleValidator leaves (validator_set.go:347),
        computed once per membership and remembered.

        The leaves are Validator.bytes(): key type, key bytes, voting
        power, in list order. A root is built in ONE native call over
        the keys and powers (native.valset_root) where the library
        loads and every key has one length, else from the leaves in
        Python: the same bytes (tests/test_native.py). The keys and
        powers are the leaf columns the constructor read, remembered
        against the list as the root is. Proposer priorities are not in
        them, so rotating the proposer keeps the memo and copy() carries
        it over.
        The memo is held against the `validators` list object it was
        computed from: update_with_change_set replaces that list
        wholesale (ed25519_cached.table_for_valset keys on the same
        fact), so a changed set never answers with the old root. A
        stale root is a consensus fault (validate_block compares it with
        the header), and the memo rests on ONE RULE: nothing assigns a
        member's `voting_power` or `pub_key`, or an element of
        `validators`, outside update_with_change_set. Whoever must,
        replaces the list (`vs.validators = list(...)`), which drops it
        and the leaf columns with it.
        """
        memo = self._root
        if memo is not None and memo[0] is self.validators:
            return memo[1]
        vals = self.validators
        # the stage's `native`: 1 where the C call built the root
        with tracing.stage(HASH_STAGE, n=len(vals)) as st:
            root = _native_root(self._leaf_columns())
            st.args["native"] = int(root is not None)
            if root is None:
                root = merkle.hash_from_byte_slices(
                    [v.bytes() for v in vals])
        self._root = (vals, root)
        return root

    # -- proposer rotation ---------------------------------------------------

    def _find_proposer(self) -> Validator:
        best = self.validators[0]
        for v in self.validators[1:]:
            best = best.compare_proposer_priority(v)
        return best

    def get_proposer(self) -> Optional[Validator]:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer

    def increment_proposer_priority(self, times: int) -> None:
        """validator_set.go:116-141: rescale into the priority window,
        center around zero, then `times` rounds of priority bumping."""
        if self.is_nil_or_empty():
            raise ValidatorSetError("empty validator set")
        if times <= 0:
            raise ValidatorSetError("times must be positive")
        self._rotate(times)

    def _rotate(self, times: int, vals: Optional[List[Validator]] = None,
                powers: Optional[np.ndarray] = None) -> bool:
        """Rescale the priorities into the window, centre them, then
        `times` rounds, the last round's winner the proposer (with
        `times` 0, a change set's rescale and centring alone). Over int64
        columns where they hold every value the loops compute (True),
        else by the loops (False): the same priorities either way. The
        constructor hands the members in the order it was given them,
        with their powers: the order they were read in."""
        if vals is None:
            vals, powers = self.validators, self._leaf_columns().powers
        won = _rotate_columns(vals, powers, self.total_voting_power(), times)
        if won is None:
            self._rotate_loops(times)
            return False
        if times:
            self.proposer = won
        return True

    def _rotate_loops(self, times: int) -> None:
        """_rotate member by member: the reference's own loops, which
        clip at the int64 limits (safeAddClip, safeSubClip)."""
        self._rescale_priorities(
            PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
        self._shift_by_avg_proposer_priority()
        for _ in range(times):
            self.proposer = self._increment_once()

    def _increment_once(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _safe_add(
                v.proposer_priority, v.voting_power
            )
        mostest = self._find_proposer()
        mostest.proposer_priority -= self.total_voting_power()
        return mostest

    def _rescale_priorities(self, diff_max: int) -> None:
        if diff_max <= 0:
            return
        prios = [v.proposer_priority for v in self.validators]
        diff = max(prios) - min(prios)
        if diff > diff_max:
            ratio = (diff + diff_max - 1) // diff_max
            for v in self.validators:
                v.proposer_priority = _int_div_go(v.proposer_priority, ratio)

    def _shift_by_avg_proposer_priority(self) -> None:
        n = len(self.validators)
        avg = sum(v.proposer_priority for v in self.validators)
        avg = _int_div_go(avg, n)
        for v in self.validators:
            v.proposer_priority = _safe_sub(v.proposer_priority, avg)

    # -- updates (epoch changes via ABCI) -------------------------------------

    def copy(self) -> "ValidatorSet":
        vs = ValidatorSet.__new__(ValidatorSet)
        vs.validators = [replace(v) for v in self.validators]
        vs._index = dict(self._index)
        vs._total_power = self._total_power
        # same leaves, same root: held against the copy's own list
        memo = self._root
        if memo is not None and memo[0] is self.validators:
            vs._root = (vs.validators, memo[1])
        cols = self._cols
        if cols is not None and cols[0] is self.validators:
            vs._cols = (vs.validators, cols[1])
        vs.proposer = None
        if self.proposer is not None:
            i = self._index.get(self.proposer.address, -1)
            vs.proposer = (
                vs.validators[i] if i >= 0 else replace(self.proposer)
            )
        return vs

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        vs = self.copy()
        vs.increment_proposer_priority(times)
        return vs

    def update_with_change_set(self, changes: Sequence[Validator]) -> None:
        """Apply adds/updates (power > 0) and removals (power == 0) —
        validator_set.go:589-639: new validators start at
        -1.125 * (total power after updates, before removals); then
        rescale, center, and re-sort by power."""
        if not changes:
            return
        seen: Dict[bytes, Validator] = {}
        for c in changes:
            if c.voting_power < 0:
                raise ValidatorSetError("negative voting power")
            if c.address in seen:
                raise ValidatorSetError("duplicate address in changes")
            seen[c.address] = c

        removals = [a for a, c in seen.items() if c.voting_power == 0]
        for a in removals:
            if not self.has_address(a):
                raise ValidatorSetError("removing a validator not in the set")

        by_addr = {v.address: replace(v) for v in self.validators}
        # total voting power after updates, BEFORE removals — the priority
        # basis for new validators (validator_set.go:443 verifyUpdates +
        # computeNewPriorities)
        tvp_after_updates = sum(v.voting_power for v in by_addr.values())
        for a, c in seen.items():
            if c.voting_power == 0:
                continue
            prev = by_addr[a].voting_power if a in by_addr else 0
            tvp_after_updates += c.voting_power - prev
        if tvp_after_updates > MAX_TOTAL_VOTING_POWER:
            raise ValidatorSetError("updates exceed MaxTotalVotingPower")

        new_prio = -(tvp_after_updates + (tvp_after_updates >> 3))
        for a, c in seen.items():
            if c.voting_power == 0:
                continue
            if a in by_addr:
                by_addr[a].voting_power = c.voting_power
            else:
                by_addr[a] = Validator(c.pub_key, c.voting_power, a, new_prio)
        for a in removals:
            del by_addr[a]

        vals = list(by_addr.values())
        if not vals:
            raise ValidatorSetError("validator set is empty after update")
        # new leaves: the new list alone unkeys the root memo; dropping it
        # lets the old list go
        self._root = None
        self._cols = None
        self._seat(vals, _Leaves.of(vals))
        self._rotate(0)
        self.proposer = None


def _checked_total(powers: List[int]) -> int:
    """The sum of the powers (in set order), or the reference's error
    where the running total passes MAX_TOTAL_VOTING_POWER: with no
    negative power, where the sum does."""
    total = sum(powers)
    if total > MAX_TOTAL_VOTING_POWER or (
            min(powers, default=0) < 0
            and max(accumulate(powers)) > MAX_TOTAL_VOTING_POWER):
        raise ValidatorSetError(
            "total voting power exceeds MaxTotalVotingPower")
    return total


def _rotate_columns(vals: Sequence[Validator], powers: Optional[np.ndarray],
                    total: int, times: int) -> Optional[Validator]:
    """ValidatorSet._rotate_loops over int64 columns, the priorities
    read from the members once and written back once. No step depends
    on a member's place (ties go by address), so `vals` may come in any
    order, with `powers` in the same. Returns the last round's winner
    (`vals[0]` with no round), or None, with no member touched, where a
    value the loops compute leaves int64: they clip it (safeAddClip,
    safeSubClip) or carry it as a Python integer."""
    prio = _int64([v.proposer_priority for v in vals])
    if prio is None or powers is None:
        return None
    # RescalePriorities: into a window of twice the total power
    diff_max = PRIORITY_WINDOW_SIZE_FACTOR * total
    lo, hi = int(prio.min()), int(prio.max())
    if diff_max > 0 and hi - lo > diff_max:
        ratio = (hi - lo + diff_max - 1) // diff_max
        if ratio > _I64_MAX:
            return None
        quot = prio // ratio
        quot[(prio < 0) & (prio % ratio != 0)] += 1  # Go truncates
        prio = quot
        lo, hi = int(prio.min()), int(prio.max())
    # shiftByAvgProposerPriority: the exact mean lies inside int64
    n = len(vals)
    exact = n * max(hi, -lo) <= _I64_MAX  # no partial sum can wrap
    avg = _int_div_go(int(prio.sum()) if exact else sum(prio.tolist()), n)
    if lo - avg < _I64_MIN or hi - avg > _I64_MAX:
        return None
    prio = prio - avg
    won = 0
    for _ in range(times):
        bumped = prio + powers
        if (((prio ^ bumped) & (powers ^ bumped)) < 0).any():
            return None  # an addition wrapped: the loop clips it
        prio = bumped
        # the highest priority, ties to the lowest address
        # (compare_proposer_priority)
        ties = np.flatnonzero(prio == prio.max()).tolist()
        won = min(ties, key=lambda i: vals[i].address)
        left = int(prio[won]) - total
        if not _I64_MIN <= left <= _I64_MAX:
            return None
        prio[won] = left
    for v, p in zip(vals, prio.tolist()):
        v.proposer_priority = p
    return vals[won]


def _int_div_go(a: int, b: int) -> int:
    """Go integer division truncates toward zero; Python floors."""
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b >= 0) else -q


_I64_MAX = 2**63 - 1
_I64_MIN = -(2**63)


def _safe_add(a: int, b: int) -> int:
    """Saturating int64 add (validator_set.go safeAddClip)."""
    return max(_I64_MIN, min(_I64_MAX, a + b))


def _safe_sub(a: int, b: int) -> int:
    return max(_I64_MIN, min(_I64_MAX, a - b))
