"""The vote intake: the votes that are waiting together have their
signature checks in flight together, and everything else about them
happens one vote at a time, in arrival order.

The receive routine (consensus/state.py) hands over the vote messages
its queue already holds when it takes one; a benchmark's driver hands
over the votes that are due. `intake` first stages every vote on the
vote set it would reach right now (`HeightVoteSet.stage_vote`:
precheck, then one submission to the verify plane, not waited for), so
that the rows of a burst meet in one flush, and then calls
`handle(item)` for each item in turn. `handle` is the caller's serial
path, unchanged (prefilter, WAL write, `add_vote`, quorum checks):
`add_vote` finds its verdict in flight or done where it would have
started it.

What may be in flight together: the signature checks, which are pure,
of DIFFERENT validators. A fused flush lays one row a validator into a
stride of the window table; a second row of one validator makes it a
two-stride flush, another program, which `VerifyPlane.prime` has not
compiled and the dispatcher would compile for tens of seconds while
every vote waits. So the items are taken in runs that end where a
validator's second vote comes (a node that fell behind finds a set's
prevotes and precommits waiting together: two runs), and a run is
settled before the next is staged.
What stays in order: prechecks, WAL records, verdicts as `handle` sees
them, admissions, evidence, `on_vote_added`, step transitions. A vote
staged against a set that an earlier vote of the same call moved on
from (height, round) is handled as the serial path handles it at that
point; a staged check nobody took up is unwound from the fused tally.
One vote, or no plane: nothing is staged and `handle` is all there is.
"""
from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence, TypeVar

from cometbft_tpu.libs import tracing
from cometbft_tpu.types.vote import Vote

Item = TypeVar("Item")


def _runs(items: Sequence[Item],
          vote_of: Callable[[Item], Vote]) -> Iterator[List[Item]]:
    """`items` in order, cut before each item whose validator already
    has a vote in the run."""
    run: List[Item] = []
    seen = set()
    for item in items:
        idx = vote_of(item).validator_index
        if idx in seen:
            yield run
            run, seen = [], set()
        run.append(item)
        seen.add(idx)
    if run:
        yield run


def intake(items: Sequence[Item],
           vote_of: Callable[[Item], Vote],
           target: Callable[[Vote], Optional[object]],
           handle: Callable[[Item], object]) -> List[object]:
    """Handle `items` (arrival order; `vote_of(item)` is the vote an
    item carries) through `handle(item)`, one at a time and in order,
    with their signature checks staged ahead. `target(vote)` is the
    HeightVoteSet `handle` would add the vote to if it ran now, or
    None. `handle` must give `add_vote` the very object `vote_of`
    returned: a staged check is found by the vote's identity. Returns
    what `handle` returned, per item; what it raises ends the call (the
    staged rest is unwound). The three stage names are read by the
    benchmark (PERF.md section 3)."""
    out: List[object] = []
    with tracing.stage("votes.intake", n=len(items)):
        for run in _runs(items, vote_of):
            staged = []
            with tracing.stage("votes.stage"):
                if len(run) > 1:
                    for item in run:
                        vote = vote_of(item)
                        hvs = target(vote)
                        s = hvs.stage_vote(vote) if hvs is not None else None
                        if s is not None:
                            staged.append(s)
            with tracing.stage("votes.settle"):
                try:
                    out += [handle(item) for item in run]
                finally:
                    for s in staged:
                        s.release()
    return out
