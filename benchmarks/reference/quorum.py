"""Where a vote set's decision falls: for one set fed `votes` in order,
the position of the vote at which more than 2/3 of the power has first
voted for one block. Plain like `plain.py`, whose signature check and
2/3 rule it uses: nothing of the program under test."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

from reference import plain


def first_quorum_index(pubs: Sequence[bytes], powers: Sequence[int],
                       votes: Sequence[Tuple[int, bytes, bytes, bytes]]
                       ) -> Optional[int]:
    """`votes` as `plain.vote_set_state` takes them: (validator index,
    block key, signed bytes, signature). The index into `votes` of the
    vote that decides the set, or None if none does. A validator's
    second vote and a vote with a bad signature add nothing."""
    needed = plain.needed_power(powers)
    voted = set()
    by_block: dict = {}
    for k, (idx, block_key, msg, sig) in enumerate(votes):
        if idx in voted or not plain.verify_sig(pubs[idx], msg, sig):
            continue
        voted.add(idx)
        by_block[block_key] = by_block.get(block_key, 0) + powers[idx]
        if by_block[block_key] > needed:
            return k
    return None
