"""Driver `replay_churn`: a node catching up on a chain whose validator
powers move at almost every height, through the program's own loop AND
its own apply path.

Each lap is one `blocksync.catchup.CatchupEngine.run()` from the
genesis state over an in-memory history of distinct signed blocks, with
the default `make_stream_verifier()`, the engine's `read_ahead` and
`max_run`, a real cursor file, and `block_exec`: a `BlockExecutor` over
a fresh kvstore app and a fresh state store file, with
`Config().crypto.batch_fn()`. The engine's `_apply_via_exec` therefore
validates each block against the state (its LastCommit verified by the
device) and applies it through FinalizeBlock, updateState and the
store, as a node's engine does. The executor is the program's own,
subclassed only to stamp each applied block and to end the lap where
the window ends.

The chain (`harness/fixtures_churn.py`) was made by the same executor
from seeded `val:` txs, each commit signed by the set the plain
reference (`reference/churn.py`) names for its height. Set-up checks
two refusals, each from a state `check_lead` heights before it:

- tampered: the first examined row of `tampered_at`'s commit carries a
  flipped signature; `run` must raise at exactly that height, naming
  the row, with the cursor behind it;
- stale quorum: the first height at or after `stale_quorum_at` whose
  set differs from the one before in powers alone gets a commit whose
  signers hold MORE than 2/3 of the power under the previous height's
  set and not under its own; `run` must raise at exactly that height.
  A catch-up that tallied the block under the previous set (a segment
  fused across a power-only change) would accept it.

The same set-up runs the engine across the seat wall carried by block
`wall_at` (a multiple of `seat_change_every`; its leave and join take
effect at `wall_at` + 2), from the state `check_lead` heights before it
to `warm_blocks` heights after it. That pass compiles every shape of
the window, builds the tables of sets the window's laps do not reach
(so the window's first lap finds none of its own built), and runs the
key-change path at full size: a set of new keys, its table, the
warm-ahead of a set the state does not know yet. The state after every
block of the pass must hold the sets the reference names.

`correct` needs the reference to accept every block of the chain and
to refuse the two bad commits as the engine did, the wall pass to hold
the reference's sets after each of its blocks, and the engine's state
at the end of every lap to hold the sets the reference names for that
height (the last, current and next set's roots).

Traffic parameters (the mix's file): read_ahead, max_run, wall_at,
 warm_blocks, tampered_at, stale_quorum_at, check_lead.
"""
from __future__ import annotations

import os
import random
import re
import time
from types import SimpleNamespace

from drivers.replay_catchup import _Source, _TimedVerifier
from harness import fixtures_churn, stage_args
from reference import churn, plain


class _WindowOver(Exception):
    pass


def _stale_height(ctx, sets, start: int, rnd):
    """(height, signer rows) of the first power-only change at or after
    `start` (then from height 3 on) for which stale signers were found."""
    n_blocks = ctx.config["history_blocks"]
    for h in [*range(start, n_blocks + 1), *range(3, start)]:
        rows = fixtures_churn.stale_signers(sets[h - 1], sets[h], rnd)
        if rows is not None:
            return h, rows
    raise RuntimeError("no height admits a stale quorum")


def prepare(ctx):
    cfg, tr = ctx.config, ctx.traffic
    n_blocks, lead = cfg["history_blocks"], tr["check_lead"]
    pl = fixtures_churn.plan(ctx.seed, cfg)
    sets = churn.sets(pl["genesis"], pl["updates"], n_blocks)
    roots = {h: churn.root(m) for h, m in sets.items()}
    bad_h = tr["tampered_at"]
    bad_row = fixtures_churn.first_signer(sets[bad_h], pl["absent"][bad_h])
    stale_h, stale_rows = _stale_height(
        ctx, sets, tr["stale_quorum_at"],
        random.Random(f"churn-stale/{ctx.seed}"))
    wall_h = tr["wall_at"]
    if wall_h % cfg["seat_change_every"] or wall_h - lead < 1:
        raise ValueError(f"wall_at {wall_h} carries no seat wall")
    warm_from = wall_h - lead
    warm_until = min(n_blocks, wall_h + tr["warm_blocks"])
    chain = fixtures_churn.Chain(ctx.cell, ctx,
                                 workers=max(2, min(8, os.cpu_count() or 4)))
    chain.submit({
        "chain": cfg["chain_id"], "seeds": pl["seeds"], "sets": sets,
        "genesis": pl["genesis"], "updates": pl["updates"],
        "absent": pl["absent"], "blocks": n_blocks,
        "keep": {bad_h - lead, stale_h - lead, warm_from},
        "bad": {bad_h: ("tamper", bad_row),
                stale_h: ("signers", stale_rows)}}, roots)
    return {"chain": chain, "sets": sets, "roots": roots,
            "bad": (bad_h, bad_row), "stale": (stale_h, stale_rows),
            "wall": (warm_from, wall_h, warm_until)}


def abandon(fx) -> None:
    fx["chain"].abandon()


def close(st) -> None:
    pass


def _executor(ctx, tag: str, batch_fn, stamp=None, deadline=None):
    """A BlockExecutor as a node wires one for catch-up: a fresh kvstore
    app (after InitChain) and a state store file of its own. `stamp`
    gets (time, state after it) of every applied block; a block whose
    apply_block starts after `deadline` ends the lap."""
    from cometbft_tpu.abci import types as abci
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import StateStore

    class _Exec(BlockExecutor):
        def apply_block(self, *a, **kw):
            if deadline is not None and time.monotonic() > deadline:
                raise _WindowOver()
            out = super().apply_block(*a, **kw)
            if stamp is not None:
                stamp(time.monotonic(), out)
            return out

    app = KVStoreApplication()
    app.init_chain(abci.RequestInitChain(chain_id=ctx.config["chain_id"]))
    path = os.path.join(ctx.tmpdir, f"state-{tag}.db")
    return _Exec(app, StateStore(path), batch_fn=batch_fn)


def _engine(ctx, st, items, state, ex, tag: str):
    from cometbft_tpu.blocksync.catchup import CatchupEngine

    return CatchupEngine(
        _Source(items), state.copy(), block_exec=ex,
        verifier=st.verifier,
        cursor_path=os.path.join(ctx.tmpdir, f"cursor-{tag}.json"),
        read_ahead=ctx.traffic["read_ahead"],
        max_run=ctx.traffic["max_run"])


def _refusal(ctx, st, start_h: int, bad_h: int, commit, tag: str) -> dict:
    """Run the engine from the state after `start_h` over the chain with
    `bad_h`'s commit replaced; (height, message) of its refusal."""
    from cometbft_tpu.blocksync.catchup import CatchupError

    items = dict(st.items)
    items[bad_h] = (items[bad_h][0], commit)
    eng = _engine(ctx, st, items, st.chain["states"][start_h],
                  _executor(ctx, tag, st.batch_fn), tag)
    got = None
    try:
        eng.run(until=bad_h + 1)
    except CatchupError as e:
        m = re.search(r"failed at height (\d+): (.*)$", str(e))
        got = (int(m.group(1)), m.group(2)) if m else ("?", str(e))
    finally:
        eng.block_exec.state_store.close()
    return {"engine": got, "cursor": eng.cursor.as_dict(),
            "behind": (eng.cursor.verified < bad_h
                       and eng.cursor.applied == eng.cursor.verified
                       and eng.state.last_block_height
                       == eng.cursor.applied)}


def _tip_roots(state) -> tuple:
    """The roots of the last, current and next sets a state holds."""
    return (state.last_validators.hash() if state.last_validators else None,
            state.validators.hash(), state.next_validators.hash())


def _expected_roots(roots, height: int) -> tuple:
    return (roots[height] if height >= 1 else None, roots[height + 1],
            roots[height + 2])


def warm(ctx, fx):
    from cometbft_tpu.blocksync.pipeline import make_stream_verifier
    from cometbft_tpu.config.config import Config
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.ops import ed25519_cached as ec

    tr = ctx.traffic
    st = SimpleNamespace(roots=fx["roots"])
    doc = fx["chain"].result()
    st.chain = doc["chain"]
    n_blocks = ctx.config["history_blocks"]
    st.items = {h: (st.chain["blocks"][h], st.chain["commits"][h])
                for h in range(1, n_blocks + 1)}
    st.sigs = {h: sum(1 for cs in c.signatures if cs.for_block())
               for h, (_, c) in st.items.items()}
    ctx.mark("fixtures_built")
    st.reference = churn.verdict(doc["outcomes"])
    st.batch_fn = Config().crypto.batch_fn()  # what cli.build_node passes
    st.stream = make_stream_verifier()  # the engine's own default
    st.verifier = _TimedVerifier(st.stream, ctx)
    st.breaker = cbatch.device_breaker()

    lead = tr["check_lead"]
    bad_h, bad_row = fx["bad"]
    got = _refusal(ctx, st, bad_h - lead, bad_h,
                   st.chain["bad"][bad_h], "tampered")
    exp = tuple(doc["bad_outcomes"][bad_h])
    got["ok"] = (exp == ("invalid_signature", bad_row)
                 and got["engine"] is not None and got["engine"][0] == bad_h
                 and re.search(rf"\b{bad_row}\b", got["engine"][1])
                 is not None and got["behind"])
    st.tampered = {"height": bad_h, "row": bad_row, "reference": exp, **got}
    ctx.info["tampered_setup"] = st.tampered
    ctx.mark("tampered_pass")

    stale_h, rows = fx["stale"]
    sets = fx["sets"]
    before = dict(sets[stale_h - 1])
    under_prev = sum(before[sets[stale_h][i][0]] for i in rows)
    got = _refusal(ctx, st, stale_h - lead, stale_h,
                   st.chain["bad"][stale_h], "stale")
    exp = tuple(doc["bad_outcomes"][stale_h])
    got["ok"] = (exp[0] == "not_enough_power"
                 and under_prev > plain.needed_power(before.values())
                 and got["engine"] is not None
                 and got["engine"][0] == stale_h
                 and "insufficient voting power" in got["engine"][1]
                 and got["behind"])
    st.stale = {"height": stale_h, "signers": len(rows),
                "reference": exp, **got}
    ctx.info["stale_setup"] = st.stale
    ctx.mark("stale_pass")

    # across a seat wall: every shape of the window, tables of sets the
    # window's laps do not reach, and the key-change path at full size
    st.warm_ok = _wall_pass(ctx, st, fx)
    ctx.mark("wall_pass")
    st.stats0 = ec.table_cache_stats()
    st.chunks0 = dict(st.stream.chunks)
    st.faults0 = st.breaker.faults
    del st.verifier.calls[:]
    return st


def _wall_pass(ctx, st, fx) -> bool:
    """Run the engine across the seat wall of `fx["wall"]`; whether the
    state after every block held the reference's sets and the wall's
    new keys took their seats. Records what closed the pass's segments
    where the program says (`catchup.scan`'s `closed_by`)."""
    start, wall_h, until = fx["wall"]
    states = []
    eng = _engine(ctx, st, st.items, st.chain["states"][start],
                  _executor(ctx, "wall", st.batch_fn,
                            lambda t, s: states.append(s)), "wall")
    t0 = time.monotonic()
    try:
        eng.run(until=until)
    finally:
        eng.block_exec.state_store.close()
    closed = stage_args.values({"t0": t0, "t1": time.monotonic()},
                               "catchup.scan", "closed_by")
    new_keys = ({k for k, _ in fx["sets"][wall_h + 2]}
                - {k for k, _ in fx["sets"][wall_h + 1]})
    seated = {s.last_block_height: s for s in states}.get(wall_h + 1)
    ok = ([s.last_block_height for s in states]
          == list(range(start + 1, until + 1))
          and all(_tip_roots(s) == _expected_roots(st.roots,
                                                   s.last_block_height)
                  for s in states)
          and len(new_keys) == 1 and seated is not None
          and all(seated.validators.has_address(churn.address(k))
                  for k in new_keys))
    ctx.info["wall_setup"] = {
        "from": start + 1, "wall": wall_h, "until": until, "ok": ok,
        "closed_by": (None if closed is None else
                      {c: closed.count(c) for c in sorted(set(closed))})}
    return ok


def window(ctx, st):
    from cometbft_tpu.blocksync.catchup import CatchupError
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.ops import ed25519_cached as ec

    applied = []  # (t, height) of every block applied
    laps, error = [], None
    t0 = time.monotonic()
    deadline = t0 + ctx.seconds

    def stamp(t, state):
        applied.append((t, state.last_block_height))

    lap = 0
    while time.monotonic() < deadline and error is None:
        n0 = len(applied)
        ex = _executor(ctx, f"lap{lap}", st.batch_fn, stamp, deadline)
        with ctx.span("lap"):
            eng = _engine(ctx, st, st.items, st.chain["genesis"], ex,
                          f"lap{lap}")
            try:
                eng.run()
            except _WindowOver:
                pass
            except CatchupError as e:  # a valid block refused
                error = str(e)
            finally:
                ex.state_store.close()
        tip = eng.state.last_block_height
        laps.append({"blocks": len(applied) - n0, "tip": tip,
                     "roots_ok": tip == 0 or _tip_roots(eng.state)
                     == _expected_roots(st.roots, tip)})
        lap += 1
    stats = ec.table_cache_stats()
    # whether the stage ring held the whole window: its readers read
    # nothing where it did not
    ctx.info["stages_dropped"] = tracing.stages_dropped()
    calls = [c for c in st.verifier.calls if c[1] <= deadline]
    applied = [(t, h) for t, h in applied if t <= deadline]
    t_last = applied[-1][0] if applied else t0
    return {
        "t0": t0, "t1": deadline, "t_last": t_last,
        "samples": {"verify_ms": [(b - a) * 1e3 for a, b, _ in calls]},
        "work": [(t, st.sigs[h]) for t, h in applied],
        "verify_s": sum(b - a for a, b, _ in calls if b <= t_last),
        "engine_s": t_last - t0,
        "blocks_applied": len(applied),
        "sigs_applied": sum(st.sigs[h] for _, h in applied),
        "error": error,
        "counters": {
            "laps": laps,
            "chunks": {k: st.stream.chunks[k] - st.chunks0[k]
                       for k in st.chunks0},
            "table_builds": stats["misses"] - st.stats0["misses"],
            "breaker_faults": st.breaker.faults - st.faults0,
        },
    }


def verify(ctx, st, obs):
    blocks = obs["blocks_applied"]
    chunks = obs["counters"]["chunks"]
    # blocks of a chunk that left the device-stamped path, or of a
    # batch re-verified on the host after a device fault
    off_path = (chunks["host_packed"] + chunks["dense"]
                + obs["counters"]["breaker_faults"]) * ctx.traffic["max_run"]
    refused = 0 if obs["error"] is None else 1
    n_blocks = ctx.config["history_blocks"]
    return {
        "attempted": blocks + refused,
        "failed": min(blocks + refused, off_path + refused),
        # the reference accepts the whole chain and refuses the two bad
        # commits where the engine did; every lap ended holding the
        # sets the reference names for its height
        "correct": (st.reference == ("ok", n_blocks)
                    and st.tampered["ok"] and st.stale["ok"] and st.warm_ok
                    and obs["error"] is None and blocks >= 1
                    and all(lp["roots_ok"] for lp in obs["counters"]["laps"])),
    }
