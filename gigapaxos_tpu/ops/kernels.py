"""Batched columnar paxos kernels.

Each kernel is a pure function ``(state, batch arrays...) -> (state, outs)``
over the whole-fleet :class:`~gigapaxos_tpu.ops.types.ColumnarState`.  A
*batch* is a struct-of-arrays of B packet lanes; lanes with ``valid=False``
are padding and must not mutate state (implemented by redirecting their
scatter indices out of bounds and using ``mode="drop"``).

Reference analogs (see SURVEY.md §3.1 hot path):

- ``accept_batch``        <- ``PaxosAcceptor.acceptAndUpdateBallot`` (HOT #1)
- ``accept_reply_batch``  <- ``PaxosCoordinator.handleAcceptReply`` majority
                             counting (HOT #2)
- ``propose_batch``       <- ``PaxosCoordinator.propose`` slot assignment
- ``commit_batch``        <- decision handling feeding
                             ``PaxosInstanceStateMachine.
                             extractExecuteAndCheckpoint`` (HOT #3 stays
                             host-side behind the Replicable boundary; this
                             kernel maintains the device window frontier)
- ``prepare_batch``       <- ``PaxosAcceptor.handlePrepare``
- ``install_coordinator_batch`` <- phase-1 completion / pvalue carryover
                             (``PaxosCoordinator`` run-for-coordinator);
                             the *merge* of prepare replies is host-side
                             (cold path), the window gathers are device-side

Determinism note: a batch is applied as ONE linearization: per-group ballot
promises take the max over the batch, so a lane whose ballot is below the
batch max for its group is rejected even if it "arrived first".  Any such
linearization is safe for paxos (rejection only affects liveness, and the
host retries).

Intra-batch preconditions (enforced by the host batcher,
``gigapaxos_tpu.paxos.batcher``):

- at most one accept lane per (group, slot) per batch (duplicates coalesced
  to the max ballot) — mirrors ``PaxosPacketBatcher`` coalescing;
- at most one accept-reply lane per (group, slot, sender) per batch, which
  makes scatter-add equivalent to scatter-OR on the vote bitmaps.

Lane order (the optional last argument ``runs`` of ``propose_batch``,
``accept_batch``, ``accept_reply_batch`` and ``commit_batch``): a
composition that hands every stage the same groups — the storm step —
sorts its lanes by group once (:func:`lane_runs`) and passes the order
along; the bodies then derive ranks, counts and maxima per group from
dense scans over the run structure instead of sorting and scattering for
them.  Called without it — the packed wrappers, the mesh table, recovery —
a body is exactly what it was.

Group view (the optional argument ``view`` after ``runs`` of
``propose_batch``, ``accept_batch`` and ``commit_batch``): a composition
with a lane order may read each replica's group table ONCE
(:func:`group_view`) and hand the bodies the ``[B]`` columns they read
instead of a row read a stage call.  A body given a view reads those
columns from it, sets the table as it would without one, and returns the
view as a third value with each column it set as the table now holds it,
on every lane of the group's run.  Called without one, a body reads the
table and returns two values, as before.

Handed-on proposal: a composition with a view that proposes its lanes and
counts their replies in the same program (the storm step) hands
``propose_batch``'s output to :func:`accept_replies_batch`, which counts
every sender's reply at once and reads no proposal word back.
"""

from __future__ import annotations

import itertools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from gigapaxos_tpu.ops.types import (COL, COL_DTYPE, ColumnarState,
                                     EMITTED_BIT, GROUP_WORDS, NO_BALLOT,
                                     NO_SLOT, PLANES, RowState, VOTE_MASK,
                                     from_word, to_word)

i32 = jnp.int32
u32 = jnp.uint32


def _majority(members):
    return members // 2 + 1


def _gi(g, valid):
    """Gather index: lane-0 row for invalid lanes (result unused)."""
    return jnp.where(valid, g, 0)


def _si(g, valid, G):
    """Scatter index: out-of-bounds for invalid lanes (mode='drop')."""
    return jnp.where(valid, g, G)


def _run_rank(key1, key2):
    """Rank of each lane within its equal-(key1, key2) run, in original
    lane order.

    O(B log B) stable-sort formulation of "occurrence index among lanes
    with the same key" — replaces the naive [B, B] pairwise comparison,
    which materializes/streams a B² boolean matrix and dominated step time
    for B beyond a few thousand.  Two i32 keys (lexsorted) because x64 is
    disabled, so a packed 64-bit key would silently truncate.
    """
    B = key1.shape[0]
    order = jnp.lexsort((key2, key1))  # stable: equal pairs in lane order
    k1, k2 = key1[order], key2[order]
    iota = jnp.arange(B, dtype=i32)
    start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_),
         (k1[1:] != k1[:-1]) | (k2[1:] != k2[:-1])])
    run_start = jax.lax.cummax(jnp.where(start, iota, 0))
    rank_sorted = iota - run_start
    return jnp.zeros((B,), i32).at[order].set(rank_sorted)


class LaneRuns(NamedTuple):
    """A batch whose lanes the caller has put in group order (see
    :func:`lane_runs`): the lanes of one group are a contiguous *run*, in
    their original lane order."""
    order: jnp.ndarray  # i32[B]  the lane each position came from
    valid: jnp.ndarray  # bool[B] ``valid`` in that order (true lanes first)
    head: jnp.ndarray   # bool[B] first lane of its run
    tail: jnp.ndarray   # bool[B] last lane of its run
    # the caller vouches that the valid lanes of one group carry distinct
    # slots inside one window, so no two lanes share a (group, w) column
    distinct_slots: bool


def lane_runs(g, valid, *lanes, distinct_slots=False):
    """Sort a batch's lanes by group ONCE: a stable sort, so the lanes of
    a group keep their lane order; invalid lanes go last.  Returns
    ``(runs, g, *lanes)`` with ``g`` and the other per-lane arrays in that
    order (they ride the sort; ``a[runs.order]`` permutes what comes
    later).  A composition hands ``runs`` to every body it runs; a body's
    own ``valid`` may then be any subset of the one given here.  With an
    order a body reads each per-group combine (rank in run, count, max)
    off a dense scan over ``[B]`` and writes a group's field once per run,
    from one lane; every scatter it issues has unique indices (a dropped
    lane gets an out-of-bounds row of its own, ``G + lane``); and it
    gathers by the ORDER's valid lanes."""
    B = g.shape[0]
    last = jnp.iinfo(i32).max
    key, order, *lanes = jax.lax.sort(
        (jnp.where(valid, g, last), jnp.arange(B, dtype=i32), *lanes),
        num_keys=1, is_stable=True)
    edge = key[1:] != key[:-1]
    one = jnp.ones((1,), jnp.bool_)
    runs = LaneRuns(order=order, valid=key != last,
                    head=jnp.concatenate([one, edge]),
                    tail=jnp.concatenate([edge, one]),
                    distinct_slots=distinct_slots)
    return (runs, _gi(key, runs.valid), *lanes)


def _run_count(runs: LaneRuns, mask, reverse=False):
    """Lanes of ``mask`` in a lane's run up to and including itself (from
    itself on with ``reverse``): a cumsum less its value at the run's
    first lane, which a cummax carries along the run."""
    first = runs.tail if reverse else runs.head
    n = mask.astype(i32)
    cs = jax.lax.cumsum(n, reverse=reverse)
    return cs - jax.lax.cummax(jnp.where(first, cs - n, 0), reverse=reverse)


def _run_total(runs: LaneRuns, mask):
    """Lanes of ``mask`` in a lane's whole run, on every lane of it."""
    return (_run_count(runs, mask) + _run_count(runs, mask, reverse=True)
            - mask.astype(i32))


def _run_last(runs: LaneRuns, mask):
    """The last lane of ``mask`` in each run: the ONE lane that writes the
    run's combine into the group table."""
    return mask & (_run_count(runs, mask, reverse=True) == 1)


def _run_max(runs: LaneRuns, x):
    """Max of ``x`` over a lane's whole run, on every lane of it: log2(B)
    doubling steps, in each of which a lane takes in the lanes 2^k before
    and after it where they are of its run (runs are contiguous, so after
    k steps a lane has seen the 2^k - 1 lanes on either side)."""
    B = x.shape[0]
    run = jnp.cumsum(runs.head.astype(i32))  # >= 1

    def shifted(a, d, fill):  # a[i - d], or a[i + |d|] for d < 0
        pad = jnp.full((abs(d),), fill, a.dtype)
        return jnp.concatenate([pad, a[:-d]] if d > 0 else [a[-d:], pad])

    d = 1
    while d < B:
        for s in (d, -d):
            x = jnp.where(shifted(run, s, 0) == run,
                          jnp.maximum(x, shifted(x, s, 0)), x)
        d *= 2
    return x


def _sd(g, keep, G):
    """Scatter index where the kept lanes' indices are unique: a dropped
    lane gets an out-of-bounds row OF ITS OWN, so all of them are."""
    return jnp.where(keep, g, G + jnp.arange(g.shape[0], dtype=i32))


def _set_words(state: ColumnarState, idx, words: dict, unique: bool):
    """One one-word set per component plane, all at the flat word index
    ``idx`` (``row * W + w``; a dropped lane's row is out of bounds, so
    its word is).  The v5e writes a word of a linear plane in place, on
    the donated operand, at 6.4 ns a word where a four-word row of a
    packed ``[G, W, 4]`` plane cost 81 ns, and nothing relays the plane
    out for it (PERF.md §6, PR 32)."""
    return {f: getattr(state, f).at[idx].set(
        v, mode="drop", unique_indices=unique) for f, v in words.items()}


def _window_words(rows, W):
    """``[n, W]``: the flat index of every word of each row's window (out
    of bounds for a row that is)."""
    return rows[:, None] * W + jnp.arange(W, dtype=rows.dtype)


_LANES = 128  # words in a row of the v5e's (8, 128) tile


def _own_rows(plane, gi, width):
    """Each lane's group's ``width`` consecutive words of a linear plane,
    read as part of ONE row gather a lane.  The row is a whole tile row
    (128 words, ``per`` groups) wherever groups pack into such rows: seen
    as ``[G * width / 128, 128]`` the plane keeps its linear order (a
    bitcast on the v5e, where ``[G, width]`` with width < 128 is another
    physical order and costs a copy of the plane a stage).  Otherwise the
    row is the group's own ``width`` words.  Returns ``(rows, c, mine)``:
    the rows ``[B, per * width]``, each word's column within its group
    ``[1, per * width]``, and the mask of the lane's own group's words
    (the row's other groups' are to be masked out)."""
    G = plane.shape[0] // width
    per = _LANES // width if (_LANES % width == 0
                              and G % (_LANES // width) == 0) else 1
    rows = plane.reshape(-1, per * width)[gi // per]
    c = jnp.arange(per * width, dtype=i32)[None, :]
    return rows, c % width, c // width == (gi % per)[:, None]


def _frontier_advance(dec_slot, gi, cursor, W):
    """How far each lane's group frontier moves: the columns of its
    window are read in place (:func:`_own_rows`) and counted with a
    compare and a row min.  Column c is d = (c - cursor) mod W ahead of
    the cursor and in order iff it holds slot cursor + d; the advance is
    the least d over the columns that are not, W if all are."""
    rows, c, mine = _own_rows(dec_slot, gi, W)
    d = (c - cursor[:, None]) % W
    ok = rows == cursor[:, None] + d
    return jnp.min(jnp.where(mine & ~ok, d, W), axis=1)


def _group_words(grp, gi):
    """Every scalar of each lane's group from ONE row read a stage call
    (:func:`_own_rows` on the group table ``grp``) where each ``[G]``
    field cost a gather of its own.  Returns ``col``: ``col(f)`` is field
    ``f`` at the lanes' groups, ``[B]`` in the field's own dtype, taken
    out of the lane's 128-word row with a mask and a row sum, not with a
    second gather.  A column is traced when it is asked for: a stage
    takes two to six of the sixteen, and tracing all of them for every
    stage call of every program cost a process 2.4 s of its boot."""
    rows, c, mine = _own_rows(grp, gi, GROUP_WORDS)

    def col(f):
        word = jnp.sum(jnp.where(mine & (c == COL[f]), rows, 0), axis=1)
        return from_word(word, COL_DTYPE[f])
    return col


def group_view(state: ColumnarState, gi, fields):
    """A replica's view for a composition that hands every stage its lanes
    in one order (see the module's note): ``{f: [B]}`` for each of
    ``fields``, from one row read of the table at the order's ``gi``.  It
    is exact on the order's valid lanes; an invalid lane's words are
    padding, as its outputs are."""
    col = _group_words(state.grp, gi)
    return {f: col(f) for f in fields}


def _stage_cols(grp, gi, runs, view):
    """A stage's ``col``: the handed-on view where the caller gives one,
    else ONE row read of the table (:func:`_group_words`)."""
    if view is None:
        return _group_words(grp, gi)
    assert runs is not None, "a group view comes with a lane order"
    return view.__getitem__


def _word(rows, f):
    """The flat table index of field ``f`` of ``rows`` (out of bounds for
    a row that is)."""
    return rows * GROUP_WORDS + COL[f]


def _set_cols(grp, rows, unique=False, **cols):
    """The group table ``grp`` with fields ``cols`` (``[B]`` or scalar
    values by name) of ``rows`` set: the one-word set of
    :func:`_set_words`, the index vector built once for all the columns a
    stage writes, ONE scatter."""
    idx = jnp.stack([_word(rows, f) for f in cols], axis=1)
    vals = jnp.stack([jnp.broadcast_to(to_word(v), rows.shape)
                      for v in cols.values()], axis=1)
    return grp.at[idx.reshape(-1)].set(
        vals.reshape(-1), mode="drop", unique_indices=unique)


# --------------------------------------------------------------------------
# accept (acceptor side)                                  ref: PaxosAcceptor
# --------------------------------------------------------------------------


class AcceptOut(NamedTuple):
    acked: jnp.ndarray        # bool[B] pvalue stored (or stale-decided)
    stale: jnp.ndarray        # bool[B] slot < exec_cursor (already decided)
    out_window: jnp.ndarray   # bool[B] beyond window: host must requeue
    cur_bal: jnp.ndarray      # i32[B]  promised ballot after this batch


def accept_batch(state: ColumnarState, g, slot, bal, rlo, rhi, valid,
                 runs: Optional[LaneRuns] = None,
                 view: Optional[dict] = None):
    G, W = state.G, state.W
    gi = _gi(g, valid if runs is None else runs.valid)
    col = _stage_cols(state.grp, gi, runs, view)
    act = col("active")
    live = valid & act  # inactive rows must not be mutated at all

    item_bal = jnp.where(live, bal, NO_BALLOT)
    if runs is None:
        grp = state.grp.at[_word(_si(g, live, G), "bal")].max(
            item_bal, mode="drop")
        # what a batch without a lane order answers with: the table
        # read again AFTER the stage's own write
        cur_bal = _group_words(grp, gi)("bal")
    else:
        cur_bal = jnp.maximum(col("bal"), _run_max(runs, item_bal))
        grp = _set_cols(state.grp, _sd(g, _run_last(runs, live), G),
                        unique=True, bal=cur_bal)

    promised_ok = live & (bal >= cur_bal)
    cursor = col("exec_cursor")
    stale = valid & act & (slot < cursor)
    in_win = (slot >= cursor) & (slot < cursor + W)
    store = promised_ok & in_win

    w = jnp.where(store, slot % W, 0)
    # the stored pvalue, a word a component plane; one accept lane per
    # (group, slot) a batch: distinct columns
    sg = _si(g, store, G) if runs is None else _sd(g, store, G)
    acc = _set_words(state, sg * W + w, dict(
        acc_slot=slot, acc_bal=bal, acc_rlo=rlo, acc_rhi=rhi),
        unique=runs is not None)

    out = AcceptOut(
        acked=store | (promised_ok & stale),
        stale=stale,
        out_window=promised_ok & ~in_win & ~stale,
        cur_bal=cur_bal,
    )
    state = state._replace(grp=grp, **acc)
    if view is None:
        return state, out
    # the run's promise, written from its last live lane; a run with none
    # keeps its promise, which is what the max with NO_BALLOT leaves
    return state, out, dict(view, bal=cur_bal)


# --------------------------------------------------------------------------
# accept-reply (coordinator side)            ref: PaxosCoordinator majority
# --------------------------------------------------------------------------


class AcceptReplyOut(NamedTuple):
    newly_decided: jnp.ndarray  # bool[B] quorum crossed: emit a commit
    preempted: jnp.ndarray      # bool[B] coordinator resigned (higher bal)
    dec_slot: jnp.ndarray       # i32[B]  slot of the decision
    dec_bal: jnp.ndarray        # i32[B]  coordinator ballot of the decision
    req_lo: jnp.ndarray         # i32[B]  request id of the decided pvalue
    req_hi: jnp.ndarray


def accept_reply_batch(state: ColumnarState, g, slot, bal, sender, acked,
                       valid, runs: Optional[LaneRuns] = None):
    """Handle (batched) accept replies.

    ``bal`` carries the accepted ballot on ack lanes and the acceptor's
    (higher) promised ballot on nack lanes, matching the reference's
    ``AcceptReplyPacket`` semantics.

    The proposal column is read a word a plane at the flat index the
    votes are added at; a caller that drops ``req_lo`` / ``req_hi`` drops
    their two gathers with them.  A composition that made the proposals
    in the same program counts its replies with
    :func:`accept_replies_batch` instead, which reads none of it.
    """
    G, W = state.G, state.W
    gi = _gi(g, valid if runs is None else runs.valid)
    w = jnp.where(valid, slot % W, 0)
    idx = gi * W + w

    col = _group_words(state.grp, gi)
    is_coord, cbal = col("is_coord"), col("cbal")
    coord_here = is_coord & col("coord_active")
    is_rel = valid & coord_here & (bal == cbal)
    # slot >= 0 guards against matching uninitialized vote columns
    # (prop_slot inits to NO_SLOT = -1)
    match = is_rel & acked & (slot >= 0) & (state.prop_slot[idx] == slot)

    sender_i = sender.astype(i32)
    bit = jnp.left_shift(i32(1), sender_i)
    prev = state.prop_votes[idx]  # pre-batch
    fresh = match & (jnp.bitwise_and(jnp.right_shift(prev, sender_i),
                                     1) == 0)

    def crossed(votes):
        cnt = jax.lax.population_count(
            jnp.bitwise_and(votes, VOTE_MASK)).astype(i32)
        return match & (cnt >= _majority(col("members")))

    votes = state.prop_votes.at[_si(g, fresh, G) * W + w].add(
        jnp.where(fresh, bit, 0), mode="drop")
    # re-gather POST-scatter so every lane of a (group, slot) column
    # sees the whole batch's votes (two fresh votes in one batch must
    # still cross quorum); `fresh` guarantees no bit is added twice,
    # so the add never carries into EMITTED_BIT
    quorum = crossed(votes[idx])
    # Exactly-once emission: besides the cross-batch EMITTED_BIT,
    # dedupe WITHIN the batch — when two replies for the same (group,
    # slot) cross quorum in one batch, only the first lane emits the
    # decision.  Non-quorum lanes get unique sentinel keys so they
    # never form runs.
    B = g.shape[0]
    iota = jnp.arange(B, dtype=i32)
    dup_before = quorum & (_run_rank(jnp.where(quorum, g, -1),
                                     jnp.where(quorum, slot, iota)) > 0)
    emitted_prev = jnp.bitwise_and(prev, EMITTED_BIT) != 0
    newly = quorum & ~emitted_prev & ~dup_before
    # `newly` is true at most once per column ever, so the add is an OR
    votes = votes.at[_si(g, newly, G) * W + w].add(
        jnp.where(newly, EMITTED_BIT, 0), mode="drop")

    # Preemption: a nack carrying a ballot above ours ends our reign
    # (ref: PaxosCoordinator preemption on higher-ballot accept replies).
    # The resign scatter is guarded by a real branch: preemption is a
    # failover-window event, and XLA:CPU pays every scatter op as a
    # serial per-lane loop — a scatter per reply wave for an
    # almost-always-empty mask was ~8% of the storm step.
    pre = valid & is_coord & ~acked & (bal > cbal)

    def resign(t):
        return _set_cols(t, _si(g, pre, G), is_coord=False,
                         coord_active=False)

    grp = jax.lax.cond(pre.any(), resign, lambda t: t, state.grp)
    # the branch's result stays the table's one value: without the barrier
    # the v5e's compiler moves the next stage's view of the table into
    # the branches and then copies the 64 MB plane twice a reply
    # (tests/test_chip_compile.py holds it to none)
    grp = jax.lax.optimization_barrier(grp)

    out = AcceptReplyOut(
        newly_decided=newly,
        preempted=pre,
        dec_slot=slot,
        dec_bal=cbal,
        req_lo=state.prop_rlo[idx],
        req_hi=state.prop_rhi[idx],
    )
    return state._replace(prop_votes=votes, grp=grp), out


# --------------------------------------------------------------------------
# propose (coordinator slot assignment)         ref: PaxosCoordinator.propose
# --------------------------------------------------------------------------


class ProposeOut(NamedTuple):
    granted: jnp.ndarray   # bool[B] slot assigned; emit AcceptPackets
    rejected: jnp.ndarray  # bool[B] not coordinator here (host forwards)
    throttled: jnp.ndarray  # bool[B] window full: host requeues
    slot: jnp.ndarray      # i32[B]  assigned slot
    cbal: jnp.ndarray      # i32[B]  coordinator ballot for the accept


def propose_batch(state: ColumnarState, g, rlo, rhi, valid,
                  runs: Optional[LaneRuns] = None,
                  view: Optional[dict] = None):
    """Assign contiguous slots to new requests, multiple per group per batch.

    Lane i's slot is ``next_slot[g] + rank_i`` where rank is the lane's
    occurrence index among same-group lanes (stable-sort run rank,
    O(B log B) — see :func:`_run_rank`; its place in the run, with
    ``runs``).

    Given a view, the proposal it returns is handed on: the granted
    lanes' vote words are NOT written here, and the caller hands the
    :class:`ProposeOut` to :func:`accept_replies_batch`, which sets them.
    """
    G, W = state.G, state.W
    B = g.shape[0]
    gi = _gi(g, valid if runs is None else runs.valid)

    col = _stage_cols(state.grp, gi, runs, view)
    act = col("active")
    coord_here = col("is_coord") & col("coord_active")
    can = valid & act & coord_here

    if runs is None:
        iota = jnp.arange(B, dtype=i32)
        rank = _run_rank(jnp.where(can, g, -1), jnp.where(can, 0, iota))
    else:
        rank = jnp.where(can, _run_count(runs, can) - 1, 0)

    slot = col("next_slot") + rank
    in_win = slot < col("exec_cursor") + W
    granted = can & in_win

    # advance next_slot by per-group granted count
    if runs is None:
        grp = state.grp.at[_word(_si(g, granted, G), "next_slot")].add(
            jnp.where(granted, 1, 0), mode="drop")
    else:  # the granted lanes lead their run: the last holds the count
        grp = _set_cols(state.grp, _sd(g, _run_last(runs, granted), G),
                        unique=True, next_slot=slot + 1)

    # initialize the proposal column for the assigned slot: slot, req id,
    # zero votes/emitted, a word a component plane; the slots of a group
    # are distinct and inside one window: distinct columns.  With a view
    # the vote word is left to the reply stage the proposal is handed to
    # (:func:`accept_replies_batch`), which sets it once from zero
    w = jnp.where(granted, slot % W, 0)
    sg = _si(g, granted, G) if runs is None else _sd(g, granted, G)
    idx = sg * W + w
    words = dict(prop_slot=slot, prop_rlo=rlo, prop_rhi=rhi)
    if view is None:
        words["prop_votes"] = jnp.zeros_like(slot)
    prop = _set_words(state, idx, words, unique=runs is not None)

    out = ProposeOut(
        granted=granted,
        rejected=valid & act & ~coord_here,
        throttled=can & ~in_win,
        slot=slot,
        cbal=col("cbal"),
    )
    state = state._replace(grp=grp, **prop)
    if view is None:
        return state, out
    # the granted lanes take ranks 0.. of their run: the last one wrote
    # next_slot plus their count
    return state, out, dict(
        view, next_slot=col("next_slot") + _run_total(runs, granted))


def accept_replies_batch(state: ColumnarState, g, proposal: ProposeOut,
                         bals, acks, runs: LaneRuns, view: dict):
    """Every sender's accept reply to the proposals this program has just
    made, counted in ONE stage: ``bals[r]`` and ``acks[r]`` are the reply
    of sender ``r``, what :func:`accept_reply_batch` takes a sender a
    call, for ``r`` in 0 .. R-1 in sender order.

    ``proposal`` is what :func:`propose_batch` returned given the same
    lane order and a view: a granted lane's column holds ``prop_slot ==
    slot``, and its vote word, which that stage left unwritten, starts at
    0.  So nothing of the proposal is read back.  A lane's votes are
    counted in sender order: a sender that matches sets its bit (the word
    starts empty and each sender has a bit of its own, so every match is
    fresh), and the first whose bit brings the count to a majority
    decides the lane.  The vote word is then set ONCE on every granted
    lane: the senders' bits, and ``EMITTED_BIT`` where the lane decided.
    A nack above ``cbal`` resigns the coordinator of its group for the
    senders after it, as between two calls.  Returns ``(state, newly,
    view)``: the planes and the table as R calls of
    :func:`accept_reply_batch` leave them, the lanes that decided, and
    the view with ``is_coord`` and ``coord_active`` as that table holds
    them.
    """
    G, W = state.G, state.W
    slot, granted = proposal.slot, proposal.granted
    cbal, quorum = view["cbal"], _majority(view["members"])

    # Preemption, in sender order: a prefix over the senders, since a
    # resigned coordinator counts no later sender's vote.  Under one real
    # branch (see accept_reply_batch), taken where a sender would resign
    # by the view as it comes in: the view only loses coordinators, so no
    # sender resigns where none would by it
    nack_up = [granted & ~a & (b > cbal) for a, b in zip(acks, bals)]

    def resign_in_order(t, is_c, c_act):
        heres, down = [], jnp.zeros_like(granted)
        for up in nack_up:
            heres.append(is_c & c_act)
            pre = up & is_c
            down = down | pre
            out = _run_total(runs, pre) > 0  # a run with a resigner
            is_c, c_act = is_c & ~out, c_act & ~out
        t = _set_cols(t, _si(g, down, G), is_coord=False,
                      coord_active=False)
        return t, heres, is_c, c_act

    def keep(t, is_c, c_act):
        return t, [is_c & c_act] * len(nack_up), is_c, c_act

    is_c = view["is_coord"]
    grp, heres, is_c, c_act = jax.lax.cond(
        jnp.any(jnp.stack(nack_up) & is_c), resign_in_order, keep,
        state.grp, is_c, view["coord_active"])
    # the table stays the branch's one value (see accept_reply_batch)
    grp = jax.lax.optimization_barrier(grp)

    votes, newly = jnp.zeros_like(slot), jnp.zeros_like(granted)
    for r, (a, b, here) in enumerate(zip(acks, bals, heres)):
        match = granted & here & (b == cbal) & a
        votes = votes | jnp.where(match, 1 << r, 0)
        cnt = jax.lax.population_count(votes).astype(i32)
        newly = newly | (match & (cnt >= quorum))
    w = jnp.where(granted, slot % W, 0)
    prop_votes = state.prop_votes.at[_sd(g, granted, G) * W + w].set(
        votes | jnp.where(newly, EMITTED_BIT, 0), mode="drop",
        unique_indices=True)

    state = state._replace(prop_votes=prop_votes, grp=grp)
    return state, newly, dict(view, is_coord=is_c, coord_active=c_act)


# --------------------------------------------------------------------------
# commit / decision                        ref: decision handling + window GC
# --------------------------------------------------------------------------


class CommitOut(NamedTuple):
    applied: jnp.ndarray     # bool[B] decision recorded
    stale: jnp.ndarray       # bool[B] already below exec_cursor
    out_window: jnp.ndarray  # bool[B] host must requeue until window moves
    new_cursor: jnp.ndarray  # i32[B]  group frontier after this batch


def commit_batch(state: ColumnarState, g, slot, rlo, rhi, valid,
                 runs: Optional[LaneRuns] = None,
                 view: Optional[dict] = None):
    G, W = state.G, state.W
    gi = _gi(g, valid if runs is None else runs.valid)
    col = _stage_cols(state.grp, gi, runs, view)
    act, cursor = col("active"), col("exec_cursor")

    stale = valid & act & (slot < cursor)
    in_win = (slot >= cursor) & (slot < cursor + W)
    store = valid & act & in_win
    w = jnp.where(store, slot % W, 0)
    # a commit batch may repeat a (group, slot): the columns are distinct
    # only where the caller vouches for it
    alone = runs is not None and runs.distinct_slots
    sg = _sd(g, store, G) if alone else _si(g, store, G)

    # a word a component plane; "decided" is dec_slot == expected slot
    # (NO_SLOT never matches), so no separate flag plane exists
    dec = _set_words(state, sg * W + w, dict(
        dec_slot=slot, dec_rlo=rlo, dec_rhi=rhi), unique=alone)

    # contiguity advance over the touched rows only
    new_cur = cursor + _frontier_advance(dec["dec_slot"], gi, cursor, W)

    if runs is None:
        grp = state.grp.at[_word(_si(g, store, G), "exec_cursor")].max(
            new_cur, mode="drop")
    else:  # a group's lanes all read the one row: one frontier, >= cursor
        grp = _set_cols(state.grp, _sd(g, _run_last(runs, store), G),
                        unique=True, exec_cursor=new_cur)

    out = CommitOut(
        applied=store,
        stale=stale,
        out_window=valid & act & (slot >= cursor + W),
        # the run's frontier where it stored a lane, as written
        new_cursor=(_group_words(grp, gi)("exec_cursor") if view is None
                    else jnp.where(_run_total(runs, store) > 0, new_cur,
                                   cursor)),
    )
    state = state._replace(grp=grp, **dec)
    if view is None:
        return state, out
    return state, out, dict(view, exec_cursor=out.new_cursor)


# --------------------------------------------------------------------------
# prepare (acceptor side)                    ref: PaxosAcceptor.handlePrepare
# --------------------------------------------------------------------------


class PrepareOut(NamedTuple):
    acked: jnp.ndarray        # bool[B]
    cur_bal: jnp.ndarray      # i32[B] promise after batch (nack carries it)
    exec_cursor: jnp.ndarray  # i32[B]
    win_slot: jnp.ndarray     # i32[B,W] accepted-pvalue window (dense rows)
    win_bal: jnp.ndarray      # i32[B,W]
    win_req_lo: jnp.ndarray   # i32[B,W]
    win_req_hi: jnp.ndarray   # i32[B,W]


def prepare_batch(state: ColumnarState, g, bal, valid):
    """Phase-1 prepare: promise update + dense gather of the accepted
    window (the reference's PrepareReply carries all accepted pvalues ≥
    firstUndecidedSlot; here that is exactly the row slice — SURVEY §7.3.4).
    """
    G, W = state.G, state.W
    gi = _gi(g, valid)
    col = _group_words(state.grp, gi)
    live = valid & col("active")  # don't mutate inactive rows

    item_bal = jnp.where(live, bal, NO_BALLOT)
    grp = state.grp.at[_word(_si(g, live, G), "bal")].max(
        item_bal, mode="drop")
    cur_bal = _group_words(grp, gi)("bal")
    acked = live & (bal >= cur_bal)

    widx = _window_words(gi, W)  # a cold path: a word a gather index
    out = PrepareOut(
        acked=acked,
        cur_bal=cur_bal,
        exec_cursor=col("exec_cursor"),
        win_slot=state.acc_slot[widx],
        win_bal=state.acc_bal[widx],
        win_req_lo=state.acc_rlo[widx],
        win_req_hi=state.acc_rhi[widx],
    )
    return state._replace(grp=grp), out


# --------------------------------------------------------------------------
# read index (reads without a round)         ref: Raft ReadIndex (thesis 6.4)
# --------------------------------------------------------------------------


class ReadIndexOut(NamedTuple):
    ok: jnp.ndarray         # bool[B] the row leads at an undisputed ballot
    cbal: jnp.ndarray       # i32[B]  the ballot peers are asked to confirm
    next_slot: jnp.ndarray  # i32[B]  the read's index: every slot below it


def read_index_batch(state: ColumnarState, g, valid):
    """The coordinator's half of a read: from ONE row read of the group
    table a lane, whether its node may answer the read (the row is live,
    this node coordinates it with phase 1 done, and its own acceptor
    promised exactly the coordinator's ballot), that ballot, and the
    read's index, ``next_slot``.  Reads only: no field changes."""
    col = _group_words(state.grp, _gi(g, valid))
    cbal = col("cbal")
    ok = valid & col("active") & col("is_coord") & col("coord_active") \
        & (col("bal") == cbal)
    return ReadIndexOut(ok=ok, cbal=cbal, next_slot=col("next_slot"))


def confirm_batch(state: ColumnarState, g, bal, valid):
    """An acceptor's half: ``bool[B]``, the row is live and its promise
    is exactly the ballot asked about (none above it, and the asker's
    own).  Reads only."""
    col = _group_words(state.grp, _gi(g, valid))
    return valid & col("active") & (col("bal") == bal)


def read_index_packed(state: ColumnarState, packed):
    """packed[2, B]: g, valid -> out[3, B]: ok, cbal, next_slot.  The
    state goes back unchanged, donated as every packed program's is: a
    state the program only reads, the compiler copies whole into its
    fast memory at every bucket, where one it hands back it reads in
    place."""
    o = read_index_batch(state, packed[0], packed[1] != 0)
    return state, jnp.stack([o.ok.astype(i32), o.cbal, o.next_slot])


def confirm_packed(state: ColumnarState, packed):
    """packed[3, B]: g, bal, valid -> out[1, B]: ok.  The state goes back
    unchanged, as :func:`read_index_packed`'s."""
    return state, confirm_batch(state, packed[0], packed[1],
                                packed[2] != 0).astype(i32)[None]


# --------------------------------------------------------------------------
# coordinator install (phase-1 completion + carryover)
# --------------------------------------------------------------------------


def install_coordinator_batch(state: ColumnarState, g, cbal, next_slot,
                              carry_slot, carry_rlo, carry_rhi, valid):
    """Install this node as active coordinator for groups ``g`` at ballot
    ``cbal`` after a host-side phase-1 majority + pvalue merge.

    ``carry_slot/carry_rlo/carry_rhi`` are ``[B, W]`` carryover pvalues to
    re-propose (columns with ``carry_slot == -1`` are empty).  The host then
    sends the corresponding AcceptPackets at the new ballot; votes columns
    are initialized here.
    """
    G, W = state.G, state.W
    grp = _set_cols(state.grp, _si(g, valid, G), is_coord=True,
                    coord_active=True, cbal=cbal, next_slot=next_slot)

    has = valid[:, None] & (carry_slot >= 0)
    w = jnp.where(has, carry_slot % W, 0)
    sg = jnp.where(has, g[:, None], G)
    prop = _set_words(state, (sg * W + w).reshape(-1), dict(
        prop_slot=carry_slot.reshape(-1), prop_rlo=carry_rlo.reshape(-1),
        prop_rhi=carry_rhi.reshape(-1),
        prop_votes=jnp.zeros((carry_slot.size,), i32)), unique=False)

    return state._replace(grp=grp, **prop), None


# --------------------------------------------------------------------------
# group lifecycle                     ref: PaxosManager.createPaxosInstance
# --------------------------------------------------------------------------


def create_groups_batch(state: ColumnarState, rows, members, version,
                        init_bal, self_coord, valid):
    """(Re)initialize rows for newly created groups.

    ``init_bal`` is the packed initial ballot ``(0, firstCoordinator)`` —
    every replica starts promised to the deterministic initial coordinator,
    which therefore safely skips phase 1 (no prior accepts can exist),
    mirroring the reference's default-coordinator fast path.
    ``self_coord`` marks rows where THIS node is that initial coordinator.
    """
    G, W = state.G, state.W
    si = _si(rows, valid, G)
    # every word of a created row's window, fresh: one dense pass a plane
    # under the created rows' mask (a creation wave is any number of rows,
    # the whole fleet at once in the storm's set-up; a pass is the same
    # few hundred microseconds for all of them)
    made = jnp.zeros((G,), jnp.bool_).at[si].set(True, mode="drop")
    made = jnp.broadcast_to(made[:, None], (G, W)).reshape(-1)
    fresh = {f: jnp.where(made, i32(v), getattr(state, f))
             for cols in PLANES.values() for f, v in cols}

    grp = _set_cols(
        state.grp, si, active=True, members=members, version=version,
        bal=init_bal, exec_cursor=0, gc_slot=NO_SLOT, is_coord=self_coord,
        coord_active=self_coord,
        cbal=jnp.where(self_coord, init_bal, NO_BALLOT), next_slot=0,
        prep_votes=u32(0))
    return state._replace(grp=grp, **fresh), None


def delete_groups_batch(state: ColumnarState, rows, valid):
    grp = _set_cols(state.grp, _si(rows, valid, state.G), active=False,
                    is_coord=False, coord_active=False)
    return state._replace(grp=grp), None


def set_cursor_batch(state: ColumnarState, rows, cursor, next_slot, valid):
    """Restore execution frontier on recovery/unpause (host is authoritative
    for executed state; ref: hot-restore via HotRestoreInfo)."""
    si = _si(rows, valid, state.G)
    grp = _set_cols(state.grp, si, exec_cursor=cursor)
    grp = grp.at[_word(si, "next_slot")].max(next_slot, mode="drop")
    return state._replace(grp=grp), None


def gc_batch(state: ColumnarState, rows, upto, valid):
    """Record checkpoint slot (log below it is GC-eligible host-side)."""
    grp = state.grp.at[_word(_si(rows, valid, state.G), "gc_slot")].max(
        upto, mode="drop")
    return state._replace(grp=grp), None


# --------------------------------------------------------------------------
# row export/import (pause/unpause, debugging)       ref: HotRestoreInfo
# --------------------------------------------------------------------------


def gather_rows(state: ColumnarState, rows) -> RowState:
    """Pull full per-row state for ``rows``, in the row form: ``[n]``
    fields, and the window planes packed as ``[n, W, k]``."""
    rows = jnp.asarray(rows)
    widx = _window_words(rows, state.W)
    col = _group_words(state.grp, rows)
    return RowState(**{
        f: jnp.stack([getattr(state, c)[widx] for c, _ in PLANES[f]],
                     axis=-1) if f in PLANES else col(f)
        for f in RowState._fields})


def scatter_rows(state: ColumnarState, rows, row_state: RowState, valid):
    """Write previously gathered rows back (unpause)."""
    G, W = state.G, state.W
    si = _si(rows, valid, G)
    widx = _window_words(si, W).reshape(-1)
    row = row_state._asdict()
    new = {c: getattr(state, c).at[widx].set(
        row[f][..., k].reshape(-1), mode="drop")
        for f, cols in PLANES.items() for k, (c, _) in enumerate(cols)}
    new["grp"] = _set_cols(state.grp, si,
                           **{f: row[f] for f in COL})
    return state._replace(**new), None


# --------------------------------------------------------------------------
# packed wrappers: ONE [k, B] i32 input and ONE [k, B] i32 output per call.
#
# Motivation: each host<->device transfer is a round trip of its own; the
# unpacked kernels take 5-7 separate batch arrays per call, which the
# runtime would pay per argument.  The node runtime therefore drives these four hot
# entry points with all lanes packed into a single array each way.
# --------------------------------------------------------------------------


def propose_packed(state: ColumnarState, packed):
    """packed[4, B]: g, rlo, rhi, valid -> out[5, B]: granted, rejected,
    throttled, slot, cbal."""
    g, rlo, rhi = packed[0], packed[1], packed[2]
    valid = packed[3] != 0
    state, o = propose_batch(state, g, rlo, rhi, valid)
    return state, jnp.stack([
        o.granted.astype(i32), o.rejected.astype(i32),
        o.throttled.astype(i32), o.slot, o.cbal])


def accept_packed(state: ColumnarState, packed):
    """packed[6, B]: g, slot, bal, rlo, rhi, valid -> out[4, B]: acked,
    stale, out_window, cur_bal."""
    state, o = accept_batch(state, packed[0], packed[1], packed[2],
                            packed[3], packed[4], packed[5] != 0)
    return state, jnp.stack([
        o.acked.astype(i32), o.stale.astype(i32),
        o.out_window.astype(i32), o.cur_bal])


def accept_reply_packed(state: ColumnarState, packed):
    """packed[6, B]: g, slot, bal, sender, acked, valid -> out[6, B]:
    newly_decided, preempted, dec_bal, req_lo, req_hi, dec_slot."""
    state, o = accept_reply_batch(state, packed[0], packed[1], packed[2],
                                  packed[3], packed[4] != 0,
                                  packed[5] != 0)
    return state, jnp.stack([
        o.newly_decided.astype(i32), o.preempted.astype(i32), o.dec_bal,
        o.req_lo, o.req_hi, o.dec_slot])


def propose_accept_self_packed(state: ColumnarState, packed):
    """packed[5, B]: g, rlo, rhi, self_member_idx, valid -> out[9, B]:
    granted, rejected, throttled, slot, cbal, self_acked,
    newly_decided, preempted, acc_cur_bal.

    Fused coordinator fast path (SURVEY §7.1 — minimize device round
    trips): propose + THIS node's own accept + own accept-reply vote in
    ONE device call.  The unfused runtime bounced the coordinator's own
    AcceptBatch through the loopback self-wave, costing two more kernel
    calls (and, on a remote accelerator, two more link round trips) per
    batch.  Other members' accepts still ride the wire; their replies
    land in :func:`accept_reply_batch` as before.

    Semantics preserved exactly:
    - the self-accept can NACK (a competitor's higher prepare landed
      between our install and this batch) — its promised ballot rides
      ``acc_cur_bal`` and drives in-kernel preemption, like the nack
      reply did on the loopback path;
    - single-member groups reach quorum on the self vote alone —
      ``newly_decided`` surfaces the decision for the host commit path.
    """
    g, rlo, rhi, smidx = packed[0], packed[1], packed[2], packed[3]
    valid = packed[4] != 0
    state, po = propose_batch(state, g, rlo, rhi, valid)
    gr = valid & po.granted
    state, ao = accept_batch(state, g, po.slot, po.cbal, rlo, rhi, gr)
    reply_bal = jnp.where(ao.acked, po.cbal, ao.cur_bal)
    state, ro = accept_reply_batch(state, g, po.slot, reply_bal, smidx,
                                   ao.acked, gr)
    return state, jnp.stack([
        po.granted.astype(i32), po.rejected.astype(i32),
        po.throttled.astype(i32), po.slot, po.cbal,
        (gr & ao.acked).astype(i32), ro.newly_decided.astype(i32),
        ro.preempted.astype(i32), ao.cur_bal])


def accept_reply_commit_self_packed(state: ColumnarState, packed):
    """packed[6, B]: g, slot, bal, sender_midx, acked, valid ->
    out[9, B]: newly_decided, preempted, dec_bal, req_lo, req_hi,
    dec_slot, applied, stale, new_cursor.

    Fused decide wave (same motivation as
    :func:`propose_accept_self_packed`): when a reply batch crosses
    quorum, the coordinator's OWN commit applies in the same device
    call — the loopback CommitBatch-to-self frame and its separate
    commit kernel call disappear.  Remote members still get their
    CommitBatch; out-of-window can't arise (a decided slot is inside
    the window that voted it)."""
    g, slot, bal = packed[0], packed[1], packed[2]
    state, ro = accept_reply_batch(state, g, slot, bal, packed[3],
                                   packed[4] != 0, packed[5] != 0)
    state, co = commit_batch(state, g, ro.dec_slot, ro.req_lo,
                             ro.req_hi, ro.newly_decided)
    return state, jnp.stack([
        ro.newly_decided.astype(i32), ro.preempted.astype(i32),
        ro.dec_bal, ro.req_lo, ro.req_hi, ro.dec_slot,
        co.applied.astype(i32), co.stale.astype(i32), co.new_cursor])


def commit_packed(state: ColumnarState, packed):
    """packed[5, B]: g, slot, rlo, rhi, valid -> out[4, B]: applied,
    stale, out_window, new_cursor."""
    state, o = commit_batch(state, packed[0], packed[1], packed[2],
                            packed[3], packed[4] != 0)
    return state, jnp.stack([
        o.applied.astype(i32), o.stale.astype(i32),
        o.out_window.astype(i32), o.new_cursor])


def request_reply_packed(state: ColumnarState, req, rep):
    """Fused COORDINATOR wave: new proposals and accept-replies of one
    worker batch in ONE device dispatch — sequential composition of
    :func:`propose_accept_self_packed` then
    :func:`accept_reply_commit_self_packed`, the order the split
    handlers run them.  The two stages touch disjoint window columns:
    a replied slot s is still undecided (cursor <= s), and the propose
    stage only assigns s' with s' - cursor < W, so s' % W == s % W
    would require s' == s, which the slot counter forbids — the
    window invariant, not luck, keeps the composition exact."""
    state, pout = propose_accept_self_packed(state, req)
    state, rout = accept_reply_commit_self_packed(state, rep)
    return state, pout, rout


def accept_commit_packed(state: ColumnarState, acc, com):
    """Fused ACCEPTOR wave: accepts for the new slots and commits for
    the older ones land in the same worker batch on every acceptor, and
    the unfused runtime paid two device dispatches for it.  Sequential
    composition of the same packed bodies, in the same order the
    manager's handlers run them (accepts first, then commits), so the
    state transition is bit-identical to the two-call path — the jit
    boundary is the only thing that moved.  Both inputs are padded to
    ONE shared bucket by the caller, bounding this kernel's jit cache
    to the ladder size."""
    state, aout = accept_packed(state, acc)
    state, cout = commit_packed(state, com)
    return state, aout, cout


# rows of the four sections of a node wave: what it takes in (the valid
# mask is each section's last row) and what it gives back, in the order
# the stages run
WAVE_SECTIONS = ("req", "rep", "acc", "com")
WAVE_IN = (5, 6, 6, 5)
WAVE_OUT = (9, 9, 4, 4)


def _cuts(widths):
    """[lo, hi) row slices of sections stacked in ``widths`` order."""
    ends = list(itertools.accumulate(widths))
    return tuple(zip([0] + ends[:-1], ends))


WAVE_IN_CUTS, WAVE_OUT_CUTS = _cuts(WAVE_IN), _cuts(WAVE_OUT)


def node_wave_packed(state: ColumnarState, packed):
    """ONE worker batch's hot frames in ONE device dispatch:
    packed[22, B] holds the four sections :data:`WAVE_SECTIONS` stacked
    (requests as :func:`propose_accept_self_packed` takes them, accept
    replies as :func:`accept_reply_commit_self_packed`, accepts as
    :func:`accept_packed`, commits as :func:`commit_packed`, each with
    its own valid row) -> out[26, B], their four outputs stacked.

    Sequential composition of the same four packed bodies in the order
    the manager's handlers run them (coordinator's stages, then the
    acceptor's), so the state transition is bit-identical to the four
    calls, or to :func:`request_reply_packed` then
    :func:`accept_commit_packed`.  All four sections share ONE bucket
    (the caller pads to the longest), which bounds this program's jit
    cache to the ladder; a section with no lanes is all padding."""
    outs = []
    for body, (lo, hi) in zip(
            (propose_accept_self_packed, accept_reply_commit_self_packed,
             accept_packed, commit_packed), WAVE_IN_CUTS):
        state, out = body(state, packed[lo:hi])
        outs.append(out)
    return state, jnp.concatenate(outs)


# --------------------------------------------------------------------------
# jit entry points
# --------------------------------------------------------------------------

# State buffers are donated: each call consumes the old state arrays and
# reuses them in place (XLA aliasing).  With the window planes linear and
# addressed a word at a time that holds for the program the v5e compiles
# too: a plane is only ever the donated operand of a scatter, so a wave
# copies no plane (tests/test_chip_compile.py asserts it).
#
# Every entry routes its traced function through the EngineLedger so the
# flight deck counts compiles/retraces per kernel; the wrapper body runs
# only under the tracer, so cached dispatches never touch it.


def _jit(name, fn):
    from gigapaxos_tpu.utils.engineledger import EngineLedger
    return jax.jit(EngineLedger.traced(name, fn), donate_argnums=0)


accept = _jit("accept", accept_batch)
accept_reply = _jit("accept_reply", accept_reply_batch)
propose = _jit("propose", propose_batch)
commit = _jit("commit", commit_batch)
propose_p = _jit("propose_p", propose_packed)
propose_accept_self_p = _jit("propose_accept_self_p",
                             propose_accept_self_packed)
accept_reply_commit_self_p = _jit("accept_reply_commit_self_p",
                                  accept_reply_commit_self_packed)
accept_p = _jit("accept_p", accept_packed)
accept_reply_p = _jit("accept_reply_p", accept_reply_packed)
commit_p = _jit("commit_p", commit_packed)
accept_commit_p = _jit("accept_commit_p", accept_commit_packed)
request_reply_p = _jit("request_reply_p", request_reply_packed)
node_wave_p = _jit("node_wave_p", node_wave_packed)
read_index_p = _jit("read_index_p", read_index_packed)
confirm_p = _jit("confirm_p", confirm_packed)
prepare = _jit("prepare", prepare_batch)
install_coordinator = _jit("install_coordinator",
                           install_coordinator_batch)
create_groups = _jit("create_groups", create_groups_batch)
delete_groups = _jit("delete_groups", delete_groups_batch)
set_cursor = _jit("set_cursor", set_cursor_batch)
gc = _jit("gc", gc_batch)
