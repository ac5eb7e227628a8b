"""Columnar kernel tests: single-lane equivalence vs the scalar oracle,
full 3-replica protocol rounds, failover with carryover, and randomized
property streams.

Strategy mirrors the reference's (SURVEY.md §4): deterministic oracles as
app/protocol fakes, property comparison of batched vs per-instance state
machines.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from gigapaxos_tpu.ops import kernels, make_state, pack_ballot
from gigapaxos_tpu.ops.types import (DEC_SLOT, NO_SLOT, join_req_id,
                                     split_req_id)
from gigapaxos_tpu.ops.oracle import make_oracle_group, PValue

B = 4  # fixed lane count -> one jit cache entry per kernel
G, W = 16, 8
i32 = jnp.int32


def _b(vals, dtype=i32, fill=0):
    out = np.full((B,), fill, dtype=np.int32 if dtype == i32 else bool)
    for i, v in enumerate(vals):
        out[i] = v
    return jnp.asarray(out, dtype)


def _valid(n):
    return _b([True] * n, jnp.bool_, fill=False)


class KNode:
    """Thin host wrapper: single-lane ops through padded kernel batches."""

    def __init__(self, node_id, Gn=G, Wn=W):
        self.id = node_id
        self.st = make_state(Gn, Wn)
        self.W = Wn

    def create(self, row, members, first_coord, version=0):
        init = pack_ballot(0, first_coord)
        self.st, _ = kernels.create_groups(
            self.st, _b([row]), _b([members]), _b([version]), _b([init]),
            _b([first_coord == self.id], jnp.bool_, fill=False), _valid(1))

    def accept(self, g, slot, bal, req):
        lo, hi = split_req_id(req)
        self.st, o = kernels.accept(
            self.st, _b([g]), _b([slot]), _b([bal]), _b([lo]), _b([hi]),
            _valid(1))
        return (bool(o.acked[0]), bool(o.stale[0]), bool(o.out_window[0]),
                int(o.cur_bal[0]))

    def propose(self, g, req):
        lo, hi = split_req_id(req)
        self.st, o = kernels.propose(
            self.st, _b([g]), _b([lo]), _b([hi]), _valid(1))
        if bool(o.granted[0]):
            return "granted", int(o.slot[0]), int(o.cbal[0])
        if bool(o.throttled[0]):
            return "throttled", NO_SLOT, int(o.cbal[0])
        if bool(o.rejected[0]):
            return "rejected", NO_SLOT, int(o.cbal[0])
        return "inactive", NO_SLOT, int(o.cbal[0])

    def accept_reply(self, g, slot, bal, sender, acked):
        self.st, o = kernels.accept_reply(
            self.st, _b([g]), _b([slot]), _b([bal]), _b([sender]),
            _b([acked], jnp.bool_, fill=False), _valid(1))
        req = join_req_id(int(o.req_lo[0]), int(o.req_hi[0])) \
            if bool(o.newly_decided[0]) else None
        return bool(o.newly_decided[0]), bool(o.preempted[0]), req

    def commit(self, g, slot, req):
        lo, hi = split_req_id(req)
        self.st, o = kernels.commit(
            self.st, _b([g]), _b([slot]), _b([lo]), _b([hi]), _valid(1))
        return (bool(o.applied[0]), bool(o.stale[0]),
                bool(o.out_window[0]), int(o.new_cursor[0]))

    def prepare(self, g, bal):
        self.st, o = kernels.prepare(
            self.st, _b([g]), _b([bal]), _valid(1))
        cursor = int(o.exec_cursor[0])
        window = {}
        for w in range(self.W):
            s = int(o.win_slot[0, w])
            if s >= 0 and s >= cursor:
                window[s] = (int(o.win_bal[0, w]),
                             join_req_id(int(o.win_req_lo[0, w]),
                                         int(o.win_req_hi[0, w])))
        return bool(o.acked[0]), int(o.cur_bal[0]), cursor, window

    def install_coordinator(self, g, cbal, next_slot, carryover):
        cs = np.full((B, self.W), NO_SLOT, np.int32)
        cl = np.zeros((B, self.W), np.int32)
        ch = np.zeros((B, self.W), np.int32)
        for i, pv in enumerate(carryover):
            cs[0, i] = pv.slot
            cl[0, i], ch[0, i] = split_req_id(pv.req_id)
        self.st, _ = kernels.install_coordinator(
            self.st, _b([g]), _b([cbal]), _b([next_slot]),
            jnp.asarray(cs), jnp.asarray(cl), jnp.asarray(ch), _valid(1))


def test_happy_path_three_replicas():
    """One full round: propose -> accept x3 -> replies -> decision -> commit.
    Mirrors SURVEY.md §3.1."""
    nodes = [KNode(i) for i in range(3)]
    for n in nodes:
        n.create(row=0, members=3, first_coord=0)

    st, slot, cbal = nodes[0].propose(0, req=1001)
    assert st == "granted" and slot == 0 and cbal == pack_ballot(0, 0)

    replies = []
    for n in nodes:
        acked, stale, ow, cur = n.accept(0, slot, cbal, 1001)
        assert acked and not stale and not ow
        replies.append((n.id, acked, cbal))

    decided_req = None
    for sender, acked, bal in replies:
        newly, pre, req = nodes[0].accept_reply(0, slot, bal, sender, acked)
        assert not pre
        if newly:
            assert decided_req is None, "decision emitted twice"
            decided_req = req
    assert decided_req == 1001  # quorum at 2nd reply

    for n in nodes:
        applied, stale, ow, cur = n.commit(0, slot, decided_req)
        assert applied and cur == 1
        assert int(n.st.exec_cursor[0]) == 1


def test_non_coordinator_propose_rejected():
    n = KNode(1)
    n.create(row=0, members=3, first_coord=0)
    st, _, _ = n.propose(0, req=5)
    assert st == "rejected"


def test_window_throttle():
    """Proposals beyond the W-window are throttled, not silently dropped."""
    n = KNode(0)
    n.create(row=0, members=1, first_coord=0)
    for k in range(W):
        st, slot, _ = n.propose(0, req=100 + k)
        assert st == "granted" and slot == k
    st, _, _ = n.propose(0, req=999)
    assert st == "throttled"
    # decide + commit slot 0 -> window advances -> propose succeeds
    cbal = pack_ballot(0, 0)
    acked, *_ = n.accept(0, 0, cbal, 100)
    newly, _, req = n.accept_reply(0, 0, cbal, 0, True)
    assert newly and req == 100
    applied, _, _, cur = n.commit(0, 0, 100)
    assert applied and cur == 1
    st, slot, _ = n.propose(0, req=999)
    assert st == "granted" and slot == W


def test_failover_with_carryover():
    """Coordinator 0 dies after getting slot 0 accepted at one node only;
    node 1 takes over via prepare and must re-propose the surviving pvalue.
    Mirrors SURVEY.md §3.5."""
    nodes = [KNode(i) for i in range(3)]
    for n in nodes:
        n.create(row=0, members=3, first_coord=0)
    b0 = pack_ballot(0, 0)

    # coordinator 0 proposes req 42, accept reaches ONLY node 2; 0 "dies"
    st, slot, cbal = nodes[0].propose(0, req=42)
    assert st == "granted" and slot == 0 and cbal == b0
    acked, *_ = nodes[2].accept(0, 0, b0, 42)
    assert acked

    # node 1 runs phase 1 at ballot (1, 1) on {1, 2}
    b1 = pack_ballot(1, 1)
    carry = {}
    next_slot = 0
    for n in (nodes[1], nodes[2]):
        acked, cur, cursor, window = n.prepare(0, b1)
        assert acked
        for s, (bal, req) in window.items():
            if s not in carry or bal > carry[s][0]:
                carry[s] = (bal, req)
            next_slot = max(next_slot, s + 1)
    assert carry == {0: (b0, 42)}

    carryover = [PValue(s, bal, req) for s, (bal, req) in carry.items()]
    nodes[1].install_coordinator(0, b1, next_slot, carryover)

    # re-propose carried pvalue at new ballot to {1, 2}
    decided = None
    for n in (nodes[1], nodes[2]):
        acked, *_ = n.accept(0, 0, b1, 42)
        assert acked
        newly, pre, req = nodes[1].accept_reply(0, 0, b1, n.id, acked)
        assert not pre
        if newly:
            decided = req
    assert decided == 42

    # stale coordinator 0 wakes and tries to propose slot 1 at old ballot:
    # acceptors nack (promise is b1), and the nack preempts it.
    st, slot1, _ = nodes[0].propose(0, req=77)
    assert st == "granted" and slot1 == 1
    acked, stale, ow, cur = nodes[1].accept(0, slot1, b0, 77)
    assert not acked and cur == b1
    newly, pre, _ = nodes[0].accept_reply(0, slot1, cur, 1, False)
    assert pre and not newly
    assert not bool(nodes[0].st.is_coord[0])


def test_stale_and_out_of_window_commits():
    n = KNode(0)
    n.create(row=0, members=1, first_coord=0)
    applied, stale, ow, cur = n.commit(0, W + 3, 7)   # far future
    assert ow and not applied
    applied, stale, ow, cur = n.commit(0, 0, 7)
    assert applied and cur == 1
    applied, stale, ow, cur = n.commit(0, 0, 7)       # replay
    assert stale and not applied and cur == 1


def test_out_of_order_commit_contiguity():
    """Decisions landing out of order only advance the cursor when the
    prefix is contiguous (extractExecuteAndCheckpoint semantics)."""
    n = KNode(0)
    n.create(row=0, members=1, first_coord=0)
    applied, _, _, cur = n.commit(0, 2, 72)
    assert applied and cur == 0
    applied, _, _, cur = n.commit(0, 1, 71)
    assert applied and cur == 0
    applied, _, _, cur = n.commit(0, 0, 70)
    assert applied and cur == 3


def _rand_stream_node(seed, n_ops=250):
    """Randomized single-lane stream applied to kernels AND oracle."""
    rng = np.random.default_rng(seed)
    node_id = 0
    kn = KNode(node_id)
    groups = [0, 1, 2, 3]
    coords = {0: 0, 1: 0, 2: 1, 3: 1}  # self coordinates groups 0,1
    oracles = {}
    for g in groups:
        kn.create(g, members=3, first_coord=coords[g])
        oracles[g] = make_oracle_group(
            3, W, pack_ballot(0, coords[g]), coords[g] == node_id)

    ballots = [pack_ballot(n, c) for n in range(3) for c in range(3)]
    for step in range(n_ops):
        g = int(rng.choice(groups))
        og = oracles[g]
        op = rng.choice(["accept", "propose", "accept_reply", "commit",
                         "prepare"])
        if op == "accept":
            slot = int(og.exec_cursor + rng.integers(-2, W + 2))
            bal = int(rng.choice(ballots))
            req = int(rng.integers(1, 1 << 40))
            got = kn.accept(g, slot, bal, req)
            want = og.accept(slot, bal, req)
            assert got == want, (step, op, g, slot, bal, got, want)
        elif op == "propose":
            req = int(rng.integers(1, 1 << 40))
            s_k = kn.propose(g, req)
            s_o = og.propose(req)
            assert s_k == s_o, (step, op, g, s_k, s_o)
        elif op == "accept_reply":
            slot = int(og.exec_cursor + rng.integers(-1, W))
            bal = int(rng.choice(ballots))
            sender = int(rng.integers(0, 3))
            acked = bool(rng.integers(0, 2))
            k_new, k_pre, k_req = kn.accept_reply(g, slot, bal, sender,
                                                  acked)
            o_new, o_pre, o_req = og.accept_reply(slot, bal, sender, acked)
            assert (k_new, k_pre) == (o_new, o_pre), (step, op, g, slot,
                                                      bal, sender, acked)
            if k_new:
                assert k_req == o_req
        elif op == "commit":
            slot = int(og.exec_cursor + rng.integers(-1, W + 1))
            req = og.prop_req.get(slot) or int(rng.integers(1, 1 << 40))
            got = kn.commit(g, slot, req)
            want = og.commit(slot, req)
            assert got == want, (step, op, g, slot, got, want)
        elif op == "prepare":
            bal = int(rng.choice(ballots))
            k_acked, k_bal, k_cur, k_win = kn.prepare(g, bal)
            o_acked, o_bal, o_cur, o_pvs = og.prepare(bal)
            o_win = {pv.slot: (pv.bal, pv.req_id) for pv in o_pvs}
            assert (k_acked, k_bal, k_cur) == (o_acked, o_bal, o_cur), (
                step, op, g, bal)
            assert k_win == o_win, (step, op, g, k_win, o_win)

    # terminal state spot-check
    for g in groups:
        og = oracles[g]
        assert int(kn.st.bal[g]) == og.bal
        assert int(kn.st.exec_cursor[g]) == og.exec_cursor
        assert int(kn.st.next_slot[g]) == og.next_slot
        assert bool(kn.st.is_coord[g]) == og.is_coord


def test_random_stream_equivalence_seed0():
    _rand_stream_node(0)


def test_random_stream_equivalence_seed1():
    _rand_stream_node(1)


def test_random_stream_equivalence_seed2():
    _rand_stream_node(2, n_ops=400)


def test_batched_proposals_get_distinct_slots():
    """Multiple proposals for one group in ONE batch get contiguous ranks."""
    n = KNode(0)
    n.create(0, members=1, first_coord=0)
    lo = _b([10, 20, 30], fill=0)
    hi = _b([0, 0, 0])
    n.st, o = kernels.propose(n.st, _b([0, 0, 0]), lo, hi, _valid(3))
    assert list(np.asarray(o.granted)[:3]) == [True, True, True]
    assert sorted(int(s) for s in np.asarray(o.slot)[:3]) == [0, 1, 2]
    assert int(n.st.next_slot[0]) == 3


def test_batched_accepts_promise_takes_batch_max():
    """Two accepts same group different ballots in one batch: only the max
    ballot is acked; promise ends at the max (one safe linearization)."""
    n = KNode(2)  # not coordinator; pure acceptor
    n.create(0, members=3, first_coord=0)
    bA, bB = pack_ballot(1, 1), pack_ballot(2, 2)
    n.st, o = kernels.accept(
        n.st, _b([0, 0]), _b([0, 1]), _b([bA, bB]), _b([1, 2]), _b([0, 0]),
        _valid(2))
    acked = list(np.asarray(o.acked)[:2])
    assert acked == [False, True]
    assert int(n.st.bal[0]) == bB


def test_quorum_crossing_in_one_batch_emits_once():
    """Two same-(group,slot) replies crossing quorum in ONE batch must emit
    exactly one decision (regression: pre-batch emitted gather let both
    lanes claim the crossing)."""
    n = KNode(0)
    n.create(0, members=3, first_coord=0)
    st, slot, cbal = n.propose(0, req=11)
    assert st == "granted"
    # both follower acks arrive in the same batch
    n.st, o = kernels.accept_reply(
        n.st, _b([0, 0]), _b([slot, slot]), _b([cbal, cbal]), _b([1, 2]),
        _b([True, True], jnp.bool_, fill=False), _valid(2))
    newly = list(np.asarray(o.newly_decided)[:2])
    assert sum(newly) == 1, newly


def test_inactive_rows_ignore_everything():
    n = KNode(0)  # row 5 never created
    acked, stale, ow, cur = n.accept(5, 0, pack_ballot(0, 0), 9)
    assert not acked and not stale and not ow
    applied, *_ = n.commit(5, 0, 9)
    assert not applied
    st, _, _ = n.propose(5, 9)
    assert st == "inactive"


# --------------------------------------------------------------------------
# the commit stage's frontier advance alone: rows set as a window can hold
# them, one commit a row, against the oracle's frontier on the same commits
# --------------------------------------------------------------------------

_NEAR_2_30 = (1 << 30) - 2  # cursors and frontiers on both sides of 2^30


def _advance_rows(kind, Wn):
    """``(cursor, {column: slot it holds}, slot committed)`` a row.  A
    column only ever holds a slot of its own residue: the slot it is
    expected to hold, the one a lap before (stale), the one a lap ahead,
    or ``NO_SLOT``.  Every kind but ``cursor_0`` takes a cursor at every
    residue of ``Wn``."""

    def ahead(cur, ds, lap=0):
        return {(cur + d) % Wn: cur + d + lap * Wn for d in ds}

    rows = []
    base = {"cursor_0": [0], "cursor_near_2_30": range(
        _NEAR_2_30, _NEAR_2_30 + Wn)}.get(kind, range(3 * Wn, 4 * Wn))
    for cur in base:
        for h in range(Wn):
            rest = set(range(Wn)) - {h}
            if kind == "all_decided":  # the last one arrives: advance W
                rows.append((cur, ahead(cur, rest), cur + h))
            elif kind == "none":  # 1 at the cursor, else 0
                rows.append((cur, {}, cur + h))
            elif kind in ("hole", "cursor_0", "cursor_near_2_30"):
                # all but cursor + h decided, the cursor's arrives
                rows.append((cur, ahead(cur, rest - {0}), cur))
            elif kind == "stale":  # the hole and all behind it a lap old
                rows.append((cur, {**ahead(cur, range(h)),
                                   **ahead(cur, range(h, Wn), -1)}, cur))
            elif kind == "future":  # the hole holds the next lap's slot
                rows.append((cur, {**ahead(cur, rest), **ahead(cur, [h], 1)},
                             cur))
            elif kind == "no_slot":  # decided below h, nothing from h on
                rows.append((cur, ahead(cur, range(h)), cur + h))
        if kind == "mixed":
            rng = np.random.default_rng(cur)
            for _ in range(Wn):
                cells = {}
                for d in range(Wn):
                    lap = rng.choice([0, 0, 0, -1, 1, None])
                    if lap is not None:
                        cells.update(ahead(cur, [d], int(lap)))
                rows.append((cur, cells, cur + int(rng.integers(-1, Wn + 1))))
    return rows


@pytest.mark.parametrize("Wn", [4, 5, 8, 16])
@pytest.mark.parametrize("kind", [
    "all_decided", "none", "hole", "stale", "future", "no_slot", "cursor_0",
    "cursor_near_2_30", "mixed"])
def test_commit_advance_equals_oracle_frontier(kind, Wn):
    rows = _advance_rows(kind, Wn)
    Bn = Wn * Wn  # one shape a W, whatever the kind
    assert 0 < len(rows) <= Bn
    dslot = np.full((Bn, Wn), NO_SLOT, np.int32)
    cursor = np.zeros((Bn,), np.int32)
    slot = np.zeros((Bn,), np.int32)
    want = []
    for i, (cur, cells, s) in enumerate(rows):
        cursor[i], slot[i] = cur, s
        for c, v in cells.items():
            assert v % Wn == c
            dslot[i, c] = v
        og = make_oracle_group(3, Wn, pack_ballot(0, 0), False)
        og.exec_cursor = cur
        # a column holds one slot: what the commit stores takes its place
        og.decided = {int(v): 1 for c, v in cells.items()
                      if not (c == s % Wn and cur <= s < cur + Wn)}
        want.append(og.commit(s, 1))
    st = make_state(Bn, Wn)
    st = st._replace(
        active=jnp.ones((Bn,), jnp.bool_), exec_cursor=jnp.asarray(cursor),
        dec=st.dec.at[:, :, DEC_SLOT].set(jnp.asarray(dslot)))
    valid = jnp.arange(Bn) < len(rows)
    one = jnp.ones((Bn,), i32)
    st, o = kernels.commit(st, jnp.arange(Bn, dtype=i32), jnp.asarray(slot),
                           one, one, valid)
    got = list(zip(*(np.asarray(a)[:len(rows)].tolist() for a in (
        o.applied, o.stale, o.out_window, o.new_cursor))))
    assert got == want
    assert np.asarray(st.exec_cursor)[:len(rows)].tolist() == [
        w[3] for w in want]
    if kind == "all_decided":
        assert all(w[3] == r[0] + Wn for w, r in zip(want, rows))


# --------------------------------------------------------------------------
# bodies handed a lane order (``kernels.lane_runs``) against the same bodies
# without one
# --------------------------------------------------------------------------


def _with_and_without_order(body, state, lanes, valid, order_valid=None,
                            distinct_slots=False):
    """Run ``body`` on the batch as given and on the batch in group order
    (``order_valid``: the lanes the order was made from, a superset of the
    call's own ``valid``).  The states must be equal field for field, and
    the outputs lane for lane after un-permuting, on the lanes that count
    (an invalid lane's outputs are padding)."""
    s1, o1 = body(state, *lanes, valid)
    runs, g2 = kernels.lane_runs(
        lanes[0], valid if order_valid is None else order_valid,
        distinct_slots=distinct_slots)
    *lanes2, valid2 = (a[runs.order] for a in (*lanes[1:], valid))
    lanes2.insert(0, g2)
    s2, o2 = body(state, *lanes2, valid2, runs)
    order, v = np.asarray(runs.order), np.asarray(valid)
    assert sorted(order) == list(range(len(v)))
    for f in s1._fields:
        np.testing.assert_array_equal(np.asarray(getattr(s2, f)),
                                      np.asarray(getattr(s1, f)), err_msg=f)
    for f in o1._fields:
        back = np.empty_like(np.asarray(getattr(o1, f)))
        back[order] = np.asarray(getattr(o2, f))
        np.testing.assert_array_equal(back[v], np.asarray(getattr(o1, f))[v],
                                      err_msg=f)
    return s1, o1


def _ordered_body_case(stage):
    from gigapaxos_tpu.ops.storm import make_fleet
    from gigapaxos_tpu.ops.types import NODE_BITS

    Gn, Wn, Bn = 32, 4, 96
    rng = np.random.default_rng(28)
    s0, s1, _s2 = make_fleet(Gn, Wn, R=3)
    # 96 lanes over 8 groups: runs of a dozen on windows of 4, so lanes
    # are throttled; invalid lanes scattered through
    g = jnp.asarray(rng.integers(0, 8, Bn).astype(np.int32))
    valid = jnp.asarray(rng.random(Bn) < 0.85)
    rlo = jnp.asarray(rng.integers(1, 1 << 30, Bn, dtype=np.int32))
    rhi = jnp.asarray(rng.integers(1, 1 << 30, Bn, dtype=np.int32))

    s0, pr = _with_and_without_order(kernels.propose_batch, s0,
                                     (g, rlo, rhi), valid)
    granted = np.asarray(pr.granted)
    assert 0 < granted.sum() < np.asarray(valid).sum()  # some throttled
    if stage == "propose":
        return

    # a fifth of the lanes carry a higher ballot: the promise is the run's
    # max, and the lanes below it are refused
    bal = pr.cbal + jnp.asarray(
        (rng.random(Bn) < 0.2).astype(np.int32) << NODE_BITS)
    if stage == "accept":
        _s, ao = _with_and_without_order(
            kernels.accept_batch, s1, (g, pr.slot, bal, rlo, rhi),
            pr.granted, order_valid=valid)
        acked = np.asarray(ao.acked)
        assert 0 < acked.sum() < granted.sum()
        return

    def twice(a):
        return jnp.concatenate([a, a])

    if stage == "reply_two_senders_one_batch":
        # senders 1 and 2 answer every granted lane in ONE batch: both
        # lanes of a (group, slot) cross quorum, one emits
        sender = jnp.concatenate([jnp.full_like(g, 1), jnp.full_like(g, 2)])
        acked = jnp.ones((2 * Bn,), jnp.bool_)
        _s, ro = _with_and_without_order(
            kernels.accept_reply_batch, s0,
            (twice(g), twice(pr.slot), twice(pr.cbal), sender, acked),
            twice(pr.granted))
        assert np.asarray(ro.newly_decided).sum() == granted.sum()
        return

    newly = None
    for sender in (0, 1):  # one sender a batch: a lane alone in its column
        s0, ro = _with_and_without_order(
            kernels.accept_reply_batch, s0,
            (g, pr.slot, pr.cbal, jnp.full_like(g, sender),
             jnp.ones((Bn,), jnp.bool_)), pr.granted, order_valid=valid,
            distinct_slots=True)
        newly = ro.newly_decided
    assert np.asarray(newly).sum() == granted.sum()
    if stage == "reply_one_sender_a_batch":
        return

    if stage == "commit_repeats_a_slot":
        _s, co = _with_and_without_order(
            kernels.commit_batch, s1,
            (twice(g), twice(pr.slot), twice(rlo), twice(rhi)), twice(newly))
        assert np.asarray(co.applied).sum() == 2 * granted.sum()
    else:
        _s, co = _with_and_without_order(
            kernels.commit_batch, s1, (g, pr.slot, rlo, rhi), newly,
            order_valid=valid, distinct_slots=True)
        assert np.asarray(co.applied).sum() == granted.sum()
    assert int(np.asarray(_s.exec_cursor).max()) == Wn


@pytest.mark.parametrize("stage", [
    "propose", "accept", "reply_two_senders_one_batch",
    "reply_one_sender_a_batch", "commit_repeats_a_slot",
    "commit_distinct_slots"])
def test_body_with_a_lane_order_equals_body_without(stage):
    _ordered_body_case(stage)
