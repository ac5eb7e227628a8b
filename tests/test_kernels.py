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
from gigapaxos_tpu.ops.types import with_columns
from gigapaxos_tpu.ops.types import NO_SLOT, join_req_id, split_req_id
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
    st = with_columns(
        make_state(Bn, Wn)._replace(dec_slot=jnp.asarray(dslot).reshape(-1)),
        active=jnp.ones((Bn,), jnp.bool_), exec_cursor=jnp.asarray(cursor))
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


# --------------------------------------------------------------------------
# the six bodies against ops/oracle.py on seeded random batches, outputs lane
# by lane and the [G, W, k] views column by column; the row exchange form
# --------------------------------------------------------------------------

SOUP_G, SOUP_W, SOUP_B = 16, 8, 32  # 128 words a plane: one tile row


class _Soup:
    """A seeded random history driven through the jitted kernels and, lane
    by lane in the batch's own linearization, through one OracleGroup a
    row: repeated groups, full windows, stale and out-of-window slots,
    lower and higher ballots, holes in the decided window, invalid lanes,
    inactive rows and rows another node coordinates.  The body under test
    may be handed a lane order (``runs``); its outputs are compared on
    every batch, the state's views once at the end."""

    def __init__(self, seed, check, runs):
        from gigapaxos_tpu.ops.types import NODE_BITS
        self.rng = np.random.default_rng(seed)
        self.check, self.runs, self.checked = check, runs, 0
        self.nb = NODE_BITS
        G, W = SOUP_G, SOUP_W
        st = make_state(G, W)
        rows = np.arange(G, dtype=np.int32)
        self.coord0 = rows % 4 != 3          # rows 3, 7, ..: node 1 leads
        self.live = rows < G - 2             # the last two never created
        init = np.where(self.coord0, pack_ballot(0, 0), pack_ballot(0, 1))
        st, _ = kernels.create_groups(
            st, jnp.asarray(rows), jnp.full((G,), 3, i32),
            jnp.zeros((G,), i32), jnp.asarray(init, i32),
            jnp.asarray(self.coord0), jnp.asarray(self.live))
        self.st = st
        self.og = {int(g): make_oracle_group(3, W, int(init[g]),
                                             bool(self.coord0[g]))
                   for g in rows[self.live]}
        self.req = 1 << 33  # request ids with both words in use
        self.inflight = {}   # (g, slot) -> (cbal, req): granted, undecided
        self.undecided = []  # (g, slot, req) decided, commit not yet sent

    # -- plumbing ----------------------------------------------------------

    def lanes(self, *cols, n=None):
        """Pad python columns to SOUP_B lanes; the padding is invalid."""
        n = len(cols[0]) if n is None else n
        assert n <= SOUP_B, n
        valid = np.zeros(SOUP_B, bool)
        valid[:n] = True
        out = []
        for c in cols:
            a = np.zeros(SOUP_B, np.int64)
            a[:n] = c
            out.append(a)
        # invalid lanes scattered through, not only at the end
        perm = self.rng.permutation(SOUP_B)
        return [a[perm] for a in out], valid[perm]

    def call(self, name, lanes, valid, distinct=False):
        """The body: jitted as the runtime calls it, or handed a lane
        order where it is the one under test with ``runs``."""
        arrs = [jnp.asarray(a, jnp.bool_ if a.dtype == bool else i32)
                for a in lanes]
        v = jnp.asarray(valid)
        if not (self.runs and name == self.check):
            self.st, o = getattr(kernels, name)(self.st, *arrs, v)
            return {f: np.asarray(x) for f, x in o._asdict().items()}
        runs, g2 = kernels.lane_runs(arrs[0], v, distinct_slots=distinct)
        rest = [a[runs.order] for a in arrs[1:]]
        self.st, o = getattr(kernels, name + "_batch")(
            self.st, g2, *rest, v[runs.order], runs)
        order, back = np.asarray(runs.order), {}
        for f, x in o._asdict().items():
            b = np.empty_like(np.asarray(x))
            b[order] = np.asarray(x)
            back[f] = b
        return back

    def expect(self, name, got, want, valid):
        """``want``: per output field, a dict lane -> value (lanes left
        out are padding or don't-care)."""
        if name != self.check:
            return
        for f, by_lane in want.items():
            for i, v in by_lane.items():
                assert valid[i]
                assert got[f][i] == v, (name, f, i, got[f][i], v)
                self.checked += 1

    def new_req(self):
        self.req += 0x100000003
        return self.req

    # -- the stages ----------------------------------------------------------

    def propose(self):
        n = SOUP_B - 4
        g = self.rng.integers(0, SOUP_G, n)
        g[: n // 2] = self.rng.integers(0, 3, n // 2)  # runs fill windows
        req = [self.new_req() for _ in range(n)]
        lo, hi = zip(*(split_req_id(r) for r in req))
        (g, lo, hi, req), valid = self.lanes(g, lo, hi, req)
        got = self.call("propose", (g, lo, hi), valid)
        want = {f: {} for f in ("granted", "rejected", "throttled", "slot",
                                "cbal")}
        granted = []
        for i in np.flatnonzero(valid):
            og = self.og.get(int(g[i]))
            status, slot, cbal = og.propose(int(req[i])) if og else (
                "inactive", None, None)
            for f in ("granted", "rejected", "throttled"):
                want[f][i] = status == f
            if status == "granted":
                want["slot"][i], want["cbal"][i] = slot, cbal
                granted.append((int(g[i]), slot, cbal, int(req[i])))
        self.expect("propose", got, want, valid)
        return granted

    def accept(self, granted):
        """The new proposals and the ones still undecided (oldest first: a
        lost accept or reply is sent again, as the host does), a tenth of
        them lost, and the odd ones."""
        self.inflight.update({(g, s): (b, r) for g, s, b, r in granted})
        again = sorted(self.inflight, key=lambda k: k[1])[:SOUP_B - 8]
        lanes = {k: self.inflight[k] for k in again
                 if self.rng.random() < 0.9}
        for g in self.rng.integers(0, SOUP_G, 8):  # the odd ones
            og = self.og.get(int(g))
            cur = og.exec_cursor if og else 0
            top = og.bal if og else 0
            if self.coord0[g]:  # its own ballot: stale, or past the window
                slot = int(self.rng.choice([cur - 1, cur - 2, cur + SOUP_W,
                                            cur + SOUP_W + 3]))
                bal = top
            else:  # node 1's rows: any slot at any ballot around the promise
                slot = int(self.rng.choice([cur - 1, cur + SOUP_W, cur + 2,
                                            cur + 5]))
                bal = int(self.rng.choice([top, top - (1 << self.nb),
                                           top + (1 << self.nb)]))
            if slot >= 0 and bal >= 0:
                lanes.setdefault((int(g), slot), (bal, self.new_req()))
        keys = list(lanes)[:SOUP_B]
        g, slot = zip(*keys)
        bal, req = zip(*(lanes[k] for k in keys))
        lo, hi = zip(*(split_req_id(r) for r in req))
        (g, slot, bal, lo, hi, req), valid = self.lanes(g, slot, bal, lo, hi,
                                                        req)
        got = self.call("accept", (g, slot, bal, lo, hi), valid)
        # the batch's linearization: a group's promise is the max over the
        # batch, then its lanes
        for gg in set(g[valid]):
            if int(gg) in self.og:
                self.og[int(gg)].prepare(int(bal[valid & (g == gg)].max()))
        want = {f: {} for f in ("acked", "stale", "out_window", "cur_bal")}
        acks = []
        for i in np.flatnonzero(valid):
            og = self.og.get(int(g[i]))
            if og is None:
                want["acked"][i] = want["stale"][i] = False
                want["out_window"][i] = False
                continue
            r = og.accept(int(slot[i]), int(bal[i]), int(req[i]))
            for f, v in zip(want, r):
                want[f][i] = v
            acks.append((int(g[i]), int(slot[i]), int(bal[i]), r[0], r[3]))
        self.expect("accept", got, want, valid)
        return acks

    def accept_reply(self, acks, sender):
        """One lane a (group, slot) a batch; a nack that preempts only on
        a group with no other lane in it."""
        lanes = {}
        for g, slot, bal, acked, cur_bal in acks:
            if self.coord0[g] and self.rng.random() < 0.85:
                lanes[g, slot] = (bal if acked else cur_bal, acked)
        for g in self.rng.integers(0, SOUP_G, 6):
            og = self.og.get(int(g))
            if og is None or any(k[0] == g for k in lanes):
                continue
            kind = self.rng.integers(0, 3)
            if kind == 0:    # a slot nobody proposed
                lanes[int(g), og.next_slot + 1] = (og.cbal, True)
            elif kind == 1:  # an older ballot's ack
                lanes[int(g), max(og.next_slot - 1, 0)] = (
                    og.cbal - (1 << self.nb), True)
            elif og.is_coord and self.rng.random() < 0.15:  # preempted
                lanes[int(g), max(og.next_slot - 1, 0)] = (
                    og.cbal + (1 << self.nb) + 1, False)
        keys = list(lanes)[:SOUP_B]
        if not keys:
            return []
        g, slot = zip(*keys)
        bal, acked = zip(*(lanes[k] for k in keys))
        (g, slot, bal, snd, acked), valid = self.lanes(
            g, slot, bal, [sender] * len(keys), acked)
        acked = acked.astype(bool)
        got = self.call("accept_reply", (g, slot, bal, snd, acked), valid,
                        distinct=True)
        want = {f: {} for f in ("newly_decided", "preempted", "req_lo",
                                "req_hi", "dec_slot")}
        decided = []
        # the batch's linearization: the acks count on the coordinator the
        # batch found, then every nack above its ballot resigns it
        led = {g: og.is_coord for g, og in self.og.items()}
        for i in sorted(np.flatnonzero(valid), key=lambda i: not acked[i]):
            og = self.og.get(int(g[i]))
            newly, pre, req = og.accept_reply(
                int(slot[i]), int(bal[i]), sender, bool(acked[i])) if og \
                else (False, False, None)
            if og and not acked[i]:
                pre = led[int(g[i])] and int(bal[i]) > og.cbal
            want["newly_decided"][i], want["preempted"][i] = newly, pre
            if newly:
                lo, hi = split_req_id(req)
                want["req_lo"][i], want["req_hi"][i] = lo, hi
                want["dec_slot"][i] = int(slot[i])
                decided.append((int(g[i]), int(slot[i]), req))
                self.inflight.pop((int(g[i]), int(slot[i])), None)
        self.expect("accept_reply", got, want, valid)
        return decided

    def commit(self):
        """Decisions not yet committed, a fifth of them held back a round
        (holes), with stale lanes and, on groups with no other lane,
        lanes beyond the window.  ``new_cursor`` is the group's frontier
        after the whole batch."""
        self.rng.shuffle(self.undecided)
        hold = [d for d in self.undecided if self.rng.random() < 0.2]
        send = [d for d in self.undecided if d not in hold][:SOUP_B - 6]
        hold += [d for d in self.undecided
                 if d not in hold and d not in send]
        self.undecided = hold
        lanes = {(g, s): r for g, s, r in send}
        for g in self.rng.integers(0, SOUP_G, 6):
            og = self.og.get(int(g))
            cur = og.exec_cursor if og else 0
            if any(k[0] == g for k in lanes):
                if cur > 0:
                    lanes[int(g), cur - 1] = self.new_req()
            else:
                lanes[int(g), cur + SOUP_W + int(g) % 2] = self.new_req()
        keys = list(lanes)[:SOUP_B]
        if not keys:
            return
        g, slot = zip(*keys)
        req = [lanes[k] for k in keys]
        lo, hi = zip(*(split_req_id(r) for r in req))
        (g, slot, lo, hi, req), valid = self.lanes(g, slot, lo, hi, req)
        got = self.call("commit", (g, slot, lo, hi), valid, distinct=True)
        want = {f: {} for f in ("applied", "stale", "out_window",
                                "new_cursor")}
        for i in np.flatnonzero(valid):
            og = self.og.get(int(g[i]))
            r = og.commit(int(slot[i]), int(req[i])) if og else (
                False, False, False, None)
            for f, v in zip(("applied", "stale", "out_window"), r):
                want[f][i] = v
        for i in np.flatnonzero(valid):
            if int(g[i]) in self.og:
                want["new_cursor"][i] = self.og[int(g[i])].exec_cursor
        self.expect("commit", got, want, valid)

    def prepare(self):
        g = self.rng.integers(0, SOUP_G, 12)
        bal = [(self.og[int(x)].bal if int(x) in self.og else 0) +
               int(self.rng.choice([-1, 0, 0, 0, 0, 1])) * (1 << self.nb)
               for x in g]
        bal = np.maximum(bal, 0)
        (g, bal), valid = self.lanes(g, bal)
        got = self.call("prepare", (g, bal), valid)
        for gg in set(g[valid]):  # the promise: the batch's max
            if int(gg) in self.og:
                self.og[int(gg)].prepare(int(bal[valid & (g == gg)].max()))
        want = {f: {} for f in ("acked", "cur_bal", "exec_cursor")}
        for i in np.flatnonzero(valid):
            og = self.og.get(int(g[i]))
            if og is None:
                want["acked"][i] = False
                continue
            acked, cur_bal, cursor, window = og.prepare(int(bal[i]))
            want["acked"][i], want["cur_bal"][i] = acked, cur_bal
            want["exec_cursor"][i] = cursor
            if self.check == "prepare":
                have = {}
                for w in range(SOUP_W):
                    s = int(got["win_slot"][i, w])
                    if s >= cursor:
                        have[s] = (int(got["win_bal"][i, w]), join_req_id(
                            int(got["win_req_lo"][i, w]),
                            int(got["win_req_hi"][i, w])))
                newest = {pv.slot: (pv.bal, pv.req_id) for pv in window
                          if pv.slot + SOUP_W not in og.accepted}
                assert have == newest, (int(g[i]), have, newest)
        self.expect("prepare", got, want, valid)

    def install_coordinator(self):
        """Node 0 takes over the rows it does not lead (or lost): a
        ballot above the promise, the accepted window carried over."""
        rows = [g for g, og in self.og.items()
                if not (og.is_coord and og.coord_active)][:4]
        if not rows:
            return
        n = len(rows)
        cs = np.full((SOUP_B, SOUP_W), NO_SLOT, np.int32)
        cl = np.zeros((SOUP_B, SOUP_W), np.int32)
        ch = np.zeros((SOUP_B, SOUP_W), np.int32)
        (g, lane_of), valid = self.lanes(rows, np.arange(n))
        cbal, nxt = np.zeros(SOUP_B, np.int64), np.zeros(SOUP_B, np.int64)
        for i in np.flatnonzero(valid):
            og = self.og[int(g[i])]
            cbal[i] = pack_ballot((og.bal >> self.nb) + 1, 0)
            og.prepare(int(cbal[i]))
            carry = [pv for s, pv in sorted(og.accepted.items())
                     if s >= og.exec_cursor and s + SOUP_W not in og.accepted]
            nxt[i] = max([og.exec_cursor] + [pv.slot + 1 for pv in carry])
            for k, pv in enumerate(carry):
                cs[i, k] = pv.slot
                cl[i, k], ch[i, k] = split_req_id(pv.req_id)
                # re-proposed at the new ballot, as the host sends it
                self.inflight[int(g[i]), pv.slot] = (int(cbal[i]), pv.req_id)
            og.install_coordinator(int(cbal[i]), int(nxt[i]), carry)
            self.coord0[int(g[i])] = True
        self.st, _ = kernels.install_coordinator(
            self.st, jnp.asarray(g, i32), jnp.asarray(cbal, i32),
            jnp.asarray(nxt, i32), jnp.asarray(cs), jnp.asarray(cl),
            jnp.asarray(ch), jnp.asarray(valid))
        # the promise rides a prepare, as the host sends one first
        self.st, _ = kernels.prepare(
            self.st, jnp.asarray(g, i32), jnp.asarray(cbal, i32),
            jnp.asarray(valid))
        self.checked += n

    def run(self, rounds=12):
        for r in range(rounds):
            granted = self.propose()
            acks = self.accept(granted)
            for sender in (0, 1, 2):
                self.undecided += self.accept_reply(acks, sender)
            self.commit()
            self.prepare()
            if r % 2:
                self.install_coordinator()

    # -- the views -----------------------------------------------------------

    def views_equal_oracle(self):
        st, W = self.st, SOUP_W
        acc, dec, prop = (np.asarray(v) for v in (st.acc, st.dec, st.prop))
        assert acc.shape == (SOUP_G, W, 4) and dec.shape == (SOUP_G, W, 3)
        assert prop.shape == (SOUP_G, W, 4)
        for g, og in self.og.items():
            for f in ("bal", "exec_cursor", "next_slot", "cbal", "is_coord",
                      "coord_active"):
                assert int(getattr(st, f)[g]) == int(getattr(og, f)), (g, f)
            want_acc = np.tile(np.array([NO_SLOT, -1, 0, 0]), (W, 1))
            for s in sorted(og.accepted):
                pv = og.accepted[s]
                want_acc[s % W] = (s, pv.bal, *split_req_id(pv.req_id))
            np.testing.assert_array_equal(acc[g], want_acc, err_msg=f"acc {g}")
            want_dec = np.tile(np.array([NO_SLOT, 0, 0]), (W, 1))
            for s in sorted(og.decided):
                want_dec[s % W] = (s, *split_req_id(og.decided[s]))
            np.testing.assert_array_equal(dec[g], want_dec, err_msg=f"dec {g}")
            want_prop = np.tile(np.array([NO_SLOT, 0, 0, 0]), (W, 1))
            for s in sorted(og.votes):
                want_prop[s % W] = (
                    s, *split_req_id(og.prop_req[s]),
                    og.votes[s] | (og.emitted[s] << 30))
            np.testing.assert_array_equal(prop[g], want_prop,
                                          err_msg=f"prop {g}")
        for g in np.flatnonzero(~self.live):  # never created: fresh
            assert (acc[g, :, 0] == NO_SLOT).all() and not acc[g, :, 2:].any()
            assert (dec[g, :, 0] == NO_SLOT).all()
            assert (prop[g, :, 0] == NO_SLOT).all()


@pytest.mark.parametrize("body,runs", [
    ("propose", False), ("propose", True), ("accept", False),
    ("accept", True), ("accept_reply", False), ("accept_reply", True),
    ("commit", False), ("commit", True), ("prepare", False),
    ("install_coordinator", False)])
def test_body_equals_oracle_lane_by_lane(body, runs):
    soup = _Soup(seed=32, check=body, runs=runs)
    soup.run()
    assert soup.checked > 100 or body == "install_coordinator", soup.checked
    assert soup.checked > 0
    soup.views_equal_oracle()
    # the soup reached what it is for
    cursors = [og.exec_cursor for og in soup.og.values()]
    assert max(cursors) > SOUP_W, cursors  # windows wrapped


def _some_history():
    soup = _Soup(seed=7, check=None, runs=False)
    soup.run(rounds=4)
    return soup.st


def test_rows_round_trip_through_the_row_form():
    """gather_rows -> scatter_rows into a fresh state -> gather_rows: the
    row form carries everything, with the planes as [n, W, k]."""
    st = _some_history()
    rows = jnp.asarray([0, 1, 2, 5, 9], i32)
    got = kernels.gather_rows(st, rows)
    assert got.acc.shape == (5, SOUP_W, 4) and got.dec.shape == (5, SOUP_W, 3)
    assert got.prop.shape == (5, SOUP_W, 4)
    assert (np.asarray(got.acc)[..., 0] != NO_SLOT).any()
    for f in ("acc", "dec", "prop"):  # the row form IS the view's rows
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(st, f))[rows])
    # into other rows of a fresh state, one lane of them invalid
    to = jnp.asarray([3, 4, 8, 10, 11], i32)
    valid = jnp.asarray([True, True, True, False, True])
    fresh, _ = kernels.scatter_rows(make_state(SOUP_G, SOUP_W), to, got,
                                    valid)
    back = kernels.gather_rows(fresh, to)
    keep = np.asarray(valid)
    for f in got._fields:
        np.testing.assert_array_equal(np.asarray(getattr(back, f))[keep],
                                      np.asarray(getattr(got, f))[keep],
                                      err_msg=f)
    untouched = kernels.gather_rows(make_state(SOUP_G, SOUP_W), to)
    for f in got._fields:
        np.testing.assert_array_equal(np.asarray(getattr(back, f))[~keep],
                                      np.asarray(getattr(untouched, f))[~keep],
                                      err_msg=f)


def test_restore_row_takes_a_snapshot_in_the_old_row_form():
    """A snapshot dict as a pause blob written before PR 32 holds it:
    ``acc`` [W, 4], ``dec`` [W, 3], ``prop`` [W, 4] and the [G] fields by
    name, through JSON (lists of ints, flags as ints)."""
    import json
    from gigapaxos_tpu.paxos.backend import ColumnarBackend
    W = 8
    old = {
        "active": 1, "members": 3, "version": 2, "bal": pack_ballot(1, 2),
        "acc": [[s, pack_ballot(1, 2), 100 + s, 7] if s in (4, 5, 9)
                else [NO_SLOT, -1, 0, 0] for s in (8, 9, 2, 3, 4, 5, 6, 7)],
        "dec": [[s, 100 + s, 7] if s == 4 else [NO_SLOT, 0, 0]
                for s in (8, 9, 2, 3, 4, 5, 6, 7)],
        "exec_cursor": 5, "gc_slot": 3, "is_coord": 1, "coord_active": 0,
        "cbal": pack_ballot(1, 2), "next_slot": 10, "prep_votes": 5,
        "prop": [[9, 109, 7, 3 | (1 << 30)] if s == 9 else [NO_SLOT, 0, 0, 0]
                 for s in (8, 9, 2, 3, 4, 5, 6, 7)],
    }
    be = ColumnarBackend(capacity=16, window=W, mesh="off")
    be.restore_row(6, json.loads(json.dumps(old)))
    st = be.state
    assert np.asarray(st.acc)[6].tolist() == old["acc"]
    assert np.asarray(st.dec)[6].tolist() == old["dec"]
    assert np.asarray(st.prop)[6].tolist() == old["prop"]
    assert int(st.acc_rlo[6 * W + 1]) == 109 and int(st.dec_slot[6 * W + 4]) == 4
    assert int(st.prop_votes[6 * W + 1]) == 3 | (1 << 30)
    for f in ("members", "version", "bal", "exec_cursor", "gc_slot", "cbal",
              "next_slot", "prep_votes"):
        assert int(getattr(st, f)[6]) == old[f], f
    assert bool(st.active[6]) and bool(st.is_coord[6])
    assert not bool(st.coord_active[6])
    # its neighbours are as fresh as before
    assert (np.asarray(st.acc)[[5, 7], :, 0] == NO_SLOT).all()
    # and what the backend snapshots now is the same form, key for key
    snap = be.snapshot_rows([6])[0]
    assert set(snap) == set(old)
    for f, v in old.items():
        assert np.asarray(snap[f]).tolist() == v, f
