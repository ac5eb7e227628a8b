"""The group table: the eleven per-group scalars as columns of one linear
word plane (``ColumnarState.grp``, word ``g * 16 + k``).

A value written by ``scatter_rows``, by ``create_groups_batch`` or by a
kernel's own one-word set comes back bit for bit through ``gather_rows``
(one 128-word row read, the lane's own columns masked out of it) and
through the read-only property, at the edge values; the seven groups that
share the written group's tile row keep what they held; the five spare
words of every group stay zero.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from gigapaxos_tpu.ops import kernels, make_state, pack_ballot
from gigapaxos_tpu.ops.types import (COL_DTYPE, GROUP_COLS, GROUP_WORDS,
                                     NO_BALLOT, NO_SLOT, RowState,
                                     with_columns)

W = 4
ROW = 3  # groups 0..7 share its 128-word tile row
INT_MAX = np.iinfo(np.int32).max
COLUMNS = [f for f, _, _ in GROUP_COLS]
# the values a column must carry unharmed
EDGES = {
    "active": (True, False), "is_coord": (True, False),
    "coord_active": (True, False),
    "members": (1, 30), "version": (0, INT_MAX),
    "bal": (NO_BALLOT, pack_ballot((1 << 19) - 1, 4095)),
    "cbal": (NO_BALLOT, pack_ballot((1 << 19) - 1, 4095)),
    "exec_cursor": (0, INT_MAX), "next_slot": (0, INT_MAX),
    "gc_slot": (NO_SLOT, INT_MAX - 1),
    "prep_votes": (0x80000001, 0xFFFFFFFF),  # bit 31 set: never converted
}


def _busy_state(G):
    """Every group holds values of its own in every column (none equal to
    a fresh row's or to an edge value), so a write that strays into a
    neighbour, or a read that takes a neighbour's column, shows."""
    g = np.arange(G)
    cols = {}
    for k, (f, dt, _) in enumerate(GROUP_COLS):
        if dt == np.bool_:
            cols[f] = jnp.asarray((g + k) % 2 == 0)
        else:
            cols[f] = jnp.asarray((1000 * (k + 1) + 7 * g).astype(dt))
    return with_columns(make_state(G, W), **cols)


def _host(st):
    return {f: np.asarray(getattr(st, f)) for f in COLUMNS}


def _check(st, before, col, want, rows=(ROW,)):
    """``col`` of ``rows`` reads ``want`` both ways, in its dtype; nothing
    else of the table moved; the spare words are zero."""
    rows = list(rows)
    got = jax.device_get(kernels.gather_rows(st, np.asarray(rows, np.int32)))
    view = np.asarray(getattr(st, col))
    want = np.asarray(want).astype(COL_DTYPE[col])
    for a in (getattr(got, col), view[rows]):
        assert a.dtype == COL_DTYPE[col]
        np.testing.assert_array_equal(a, np.broadcast_to(want, a.shape))
    after = _host(st)
    others = np.setdiff1d(np.arange(st.G), rows)
    for f in COLUMNS:
        np.testing.assert_array_equal(after[f][others], before[f][others],
                                      err_msg=f"{f} of a neighbour moved")
    table = np.asarray(st.grp).reshape(st.G, GROUP_WORDS)
    assert not table[:, len(GROUP_COLS):].any(), "a spare word was written"
    return after


@pytest.mark.parametrize("G", [16, 12])  # whole tile rows; rows of its own
@pytest.mark.parametrize("col", COLUMNS)
def test_scatter_rows_round_trips_the_edge_values(col, G):
    st = _busy_state(G)
    for edge in EDGES[col]:
        before = _host(st)
        row = jax.device_get(kernels.gather_rows(st, np.asarray([ROW])))
        row = row._replace(**{col: np.asarray([edge]).astype(COL_DTYPE[col])})
        st, _ = kernels.scatter_rows(
            st, jnp.asarray([ROW], jnp.int32),
            RowState(*[jnp.asarray(a) for a in row]), jnp.asarray([True]))
        after = _check(st, before, col, edge)
        for f in COLUMNS:  # the row's other columns went back as they were
            if f != col:
                assert after[f][ROW] == before[f][ROW], f


@pytest.mark.parametrize("col", COLUMNS)
def test_create_groups_writes_every_column(col):
    """A created row holds what ``create_groups_batch`` is given and a
    fresh row's value elsewhere; an invalid lane writes nothing."""
    G = 16
    st = _busy_state(G)
    before = _host(st)
    rows = np.asarray([ROW, 5, 9, 11], np.int32)
    valid = np.asarray([True, True, True, False])
    members = np.asarray([3, 30, 1, 5], np.int32)
    version = np.asarray([0, INT_MAX, 7, 9], np.int32)
    init_bal = np.asarray([0, pack_ballot(2, 4095), NO_BALLOT, 8], np.int32)
    self_coord = np.asarray([True, False, True, True])
    st, _ = kernels.create_groups(st, *map(jnp.asarray, (
        rows, members, version, init_bal, self_coord, valid)))
    want = {
        "active": True, "members": members[:3], "version": version[:3],
        "bal": init_bal[:3], "exec_cursor": 0, "gc_slot": NO_SLOT,
        "is_coord": self_coord[:3], "coord_active": self_coord[:3],
        "cbal": np.where(self_coord, init_bal, NO_BALLOT)[:3],
        "next_slot": 0, "prep_votes": 0,
    }[col]
    # every column of the created rows is written: hold the neighbours
    # (and the invalid lane's row) to what they held in all of them
    _check(st, before, col, want, rows=rows[:3])


def _lanes(n, **cols):
    """Batch arrays of n lanes: lane 0 is ROW's, the rest padding."""
    out = {"g": np.full(n, ROW, np.int32),
           "valid": np.arange(n) < 1}
    for k, v in cols.items():
        a = np.zeros(n, np.asarray(v).dtype)
        a[0] = v
        out[k] = a
    return {k: jnp.asarray(v) for k, v in out.items()}


def _accept(st, runs):
    bal = pack_ballot(9, 2)
    ln = _lanes(4, slot=st.exec_cursor[ROW], bal=bal, r=5)
    order = None
    if runs:
        order, g, *_ = kernels.lane_runs(ln["g"], ln["valid"])
        assert bool(order.valid[0])  # the valid lane sorts first
        ln["g"] = g
    st, out = kernels.accept_batch(st, ln["g"], ln["slot"], ln["bal"],
                                   ln["r"], ln["r"], ln["valid"], order)
    assert bool(out.acked[0]) and int(out.cur_bal[0]) == bal
    return st, bal


def _commit(st, runs):
    cur = int(st.exec_cursor[ROW])
    ln = _lanes(4, slot=cur, r=5)
    order = None
    if runs:
        order, g, *_ = kernels.lane_runs(ln["g"], ln["valid"],
                                         distinct_slots=True)
        ln["g"] = g
    st, out = kernels.commit_batch(st, ln["g"], ln["slot"], ln["r"],
                                   ln["r"], ln["valid"], order)
    assert bool(out.applied[0]) and int(out.new_cursor[0]) == cur + 1
    return st, cur + 1


def _propose(st, runs):
    nxt = int(st.exec_cursor[ROW]) + 1
    st = with_columns(st, next_slot=st.next_slot.at[ROW].set(nxt))
    ln = _lanes(4, r=5)
    order = None
    if runs:
        order, g, *_ = kernels.lane_runs(ln["g"], ln["valid"])
        ln["g"] = g
    st, out = kernels.propose_batch(st, ln["g"], ln["r"], ln["r"],
                                    ln["valid"], order)
    assert bool(out.granted[0]) and int(out.slot[0]) == nxt
    return st, nxt + 1


def _prepare(st):
    bal = pack_ballot(11, 1)
    ln = _lanes(4, bal=bal)
    st, out = kernels.prepare_batch(st, ln["g"], ln["bal"], ln["valid"])
    assert bool(out.acked[0]) and int(out.cur_bal[0]) == bal
    return st, bal


def _install(col):
    def run(st):
        ln = _lanes(4, cbal=EDGES["cbal"][1], next_slot=INT_MAX)
        none = jnp.full((4, W), NO_SLOT, jnp.int32)
        st, _ = kernels.install_coordinator_batch(
            st, ln["g"], ln["cbal"], ln["next_slot"], none, none, none,
            ln["valid"])
        return st, {"is_coord": True, "coord_active": True,
                    "cbal": EDGES["cbal"][1], "next_slot": INT_MAX}[col]
    return run


def _preempted(st):
    """A nack above the coordinator's ballot ends its reign."""
    ln = _lanes(4, slot=0, bal=int(st.cbal[ROW]) + 1, sender=1)
    st, out = kernels.accept_reply_batch(
        st, ln["g"], ln["slot"], ln["bal"], ln["sender"],
        jnp.zeros((4,), jnp.bool_), ln["valid"])
    assert bool(out.preempted[0])
    return st, False


def _delete(st):
    ln = _lanes(4)
    st, _ = kernels.delete_groups_batch(st, ln["g"], ln["valid"])
    return st, False


def _set_cursor(col):
    def run(st):
        ln = _lanes(4, cursor=INT_MAX - 5, next_slot=INT_MAX)
        st, _ = kernels.set_cursor_batch(st, ln["g"], ln["cursor"],
                                         ln["next_slot"], ln["valid"])
        return st, {"exec_cursor": INT_MAX - 5, "next_slot": INT_MAX}[col]
    return run


def _gc(st):
    ln = _lanes(4, upto=INT_MAX - 1)
    st, _ = kernels.gc_batch(st, ln["g"], ln["upto"], ln["valid"])
    return st, INT_MAX - 1


# every kernel that sets a column of the table, by the column it sets
# (``members``, ``version`` and ``prep_votes`` are written by creation and
# ``scatter_rows`` alone: the two tests above)
KERNEL_SETS = {
    "bal.accept": lambda st: _accept(st, False),
    "bal.accept.lane_order": lambda st: _accept(st, True),
    "bal.prepare": _prepare,
    "exec_cursor.commit": lambda st: _commit(st, False),
    "exec_cursor.commit.lane_order": lambda st: _commit(st, True),
    "exec_cursor.set_cursor": _set_cursor("exec_cursor"),
    "next_slot.propose": lambda st: _propose(st, False),
    "next_slot.propose.lane_order": lambda st: _propose(st, True),
    "next_slot.set_cursor": _set_cursor("next_slot"),
    "next_slot.install_coordinator": _install("next_slot"),
    "cbal.install_coordinator": _install("cbal"),
    "is_coord.install_coordinator": _install("is_coord"),
    "coord_active.install_coordinator": _install("coord_active"),
    "is_coord.accept_reply_preempted": _preempted,
    "coord_active.accept_reply_preempted": _preempted,
    "active.delete_groups": _delete,
    "is_coord.delete_groups": _delete,
    "coord_active.delete_groups": _delete,
    "gc_slot.gc": _gc,
}


@pytest.mark.parametrize("case", sorted(KERNEL_SETS))
def test_a_kernels_own_set_lands_in_its_column(case):
    col = case.split(".")[0]
    # ROW active and coordinating, at a cursor with room in its window
    st = _busy_state(16)
    st = with_columns(
        st, active=st.active.at[ROW].set(True),
        is_coord=st.is_coord.at[ROW].set(True),
        coord_active=st.coord_active.at[ROW].set(True))
    before = _host(st)
    st, want = KERNEL_SETS[case](st)
    after = _check(st, before, col, want)
    # of ROW's own columns only what the kernel sets moved
    moved = {f for f in COLUMNS if after[f][ROW] != before[f][ROW]}
    sets = {c.split(".")[0] for c in KERNEL_SETS
            if c.split(".", 1)[1] == case.split(".", 1)[1]}
    assert col in moved or want == before[col][ROW]
    assert moved <= sets, (moved, sets)


def test_fresh_state_and_views():
    """A fresh table holds each column's fresh value, the spare words
    zero; the views are ``[G]`` arrays of the fields' own dtypes."""
    st = make_state(24, W)
    assert len(jax.tree_util.tree_leaves(st)) == 12
    assert st.grp.shape == (24 * GROUP_WORDS,) and st.G == 24 and st.W == W
    for f, dt, fresh in GROUP_COLS:
        a = np.asarray(getattr(st, f))
        assert a.shape == (24,) and a.dtype == dt
        assert (a == np.asarray(fresh).astype(dt)).all(), f
    table = np.asarray(st.grp).reshape(24, GROUP_WORDS)
    assert not table[:, len(GROUP_COLS):].any()
