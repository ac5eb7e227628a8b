"""Fused decide-storm pipeline + sharded multi-chip path (virtual CPU
mesh; the driver's dryrun_multichip runs the same code)."""

import numpy as np
import pytest


def test_storm_decides_every_lane_once():
    import jax.numpy as jnp
    from gigapaxos_tpu.ops.storm import make_fleet, storm

    G, W, B = 256, 8, 64
    states = make_fleet(G, W, R=3)
    rng = np.random.default_rng(0)
    total = 0
    for it in range(4):
        g = jnp.asarray(rng.permutation(G)[:B].astype(np.int32))
        rlo = jnp.asarray(rng.integers(1, 1 << 30, B, dtype=np.int32))
        rhi = jnp.asarray(rng.integers(1, 1 << 30, B, dtype=np.int32))
        states, n = storm(states, g, rlo, rhi, jnp.ones((B,), bool))
        assert int(n) == B  # distinct groups, empty windows: all decide
        total += int(n)
    assert total == 4 * B
    # every replica's cursor advanced identically
    c0 = np.asarray(states[0].exec_cursor)
    for s in states[1:]:
        np.testing.assert_array_equal(c0, np.asarray(s.exec_cursor))


def test_storm_duplicate_groups_in_batch():
    import jax.numpy as jnp
    from gigapaxos_tpu.ops.storm import make_fleet, storm

    G, W, B = 16, 8, 32  # B > G: every group gets ~2 lanes
    states = make_fleet(G, W, R=3)
    g = jnp.asarray((np.arange(B) % G).astype(np.int32))
    rlo = jnp.asarray(np.arange(1, B + 1, dtype=np.int32))
    rhi = jnp.asarray(np.ones(B, np.int32))
    states, n = storm(states, g, rlo, rhi, jnp.ones((B,), bool))
    assert int(n) == B  # 2 slots per group, both decided
    np.testing.assert_array_equal(np.asarray(states[0].exec_cursor),
                                  np.full(G, 2))


def _composition(states, g, rlo, rhi, valid, ordered=False,
                 nack_promise=False):
    """The step's stages.  Unordered, the reference the storm step
    must equal: the step as it was before the lanes were put in group
    order, each body called without an order or a view, a reply stage a
    sender, every lane where the caller put it.  Ordered, the storm step
    as it runs them: one lane order, each replica's group view read once
    and handed from stage to stage, the proposal handed from propose to
    ONE reply stage that counts every sender's reply; it also answers
    with the valid lanes of the order and, after every stage, each column
    of the stage's view beside the same column read from the table the
    stage left.  ``nack_promise``: an acceptor
    answers a nack with its promise, as a served node does, so a nack
    above the coordinator's ballot resigns it (the storm's replies carry
    the coordinator's own ballot, which cannot)."""
    import jax.numpy as jnp
    from gigapaxos_tpu.ops import kernels
    from gigapaxos_tpu.ops.storm import ACCEPTOR_COLS, COORDINATOR_COLS

    R = len(states)
    new = list(states)
    seen = []
    if ordered:
        runs, g, rlo, rhi = kernels.lane_runs(g, valid, rlo, rhi,
                                              distinct_slots=True)
        valid = runs.valid
        views = [kernels.group_view(s, g, COORDINATOR_COLS if r == 0
                                    else ACCEPTOR_COLS)
                 for r, s in enumerate(states)]

    def stage(r, body, *lanes):
        if not ordered:
            new[r], out = body(new[r], *lanes)
            return out
        new[r], out, views[r] = body(new[r], *lanes, runs, views[r])
        table = kernels._group_words(new[r].grp, g)
        seen.append({f: (v, table(f)) for f, v in views[r].items()})
        return out

    pr = stage(0, kernels.propose_batch, g, rlo, rhi, valid)
    slot, bal, granted = pr.slot, pr.cbal, pr.granted
    acks = [stage(r, kernels.accept_batch, g, slot, bal, rlo, rhi, granted)
            for r in range(R)]
    replies = [jnp.where(ar.acked, bal, ar.cur_bal) if nack_promise else bal
               for ar in acks]
    if ordered:  # the coordinator counts every sender's reply at once
        newly = stage(0, kernels.accept_replies_batch, g, pr, replies,
                      [ar.acked for ar in acks])
    else:
        newly = jnp.zeros_like(granted)
        for r, (ar, reply) in enumerate(zip(acks, replies)):
            rr = stage(0, kernels.accept_reply_batch, g, slot, reply,
                       jnp.full_like(g, r), ar.acked, granted)
            newly = newly | rr.newly_decided
    for r in range(R):
        stage(r, kernels.commit_batch, g, slot, rlo, rhi, newly)
    return tuple(new), jnp.sum(newly.astype(jnp.int32)), (valid, seen)


def _assert_views_are_the_tables(valid, seen, where):
    """Every column of every stage's view is what the table holds for the
    lane's group, on the order's valid lanes."""
    v = np.asarray(valid)
    # propose, three accepts, the replies at once, three commits
    assert len(seen) == 1 + 3 + 1 + 3
    for k, cols in enumerate(seen):
        for f, (view, table) in cols.items():
            np.testing.assert_array_equal(
                np.asarray(view)[v], np.asarray(table)[v],
                err_msg=f"{where}: stage {k}, {f}")


def _assert_same_fleet(got, want, where):
    for r in range(len(want)):
        for f in want[r]._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got[r], f)),
                np.asarray(getattr(want[r], f)),
                err_msg=f"{where}, replica {r}, {f}")


# name: (G, W, B, groups the lanes fall on, share of valid lanes,
#        lanes forced onto group 3)
ORDER_CASES = {
    "unsorted": (256, 8, 64, 256, 1.0, 0),
    "duplicate_heavy": (64, 8, 128, 16, 1.0, 0),   # B lanes over B/8 groups
    "invalid_scattered": (64, 8, 128, 16, 0.7, 0),
    "over_window_on_one_group": (64, 8, 96, 64, 0.9, 40),
    "all_lanes_one_group": (16, 4, 32, 1, 1.0, 0),
    "no_valid_lane": (16, 4, 32, 16, 0.0, 0),
}


@pytest.mark.parametrize("case", sorted(ORDER_CASES))
def test_ordered_step_equals_the_unordered_composition(case):
    """Sorting the lanes by group once and running every stage in that
    order leaves every field of every replica, and the decided count, as
    the unordered bodies leave them: same slot per lane (lane order within
    the window), same promises, same planes — over several steps, so
    windows fill, throttle and wrap.  The views the step hands on are
    the tables: after every stage, each column of each replica's view is
    what a row read of its table would give, the step's last views
    included."""
    import functools

    import jax
    import jax.numpy as jnp
    from gigapaxos_tpu.ops.storm import make_fleet, storm

    G, W, B, hit, p_valid, hot = ORDER_CASES[case]
    rng = np.random.default_rng(sorted(ORDER_CASES).index(case))
    reference = jax.jit(_composition)
    viewed = jax.jit(functools.partial(_composition, ordered=True))
    want, got = make_fleet(G, W, R=3), make_fleet(G, W, R=3)
    total = 0
    for it in range(5):
        g = rng.integers(0, hit, B).astype(np.int32)
        g[rng.choice(B, hot, replace=False)] = 3
        lanes = [jnp.asarray(a) for a in (
            g, rng.integers(1, 1 << 30, B, dtype=np.int32),
            rng.integers(1, 1 << 30, B, dtype=np.int32),
            rng.random(B) < p_valid)]
        # the views of the step the storm takes, checked on a copy
        mirror, n_mirror, (v, seen) = viewed(got, *lanes)
        _assert_views_are_the_tables(v, seen, f"{case}: step {it}")
        want, n_want, _ = reference(want, *lanes)
        got, n_got = storm(got, *lanes)
        assert int(n_got) == int(n_want) == int(n_mirror), (case, it)
        total += int(n_got)
        _assert_same_fleet(got, want, f"{case}: step {it}")
        _assert_same_fleet(mirror, want, f"{case}: step {it}, viewed")
    assert (total > 0) == (p_valid > 0)


@pytest.mark.parametrize("nack_promise", [False, True],
                         ids=["coordinator_ballot", "promise_on_nack"])
def test_views_under_preemption(nack_promise):
    """Replicas 1 and 2 promise a higher ballot than the coordinator's on
    two overlapping sets of groups before the first step, so their
    accepts nack there and the quorum is lost where both took a group;
    on a fifth of the groups the coordinator runs at a ballot above the
    promise of an acceptor that took nothing, whose accepts raise its
    promise within the step, so its view's ``bal`` moves.  With the acceptor's promise on a nack, reply 1
    resigns the coordinator of its groups and reply 2, which acks the
    groups that only replica 1 took, must find ``is_coord`` cleared in
    the view: a stale view counts that vote and decides.  Every field of
    every replica and the decided count equal the unordered bodies',
    over several steps; every stage's view equals its table."""
    import functools

    import jax
    import jax.numpy as jnp
    from gigapaxos_tpu.ops import kernels
    from gigapaxos_tpu.ops.storm import make_fleet, storm
    from gigapaxos_tpu.ops.types import pack_ballot

    G, W, B = 64, 8, 96
    rng = np.random.default_rng(39)
    groups = np.arange(G, dtype=np.int32)
    took = {1: groups % 3 == 0, 2: groups % 2 == 0}

    def promised(states):
        states = list(states)
        # the coordinator of a fifth of the groups at ballot (1, 0), which
        # the acceptors that promised nothing higher take on its accepts
        states[0], _ = kernels.install_coordinator(
            states[0], jnp.asarray(groups),
            jnp.full((G,), pack_ballot(1, 0), jnp.int32),
            jnp.zeros((G,), jnp.int32), *[jnp.full((G, W), -1, jnp.int32)] * 3,
            jnp.asarray(groups % 5 == 0))
        for r, mask in took.items():
            states[r], po = kernels.prepare(
                states[r], jnp.asarray(groups),
                jnp.full((G,), pack_ballot(1, r), jnp.int32),
                jnp.asarray(mask))
            assert np.asarray(po.acked).sum() == mask.sum()
        return tuple(states)

    step = functools.partial(_composition, nack_promise=nack_promise)
    reference = jax.jit(step)
    viewed = jax.jit(functools.partial(step, ordered=True))
    want = promised(make_fleet(G, W, R=3))
    got = promised(make_fleet(G, W, R=3))
    total = 0
    for it in range(4):
        lanes = [jnp.asarray(a) for a in (
            rng.integers(0, G, B).astype(np.int32),
            rng.integers(1, 1 << 30, B, dtype=np.int32),
            rng.integers(1, 1 << 30, B, dtype=np.int32),
            rng.random(B) < 0.9)]
        want, n_want, _ = reference(want, *lanes)
        if nack_promise:
            got, n_got, (v, seen) = viewed(got, *lanes)
        else:
            _m, _n, (v, seen) = viewed(got, *lanes)
            got, n_got = storm(got, *lanes)
        _assert_views_are_the_tables(v, seen, f"step {it}")
        assert int(n_got) == int(n_want), it
        total += int(n_got)
        _assert_same_fleet(got, want, f"step {it}")
    assert 0 < total
    coord, cursor = (np.asarray(f) for f in (got[0].is_coord,
                                             got[0].exec_cursor))
    both, only1 = took[1] & took[2], took[1] & ~took[2]
    assert (cursor[both] == 0).all()  # two nacks of three: no quorum
    # with a promise on the nack, reply 1 resigns the groups only replica
    # 1 took before reply 2's ack can count; without one they decide
    assert coord[only1].all() != nack_promise
    assert (cursor[only1] == 0).all() == nack_promise
    assert coord[~(took[1] | took[2])].all()
    raised = (groups % 5 == 0) & ~took[1] & (cursor > 0)
    assert raised.any()
    assert (np.asarray(got[1].bal)[raised] == pack_ballot(1, 0)).all()


# name: (G, W, B, groups the lanes fall on, share of valid lanes, lanes
#        forced onto group 3, replicas, what the replies are)
REPLY_CASES = {
    **{c: (*ORDER_CASES[c], 3, "random") for c in ORDER_CASES},
    # sender 1 nacks above the ballot on the even groups, and sender 2,
    # after it, acks: its vote must not count there
    "nack_above_then_ack": (64, 8, 96, 64, 1.0, 0, 3, "preempt"),
    # a third of the groups have no coordinator here, and 12 lanes a
    # group fill the window of 8: lanes that were not granted
    "not_granted": (64, 8, 128, 12, 0.9, 0, 3, "random"),
    "five_senders": (64, 8, 96, 32, 0.9, 0, 5, "random"),
}


def _replies_both_ways(states, g, rlo, rhi, valid, acks, bump):
    """One propose of a coordinator with a lane order, then the replies
    of ``len(acks)`` senders to it, both ways: ONE
    ``accept_replies_batch`` handed the proposal and the view, and R
    calls of ``accept_reply_batch`` in sender order after a propose
    without a view, which wrote the vote words itself.  Sender r's reply
    carries the coordinator's ballot plus ``bump[r]``.  Returns each
    side's state and decided lanes, with the view on one side and the
    same columns read from the table on the other, and the order's
    valid lanes."""
    import jax.numpy as jnp
    from gigapaxos_tpu.ops import kernels
    from gigapaxos_tpu.ops.storm import COORDINATOR_COLS

    runs, g, rlo, rhi = kernels.lane_runs(g, valid, rlo, rhi,
                                          distinct_slots=True)
    view = kernels.group_view(states[0], g, COORDINATOR_COLS)
    one, pr, view = kernels.propose_batch(states[0], g, rlo, rhi,
                                          runs.valid, runs, view)
    each, pr_each = kernels.propose_batch(states[0], g, rlo, rhi,
                                          runs.valid, runs)
    bals = [pr.cbal + b for b in bump]
    one, newly, view = kernels.accept_replies_batch(
        one, g, pr, bals, list(acks), runs, view)
    newly_each = jnp.zeros_like(pr.granted)
    for r, (a, b) in enumerate(zip(acks, bals)):
        each, rr = kernels.accept_reply_batch(
            each, g, pr.slot, b, jnp.full_like(g, r), a, pr.granted, runs)
        newly_each = newly_each | rr.newly_decided
    table = kernels._group_words(each.grp, g)
    return (one, newly, view), \
        (each, newly_each, {f: table(f) for f in view}), \
        (pr, pr_each, runs.valid)


@pytest.mark.parametrize("case", sorted(REPLY_CASES))
def test_replies_at_once_equal_the_calls_in_sender_order(case):
    """The storm's reply stage, which counts every sender's reply to the
    proposal its step just made in registers and sets each granted
    lane's vote word once, leaves every plane and the table, and answers
    the same decided lanes, as one ``accept_reply_batch`` a sender in
    sender order, bit for bit, and its view is the table those calls
    leave: over several steps (windows wrap over vote words of earlier
    slots, and the planes start with junk in them), with nacks above the
    coordinator's ballot that resign it for the senders after them, lanes
    that were not granted, and all lanes on one group."""
    import jax
    import jax.numpy as jnp
    from gigapaxos_tpu.ops import kernels
    from gigapaxos_tpu.ops.storm import make_fleet, storm

    G, W, B, hit, p_valid, hot, R, replies = REPLY_CASES[case]
    rng = np.random.default_rng(43 + sorted(REPLY_CASES).index(case))
    fleet = list(make_fleet(G, W, R=R))
    coord = fleet[0]._replace(**{f: jnp.asarray(rng.integers(
        -9, 1 << 30, G * W, dtype=np.int32)) for f in (
            "prop_slot", "prop_votes", "prop_rlo", "prop_rhi")})
    if case == "not_granted":
        rows = jnp.asarray(np.arange(0, G, 3, dtype=np.int32))
        coord = coord._replace(grp=kernels._set_cols(
            coord.grp, rows, is_coord=False, coord_active=False))
    fleet[0] = coord
    both = jax.jit(_replies_both_ways)
    decided = resigned = 0
    for it in range(4):
        g = rng.integers(0, hit, B).astype(np.int32)
        g[rng.choice(B, hot, replace=False)] = 3
        lanes = [jnp.asarray(a) for a in (
            g, rng.integers(1, 1 << 30, B, dtype=np.int32),
            rng.integers(1, 1 << 30, B, dtype=np.int32),
            rng.random(B) < p_valid)]
        if replies == "preempt":  # in the lane order, by the lane's group
            even = np.sort(g) % 2 == 0
            acks = np.ones((R, B), bool)
            acks[1] = ~even
            bump = np.zeros((R, B), np.int32)
            bump[1] = even
        else:
            acks = rng.random((R, B)) < 0.8
            bump = np.where(acks, rng.random((R, B)) < 0.05,
                            rng.random((R, B)) < 0.1).astype(np.int32)
        one, each, (pr, pr_each, v) = both(
            tuple(fleet), *lanes, jnp.asarray(acks), jnp.asarray(bump))
        where = f"{case}: step {it}"
        for a, b in zip(pr, pr_each):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        (st1, newly1, view), (st2, newly2, table) = one, each
        np.testing.assert_array_equal(np.asarray(newly1), np.asarray(newly2),
                                      err_msg=where)
        _assert_same_fleet((st1,), (st2,), where)
        v = np.asarray(v)
        for f in view:
            np.testing.assert_array_equal(
                np.asarray(view[f])[v], np.asarray(table[f])[v],
                err_msg=f"{where}, view {f}")
        decided += int(np.asarray(newly1).sum())
        resigned += int((np.asarray(fleet[0].is_coord)
                         & ~np.asarray(st1.is_coord)).sum())
        if replies == "preempt":
            lost = np.asarray(pr.granted) & even
            assert lost.any() and not np.asarray(newly1)[lost].any()
            assert not np.asarray(view["is_coord"])[lost].any()
        # the fleet moves on by the storm step, so windows fill and wrap
        fleet = list(storm(tuple(fleet), *lanes)[0])
    assert (decided > 0) == (p_valid > 0), case
    assert (resigned > 0) == (p_valid > 0), case


def _count_primitives(jaxpr, out, under_cond=False):
    """Equations by primitive, those under a ``cond`` apart: a branch
    runs only when its predicate holds."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        out.append((name, under_cond, eqn))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count_primitives(sub, out, under_cond or name == "cond")


def _gathers_of_planes(jaxpr, held):
    """The gathers whose operand is one of the variables ``held`` or a
    version of one: what a scatter into it, or a reshape of it, made of
    it.  Followed into ``jit`` and ``cond`` bodies, whose operands are
    their equation's (a cond's after its predicate)."""
    held, found = set(held), []
    for eqn in jaxpr.eqns:
        ins = [v in held if not hasattr(v, "val") else False
               for v in eqn.invars]
        if not any(ins):
            continue
        if eqn.primitive.name == "gather":
            if ins[0]:
                found.append(eqn)
            continue
        subs = [getattr(b, "jaxpr", b) for b in eqn.params.get(
            "branches", [eqn.params.get("jaxpr")]) if b is not None]
        for sub in subs:
            off = len(ins) - len(sub.invars)
            found += _gathers_of_planes(
                sub, [v for v, h in zip(sub.invars, ins[off:]) if h])
        size = next(v.aval.size for v, h in zip(eqn.invars, ins) if h)
        held.update(v for v in eqn.outvars if v.aval.size == size)
    return found


def test_step_sorts_once_and_keeps_its_passes_down():
    """Counts only: every serial pass over the lanes is a sort or a
    scatter.  One sort a step (the lane order), no scatter into a
    lane-shaped array (what un-permuting a rank is), and 32 scatters that
    always run, each of ONE word a lane: propose 1 + 3 (``next_slot`` and
    the proposal's slot and request id; its vote word is the reply
    stage's), accept (1 + 4) x R, the replies' ONE vote set, commit
    (3 + 1) x R; the resign scatter (two columns of the group table) sits
    under a cond.  The replies read no proposal word: no gather in the
    step reads ``prop_slot`` or ``prop_votes``.  Every one that
    always runs states that its indices are unique, and writes the group
    table ``[G * 16]`` or a linear ``[G * W]`` component plane: there is
    no ``[G]`` array.  The table is read ONCE a replica a step, R row
    gathers where each stage call read it (1 + 3 R, and R more for the
    cursor a commit answered with): the coordinator's view carries
    ``active``, ``is_coord``, ``coord_active``, ``cbal``, ``next_slot``,
    ``exec_cursor``, ``bal`` and ``members`` through its propose, accept,
    replies and commit; an acceptor's carries ``active``, ``bal`` and
    ``exec_cursor`` through its accept and commit; each stage sets what
    it writes in the view too.  No gather reads a lane-shaped operand and
    no scan runs along a window row."""
    import jax
    import jax.numpy as jnp
    from gigapaxos_tpu.ops.storm import decide_storm_step
    from gigapaxos_tpu.ops.types import make_state

    G, W, B, R = 64, 8, 16, 3
    st = jax.eval_shape(lambda: make_state(G, W))
    lane = jax.ShapeDtypeStruct((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(decide_storm_step)(
        (st,) * R, lane, lane, lane, jax.ShapeDtypeStruct((B,), jnp.bool_))
    eqns = []
    _count_primitives(jaxpr.jaxpr, eqns)
    assert sum(n == "sort" for n, _, _ in eqns) == 1
    scatters = [(c, e) for n, c, e in eqns if n.startswith("scatter")]
    always = [e for c, e in scatters if not c]
    assert len(always) == 5 + 9 * R, len(always)
    assert len(scatters) - len(always) == 1
    table = 16 * G
    assert sum(e.invars[0].aval.shape == (table,) for e in always) == \
        1 + 2 * R  # next_slot; bal and exec_cursor of each replica
    for e in always:
        assert e.invars[0].aval.shape in ((table,), (G * W,)), e
        assert e.invars[2].aval.shape == (B,), e  # a word a lane
        assert e.params["unique_indices"], e
    # every gather reads a state array and every scan runs along the
    # lanes: the commit stage counts its frontier's advance on the
    # [B, W] row where it lies, with a compare and a row min
    gathers = [e for n, _, e in eqns if n == "gather"]
    assert gathers
    for e in gathers:  # the plane may be seen as rows of 128 words
        assert e.invars[0].aval.size in (table, G * W), e
    assert sum(e.invars[0].aval.size == table for e in gathers) == R
    leaves = jaxpr.jaxpr.invars
    n = len(st)  # a replica's leaves, in ColumnarState's field order
    assert not _gathers_of_planes(jaxpr.jaxpr, [
        leaves[r * n + st._fields.index(f)]
        for r in range(R) for f in ("prop_slot", "prop_votes")])
    for n, _, e in eqns:
        if n.startswith("cum"):
            assert e.invars[0].aval.shape == (B,), e


def test_sharded_storm_on_virtual_mesh():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices (virtual cpu mesh)")
    import jax.numpy as jnp
    from gigapaxos_tpu.ops.storm import make_fleet
    from gigapaxos_tpu.parallel.sharding import (make_group_mesh,
                                                 make_sharded_storm,
                                                 shard_fleet)

    n = 4
    G, W, B = 64 * n, 8, 96
    mesh = make_group_mesh(n)
    states = shard_fleet(make_fleet(G, W, R=3), mesh)
    storm = make_sharded_storm(mesh, n_replicas=3)
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.permutation(G)[:B].astype(np.int32))
    rlo = jnp.asarray(rng.integers(1, 1 << 30, B, dtype=np.int32))
    rhi = jnp.asarray(rng.integers(1, 1 << 30, B, dtype=np.int32))
    valid = jnp.ones((B,), bool)
    states, decided = storm(states, g, rlo, rhi, valid)
    assert int(decided) == B
    # same groups again: new slots assigned, decided again
    states, decided2 = storm(states, g, rlo, rhi, valid)
    assert int(decided2) == B


def test_graft_entry_single_chip():
    import jax
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert int(out[1]) > 0


def test_graft_dryrun_multichip():
    # No skip: dryrun_multichip self-provisions a virtual 8-device CPU
    # platform in a subprocess when this process has fewer devices, which
    # is exactly what the driver's external MULTICHIP check relies on.
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)
