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


def _reference_step(states, g, rlo, rhi, valid):
    """The step as it was before the lanes were put in group order: the
    same ten stages, each body called without an order, every lane where
    the caller put it.  The reference the ordered step must equal."""
    import jax.numpy as jnp
    from gigapaxos_tpu.ops import kernels

    R = len(states)
    new = list(states)
    new[0], pr = kernels.propose_batch(new[0], g, rlo, rhi, valid)
    slot, bal, granted = pr.slot, pr.cbal, pr.granted
    acks = []
    for r in range(R):
        new[r], ar = kernels.accept_batch(new[r], g, slot, bal, rlo, rhi,
                                          granted)
        acks.append(ar.acked)
    newly = jnp.zeros_like(granted)
    for r in range(R):
        new[0], rr = kernels.accept_reply_batch(
            new[0], g, slot, bal, jnp.full_like(g, r), acks[r], granted)
        newly = newly | rr.newly_decided
    for r in range(R):
        new[r], _ = kernels.commit_batch(new[r], g, slot, rlo, rhi, newly)
    return tuple(new), jnp.sum(newly.astype(jnp.int32))


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
    windows fill, throttle and wrap."""
    import jax
    import jax.numpy as jnp
    from gigapaxos_tpu.ops.storm import make_fleet, storm

    G, W, B, hit, p_valid, hot = ORDER_CASES[case]
    rng = np.random.default_rng(sorted(ORDER_CASES).index(case))
    reference = jax.jit(_reference_step)
    want, got = make_fleet(G, W, R=3), make_fleet(G, W, R=3)
    total = 0
    for it in range(5):
        g = rng.integers(0, hit, B).astype(np.int32)
        g[rng.choice(B, hot, replace=False)] = 3
        lanes = [jnp.asarray(a) for a in (
            g, rng.integers(1, 1 << 30, B, dtype=np.int32),
            rng.integers(1, 1 << 30, B, dtype=np.int32),
            rng.random(B) < p_valid)]
        want, n_want = reference(want, *lanes)
        got, n_got = storm(got, *lanes)
        assert int(n_got) == int(n_want), (case, it)
        total += int(n_got)
        for r in range(3):
            for f in want[r]._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(got[r], f)),
                    np.asarray(getattr(want[r], f)),
                    err_msg=f"{case}: step {it}, replica {r}, {f}")
    assert (total > 0) == (p_valid > 0)


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


def test_step_sorts_once_and_keeps_its_passes_down():
    """Counts only: every serial pass over the lanes is a sort or a
    scatter.  One sort a step (the lane order), no scatter into a
    lane-shaped array (what un-permuting a rank is), and 35 scatters that
    always run, each of ONE word a lane: propose 1 + 4 (``next_slot`` and
    the four proposal components), accept (1 + 4) x R, accept-reply
    1 x R (the vote add), commit (3 + 1) x R; the R resign scatters (two
    columns of the group table each) sit under a cond.  Every one that
    always runs states that its indices are unique, and writes the group
    table ``[G * 16]`` or a linear ``[G * W]`` component plane: there is
    no ``[G]`` array.  The table is read ONCE a stage call, 1 + 3 R row
    gathers (and once more for the cursor a commit answers with, which
    the step drops, and the compiler the read with it); no gather reads a
    lane-shaped operand and no scan runs along a window row."""
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
    assert len(always) == 5 + 10 * R, len(always)
    assert len(scatters) - len(always) == R
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
    assert sum(e.invars[0].aval.size == table
               for e in gathers) == 1 + 3 * R + R
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
