"""Fused decide-storm pipeline: the flagship device step.

One jitted call runs the ENTIRE §3.1 hot path for a batch of B requests
against an emulated R-replica fleet living on one chip:

    propose (coordinator) → accept ×R → accept_reply ×R (quorum count)
    → commit ×R (window frontier advance)

This is the BASELINE.json config-3 workload ("1M groups, batched
AcceptPacket storms") expressed the TPU way: instead of R processes
exchanging packets per slot, the whole pipeline is one XLA program — the
network hops that remain in a real deployment happen *between* storm steps
(host batcher ↔ transport), not inside them.  It is also the
``__graft_entry__`` forward step the driver compile-checks.

All replica states are donated; steady-state HBM traffic is just the
touched rows.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from gigapaxos_tpu.ops import kernels
from gigapaxos_tpu.ops.types import ColumnarState

i32 = jnp.int32


def decide_storm_step(states: Tuple[ColumnarState, ...], g, rlo, rhi,
                      valid):
    """Drive B request lanes to decision across R replica states.

    ``states[0]`` is the coordinator replica (its coordinator columns are
    active for all groups); all R states act as acceptors.  Returns
    ``(new_states, decided_count)`` where ``decided_count`` counts lanes
    whose quorum crossed in this step (== #granted lanes in steady state).
    """
    R = len(states)
    # ONE lane order for the whole step: every stage is handed the same
    # groups, and the step returns states and a count, so nothing is ever
    # permuted back.  The slots granted to a group are distinct and inside
    # one window, which is what `distinct_slots` vouches for.
    runs, g, rlo, rhi = kernels.lane_runs(g, valid, rlo, rhi,
                                          distinct_slots=True)
    s0 = states[0]
    s0, pr = kernels.propose_batch(s0, g, rlo, rhi, runs.valid, runs)
    slot, bal, granted = pr.slot, pr.cbal, pr.granted

    acks = []
    new_states = [s0] + list(states[1:])
    for r in range(R):
        sr, ar = kernels.accept_batch(new_states[r], g, slot, bal, rlo,
                                      rhi, granted, runs)
        new_states[r] = sr
        acks.append(ar.acked)

    newly = jnp.zeros_like(granted)
    for r in range(R):
        sender = jnp.full_like(g, r)
        s0 = new_states[0]
        s0, rr = kernels.accept_reply_batch(s0, g, slot, bal, sender,
                                            acks[r], granted, runs)
        new_states[0] = s0
        newly = newly | rr.newly_decided

    for r in range(R):
        sr, _cr = kernels.commit_batch(new_states[r], g, slot, rlo, rhi,
                                       newly, runs)
        new_states[r] = sr

    return tuple(new_states), jnp.sum(newly.astype(i32))


storm = jax.jit(decide_storm_step, donate_argnums=0)


def make_fleet(G: int, W: int, R: int = 3):
    """R replica states with all G rows active, members=R, node 0 the
    initial coordinator of every group (ballot (0,0))."""
    from gigapaxos_tpu.ops.types import make_state

    states = []
    rows = jnp.arange(G, dtype=i32)
    members = jnp.full((G,), R, i32)
    version = jnp.zeros((G,), i32)
    init_bal = jnp.zeros((G,), i32)  # pack_ballot(0, 0)
    valid = jnp.ones((G,), jnp.bool_)
    for r in range(R):
        st = make_state(G, W)
        self_coord = jnp.full((G,), r == 0)
        st, _ = kernels.create_groups(st, rows, members, version, init_bal,
                                      self_coord, valid)
        states.append(st)
    return tuple(states)
