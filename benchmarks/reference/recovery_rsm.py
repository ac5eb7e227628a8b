"""Plain reference of the recovery configuration: R replicas of a replicated
counter per group, one of them crash-stopped inside the window and started
again from its own log, replayed over the generator's own record
(``loadgen_failover.py``): ids, groups, send order, every retransmit.

What has to hold (the configuration's ``guarantees``), over ALL R replicas,
the restarted one included, once the drain and the catch-up have ended:
every request the client got an answer to (before the kill, while the
victim was dead, during its recovery) is applied on every replica; one order
per group; a request is applied once, however often it was retransmitted and
whatever restarted or jumped to a checkpoint in between; every request is
answered; the restarted node came up with every group it had, none below
its last checkpoint; every group the victim led has one live coordinator,
known to all R.

A group's order and answers are ``failover_rsm``'s (one outstanding a group,
so the order of sending; ``counter_rsm.run_group`` for the state after
each).  Imports nothing of the program and takes nothing it has made.

``broken`` makes the CONTROLS: the reference's own final states with one
guarantee taken away, to show that the comparison fails when it should:

- ``"stale_replica"``: the restarted replica never learned one group's last
  acknowledged request (nothing later came on that group, so no gap ever
  showed);
- ``"applied_twice"``: one request that was sent twice is executed twice, on
  every replica (decided in two slots).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from benchmarks.reference.counter_rsm import run_group
from benchmarks.reference.failover_rsm import (one_coordinator, orders,
                                               replay)

CONTROLS = ("stale_replica", "applied_twice")
State = Dict[str, Tuple[int, int]]  # group -> (count, digest)


def check(streams, results, replica_states: Sequence[State], restarted: int,
          recovery: Dict, ballots: Optional[np.ndarray],
          cbals: Optional[np.ndarray], nodes: Sequence[int]):
    """The numbers compared, each with its limit (exact: 0).

    ``results``: for each stream what the generator kept (``t_recv``,
    ``status``, ``reply`` as (count, digest) or None); ``replica_states``:
    every replica's final (count, digest) per group, in the order of
    ``nodes``; ``restarted``: the restarted replica's place in it;
    ``recovery``: ``live_groups`` the deployment has, ``groups_recovered``
    the node came up with, and for its groups ``checkpoint_slot`` (its last
    checkpoint before the kill) beside ``cursor_after_boot`` (first slot
    not executed when its recovery returned); ``ballots[i, j]`` /
    ``cbals[i, j]``: replica i's promised ballot, and the ballot it
    coordinates at, for the j-th group the victim led (packed)."""
    answered = [np.asarray(r["t_recv"]) >= 0 for r in results]
    want_ans, want_states, ids = replay(streams, answered)
    wrong = never = refused = 0
    for res, want, got_any in zip(results, want_ans, answered):
        for k, w in enumerate(want):
            if not got_any[k]:
                never += 1
            elif res["status"][k] != 0:
                refused += 1
            else:
                wrong += res["reply"][k] != w
    mismatch = twice = behind = 0
    groups = set(want_states)
    for st in replica_states:
        groups |= set(st)
    for g in groups:
        have = [st.get(g) for st in replica_states]
        may = want_states.get(g, [])
        # the same on every replica, and one of what the reference allows
        mismatch += sum(h != have[0] or h not in may for h in have)
        twice += sum(max((h or (0, 0))[0] - ids.get(g, 0), 0) for h in have)
        least = min((m[0] for m in may), default=0)
        behind += (have[restarted] or (0, 0))[0] < least
    ck = np.asarray(recovery["checkpoint_slot"], np.int64)
    cur = np.asarray(recovery["cursor_after_boot"], np.int64)
    out = [("answers_wrong", wrong, 0), ("answers_refused", refused, 0),
           ("never_answered", never, 0), ("executed_twice", twice, 0),
           ("state_mismatch", mismatch, 0),
           ("restarted_rows_behind", behind, 0),
           ("groups_not_recovered",
            int(recovery["live_groups"]) - int(recovery["groups_recovered"]),
            0),
           ("rolled_back_below_checkpoint", int(np.sum(cur < ck + 1)), 0)]
    if ballots is not None:
        out.append(("coordinators_missing",
                    int(np.sum(~one_coordinator(ballots, cbals, nodes, 0))),
                    0))
    return out


def pick_victim(broken: str, results, rng) -> Optional[int]:
    """The request of the window (the last stream) a control hits: for
    ``stale_replica`` the last acknowledged one of some group; for
    ``applied_twice`` one that was sent more than once.  None where the
    window has no such request."""
    res = results[-1]
    if broken == "stale_replica":
        ok = np.flatnonzero((np.asarray(res["t_recv"]) >= 0)
                            & (np.asarray(res["status"]) == 0))
        groups = np.asarray(res["seq_group"])[ok]
        # the last acknowledged request of each group
        _g, first_rev = np.unique(groups[::-1], return_index=True)
        cand = ok[len(ok) - 1 - first_rev]
    else:
        seqs, n = np.unique(res["sends"][:, 0].astype(np.int64),
                            return_counts=True)
        cand = seqs[n > 1]
    return int(rng.choice(cand)) if len(cand) else None


def broken_run(broken: str, streams, results, n_replicas: int,
               restarted: int, victim: Optional[int]):
    """The broken machine in the program's place on the run's own requests:
    returns (results, replica_states) as :func:`check` takes them."""
    if victim is None:
        raise ValueError(f"the window has no request for {broken!r}")
    answered = [np.asarray(r["t_recv"]) >= 0 for r in results]
    answers, states, _ids = replay(streams, answered)
    final = {g: sts[0] for g, sts in states.items()}
    replica_states = [dict(final) for _ in range(n_replicas)]
    last = len(streams) - 1
    g = streams[last][victim][0]
    reqs = orders(streams)[g]
    rids = [rid for _si, _k, rid in reqs]
    i = [(si, k) for si, k, _rid in reqs].index((last, victim))
    if broken == "stale_replica":
        if i != len(reqs) - 1 and answered[reqs[-1][0]][reqs[-1][1]]:
            raise ValueError("not the group's last acknowledged request")
        outs = run_group(rids[:i])
        if outs:
            replica_states[restarted][g] = outs[-1]
        else:
            del replica_states[restarted][g]
    elif broken == "applied_twice":
        outs = run_group(rids[:i + 1] + rids[i:])
        for (si, k, _rid), out in zip(reqs[i:], outs[i + 1:]):
            answers[si][k] = out
        for st in replica_states:
            st[g] = outs[-1]
    else:
        raise ValueError(f"no control {broken!r}")
    fake = [dict(r, reply=a) for r, a in zip(results, answers)]
    return fake, replica_states
