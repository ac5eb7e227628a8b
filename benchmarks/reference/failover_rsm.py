"""Plain reference of the failover configuration: R replicas of a replicated
counter per group, one of them crash-stopped inside the window, replayed
over the generator's own record (``loadgen_failover.py``): ids, groups,
send order, every retransmit and where it went, which requests were
unanswered at the kill.

What has to hold, restated for a failure (the configuration's
``guarantees``): every request the client got an answer to, before or after
the kill, is on all SURVIVING replicas; one order per group; a request is
applied once however often and to whichever replica it was retransmitted;
every request is answered; after the drain every group the victim led has
one live coordinator at a ballot above the victim's, on all survivors.

The mixes never have two requests of one group outstanding, so a group's
order is the order its requests were sent in, and the answer to each is the
state after it (``counter_rsm.run_group``).  Only a request never answered
may be in the order or not, but then the same way on every survivor.
Imports nothing of the program and takes nothing it has made.

``broken`` makes the CONTROLS: the same machine with one guarantee of a
failover taken away, to show that the comparison fails when it should:

- ``"lost_carryover"``: a request acknowledged just before the kill had
  been decided by the victim alone; the survivors held it accepted and
  undecided, and the new coordinator forgets it (no survivor ever has it);
- ``"doubled_retransmit"``: a request retransmitted to another replica is
  executed where it first went and again where it went next;
- ``"two_coordinators"``: one group the victim led ends with two survivors
  that each believe they coordinate it, at different ballots.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.reference.counter_rsm import run_group

NODE_BITS = 12  # a packed ballot is num << 12 | coordinator
CONTROLS = ("lost_carryover", "doubled_retransmit", "two_coordinators")
State = Dict[str, Tuple[int, int]]  # group -> (count, digest)


def orders(streams: Sequence[Sequence[Tuple[str, int]]]
           ) -> Dict[str, List[Tuple[int, int, int]]]:
    """Each group's requests (stream, place in it, id) in send order."""
    per_group: Dict[str, List[Tuple[int, int, int]]] = {}
    for si, stream in enumerate(streams):
        for k, (g, rid) in enumerate(stream):
            per_group.setdefault(g, []).append((si, k, rid))
    return per_group


def replay(streams, answered: Sequence[np.ndarray]):
    """Apply ``streams`` in send order.  ``answered[s][k]``: request k of
    stream s got an answer.  Returns (answers, states, ids): the (count,
    digest) each request is answered with; for each group the final states
    a survivor may be in (one, or two where the group's last request was
    never answered: with it and without it); the distinct ids a group
    has."""
    answers = [[(0, 0)] * len(st) for st in streams]
    states: Dict[str, List[Tuple[int, int]]] = {}
    ids: Dict[str, int] = {}
    for g, reqs in orders(streams).items():
        # a request never answered that is NOT its group's last cannot be:
        # the generator sends a group's next only after an answer
        rids = [rid for _si, _k, rid in reqs]
        outs = run_group(rids)
        for (si, k, _rid), out in zip(reqs, outs):
            answers[si][k] = out
        si, k, _rid = reqs[-1]
        states[g] = [outs[-1]]
        if not answered[si][k]:
            states[g].append(outs[-2] if len(outs) > 1 else (0, 0))
        ids[g] = len(set(rids))
    return answers, states, ids


def check(streams, results, replica_states: Sequence[State],
          ballots: Optional[np.ndarray], cbals: Optional[np.ndarray],
          survivors: Sequence[int], victim_ballot_num: int = 0):
    """The numbers compared, each with its limit (exact: 0).

    ``results``: for each stream what the generator kept (``t_recv``,
    ``status``, ``reply`` as (count, digest) or None); ``replica_states``:
    each SURVIVOR's final (count, digest) per group; ``ballots[i, j]`` /
    ``cbals[i, j]``: survivor i's promised ballot, and the ballot it
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
    diverged = twice = 0
    groups = set(want_states)
    for st in replica_states:
        groups |= set(st)
    for g in groups:
        have = [st.get(g) for st in replica_states]
        may = want_states.get(g, [])
        # the same on every survivor, and one of what the reference allows
        diverged += sum(h != have[0] or h not in may for h in have)
        twice += sum(max((h or (0, 0))[0] - ids.get(g, 0), 0) for h in have)
    out = [("answers_wrong", wrong, 0), ("answers_refused", refused, 0),
           ("never_answered", never, 0), ("executed_twice", twice, 0),
           ("replica_groups_diverged", diverged, 0)]
    if ballots is not None:
        out.append(("groups_without_coordinator",
                    int(np.sum(~one_coordinator(
                        ballots, cbals, survivors, victim_ballot_num))), 0))
    return out


def one_coordinator(ballots: np.ndarray, cbals: np.ndarray,
                    survivors: Sequence[int], above: int) -> np.ndarray:
    """For each group (a column): every survivor promised the same ballot,
    its number is above ``above``, its coordinator is a survivor, and that
    survivor, and no other, coordinates at it."""
    ballots, cbals = np.asarray(ballots), np.asarray(cbals)
    ids = np.asarray(list(survivors))[:, None]
    b = ballots[0]
    same = (ballots == b).all(axis=0)
    coord = b & ((1 << NODE_BITS) - 1)
    leads = (cbals == ballots) & (ballots >= 0) \
        & ((ballots & ((1 << NODE_BITS) - 1)) == ids)
    return same & ((b >> NODE_BITS) > above) & (leads.sum(axis=0) == 1) \
        & (leads & (ids == coord)).any(axis=0)


def pick_victim(broken: str, results, rng) -> Optional[int]:
    """The request of the window (the last stream) a control hits: for
    ``lost_carryover`` one acknowledged before the kill that went to the
    victim and stayed there; for ``doubled_retransmit`` one that was sent
    more than once.  None where the window has no such request."""
    res = results[-1]
    if broken == "lost_carryover":
        ok = (res["t_recv"] >= 0) & (res["status"] == 0)
        cand = np.flatnonzero(ok & (res["t_recv"] < res["t_kill"])
                              & (res["home"] == res["victim"]))
        cand = cand[-64:]  # the last before the kill: still in a window
    else:
        seqs, n = np.unique(res["sends"][:, 0].astype(np.int64),
                            return_counts=True)
        cand = seqs[n > 1]
    return int(rng.choice(cand)) if len(cand) else None


def broken_run(broken: str, streams, results, ballots, cbals,
               survivors: Sequence[int], victim: Optional[int]):
    """The broken machine in the program's place on the run's own requests:
    returns (results, replica_states, ballots, cbals) as :func:`check` takes
    them, answered as the broken machine answers."""
    answered = [np.asarray(r["t_recv"]) >= 0 for r in results]
    answers, states, _ids = replay(streams, answered)
    per_group = orders(streams)
    final = {g: sts[0] for g, sts in states.items()}
    replica_states = [dict(final) for _ in survivors]
    ballots, cbals = np.array(ballots), np.array(cbals)
    last = len(streams) - 1
    if broken == "two_coordinators":
        # a second survivor installed itself one ballot higher and the
        # first never heard of it
        other = 1 if (ballots[0, 0] & ((1 << NODE_BITS) - 1)) \
            == survivors[0] else 0
        up = ((int(ballots[0, 0]) >> NODE_BITS) + 1) << NODE_BITS \
            | survivors[other]
        ballots[other, 0] = cbals[other, 0] = up
    elif victim is None:
        raise ValueError(f"the window has no request for {broken!r}")
    else:
        g = streams[last][victim][0]
        reqs = per_group[g]
        rids = [rid for _si, _k, rid in reqs]
        i = [(si, k) for si, k, _rid in reqs].index((last, victim))
        if broken == "lost_carryover":
            outs = run_group(rids[:i] + rids[i + 1:])
            for (si, k, _rid), out in zip(reqs[i + 1:], outs[i:]):
                answers[si][k] = out
            for st in replica_states:
                if outs:
                    st[g] = outs[-1]
                else:
                    del st[g]
        elif broken == "doubled_retransmit":
            outs = run_group(rids[:i + 1] + rids[i:])
            for (si, k, _rid), out in zip(reqs[i:], outs[i + 1:]):
                answers[si][k] = out
            for st in replica_states:
                st[g] = outs[-1]
        else:
            raise ValueError(f"no control {broken!r}")
    fake = [dict(r, reply=a) for r, a in zip(results, answers)]
    return fake, replica_states, ballots, cbals
