"""Plain reference of the served configuration: R replicas of a replicated
state machine, one log per group, every write applied once, in one order,
on every replica.

The app is the counter the configuration states (``CounterApp``): a group's
state is (count, digest); a write adds 1 to the count and mixes the request
id into the digest, which is not commutative, so a write lost, doubled or
reordered on any replica shows.  The answer to a write is the state after
it.  Imports nothing of the program and takes nothing it has made.

The closed-loop mixes never have two requests of one group outstanding, so
the order of a group's writes is the order they were sent in.

``broken`` makes the CONTROL: the same machine with one stated guarantee
taken away, to show that the comparison fails when it should:

- ``"lost_write"``: the last replica drops one acknowledged write (every
  acked write is executed on all three replicas);
- ``"reordered"``: the last replica applies two writes of one group in the
  other order (one order per group);
- ``"doubled"``: a retransmitted write is executed twice everywhere (a
  write is applied once);
- ``"stale_answer"``: one write is answered from the state before it
  (linearizable per group).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

MASK = 0xFFFFFFFFFFFFFFFF
State = Dict[str, Tuple[int, int]]  # group -> (count, digest)


def run_group(req_ids: Sequence[int]) -> List[Tuple[int, int]]:
    """One group's writes applied in order: the state after each."""
    c = d = 0
    out = []
    for rid in req_ids:
        c, d = c + 1, ((d * 1000003) ^ rid) & MASK
        out.append((c, d))
    return out


def replay(streams: Sequence[Sequence[Tuple[str, int]]], replicas: int = 3,
           broken: Optional[str] = None, victim: int = 0):
    """Apply ``streams`` (lists of (group, req_id), in send order, one after
    the other) on ``replicas`` replicas.  Returns (answers, states):
    ``answers[s][k]`` the (count, digest) request k of stream s is answered
    with, ``states[r]`` replica r's final state.  ``victim`` picks the
    request of the LAST stream that a ``broken`` guarantee hits;
    ``"reordered"`` swaps it with its group's write before it (after it,
    where it is the first) and raises ValueError where the group has no
    other write."""
    per_group: Dict[str, List[Tuple[int, int, int]]] = {}
    for si, stream in enumerate(streams):
        for k, (g, rid) in enumerate(stream):
            per_group.setdefault(g, []).append((si, k, rid))
    answers: List[List[Tuple[int, int]]] = [[(0, 0)] * len(st)
                                            for st in streams]
    state: State = {}
    for g, reqs in per_group.items():
        outs = run_group([rid for _si, _k, rid in reqs])
        for (si, k, _rid), out in zip(reqs, outs):
            answers[si][k] = out
        state[g] = outs[-1]
    states = [dict(state) for _ in range(replicas)]
    if broken is None:
        return answers, states

    last = len(streams) - 1
    g = streams[last][victim][0]
    reqs = per_group[g]
    rids = [rid for _si, _k, rid in reqs]
    i = [(si, k) for si, k, _rid in reqs].index((last, victim))
    if broken == "lost_write":
        rest = run_group(rids[:i] + rids[i + 1:])
        if rest:
            states[-1][g] = rest[-1]
        else:
            del states[-1][g]
    elif broken == "reordered":
        if len(rids) < 2:
            raise ValueError(f"group {g} has one write: nothing to reorder")
        j = i - 1 if i else i + 1
        rids[i], rids[j] = rids[j], rids[i]
        states[-1][g] = run_group(rids)[-1]
    elif broken == "doubled":
        outs = run_group(rids[:i + 1] + rids[i:])
        for (si, k, _rid), out in zip(reqs[i:], outs[i + 1:]):
            answers[si][k] = out
        for st in states:
            st[g] = outs[-1]
    elif broken == "stale_answer":
        answers[last][victim] = run_group(rids[:i])[-1] if i else (0, 0)
    else:
        raise ValueError(f"no control {broken!r}")
    return answers, states
