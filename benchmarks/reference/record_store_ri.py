"""Plain reference of the read-mostly record store (``ycsb-b-3r-100k``): R
replicas of a replicated state machine per record in which only UPDATES take
places in the record's one order, and a read is answered from the record as
the first c updates left it, c being the count its reply states (reads by
read index: a read takes no place, Raft's ReadIndex).

A record is ``fields`` x ``field_bytes`` bytes, made from the seed, as in
``record_store.py``, whose loader and byte helpers this one shares.  ``R``
reads the whole record; ``U`` + field + bytes overwrites one field.  Every
reply begins with the 8-byte count of updates the record has executed: an
update's own place, and for a read the number of updates it saw.  Imports
nothing of the program and takes nothing it has made but the replies and
the replicas' checkpoints it is there to judge.

What holds, per record, over its answered operations:

- the updates, sorted by the count their replies state, take the places
  1, 2, 3 .. once each, in an order that does not run against real time
  (an update answered before another was first sent has the lower place);
- a read stating count c returns the seed record with the first c updates
  applied;
- c is at least the place of every update answered before the read was
  first sent (no read is older than real time allows);
- the first c updates were all first sent before the read was answered (no
  read sees an update from its future);
- every replica's checkpoint is the count and the record after the last
  place.

``broken`` makes the CONTROL: the same machine, fed the run's operations in
the order the run took, with one stated guarantee taken away:

- ``"stale_read"``: a read is answered one update older than real time
  allows (linearizable per record);
- ``"lost_update"``: the last replica drops one acknowledged update (every
  acked write is on all replicas);
- ``"doubled"``: an update is executed twice everywhere (applied once);
- ``"reordered"``: the last replica applies two updates of one field of one
  record in the other order (one order per record).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.reference.record_store import (  # noqa: F401
    apply, by_record, checkpoint_of, count_of, initial_records)

CONTROLS = ("stale_read", "lost_update", "doubled", "reordered")


def is_update(payload: bytes) -> bool:
    return payload[:1] == b"U"


def order_of(ops: Dict) -> Dict[int, Tuple[List[int], List[int]]]:
    """Each record's ANSWERED operations: its updates in the order their
    replies state (ties, which are faults, in the order sent), and its
    reads."""
    ok = (ops["t_recv"] >= 0) & (ops["status"] == 0)
    counts = [count_of(r) for r in ops["reply"]]
    out = {}
    for rec, ks in by_record(ops).items():
        ups = sorted((k for k in ks if ok[k] and is_update(ops["payload"][k])),
                     key=lambda k: counts[k])
        reads = [k for k in ks if ok[k] and not is_update(ops["payload"][k])]
        out[rec] = (ups, reads)
    return out


def least_places(ops: Dict, ups: List[int], ks: List[int]) -> np.ndarray:
    """For each operation of ``ks``: the highest place among the updates
    ``ups`` (in place order) answered before it was first sent (0: none),
    the least count a read sent then may state."""
    if not ups or not ks:
        return np.zeros(len(ks), np.int64)
    t_recv = ops["t_recv"][ups]
    by_time = np.argsort(t_recv, kind="stable")
    most = np.maximum.accumulate(by_time + 1)  # places are 1-based
    before = np.searchsorted(t_recv[by_time], ops["t_send"][ks], "left")
    return np.where(before > 0, most[np.maximum(before - 1, 0)], 0)


def check(initial: np.ndarray, ops: Dict, field_bytes: int,
          checkpoints: Sequence[Dict[int, bytes]], untouched_changed: int
          ) -> List[Tuple[str, int, int]]:
    """The numbers compared, each with its limit (exact: 0).

    ``ops``: every operation sent since the records were loaded, warm-up
    included (arrays ``record``, ``t_send``, ``t_recv``, ``status``; lists
    ``payload``, ``reply``).  ``checkpoints[r]``: replica r's checkpoint of
    every record an UPDATE addressed.  ``untouched_changed``: records no
    update addressed whose checkpoint on some replica is not the seed's."""
    t_send, t_recv, status = ops["t_send"], ops["t_recv"], ops["status"]
    never = int(np.sum(t_recv < 0))
    refused = int(np.sum((t_recv >= 0) & (status != 0)))
    wrong = misplaced = early = stale = future = diverged = 0
    sent = by_record(ops)
    for rec, (ups, reads) in order_of(ops).items():
        states = [initial[rec].tobytes()]
        sent_by = [-np.inf]  # latest first send among the first p updates
        latest = -np.inf
        for place, k in enumerate(ups, start=1):
            reply = ops["reply"][k]
            misplaced += count_of(reply) != place
            wrong += reply != checkpoint_of(place, b"")
            early += t_recv[k] < latest
            latest = max(latest, t_send[k])
            states.append(apply(states[-1], ops["payload"][k], field_bytes))
            sent_by.append(max(sent_by[-1], t_send[k]))
        for k, least in zip(reads, least_places(ops, ups, reads).tolist()):
            reply = ops["reply"][k]
            c = count_of(reply)
            if c >= len(states) or reply != checkpoint_of(c, states[c]):
                wrong += 1
                continue
            stale += c < least
            future += sent_by[c] > t_recv[k]
        if any(is_update(ops["payload"][k]) for k in sent[rec]):
            final = checkpoint_of(len(ups), states[-1])
            diverged += sum(cp.get(rec) != final for cp in checkpoints)
    return [("answers_wrong", int(wrong), 0),
            ("places_taken_twice", int(misplaced), 0),
            ("order_against_real_time", int(early), 0),
            ("reads_older_than_real_time", int(stale), 0),
            ("reads_from_the_future", int(future), 0),
            ("answers_refused", refused, 0),
            ("never_answered", never, 0),
            ("replica_groups_diverged", int(diverged), 0),
            ("writes_nobody_sent", int(untouched_changed), 0)]


def simulate(initial: np.ndarray, ops: Dict, field_bytes: int,
             replicas: int, order: Dict[int, Tuple[List[int], List[int]]],
             broken: Optional[str] = None, victim: Optional[int] = None):
    """The reference machine in the program's place: each record's updates
    applied in ``order``, each read answered with the count the run's reply
    states (where the program placed it) and the record after that many
    updates; returns (replies, checkpoints) as ``check`` takes them, the
    checkpoints of every record an update addressed.  ``victim`` (an index
    into ``ops``) is the operation a ``broken`` guarantee hits."""
    replies: List[Optional[bytes]] = [None] * len(ops["reply"])
    checkpoints: List[Dict[int, bytes]] = [{} for _ in range(replicas)]
    sent = by_record(ops)
    for rec, (ups, reads) in order.items():
        states = [initial[rec].tobytes()]
        count = 0
        for k in ups:
            times = 2 if broken == "doubled" and k == victim else 1
            count += times
            for _ in range(times):
                states.append(apply(states[-1], ops["payload"][k],
                                    field_bytes))
            replies[k] = checkpoint_of(count, b"")
        for k in reads:
            c = min(count_of(ops["reply"][k]), len(ups))
            if broken == "stale_read" and k == victim:
                c = int(least_places(ops, ups, [k])[0]) - 1
            replies[k] = checkpoint_of(c, states[c])
        if not any(is_update(ops["payload"][k]) for k in sent[rec]):
            continue
        for r in range(replicas):
            checkpoints[r][rec] = checkpoint_of(count, states[-1])
        if victim in ups and broken in ("lost_update", "reordered"):
            mine = list(ups)
            if broken == "lost_update":
                mine.remove(victim)
            else:  # swap with the update of the same field before it
                i = mine.index(victim)
                j = max(j for j in range(i) if ops["payload"][mine[j]][:2]
                        == ops["payload"][victim][:2])
                mine[i], mine[j] = mine[j], mine[i]
            state = initial[rec].tobytes()
            for k in mine:
                state = apply(state, ops["payload"][k], field_bytes)
            checkpoints[-1][rec] = checkpoint_of(len(mine), state)
    return replies, checkpoints


def pick_victim(initial: np.ndarray, ops: Dict, field_bytes: int,
                order: Dict[int, Tuple[List[int], List[int]]], broken: str,
                rng: np.random.Generator) -> int:
    """An operation on which taking ``broken``'s guarantee away shows: a
    read with an update answered before it was sent (``stale_read``); the
    LAST update of a field that has an earlier update with other bytes
    (``reordered``, and good for a lost or doubled update too)."""
    pool = []
    for rec, (ups, reads) in order.items():
        if broken == "stale_read":
            pool += [k for k, least in zip(
                reads, least_places(ops, ups, reads).tolist()) if least]
            continue
        last: Dict[bytes, int] = {}
        for k in ups:
            last[ops["payload"][k][:2]] = k
        for fld, k in last.items():
            if any(ops["payload"][j][:2] == fld
                   and ops["payload"][j] != ops["payload"][k]
                   for j in ups[:ups.index(k)]):
                pool.append(k)
    if not pool:
        raise ValueError(f"no operation on which {broken!r} would show")
    return int(pool[int(rng.integers(0, len(pool)))])
