"""Plain reference of the record store (``ycsb-a-3r-100k``): R replicas of a
replicated state machine per record, one order per record, every operation
applied once, in that order, on every replica; reads are operations of that
order like updates.

A record is ``fields`` x ``field_bytes`` bytes, made from the seed.  ``R``
reads the whole record; ``U`` + field + bytes overwrites one field.  Every
reply begins with the 8-byte count of operations the record has executed:
the operation's place in the record's one order.  Imports nothing of the
program and takes nothing it has made but the replies and the replicas'
checkpoints it is there to judge.

Concurrent clients may have several operations outstanding on one record,
so a record's operations have NO send order to replay in.  The order is the
one the replies state: the answered operations of a record, sorted by the
count their replies carry, must take the places 1, 2, 3 .. once each; a read
at place p must return the seed record with the updates at places below p
applied; an operation whose reply was in before another of its record was
first sent must have the lower place; every replica's checkpoint of the
record must be the count and the record after the last place.

``broken`` makes the CONTROL: the same machine, fed the run's operations in
the order the run took, with one stated guarantee taken away:

- ``"lost_update"``: the last replica drops one acknowledged update (every
  acked operation is on all replicas);
- ``"stale_read"``: a read is answered with the record as it was before the
  last update below it (linearizable per record; reads are rounds);
- ``"doubled"``: an update is executed twice everywhere (applied once);
- ``"reordered"``: the last replica applies two updates of one field of one
  record in the other order (one order per record).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HEAD = 8  # bytes of count in front of every reply and checkpoint
CONTROLS = ("lost_update", "stale_read", "doubled", "reordered")


def initial_records(seed: int, n: int, fields: int,
                    field_bytes: int) -> np.ndarray:
    """The ``n`` records as loaded before the window: uint8 ``[n, fields *
    field_bytes]`` from the seed."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    return rng.integers(0, 256, (n, fields * field_bytes), np.uint8)


def checkpoint_of(count: int, rec: bytes) -> bytes:
    return count.to_bytes(HEAD, "little") + rec


def apply(rec: bytes, payload: bytes, field_bytes: int) -> bytes:
    """``rec`` after the operation ``payload``: an update overwrites its
    field, a read changes nothing."""
    if payload[:1] != b"U":
        return rec
    at = payload[1] * field_bytes
    return rec[:at] + payload[2:] + rec[at + field_bytes:]


def count_of(reply: Optional[bytes]) -> int:
    """The place a reply states (0: none, no place is 0)."""
    if reply is None or len(reply) < HEAD:
        return 0
    return int.from_bytes(reply[:HEAD], "little")


def by_record(ops: Dict) -> Dict[int, List[int]]:
    """Indices into ``ops`` of each record's operations."""
    out: Dict[int, List[int]] = {}
    for k, rec in enumerate(ops["record"].tolist()):
        out.setdefault(rec, []).append(k)
    return out


def order_of(ops: Dict) -> Dict[int, List[int]]:
    """Each record's ANSWERED operations in the order their replies state
    (ties, which are faults, in the order sent)."""
    ok = (ops["t_recv"] >= 0) & (ops["status"] == 0)
    counts = [count_of(r) for r in ops["reply"]]
    return {rec: sorted((k for k in ks if ok[k]), key=lambda k: counts[k])
            for rec, ks in by_record(ops).items()}


def check(initial: np.ndarray, ops: Dict, field_bytes: int,
          checkpoints: Sequence[Dict[int, bytes]], untouched_changed: int
          ) -> List[Tuple[str, int, int]]:
    """The numbers compared, each with its limit (exact: 0).

    ``ops``: every operation sent since the records were loaded, warm-up
    included (arrays ``record``, ``t_send``, ``t_recv``, ``status``; lists
    ``payload``, ``reply``).  ``checkpoints[r]``: replica r's checkpoint of
    every record an operation addressed.  ``untouched_changed``: records no
    operation addressed whose checkpoint on some replica is not the seed's."""
    t_send, t_recv, status = ops["t_send"], ops["t_recv"], ops["status"]
    never = int(np.sum(t_recv < 0))
    refused = int(np.sum((t_recv >= 0) & (status != 0)))
    wrong = twice = early = diverged = 0
    sent = by_record(ops)
    for rec, order in order_of(ops).items():
        state = initial[rec].tobytes()
        latest_send = -1.0  # latest first send among the places below
        seen = set()
        for place, k in enumerate(order, start=1):
            reply = ops["reply"][k]
            c = count_of(reply)
            twice += c in seen or c > len(sent[rec])
            seen.add(c)
            payload = ops["payload"][k]
            state = apply(state, payload, field_bytes)
            want = checkpoint_of(place, state if payload == b"R" else b"")
            wrong += reply != want
            # it was answered before an operation with a lower place was
            # first sent: the order runs against real time
            early += t_recv[k] < latest_send
            latest_send = max(latest_send, t_send[k])
        final = checkpoint_of(len(order), state)
        diverged += sum(cp.get(rec) != final for cp in checkpoints)
    return [("answers_wrong", int(wrong), 0),
            ("places_taken_twice", int(twice), 0),
            ("order_against_real_time", int(early), 0),
            ("answers_refused", refused, 0),
            ("never_answered", never, 0),
            ("replica_groups_diverged", int(diverged), 0),
            ("writes_nobody_sent", int(untouched_changed), 0)]


def simulate(initial: np.ndarray, ops: Dict, field_bytes: int,
             replicas: int, order: Dict[int, List[int]],
             broken: Optional[str] = None, victim: Optional[int] = None):
    """The reference machine in the program's place: the operations of each
    record applied in ``order``; returns (replies, checkpoints) as ``check``
    takes them.  ``victim`` (an index into ``ops``) is the operation a
    ``broken`` guarantee hits, see ``pick_victim``."""
    replies: List[Optional[bytes]] = [None] * len(ops["reply"])
    checkpoints: List[Dict[int, bytes]] = [{} for _ in range(replicas)]
    for rec, ks in order.items():
        state = before_update = initial[rec].tobytes()
        count = 0
        hit = victim in ks and broken is not None
        for k in ks:
            payload = ops["payload"][k]
            times = 2 if hit and broken == "doubled" and k == victim else 1
            for _ in range(times):
                count += 1
                if payload != b"R":
                    before_update = state
                    state = apply(state, payload, field_bytes)
            shown = before_update if hit and broken == "stale_read" \
                and k == victim else state
            replies[k] = checkpoint_of(count,
                                       shown if payload == b"R" else b"")
        for r in range(replicas):
            checkpoints[r][rec] = checkpoint_of(count, state)
        if hit and broken in ("lost_update", "reordered"):
            mine = list(ks)
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
                order: Dict[int, List[int]], broken: str,
                rng: np.random.Generator) -> int:
    """An operation on which taking ``broken``'s guarantee away shows: a
    read whose record the last update below it changed; the LAST update of
    a field that has an earlier update with other bytes (``reordered``, and
    good for a lost or doubled update too)."""
    reads, updates = [], []
    for rec, ks in order.items():
        last: Dict[bytes, int] = {}  # field -> its latest update so far
        state = initial[rec].tobytes()
        changed = False  # by the last update so far
        for k in ks:
            p = ops["payload"][k]
            if p == b"R":
                if changed:
                    reads.append(k)
                continue
            after = apply(state, p, field_bytes)
            changed, state = after != state, after
            last[p[:2]] = k
        for fld, k in last.items():
            if any(ops["payload"][j][:2] == fld
                   and ops["payload"][j] != ops["payload"][k]
                   for j in ks[:ks.index(k)]):
                updates.append(k)
    pool = reads if broken == "stale_read" else updates
    if not pool:
        raise ValueError(f"no operation on which {broken!r} would show")
    return int(pool[int(rng.integers(0, len(pool)))])
