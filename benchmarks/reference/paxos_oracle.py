"""Plain reference of the storm configuration: one Paxos group as a plain
object per replica, and one storm step over it, phase by phase.

A copy of the repo's scalar oracle (``gigapaxos_tpu/ops/oracle.py``) cut to
what a decide-storm uses (propose, accept, accept reply, commit), kept here
so that no later PR can change what the benchmark compares with.  Imports
nothing of the program.  Ballots are packed ints; ``NO_BALLOT`` and
``NO_SLOT`` are -1 as in the state's layout.

``broken`` makes the CONTROL (one stated guarantee taken away):

- ``"lost_commit"``: the last replica never learns the step's last decision
  (a decision reaches every replica);
- ``"no_quorum"``: the coordinator decides on its own vote alone, and only
  it commits (a decision needs a majority of accepts).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

NO_BALLOT = -1
NO_SLOT = -1


@dataclass
class Group:
    members: int
    window: int
    bal: int = 0                                   # promised (packed)
    accepted: Dict[int, Tuple[int, int]] = field(default_factory=dict)
    decided: Dict[int, int] = field(default_factory=dict)   # slot -> req
    exec_cursor: int = 0
    is_coord: bool = False
    cbal: int = NO_BALLOT
    next_slot: int = 0
    votes: Dict[int, int] = field(default_factory=dict)     # slot -> bitmap
    prop_req: Dict[int, int] = field(default_factory=dict)
    emitted: Dict[int, bool] = field(default_factory=dict)

    def accept(self, slot: int, bal: int, req: int) -> bool:
        if bal < self.bal:
            return False
        self.bal = bal
        if slot < self.exec_cursor:
            return True
        if slot >= self.exec_cursor + self.window:
            return False
        self.accepted[slot] = (bal, req)
        return True

    def commit(self, slot: int, req: int) -> None:
        if not self.exec_cursor <= slot < self.exec_cursor + self.window:
            return
        self.decided[slot] = req
        while self.exec_cursor in self.decided:
            self.exec_cursor += 1

    def propose(self, req: int) -> Optional[Tuple[int, int]]:
        """(slot, ballot) when a slot is granted, None when the window is
        full or this replica does not coordinate."""
        if not self.is_coord:
            return None
        slot = self.next_slot
        if slot >= self.exec_cursor + self.window:
            return None
        self.next_slot += 1
        self.votes[slot] = 0
        self.prop_req[slot] = req
        self.emitted[slot] = False
        return slot, self.cbal

    def accept_reply(self, slot: int, bal: int, sender: int,
                     acked: bool, majority: Optional[int] = None) -> bool:
        """True when this reply makes the slot decided."""
        if not acked or not self.is_coord or bal != self.cbal \
                or slot not in self.votes:
            return False
        self.votes[slot] |= 1 << sender
        need = self.members // 2 + 1 if majority is None else majority
        if bin(self.votes[slot]).count("1") >= need \
                and not self.emitted[slot]:
            self.emitted[slot] = True
            return True
        return False


def make_fleet(replicas: int, window: int) -> List[Group]:
    """One group's replicas as the storm's fleet starts them: ballot (0,0)
    promised everywhere, replica 0 the coordinator."""
    fleet = [Group(members=replicas, window=window) for _ in range(replicas)]
    fleet[0].is_coord, fleet[0].cbal = True, 0
    return fleet


def storm_step(fleet: List[Group], lanes: List[int],
               broken: Optional[str] = None) -> int:
    """One storm step for ONE group: its lanes (request ids, in batch
    order) through propose, accept on every replica, the replies in
    replica order, and commit on every replica.  Returns the decisions."""
    coord = fleet[0]
    granted = []
    for req in lanes:
        got = coord.propose(req)
        if got is not None:
            granted.append((got[0], got[1], req))
    acks = [[og.accept(slot, bal, req) for slot, bal, req in granted]
            for og in fleet]
    newly = [False] * len(granted)
    senders = range(1) if broken == "no_quorum" else range(len(fleet))
    for r in senders:
        for i, (slot, bal, _req) in enumerate(granted):
            newly[i] |= coord.accept_reply(
                slot, bal, r, acks[r][i],
                majority=1 if broken == "no_quorum" else None)
    for r, og in enumerate(fleet):
        tail = r == len(fleet) - 1
        if broken == "no_quorum" and r > 0:
            continue
        last = max((i for i, d in enumerate(newly) if d), default=None)
        for i, ((slot, _bal, req), dec) in enumerate(zip(granted, newly)):
            if dec and not (broken == "lost_commit" and tail and i == last):
                og.commit(slot, req)
    return sum(newly)
