"""Reads without a round (``PC.READ_INDEX``; ref: Raft's ReadIndex, Ongaro's
thesis §6.4): the coordinator's book of reads between their arrival and
their answer.

A read that arrives at its group's active coordinator takes no slot.  The
coordinator notes the group's ``next_slot`` (the read's *index*) and the
ballot it coordinates at, asks each peer in one ``ReadConfirm`` frame a
worker batch whether it has promised exactly that ballot, and answers the
read from its app (``Replicable.read``) once a majority, itself included,
said so AND its app has executed every slot below the index.  Why that is
linearizable: a write completed before the read arrived was decided at a
ballot at most the coordinator's (a higher one would have needed a
majority promised to it, and that majority meets the confirming one after
the read arrived), so it sits at a slot below the index, carried there by
the coordinator's install if an earlier one decided it.

This module is the bookkeeping alone, on the worker thread of one node:
which reads wait, which frame asked about them, how many oks each still
needs, and which are ready.  ``PaxosNode`` launches the device programs,
sends the frames and answers.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Set, Tuple

import numpy as np

# a read neither answered nor confirmed this long after it arrived (a
# frame lost, a peer gone, a slot below its index never decided here)
# goes as a round
TIMEOUT_S = 1.0


@dataclass(slots=True)
class Read:
    rid: int
    row: int
    gkey: int
    waiter: int     # answered to: the client, or the replica that forwarded
    entry: int      # the Proposal's entry, should the read go as a round
    client: bool    # its waiter sits in the node's _client_wait
    flags: int
    payload: bytes
    index: int = 0  # next_slot at arrival: every slot below it first
    need: int = 0   # peers' oks still wanted (the coordinator is one)
    t0: float = 0.0  # arrival, engine clock: the timeout's
    m0: float = 0.0  # arrival, monotonic: ri.wait
    m1: float = 0.0  # confirmed, monotonic: ri.exec_wait


class PendingReads:
    """The reads of one coordinator that wait for their confirmation or
    for their index to execute."""

    def __init__(self):
        self.reads: Dict[int, Read] = {}  # req id -> read not answered
        self.ready: Dict[int, Read] = {}  # of them, confirmed by a majority
        # frame id -> (sent at, engine clock; the read ids of each lane;
        # peer -> the lanes asked of it; peers that answered)
        self.frames: Dict[int, Tuple[float, List[List[int]],
                                     Dict[int, np.ndarray], Set[int]]] = {}
        self.fell = 0  # reads that went as rounds since the last answer

    def add(self, rd: Read) -> None:
        self.reads[rd.rid] = rd
        if rd.need <= 0:
            rd.m1 = rd.m0
            self.ready[rd.rid] = rd

    def open_frame(self, xid: int, t0: float, lane_rids: List[List[int]],
                   asked: Dict[int, np.ndarray]) -> None:
        if asked:
            self.frames[xid] = (t0, lane_rids, asked, set())

    def on_reply(self, xid: int, sender: int, ok: np.ndarray) -> List[Read]:
        """A peer's answer: each ok lane counts for its reads; a read a
        lane refused leaves the book and is returned, to go as a round
        (unless a majority confirmed it already).  Unknown, repeated or
        misshapen answers count nothing."""
        fr = self.frames.get(xid)
        if fr is None:
            return []
        _t0, lane_rids, asked, answered = fr
        lanes = asked.get(sender)
        if lanes is None or sender in answered or len(lanes) != len(ok):
            return []
        answered.add(sender)
        if len(answered) == len(asked):
            del self.frames[xid]
        now, out = time.monotonic(), []
        for k, good in zip(lanes.tolist(), ok.tolist()):
            for rid in lane_rids[k]:
                rd = self.reads.get(rid)
                if rd is None or rd.need <= 0:
                    continue
                if good:
                    rd.need -= 1
                    if rd.need == 0:
                        rd.m1 = now
                        self.ready[rid] = rd
                else:
                    del self.reads[rid]
                    out.append(rd)
        return out

    def take_ready(self, cursor: np.ndarray) -> List[Read]:
        """The confirmed reads whose index the app has executed up to
        (``cursor``: the node's first not-executed slot a row), out of the
        book."""
        out = [rd for rd in self.ready.values() if cursor[rd.row] >= rd.index]
        for rd in out:
            del self.ready[rd.rid]
            del self.reads[rd.rid]
        return out

    def expire(self, now: float) -> List[Read]:
        """Reads older than ``TIMEOUT_S`` on the engine clock, out of the
        book, and the frames as old."""
        out = [rd for rd in self.reads.values() if now - rd.t0 > TIMEOUT_S]
        for rd in out:
            del self.reads[rd.rid]
            self.ready.pop(rd.rid, None)
        for xid in [x for x, fr in self.frames.items()
                    if now - fr[0] > TIMEOUT_S]:
            del self.frames[xid]
        return out
