"""The least bytes a Paxos decision must move: the roofline's numerator.

Defined by the WORK, not by the implementation.  For each lane (one
request on its way to a decision) an op must read some fields of its
group's row and write some, once each.  The sizes are those of the state's
layout (``ops/types.py``): a scalar column is 4 B (a bool column 1 B), an
entry of a ``[W,4]`` plane 16 B, an entry of a ``[W,3]`` plane 12 B, one word
of an entry 4 B.  Not the slab, not tiles, not whatever a compiler copies:
a kernel rewritten another way, fused or split, reads the same count.

These kernels gather and scatter integers; there is nothing to multiply,
so bandwidth is the only bound stated.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

I32, BOOL = 4, 1
ACC_ENTRY, DEC_ENTRY, PROP_ENTRY, WORD = 16, 12, 16, 4

# op -> (bytes of the row it must read, bytes it must write), per lane
OPS: Dict[str, Dict[str, Dict[str, int]]] = {
    # coordinator: is this row mine, where is its window, which slot next;
    # write the slot counter and the proposal entry
    "propose": {
        "read": {"active": BOOL, "is_coord": BOOL, "coord_active": BOOL,
                 "next_slot": I32, "exec_cursor": I32, "cbal": I32},
        "write": {"next_slot": I32, "prop_entry": PROP_ENTRY}},
    # acceptor: promise check against the row's ballot, window check;
    # write the promise and the accepted entry
    "accept": {
        "read": {"active": BOOL, "bal": I32, "exec_cursor": I32},
        "write": {"bal": I32, "acc_entry": ACC_ENTRY}},
    # coordinator: match the reply to the proposal entry, count votes
    # against the membership; write the vote word
    "accept_reply": {
        "read": {"is_coord": BOOL, "coord_active": BOOL, "cbal": I32,
                 "members": I32, "prop_entry": PROP_ENTRY},
        "write": {"prop_votes": WORD}},
    # every replica: window check, store the decision, and move the cursor
    # over the decided run: at least the slot word of the entry at the
    # cursor and of the one after the run (two words)
    "commit": {
        "read": {"active": BOOL, "exec_cursor": I32, "dec_slots": 2 * WORD},
        "write": {"dec_entry": DEC_ENTRY, "exec_cursor": I32}},
}

# what a lane brings with it and takes away, whatever the packing: the
# group, the request id (two words) in; the slot and the ballot out
LANE_IN, LANE_OUT = 3 * I32, 2 * I32


def op_bytes(op: str) -> int:
    o = OPS[op]
    return sum(o["read"].values()) + sum(o["write"].values())


def decision_bytes(replicas: int) -> int:
    """Bytes one decision must move on an R-replica fleet: one propose on
    the coordinator, an accept on each replica, each replica's reply
    counted on the coordinator, a commit on each replica."""
    return (LANE_IN + LANE_OUT + op_bytes("propose")
            + replicas * (op_bytes("accept") + op_bytes("accept_reply")
                          + op_bytes("commit")))


def load_peaks(device_kind: Optional[str] = None) -> dict:
    """The peaks of ``device_kind`` (default: the device JAX runs on); a
    kind not in the table is an error."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json (has {sorted(table)})")
    return table[device_kind]


def roofline_pct(decisions: float, replicas: int, device_s: float,
                 peaks: dict):
    """Share of the HBM roofline: least time for the bytes the decisions
    must move, over the device time the kernels took.  None where there is
    nothing to read (no decisions, or no device time)."""
    if not decisions or not device_s or device_s <= 0:
        return None
    least_s = decisions * decision_bytes(replicas) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
