"""The least bytes an election must move: the numerator of
``election_kernels_roofline``, beside ``roofline.py``'s count for a decision.

Defined by the WORK, from the state's layout (``ops/types.py``), as there: a
scalar column is 4 B (a bool column 1 B), an entry of the accepted window
16 B (slot, ballot, request id in two words), an entry of the proposal
window 16 B.  A promise, on each acceptor, reads the row's ballot, whether
the row is live, its cursor and the group's W accepted entries (what a
PrepareReply has to carry is whatever of them lies at or after the cursor,
and only the entries say), and writes the ballot.  An install, on the new
coordinator alone, writes the coordinator's fields and one proposal entry
for each slot carried over.
"""

from __future__ import annotations

from benchmarks.roofline import ACC_ENTRY, BOOL, I32, PROP_ENTRY

# what a lane brings and takes away: the group and the ballot in; whether
# it was promised, the promise standing and the cursor out
PROMISE_LANE = 2 * I32 + BOOL + 2 * I32
INSTALL_LANE = 3 * I32  # the group, the ballot, the next slot


def promise_bytes(window: int) -> int:
    """One group's promise on one acceptor."""
    read = BOOL + I32 + I32 + window * ACC_ENTRY  # active, bal, cursor, acc
    return PROMISE_LANE + read + I32               # ... and bal written


def install_bytes(carried: int = 0) -> int:
    """One group's install on its new coordinator: ``is_coord``,
    ``coord_active``, ``cbal``, ``next_slot`` and the carried entries."""
    return INSTALL_LANE + 2 * BOOL + 2 * I32 + carried * PROP_ENTRY


def election_bytes(promises: int, installs: int, carried: int,
                   window: int) -> int:
    """``promises`` promise lanes (a group on an acceptor each),
    ``installs`` installs carrying ``carried`` slots between them."""
    return (promises * promise_bytes(window) + installs * install_bytes()
            + carried * PROP_ENTRY)
