"""The least bytes a recovery must move on the device: the numerator of
``recovery_kernels_roofline``, beside ``roofline.py``'s count for a decision
and ``roofline_elections.py``'s for an election.

Defined by the WORK, from the state's layout (``ops/types.py``), as there: a
scalar column is 4 B (a bool column 1 B), a window entry's slot word 4 B.  An
install makes a group exist with nothing in its windows: it writes the row's
eleven scalar fields and the slot word of each entry of its three windows
(an entry is empty when its slot word says so; its other words need not be
touched).  A restored checkpoint sets the row's cursor and raises its next
slot.  The roll-forward is an accept for each logged accept and a commit
for each logged decision, ``roofline.py``'s bytes for those ops, with what
each lane brings and takes away.
"""

from __future__ import annotations

from benchmarks.roofline import BOOL, I32, WORD, op_bytes

# the scalar fields of a row, all written by an install: active, is_coord,
# coord_active (a byte each); members, version, bal, exec_cursor, gc_slot,
# cbal, next_slot, prep_votes (a word each)
ROW_SCALARS = 3 * BOOL + 8 * I32
WINDOWS = 3  # accepted, decided, proposed: a slot word an entry each
INSTALL_LANE = 4 * I32 + BOOL  # row, members, version, ballot; self_coord
CURSOR_LANE = 3 * I32          # row, cursor, next slot
ACCEPT_LANE = 5 * I32 + 3 * BOOL + I32  # row, slot, bal, id x2; 3 flags, bal
COMMIT_LANE = 4 * I32 + 3 * BOOL        # row, slot, id x2; 3 flags


def install_bytes(window: int) -> int:
    """One group's row made to exist, its windows empty."""
    return INSTALL_LANE + ROW_SCALARS + WINDOWS * window * WORD


def cursor_bytes() -> int:
    """One restored checkpoint: ``exec_cursor`` written, ``next_slot``
    read and written."""
    return CURSOR_LANE + I32 + 2 * I32


def replay_bytes(accepts: int, decisions: int) -> int:
    """The WAL's roll-forward: an accept a logged accept, a commit a logged
    decision."""
    return (accepts * (ACCEPT_LANE + op_bytes("accept"))
            + decisions * (COMMIT_LANE + op_bytes("commit")))


def recovery_bytes(rows: int, restored: int, accepts: int, decisions: int,
                   window: int) -> int:
    """An install of ``rows`` rows, ``restored`` cursors set from
    checkpoints, and a roll-forward of ``accepts`` + ``decisions`` lanes."""
    return (rows * install_bytes(window) + restored * cursor_bytes()
            + replay_bytes(accepts, decisions))
