"""The bytes the read index's two programs move a lane: the numerator of
``read_index_kernels_roofline``, beside ``roofline.py``'s count for a
decision and ``roofline_elections.py``'s for an election.

A coordinator lane (``read_index_p``, a read's group on its coordinator)
reads its group's row of the group table, which the program reads as ONE
128-word row (``kernels._group_words``: the group's sixteen words and its
seven neighbours', a tile row of the v5e), with the group in and whether the
read may go on, the ballot and the index out.  An acceptor lane
(``confirm_p``, a group on a peer) reads the row's promise, 4 B, with the
group and the ballot asked about in and the ok bit out.
"""

from __future__ import annotations

from benchmarks.roofline import BOOL, I32

ROW_READ = 128 * I32  # one tile row of the group table
COORD_LANE = ROW_READ + I32 + (BOOL + 2 * I32)   # g in; ok, cbal, index out
ACCEPTOR_LANE = I32 + 2 * I32 + BOOL             # bal read; g, bal in; ok


def read_index_bytes(coordinator_lanes: int, acceptor_lanes: int) -> int:
    return coordinator_lanes * COORD_LANE + acceptor_lanes * ACCEPTOR_LANE
