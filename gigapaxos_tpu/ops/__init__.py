"""Columnar consensus kernels — the TPU data plane.

Reference analog: the per-instance hot loops of
``gigapaxos/PaxosAcceptor.java`` (handlePrepare, acceptAndUpdateBallot) and
``gigapaxos/PaxosCoordinator.java`` / ``PaxosCoordinatorState.java``
(propose, handleAcceptReply majority counting) — redesigned columnar: state
for ALL groups lives in linear ``[G * 16]`` / ``[G * W]`` device planes and
each message type is one batched XLA kernel over a struct-of-arrays packet
batch.
"""

from gigapaxos_tpu.ops.types import (
    ColumnarState,
    make_state,
    pack_ballot,
    unpack_ballot,
    NODE_BITS,
    NO_BALLOT,
)
from gigapaxos_tpu.ops import kernels

__all__ = [
    "ColumnarState",
    "make_state",
    "pack_ballot",
    "unpack_ballot",
    "NODE_BITS",
    "NO_BALLOT",
    "kernels",
]
