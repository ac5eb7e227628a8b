"""Columnar paxos state: one row per group, slot window of width W.

Reference analog: the fields of ``gigapaxos/PaxosAcceptor.java`` (ballot,
slot, accepted-pvalues map, GC slot) and ``gigapaxos/
PaxosCoordinatorState.java`` (coordinator ballot, next slot, waiting-for-
majority maps), flattened from one-heap-object-per-group into
struct-of-arrays device buffers.

Design notes (TPU-first):

- **Packed ballots.** A paxos ballot is the lexicographic pair
  ``(ballotNumber, coordinatorID)`` (ref: ``gigapaxos/paxosutil/
  Ballot.java``).  We pack it into one int32 — ``num << NODE_BITS | coord``
  — so ballot comparison is a single integer compare, which vectorizes
  trivially.  ``NODE_BITS=12`` allows 4096 node ids and ~2^19 ballot
  numbers per group (a ballot number increments only on coordinator
  changes).  ``NO_BALLOT = -1`` sorts below every real ballot.

- **Slot window.** Each group stores a circular window of W slots; slot
  ``s`` lives in column ``s % W``.  A slot is admissible while
  ``exec_cursor <= s < exec_cursor + W``.  This bounds per-group device
  memory exactly like the reference bounds it with checkpoint-interval log
  GC (ref: ``PaxosConfig PC.CHECKPOINT_INTERVAL`` ~400 slots; here W is
  the analogous knob, and the out-of-window case is handled by host-side
  requeueing).

- **Vote bitmaps.** Acceptor votes are a bitmap per (group, slot) packed
  into the low bits of the ``PROP_VOTES`` word; quorum =
  ``population_count(votes & VOTE_MASK) >= majority(members)``.  Bit 30
  (``EMITTED_BIT``) of the same word records "decision already emitted",
  capping groups at 30 replicas (the reference is practically ≤ ~10).

- **Window planes, one per component.** The acceptor's stored pvalue
  (``acc``: slot, ballot, req lo/hi), the decided pvalue (``dec``: slot,
  req lo/hi) and the coordinator's proposal (``prop``: slot, req lo/hi,
  votes|emitted) are held as eleven LINEAR ``i32[G * W]`` planes, one per
  component, and every hot stage addresses them by the one flat word
  index ``g * W + (slot % W)``.  What the TPU v5e measured (PERF.md §6,
  PRs 28-32): a four-word row write into a ``[G, W, 4]`` plane costs
  81 ns a row in any order and under every flag, a one-word write into
  a flat plane 6.4 ns; and no physical order of a
  ``[G, W, k]`` array serves a row write, a one-word vote add and a
  ``[B, k]`` read at once, so every stage had the compiler copy the
  whole 268 MB plane into its own order and back.  A flat component
  plane has one order (``{0:T(1024)}``) from parameter to result, and a
  scatter updates its donated operand in place.  A column's "decided"
  flag is simply ``dec_slot == slot`` (``NO_SLOT`` never matches a real
  slot), so no separate bool plane exists.  The ``[G, W, k]`` packing
  survives as the READ view (``state.acc`` / ``.dec`` / ``.prop``,
  built on demand, cold path) and as the row exchange format
  (:class:`RowState`: what ``gather_rows`` returns, what snapshots,
  pause blobs and ``scatter_rows`` carry).

- **One word table for a group's scalars.** The eleven per-group scalars
  (``GROUP_COLS``: ``active`` .. ``prep_votes``) are the columns of ONE
  linear plane ``grp: i32[G * GROUP_WORDS]``, word ``g * 16 + k``.  A
  boolean is a 0/1 word, ``prep_votes`` is bit-cast; five words a group
  are spare and stay zero.  Sixteen words a group and not eleven, so that
  eight groups fill a 128-word tile row exactly as a ``W = 16`` window
  does: a stage reads every scalar of its groups with ONE row gather
  (``kernels._group_words``) where it read each ``[G]`` field with a
  gather of its own (63.9 ms of the storm step's 148, PERF.md §6, PR 37),
  and writes a field with the one-word set of the window planes.
  ``state.bal``, ``state.active``, ... are read-only views (a strided
  copy on demand; cold path).

- **Request ids.** The device stores only 64-bit request ids (two int32
  lanes); payload bytes stay host-side keyed by id, mirroring the
  reference's split between ``RequestPacket`` identity and body.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# --- packed ballots ---------------------------------------------------------

NODE_BITS = 12
NODE_MASK = (1 << NODE_BITS) - 1
NO_BALLOT = -1  # sorts below every packed ballot (packed values are >= 0)
NO_SLOT = -1

# --- window-plane components: column indices of the [.., W, k] views -------

# acc[.., W, 4]: the acceptor's stored pvalue per window column
ACC_SLOT, ACC_BAL, ACC_RLO, ACC_RHI = 0, 1, 2, 3
# dec[.., W, 3]: decided pvalue per window column (decided <=> DEC_SLOT
# column holds the expected slot; NO_SLOT = never)
DEC_SLOT, DEC_RLO, DEC_RHI = 0, 1, 2
# prop[.., W, 4]: the coordinator's proposal per window column.  The
# PROP_VOTES word is the sender-vote bitmap (bits 0..29) with bit 30
# recording "decision emitted" — one i32 so the reply path's vote +
# emitted updates ride a single scatter.
PROP_SLOT, PROP_RLO, PROP_RHI, PROP_VOTES = 0, 1, 2, 3
EMITTED_BIT = 1 << 30
VOTE_MASK = EMITTED_BIT - 1

# the component planes of each view, in column order, with a fresh
# column's value
PLANES = {
    "acc": (("acc_slot", NO_SLOT), ("acc_bal", NO_BALLOT), ("acc_rlo", 0),
            ("acc_rhi", 0)),
    "dec": (("dec_slot", NO_SLOT), ("dec_rlo", 0), ("dec_rhi", 0)),
    "prop": (("prop_slot", NO_SLOT), ("prop_rlo", 0), ("prop_rhi", 0),
             ("prop_votes", 0)),
}


# the group table: column k of ``grp`` (word ``g * GROUP_WORDS + k``), the
# dtype its read view and its RowState field have, a fresh row's value
GROUP_WORDS = 16
GROUP_COLS = (
    ("active", np.bool_, 0),        # row allocated
    ("members", np.int32, 0),       # replica count N (quorum = N//2+1)
    ("version", np.int32, 0),       # reconfiguration epoch of the group
    # acceptor (ref: PaxosAcceptor.java)
    ("bal", np.int32, NO_BALLOT),   # promised ballot (packed)
    ("exec_cursor", np.int32, 0),   # first not-known-decided contiguous slot
    ("gc_slot", np.int32, NO_SLOT),  # checkpointed slot (log GC'd below)
    # coordinator (ref: PaxosCoordinator/PaxosCoordinatorState.java)
    ("is_coord", np.bool_, 0),      # this node believes it coordinates g
    ("coord_active", np.bool_, 0),  # phase-1 complete, may assign slots
    ("cbal", np.int32, NO_BALLOT),  # coordinator ballot (packed)
    ("next_slot", np.int32, 0),     # next slot to assign
    ("prep_votes", np.uint32, 0),   # phase-1 prepare-reply bitmap
)
COL = {f: k for k, (f, _, _) in enumerate(GROUP_COLS)}
COL_DTYPE = {f: dtype for f, dtype, _ in GROUP_COLS}


def to_word(x):
    """A field's value as its i32 table word: a boolean 0/1, ``u32``
    bit-cast (never converted: bit 31 stays bit 31)."""
    x = jnp.asarray(x)
    if x.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return x.astype(jnp.int32)


def from_word(word, dtype):
    """A table word as its field's dtype (:func:`to_word`'s inverse)."""
    if dtype == np.bool_:
        return word != 0
    if dtype == np.uint32:
        return jax.lax.bitcast_convert_type(word, jnp.uint32)
    return word


def pack_ballot(num: int, coord: int):
    """Pack (ballotNumber, coordinatorID) into one comparable int32."""
    return (num << NODE_BITS) | (coord & NODE_MASK)


def unpack_ballot(packed: int) -> Tuple[int, int]:
    if packed < 0:
        return (-1, -1)
    return (packed >> NODE_BITS, packed & NODE_MASK)


# --- the state --------------------------------------------------------------


class ColumnarState(NamedTuple):
    """All-groups paxos state as device arrays: the group table
    ``[G * GROUP_WORDS]`` (word ``g * 16 + k``, ``k`` a column of
    ``GROUP_COLS``) and ``[G * W]`` for a window-plane component (word
    ``g * W + w``).  Twelve leaves."""

    grp: jnp.ndarray           # i32[G*16] the groups' scalars (GROUP_COLS)

    # -- acceptor (ref: PaxosAcceptor.java) --
    acc_slot: jnp.ndarray      # i32[G*W] accepted pvalue: slot
    acc_bal: jnp.ndarray       # i32[G*W]   ballot (packed)
    acc_rlo: jnp.ndarray       # i32[G*W]   request id, low word
    acc_rhi: jnp.ndarray       # i32[G*W]   request id, high word
    dec_slot: jnp.ndarray      # i32[G*W] decided pvalue: slot
    dec_rlo: jnp.ndarray       # i32[G*W]
    dec_rhi: jnp.ndarray       # i32[G*W]

    # -- coordinator (ref: PaxosCoordinator/PaxosCoordinatorState.java) --
    prop_slot: jnp.ndarray     # i32[G*W] proposal: slot
    prop_rlo: jnp.ndarray      # i32[G*W]
    prop_rhi: jnp.ndarray      # i32[G*W]
    prop_votes: jnp.ndarray    # i32[G*W]   vote bitmap | EMITTED_BIT

    @property
    def G(self) -> int:
        return self.grp.shape[0] // GROUP_WORDS

    @property
    def W(self) -> int:
        return self.acc_slot.shape[-1] // self.G

    def _view(self, name):
        return jnp.stack([getattr(self, f) for f, _ in PLANES[name]],
                         axis=-1).reshape(self.G, self.W, -1)

    # the [G, W, k] read views: a copy built on demand (cold path: tests,
    # the storm driver's read-back); no kernel touches them
    @property
    def acc(self):
        return self._view("acc")

    @property
    def dec(self):
        return self._view("dec")

    @property
    def prop(self):
        return self._view("prop")


def _column_view(f):
    return property(lambda self: from_word(self.grp[COL[f]::GROUP_WORDS],
                                           COL_DTYPE[f]))


# ``state.active`` .. ``state.prep_votes``: each column of the table as
# the ``[G]`` array of its dtype, a strided copy on demand like the views
# above (tests, ``inspect``, the storm driver's read-back)
for _f in COL:
    setattr(ColumnarState, _f, _column_view(_f))


class RowState(NamedTuple):
    """Rows of a state in the exchange form: what ``kernels.gather_rows``
    returns and ``scatter_rows`` writes back, field for field what a
    snapshot dict or a pause blob holds.  ``[n]``, and ``[n, W, k]`` for
    the window planes (``ACC_*`` / ``DEC_*`` / ``PROP_*`` columns)."""

    active: jnp.ndarray
    members: jnp.ndarray
    version: jnp.ndarray
    bal: jnp.ndarray
    acc: jnp.ndarray           # i32[n,W,4]
    dec: jnp.ndarray           # i32[n,W,3]
    exec_cursor: jnp.ndarray
    gc_slot: jnp.ndarray
    is_coord: jnp.ndarray
    coord_active: jnp.ndarray
    cbal: jnp.ndarray
    next_slot: jnp.ndarray
    prep_votes: jnp.ndarray
    prop: jnp.ndarray          # i32[n,W,4]


def make_state(G: int, W: int) -> ColumnarState:
    """Fresh all-inactive state.  G groups capacity, window width W."""
    i32 = jnp.int32
    # NOTE: every field gets its OWN buffer — sharing one array across
    # fields breaks donate_argnums ("attempt to donate the same buffer
    # twice").
    fresh = np.zeros((GROUP_WORDS,), np.int32)
    fresh[:len(GROUP_COLS)] = [v for _, _, v in GROUP_COLS]
    planes = {f: jnp.full((G * W,), v, i32)
              for cols in PLANES.values() for f, v in cols}
    return ColumnarState(grp=jnp.tile(jnp.asarray(fresh), G), **planes)


def with_columns(state: ColumnarState, **cols) -> ColumnarState:
    """``state`` with whole columns of its group table replaced: ``[G]``
    arrays by field name.  For building a state by hand (tests, the
    Pallas path's write-back); a dense strided write, no kernel uses it."""
    grp = state.grp
    for f, v in cols.items():
        grp = grp.at[COL[f]::GROUP_WORDS].set(to_word(v))
    return state._replace(grp=grp)


def split_req_id(req_id: int) -> Tuple[int, int]:
    """64-bit request id -> (lo32, hi32) as signed int32-safe Python ints."""
    lo = req_id & 0xFFFFFFFF
    hi = (req_id >> 32) & 0xFFFFFFFF
    # to signed
    if lo >= 1 << 31:
        lo -= 1 << 32
    if hi >= 1 << 31:
        hi -= 1 << 32
    return lo, hi


def join_req_id(lo: int, hi: int) -> int:
    return ((int(hi) & 0xFFFFFFFF) << 32) | (int(lo) & 0xFFFFFFFF)


def state_nbytes(G: int, W: int) -> int:
    """Approximate device bytes for a state of this capacity."""
    per_g = 4 * GROUP_WORDS  # the group table: sixteen words a group
    per_gw = 4 * (4 + 3 + 4)  # the acc, dec and prop component planes, i32
    return G * per_g + G * W * per_gw
