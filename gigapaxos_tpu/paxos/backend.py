"""AcceptorBackend SPI: pluggable consensus data planes.

This is the SPI the north star calls for (BASELINE.json): the node runtime
(PaxosManager analog) drives ALL acceptor/coordinator state transitions
through this batch-level interface, and two backends implement it:

- :class:`ScalarBackend` — one Python object per group
  (``ops.oracle.OracleGroup``), looping over batch items.  This is the
  architectural stand-in for the reference's per-instance Java hot path
  (``PaxosManager`` dispatching each packet to a heap-allocated
  ``PaxosInstanceStateMachine``) and provides the baseline side of the
  ≥10× comparison.
- :class:`ColumnarBackend` — the JAX/TPU columnar kernels over ``[G, W]``
  device arrays (``ops.kernels``), with batch padding to power-of-two
  buckets so the jit cache stays small.

All inputs/outputs are numpy arrays (host-side); the manager's batcher
builds them straight from decoded struct-of-arrays packets.
"""

from __future__ import annotations

import abc
import contextlib
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from gigapaxos_tpu.ops.kernels import (WAVE_IN, WAVE_IN_CUTS,
                                       WAVE_OUT_CUTS, WAVE_SECTIONS)
from gigapaxos_tpu.ops.oracle import OracleGroup, PValue, make_oracle_group
from gigapaxos_tpu.ops.types import (COL, COL_DTYPE, GROUP_WORDS, NO_BALLOT,
                                     NO_SLOT, PLANES)
from gigapaxos_tpu.utils.engineledger import EngineLedger
from gigapaxos_tpu.utils.instrument import (RequestInstrumenter, span,
                                            traced)
from gigapaxos_tpu.utils.profiler import DelayProfiler


class AcceptRes(NamedTuple):
    acked: np.ndarray
    stale: np.ndarray
    out_window: np.ndarray
    cur_bal: np.ndarray


class AcceptReplyRes(NamedTuple):
    newly_decided: np.ndarray
    preempted: np.ndarray
    req_lo: np.ndarray
    req_hi: np.ndarray
    dec_bal: np.ndarray


class ProposeRes(NamedTuple):
    granted: np.ndarray
    rejected: np.ndarray
    throttled: np.ndarray
    slot: np.ndarray
    cbal: np.ndarray


class CommitRes(NamedTuple):
    applied: np.ndarray
    stale: np.ndarray
    out_window: np.ndarray
    new_cursor: np.ndarray


class ReadIndexRes(NamedTuple):
    ok: np.ndarray          # this node may answer the read (see kernels)
    cbal: np.ndarray        # the ballot its peers are asked to confirm
    next_slot: np.ndarray   # the read's index


class PrepareRes(NamedTuple):
    acked: np.ndarray
    cur_bal: np.ndarray
    exec_cursor: np.ndarray
    win_slot: np.ndarray    # [B, W]
    win_bal: np.ndarray
    win_req_lo: np.ndarray
    win_req_hi: np.ndarray


def _split64(req: np.ndarray):
    """u64/int64 request-id array -> (lo32, hi32) int32 views."""
    req = np.ascontiguousarray(req, np.uint64)
    lo = (req & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
    hi = (req >> np.uint64(32)).astype(np.uint32).view(np.int32)
    return lo, hi


def _join64(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    return (lo.view(np.uint32).astype(np.uint64) |
            (hi.view(np.uint32).astype(np.uint64) << np.uint64(32)))


class AcceptorBackend(abc.ABC):
    """Batch-level consensus state-transition engine for all groups of one
    node.  Rows are the dense indices from ``GroupTable``."""

    @property
    @abc.abstractmethod
    def window(self) -> int: ...

    @abc.abstractmethod
    def create(self, rows, members, versions, init_bal, self_coord): ...

    @abc.abstractmethod
    def delete(self, rows): ...

    @abc.abstractmethod
    def accept(self, rows, slots, bals, req_ids) -> AcceptRes: ...

    @abc.abstractmethod
    def accept_reply(self, rows, slots, bals, senders, acked
                     ) -> AcceptReplyRes: ...

    @abc.abstractmethod
    def propose(self, rows, req_ids) -> ProposeRes: ...

    @abc.abstractmethod
    def commit(self, rows, slots, req_ids) -> CommitRes: ...

    @abc.abstractmethod
    def prepare(self, rows, bals) -> PrepareRes: ...

    @abc.abstractmethod
    def install_coordinator(self, rows, cbals, next_slots, carry_slot,
                            carry_req) -> None: ...

    @abc.abstractmethod
    def set_cursor(self, rows, cursors, next_slots) -> None: ...

    @abc.abstractmethod
    def gc(self, rows, upto) -> None: ...

    @abc.abstractmethod
    def cursor_of(self, row: int) -> int: ...

    @abc.abstractmethod
    def snapshot_row(self, row: int) -> dict:
        """Serializable per-row hot state (pause; ref HotRestoreInfo)."""

    @abc.abstractmethod
    def restore_row(self, row: int, snap: dict) -> None: ...

    def snapshot_rows(self, rows) -> List[dict]:
        """Batched snapshot (deactivator sweeps); backends override when
        they can gather many rows in one device round trip."""
        return [self.snapshot_row(int(r)) for r in rows]

    @staticmethod
    def gate_acks(res: AcceptRes) -> AcceptRes:
        """Withdraw every ack in an accept result: the durability
        barrier AFTER the engine call failed (WAL impaired), so the
        on-device votes must not be reported to any coordinator — a
        quorum counting a non-fsynced vote breaks no_lost_acks.  The
        replies go out nacked at the acceptor's current ballot (the
        coordinator simply never counts this acceptor; the vote stays
        inert on-device and is re-persisted if the slot is re-driven).
        Pure SPI-surface helper: no backend state is touched."""
        return res._replace(acked=np.zeros_like(np.asarray(res.acked)))

    def inspect_rows(self, rows) -> Dict[str, np.ndarray]:
        """Device-truth consensus cursors for the introspection plane
        (``GET /groups``): promised ballot, coordinator ballot, next
        proposal slot, exec cursor — per row, as parallel arrays.
        Default goes through the (heavier) snapshot path; the columnar
        backend overrides with one gather + one transfer."""
        snaps = self.snapshot_rows(np.asarray(rows, np.int64))

        def field(s: dict, key: str, scal_idx: int, default: int) -> int:
            # the native store packs its per-row scalars into `scal`
            # ([bal, cbal, exec_cursor, next_slot, ...]); the scalar
            # oracle snapshot carries named keys
            if "scal" in s:
                return int(s["scal"][scal_idx])
            return int(s.get(key, default))

        return {
            "bal": np.asarray(
                [field(s, "bal", 0, -1) for s in snaps], np.int64),
            "cbal": np.asarray(
                [field(s, "cbal", 1, -1) for s in snaps], np.int64),
            "next_slot": np.asarray(
                [field(s, "next_slot", 3, 0) for s in snaps], np.int64),
            "exec_cursor": np.asarray(
                [field(s, "exec_cursor", 2, 0) for s in snaps],
                np.int64),
        }

    engine_platform = "cpu"  # overridden by device-resident backends
    engine_mesh = "off"  # device-mesh size when group-axis sharded
    launches = 0  # hot programs launched (a chunk each); columnar only

    def memory_info(self) -> Optional[dict]:
        """Slab memory accounting (``GET /engine``): per-plane bytes,
        bytes/group, and a max-groups capacity estimate.  None for
        backends without device-resident slabs (scalar/native)."""
        return None

    def warm_elections(self) -> None:
        """Load whatever a takeover would otherwise compile the first
        time a leader dies.  Nothing to load on the engines that
        compile nothing."""

    def read_index(self, rows) -> ReadIndexRes:
        """The coordinator's half of a read without a round, a lane a
        row: ``kernels.read_index_batch`` says what it answers."""
        raise NotImplementedError

    def confirm(self, rows, bals) -> np.ndarray:
        """An acceptor's half: ``bool[n]``, each row's promise is exactly
        ``bals`` (``kernels.confirm_batch``)."""
        raise NotImplementedError

    def warm_reads(self) -> None:
        """Load the read index's programs at every bucket a batch of
        reads can reach.  Nothing to load on the engines that compile
        nothing."""

    def warm_recovery(self) -> None:
        """Load whatever a recovery (the install of every row, the
        checkpoints' cursors, the WAL's roll-forward) and a catch-up
        launch at sizes no traffic reaches.  Nothing to load on the
        engines that compile nothing."""

    def programs(self, *kernels: str) -> str:
        """The device programs behind ``kernels`` as a device trace
        names them, joined by ``+`` (a span's ``programs``); empty on
        the engines that launch none."""
        return ""

    def release(self) -> None:
        """Give back what the engine holds outside the host's heap,
        now: a crash-stopped node of a process that lives on
        (``PaxosEmulation.kill``) must not keep its slab beside its
        successor's.  The backend is unusable afterwards.  Nothing to
        give back on the engines that live on the heap."""

    def row_ownership(self) -> Optional[dict]:
        """Active-row counts per mesh device; None when not
        applicable."""
        return None

    def kernel_costs(self) -> Dict[str, dict]:
        """Compiled-HLO cost analysis (flops / bytes accessed) per hot
        kernel; empty for non-jit backends."""
        return {}

    def accept_commit(self, rows_a, slots_a, bals_a, reqs_a,
                      rows_c, slots_c, reqs_c
                      ) -> Tuple[AcceptRes, CommitRes]:
        """Fused acceptor wave: accepts then commits, in the order the
        manager's handlers run them.  Default is the two plain calls
        (scalar/native semantics are already per-item); the columnar
        backend overrides with ONE device dispatch."""
        return (self.accept(rows_a, slots_a, bals_a, reqs_a),
                self.commit(rows_c, slots_c, reqs_c))


# --------------------------------------------------------------------------
# scalar backend (baseline / trickle-traffic path)
# --------------------------------------------------------------------------


class ScalarBackend(AcceptorBackend):
    """Per-instance Python objects; the reference-architecture stand-in."""

    def __init__(self, window: int = 16):
        self._window = window
        self.groups: Dict[int, OracleGroup] = {}

    @property
    def window(self) -> int:
        return self._window

    def _g(self, row: int) -> Optional[OracleGroup]:
        return self.groups.get(int(row))

    def create(self, rows, members, versions, init_bal, self_coord):
        for i in range(len(rows)):
            self.groups[int(rows[i])] = make_oracle_group(
                int(members[i]), self._window, int(init_bal[i]),
                bool(self_coord[i]), int(versions[i]))

    def delete(self, rows):
        for r in rows:
            self.groups.pop(int(r), None)

    def accept(self, rows, slots, bals, req_ids) -> AcceptRes:
        n = len(rows)
        acked = np.zeros(n, bool)
        stale = np.zeros(n, bool)
        ow = np.zeros(n, bool)
        cur = np.full(n, NO_BALLOT, np.int32)
        for i in range(n):
            g = self._g(rows[i])
            if g is None:
                continue
            acked[i], stale[i], ow[i], cur[i] = g.accept(
                int(slots[i]), int(bals[i]), int(req_ids[i]))
        return AcceptRes(acked, stale, ow, cur)

    def accept_reply(self, rows, slots, bals, senders, acked
                     ) -> AcceptReplyRes:
        n = len(rows)
        newly = np.zeros(n, bool)
        pre = np.zeros(n, bool)
        rlo = np.zeros(n, np.int32)
        rhi = np.zeros(n, np.int32)
        dbal = np.full(n, NO_BALLOT, np.int32)
        for i in range(n):
            g = self._g(rows[i])
            if g is None:
                continue
            nd, p, req = g.accept_reply(int(slots[i]), int(bals[i]),
                                        int(senders[i]), bool(acked[i]))
            newly[i], pre[i] = nd, p
            if nd:
                dbal[i] = g.cbal
                r = np.asarray([req], np.uint64)
                lo, hi = _split64(r)
                rlo[i], rhi[i] = lo[0], hi[0]
        return AcceptReplyRes(newly, pre, rlo, rhi, dbal)

    def propose(self, rows, req_ids) -> ProposeRes:
        n = len(rows)
        granted = np.zeros(n, bool)
        rejected = np.zeros(n, bool)
        throttled = np.zeros(n, bool)
        slot = np.full(n, NO_SLOT, np.int32)
        cbal = np.full(n, NO_BALLOT, np.int32)
        for i in range(n):
            g = self._g(rows[i])
            if g is None:
                continue
            st, s, cb = g.propose(int(req_ids[i]))
            granted[i] = st == "granted"
            rejected[i] = st == "rejected"
            throttled[i] = st == "throttled"
            slot[i], cbal[i] = s, cb
        return ProposeRes(granted, rejected, throttled, slot, cbal)

    def commit(self, rows, slots, req_ids) -> CommitRes:
        n = len(rows)
        applied = np.zeros(n, bool)
        stale = np.zeros(n, bool)
        ow = np.zeros(n, bool)
        cur = np.zeros(n, np.int32)
        for i in range(n):
            g = self._g(rows[i])
            if g is None:
                continue
            applied[i], stale[i], ow[i], cur[i] = g.commit(
                int(slots[i]), int(req_ids[i]))
        return CommitRes(applied, stale, ow, cur)

    def prepare(self, rows, bals) -> PrepareRes:
        n = len(rows)
        W = self._window
        acked = np.zeros(n, bool)
        cur_bal = np.full(n, NO_BALLOT, np.int32)
        cursor = np.zeros(n, np.int32)
        ws = np.full((n, W), NO_SLOT, np.int32)
        wb = np.full((n, W), NO_BALLOT, np.int32)
        wl = np.zeros((n, W), np.int32)
        wh = np.zeros((n, W), np.int32)
        for i in range(n):
            g = self._g(rows[i])
            if g is None:
                continue
            a, cb, cu, pvs = g.prepare(int(bals[i]))
            acked[i], cur_bal[i], cursor[i] = a, cb, cu
            for j, pv in enumerate(pvs[:W]):
                ws[i, j] = pv.slot
                wb[i, j] = pv.bal
                r = np.asarray([pv.req_id], np.uint64)
                lo, hi = _split64(r)
                wl[i, j], wh[i, j] = lo[0], hi[0]
        return PrepareRes(acked, cur_bal, cursor, ws, wb, wl, wh)

    def read_index(self, rows) -> ReadIndexRes:
        n = len(rows)
        ok = np.zeros(n, bool)
        cbal = np.full(n, NO_BALLOT, np.int32)
        nxt = np.zeros(n, np.int32)
        for i in range(n):
            g = self._g(rows[i])
            if g is not None:
                ok[i], cbal[i], nxt[i] = g.read_index()
        return ReadIndexRes(ok, cbal, nxt)

    def confirm(self, rows, bals) -> np.ndarray:
        return np.asarray([g is not None and g.confirm(int(b)) for g, b in
                           zip(map(self._g, rows), bals)], bool)

    def install_coordinator(self, rows, cbals, next_slots, carry_slot,
                            carry_req) -> None:
        for i in range(len(rows)):
            g = self._g(rows[i])
            if g is None:
                continue
            pvs = []
            for j in range(carry_slot.shape[1]):
                if carry_slot[i, j] >= 0:
                    pvs.append(PValue(int(carry_slot[i, j]), 0,
                                      int(carry_req[i, j])))
            g.install_coordinator(int(cbals[i]), int(next_slots[i]), pvs)

    def set_cursor(self, rows, cursors, next_slots) -> None:
        for i in range(len(rows)):
            g = self._g(rows[i])
            if g is None:
                continue
            g.exec_cursor = int(cursors[i])
            g.next_slot = max(g.next_slot, int(next_slots[i]))

    def gc(self, rows, upto) -> None:
        for i in range(len(rows)):
            g = self._g(rows[i])
            if g is not None:
                g.garbage_collect(int(upto[i]))

    def cursor_of(self, row: int) -> int:
        g = self._g(row)
        return g.exec_cursor if g else 0

    def snapshot_row(self, row: int) -> dict:
        g = self.groups[int(row)]
        return {
            "members": g.members, "version": g.version, "bal": g.bal,
            "accepted": [(pv.slot, pv.bal, pv.req_id)
                         for pv in g.accepted.values()],
            "decided": list(g.decided.items()),
            "exec_cursor": g.exec_cursor, "gc_slot": g.gc_slot,
            "is_coord": g.is_coord, "coord_active": g.coord_active,
            "cbal": g.cbal, "next_slot": g.next_slot,
        }

    def restore_row(self, row: int, snap: dict) -> None:
        g = make_oracle_group(snap["members"], self._window, snap["bal"],
                              False, snap["version"])
        for s, b, r in snap["accepted"]:
            g.accepted[s] = PValue(s, b, r)
        g.decided = dict(snap["decided"])
        g.exec_cursor = snap["exec_cursor"]
        g.gc_slot = snap["gc_slot"]
        g.is_coord = snap["is_coord"]
        g.coord_active = snap["coord_active"]
        g.cbal = snap["cbal"]
        g.next_slot = snap["next_slot"]
        self.groups[int(row)] = g


# --------------------------------------------------------------------------
# native backend (C++ per-instance engine)
# --------------------------------------------------------------------------


class NativeBackend(AcceptorBackend):
    """C++ per-instance group store behind the same SPI
    (``native/groupstore.cc``).

    Role (SURVEY §2.6, §7.3.3): the reference's per-instance hot path is
    JIT'd Java; a CPython loop is an unfair stand-in for it.  This engine
    is (a) the honest "per-instance Java-equivalent" baseline for the
    >=10x TPU comparison in ``bench.py``, and (b) the node runtime's
    low-latency path — per-call overhead is one ctypes call, no device
    round trip, so trickle traffic doesn't pay the columnar dispatch tax.
    Semantics are the ``ops.oracle`` state machine verbatim (property-
    tested for parity in ``tests/test_native.py``).
    """

    def __init__(self, capacity: int, window: int = 16):
        from gigapaxos_tpu.native import GroupStore
        self.store = GroupStore(capacity, window)
        self._window = window
        self.capacity = capacity

    @property
    def window(self) -> int:
        return self._window

    def create(self, rows, members, versions, init_bal, self_coord):
        self.store.create(rows, members, versions, init_bal, self_coord)

    def delete(self, rows):
        self.store.delete(rows)

    def accept(self, rows, slots, bals, req_ids) -> AcceptRes:
        acked, stale, ow, cur = self.store.accept(rows, slots, bals,
                                                  req_ids)
        return AcceptRes(acked, stale, ow, cur)

    def accept_reply(self, rows, slots, bals, senders, acked
                     ) -> AcceptReplyRes:
        newly, pre, dec_req, dec_bal = self.store.accept_reply(
            rows, slots, bals, senders, acked)
        lo, hi = _split64(dec_req)
        return AcceptReplyRes(newly, pre, lo, hi, dec_bal)

    def propose(self, rows, req_ids) -> ProposeRes:
        status, slot, cbal = self.store.propose(rows, req_ids)
        return ProposeRes(status == 0, status == 1, status == 2, slot,
                          cbal)

    def commit(self, rows, slots, req_ids) -> CommitRes:
        applied, stale, ow, cur = self.store.commit(rows, slots, req_ids)
        return CommitRes(applied, stale, ow, cur)

    def prepare(self, rows, bals) -> PrepareRes:
        acked, cur_bal, cursor, ws, wb, wreq = self.store.prepare(rows,
                                                                  bals)
        lo, hi = _split64(wreq.reshape(-1))
        n = len(rows)
        return PrepareRes(acked, cur_bal, cursor, ws, wb,
                          lo.reshape(n, -1), hi.reshape(n, -1))

    def _scal(self, rows) -> np.ndarray:
        """``[n, 8]``: each row's snapshot scalars, [bal, cbal,
        exec_cursor, next_slot, gc_slot, version, members, is_coord << 1
        | coord_active]."""
        return np.asarray([s["scal"] for s in self.snapshot_rows(rows)],
                          np.int32).reshape(-1, 8)

    def read_index(self, rows) -> ReadIndexRes:
        scal = self._scal(rows)
        return ReadIndexRes((scal[:, 7] == 3) & (scal[:, 0] == scal[:, 1]),
                            scal[:, 1], scal[:, 3])

    def confirm(self, rows, bals) -> np.ndarray:
        return self._scal(rows)[:, 0] == np.asarray(bals, np.int32)

    def install_coordinator(self, rows, cbals, next_slots, carry_slot,
                            carry_req) -> None:
        self.store.install(rows, cbals, next_slots, carry_slot, carry_req)

    def set_cursor(self, rows, cursors, next_slots) -> None:
        self.store.set_cursor(rows, cursors, next_slots)

    def gc(self, rows, upto) -> None:
        self.store.gc(rows, upto)

    def cursor_of(self, row: int) -> int:
        return self.store.cursor_of(row)

    def snapshot_row(self, row: int) -> dict:
        return self.store.snapshot_row(int(row))

    def restore_row(self, row: int, snap: dict) -> None:
        self.store.restore_row(int(row), snap)


# --------------------------------------------------------------------------
# columnar backend (the TPU data plane)
# --------------------------------------------------------------------------


_BUCKET_CAP = 4096  # largest jit bucket; bigger batches dispatch chunked
_WAVE_ROWS = sum(WAVE_IN)  # rows of node_wave_p's one packed input


def _bucket(n: int, lo: int = 8) -> int:
    """Smallest 8**k * lo >= n, CLAMPED at ``_BUCKET_CAP``.  Coarse on
    purpose: each (op, bucket) pair is one jit specialization, and at
    serving capacity a single compile costs ~10-20s of one-core wall —
    a x2 ladder was paying that up to 7 times per op mid-measurement.
    The x8 ladder is exactly {8, 64, 512, 4096}, and the clamp closes
    the ladder: a 4097-item batch used to pad 8x to 32768 and trigger a
    fresh multi-second compile mid-serving; now every caller splits such
    batches into <=4096-lane chunks (:func:`_chunks`), so the compile
    set is finite and fully warmable."""
    b = lo
    while b < n and b < _BUCKET_CAP:
        b <<= 3
    return b


def _ladder(top: int = _BUCKET_CAP):
    """The buckets :func:`_bucket` can return, up to ``top``'s own."""
    b = _bucket(0)
    while b < top:
        yield b
        b <<= 3
    yield b


def _chunks(n: int) -> List[Tuple[int, int]]:
    """[lo, hi) slices of at most ``_BUCKET_CAP`` lanes covering ``n``
    (a single slice for small batches; ``[(0, 0)]`` for empty input so
    fused callers still get a lane-aligned dispatch)."""
    if n <= _BUCKET_CAP:
        return [(0, n)]
    return [(at, min(at + _BUCKET_CAP, n))
            for at in range(0, n, _BUCKET_CAP)]


# One sharded program LAUNCH at a time per process: the nodes of an
# in-process emulation dispatch shard_map programs (one psum per
# output) over the SAME devices from their own worker threads.  Two
# unordered multi-device launches can reach the devices in different
# orders, and a collective whose participants run different programs
# never completes; on a virtual CPU mesh the interleaved rendezvous
# additionally thrash XLA:CPU's small thread pool ("has been waiting
# 5000ms" stalls).  The lock covers the launch only: dispatch is
# asynchronous, so on an accelerator it is held for the enqueue, not
# for the program's run.
_MESH_DISPATCH_LOCK = threading.Lock()


class EngineWave:
    """Handle for an in-flight engine wave (the submit half of a
    submit/collect pair).  ``collect()`` blocks until the device
    results are host-resident and returns the op's result tuple; call
    it exactly once.  The submit already launched the jit call(s) and
    started the device->host copies, so the wall spent inside
    ``collect`` is pure blocked-on-device time — recorded under the
    ``eng.collect`` DelayProfiler total, with the submit->collect gap
    (the overlap the caller actually won) under ``eng.overlap``."""

    __slots__ = ("_finish", "_n", "_submitted", "_wave")

    def __init__(self, finish: Callable, n: int):
        self._finish = finish
        self._n = n
        self._submitted = time.monotonic()
        # bind the wave id at submit: collect may run after the worker
        # thread has moved on to a later batch's wave
        self._wave = RequestInstrumenter.current_wave()

    def collect(self):
        overlap = time.monotonic() - self._submitted
        DelayProfiler.add_total("eng.overlap", overlap, self._n)
        # span duration = host blocked materializing; overlap_s attr =
        # the device-ran-while-host-worked gap — the device-vs-host
        # split of the wave, queryable per request
        with span("eng.collect", n=self._n, wave=self._wave,
                  lanes=self._n, overlap_s=round(overlap, 6)):
            res = self._finish()
        # full wave wall (submit->materialized) as a histogram: the
        # wave-time distribution the flight deck renders
        DelayProfiler.update_delay("eng.wave", self._submitted)
        return res


def _d2h_start(out) -> None:
    """Begin the async device->host copy of a kernel output (JAX async
    dispatch)."""
    out.copy_to_host_async()


def _accept_res(out) -> AcceptRes:
    return AcceptRes(out[0] != 0, out[1] != 0, out[2] != 0, out[3])


def _commit_res(out) -> CommitRes:
    return CommitRes(out[0] != 0, out[1] != 0, out[2] != 0, out[3])


def _propose_self_res(out):
    """``propose_accept_self_p``'s [9, n] -> (ProposeRes, self_acked,
    newly_decided, preempted, acc_cur_bal)."""
    granted = out[0] != 0
    return (ProposeRes(granted, out[1] != 0, out[2] != 0,
                       np.where(granted, out[3], NO_SLOT), out[4]),
            out[5] != 0, out[6] != 0, out[7] != 0, out[8])


def _reply_res(out) -> AcceptReplyRes:
    newly = out[0] != 0
    # decision fields only meaningful on newly-decided lanes
    return AcceptReplyRes(
        newly, out[1] != 0, np.where(newly, out[3], 0),
        np.where(newly, out[4], 0), np.where(newly, out[2], NO_BALLOT))


def _reply_self_res(out):
    """``accept_reply_commit_self_p``'s [9, n] -> (AcceptReplyRes,
    applied, stale): the last two are the coordinator's own commit."""
    return _reply_res(out), out[6] != 0, out[7] != 0


def _collect_cols(outs: List[Tuple[object, int]]) -> np.ndarray:
    """Materialize chunked [k, bucket] device outputs into one host
    [k, n] array (single-chunk fast path skips the concatenate)."""
    parts = [np.asarray(o)[:, :m] for o, m in outs if m]
    if len(parts) == 1:
        return parts[0]
    if not parts:  # zero live lanes: keep the [k, 0] shape
        return np.asarray(outs[0][0])[:, :0]
    return np.concatenate(parts, axis=1)


class ColumnarBackend(AcceptorBackend):
    """JAX columnar kernels over [G, W] device arrays.

    Batches are padded to power-of-two buckets (one jit specialization per
    bucket size) with invalid lanes masked — no recompile ever depends on
    live batch size or group occupancy (SURVEY §7.3.1).
    """

    def __init__(self, capacity: int, window: int = 16,
                 use_pallas_accept: Optional[bool] = None,
                 mesh=None):
        # mesh: a Mesh object pins sharding; None resolves PC.ENGINE_MESH
        # ("off"/"auto"/int — parallel.sharding.resolve_engine_mesh is
        # the single authority); the string "off" forces single-device.
        import jax

        from gigapaxos_tpu.ops import kernels, make_state
        from gigapaxos_tpu.utils.jaxcache import enable_persistent_cache

        # warm compiles for every process after the first: the packed
        # kernels at serving capacity take ~10-20s EACH to compile on a
        # one-core host, and without the persistent cache the node pays
        # that mid-measurement for every (op, bucket) specialization.
        # Idempotent (module-level once-flag in jaxcache): constructing
        # a second backend must not silently repoint the process-global
        # jax cache config.
        enable_persistent_cache()
        self._jax = jax
        self._k = kernels
        self._kpfx = ""  # + a table entry's name = its ledger name
        self.state = make_state(capacity, window)
        self._window = window
        self.capacity = capacity
        # group-axis sharding over a device mesh (SURVEY §2.7): state
        # lives sharded; batch inputs are replicated; the kernel table
        # is swapped for shard_map programs (ops/meshkernels.py) that
        # run each wave shard-local.  PC.ENGINE_MESH "auto" shards
        # across all local devices when there are >1 — which includes
        # the test env's virtual 8-CPU mesh, so the e2e suites exercise
        # this path, not just the storm dryrun.
        mesh_auto_ok = mesh != "off"
        if mesh == "off":
            mesh = None
        self._mesh = mesh
        self._repl = None
        # the engine runs where JAX's default backend is (the tests pin
        # the CPU in tests/conftest.py; nothing in the package does)
        devs = jax.local_devices()
        if self._mesh is None and mesh_auto_ok:
            from gigapaxos_tpu.parallel.sharding import resolve_engine_mesh
            self._mesh = resolve_engine_mesh(capacity, devs)
        # resolve the tri-state arg into a local; the parameter itself
        # is never rebound (analysis `shadow` rule)
        pallas_ok = use_pallas_accept
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from gigapaxos_tpu.ops.meshkernels import mesh_kernels
            ns = NamedSharding(self._mesh, PartitionSpec("groups"))
            self.state = jax.device_put(
                self.state,
                jax.tree_util.tree_map(lambda _: ns, self.state))
            self._repl = NamedSharding(self._mesh, PartitionSpec())
            # swap the kernel table: same attribute surface, but every
            # per-wave entry is a shard_map program (ops/meshkernels.py)
            # that keeps the wave shard-local — no cross-device gather
            # on the hot path
            self._k = mesh_kernels(self._mesh)
            self._kpfx = "mesh."
            self.engine_mesh = int(self._mesh.size)
            pallas_ok = False  # Mosaic path is single-device
        self.engine_platform = (
            self._mesh.devices.flat[0] if self._mesh is not None
            else devs[0]).platform
        # fused Pallas accept path (ops/pallas_accept.py): opt-in via
        # arg or PC.USE_PALLAS_ACCEPT; the probe call compiles it, and
        # a kernel that was asked for and does not build raises
        self._pallas = None
        from gigapaxos_tpu.utils.config import Config
        from gigapaxos_tpu.paxos.paxosconfig import PC
        if pallas_ok is None:
            pallas_ok = bool(Config.get(PC.USE_PALLAS_ACCEPT))
        # see _MESH_DISPATCH_LOCK: one sharded launch at a time
        self._serialize_dispatch = self._mesh is not None
        if pallas_ok:
            from gigapaxos_tpu.ops.pallas_accept import PallasAccept
            self._pallas = PallasAccept()
            probe = np.zeros(1, np.int32)
            self.state, _out = self._pallas(
                self.state, probe, probe, probe, probe, probe,
                np.ones(1, bool))
        self._kcosts: Optional[Dict[str, dict]] = None
        self._warm_kernels()

    def _warm_kernels(self) -> None:
        """Compile the hot SERVING kernels on all-padding inputs NOW,
        at construction, instead of mid-serving: a cold first-touch
        compile (~2-20 s at serving capacities on a one-core host)
        landing inside a request window reads as a multi-second latency
        spike or a client timeout.  All-invalid lanes make every warm
        call a state no-op; with the persistent cache this is a disk
        load after the first process on a machine.  ``node_wave_p``,
        the one program a node's worker batches launch where whole
        waves fuse, is loaded at EVERY bucket of the ladder: which
        buckets a warm-up's traffic reaches is chance, and a program
        first used inside a window compiles there.  The eight split
        and pair kernels at the smallest bucket only: their larger
        buckets still compile on first use, and the load ramp, not the
        trickle path, absorbs those.  What a
        takeover runs (``prepare``, ``install_coordinator``, and
        ``propose_accept_self_p`` for what was orphaned or parked) has
        no ramp: a leader's death is its first use, so a node with
        peers loads those at every bucket at boot
        (:meth:`warm_elections`, called by ``PaxosNode``)."""
        k, b = self._k, _bucket(0)

        def z(rows_, b=b):
            return self._dev(np.zeros((rows_, b), np.int32))

        # the warming bracket tells the ledger these traces define the
        # hot set (and are never retrace incidents); mark_warm arms the
        # alarm — any later re-trace of a kernel warmed here fires the
        # flight recorder
        with EngineLedger.warming():
            st = self.state
            st, _ = k.propose_p(st, z(4))
            st, _ = k.accept_p(st, z(6))
            st, _ = k.accept_reply_p(st, z(6))
            st, _ = k.commit_p(st, z(5))
            st, _ = k.propose_accept_self_p(st, z(5))
            st, _ = k.accept_reply_commit_self_p(st, z(6))
            st, _, _ = k.accept_commit_p(st, z(6), z(5))
            st, _, _ = k.request_reply_p(st, z(5), z(6))
            for b in _ladder():
                with self._disp():
                    st, _ = k.node_wave_p(st, z(_WAVE_ROWS, b))
            self.state = st
        EngineLedger.mark_warm()

    def warm_elections(self) -> None:
        """Load ``prepare``, ``install_coordinator`` and the new
        coordinator's re-proposal wave (``propose_accept_self_p``:
        orphaned and parked requests proposed under the new ballot, as
        many in one call as were waiting) at every bucket a takeover
        can dispatch: a mass election runs in ``_BUCKET_CAP`` chunks
        and its stragglers (rows with accepted pvalues, re-drives) one
        or a few rows at a time, so every step of the ladder up to the
        capacity's own.  All-invalid lanes, a state no-op, under the
        ledger's warming bracket like :meth:`_warm_kernels`; what it
        saves is a compile (seconds at serving capacity) inside the
        seconds in which the dead leader's groups have no
        coordinator."""
        k, W = self._k, self._window
        with EngineLedger.warming():
            st = self.state
            for b in _ladder(_bucket(self.capacity)):
                z, no = np.zeros(b, np.int32), np.zeros(b, bool)
                zw = np.zeros((b, W), np.int32)
                with self._disp():
                    st, _ = k.prepare(st, self._dev(z), self._dev(z),
                                      self._dev(no))
                    st, _ = k.install_coordinator(
                        st, self._dev(z), self._dev(z), self._dev(z),
                        self._dev(zw), self._dev(zw), self._dev(zw),
                        self._dev(no))
                    st, _ = k.propose_accept_self_p(
                        st, self._dev(np.zeros((5, b), np.int32)))
            self.state = st

    def warm_recovery(self) -> None:
        """Load ``create_groups``, ``set_cursor``, ``accept_p`` and
        ``commit_p`` at every bucket of the ladder: a recovery installs
        every row in ``_BUCKET_CAP`` chunks, sets as many cursors as it
        has checkpoints and replays as many lanes as its WAL holds, and
        a catch-up installs whatever a peer's answer brings, so the last
        chunk of each is any step of the ladder, and none of them is a
        size the served path launches (a worker batch is one
        ``node_wave_p``).  All-invalid lanes, a state no-op, under the
        ledger's warming bracket like :meth:`warm_elections`.  A node
        calls it at the head of its recovery; in a process that has
        loaded them already (another node of an in-process emulation,
        or a driver before its window) every call is a cache hit."""
        k = self._k
        with EngineLedger.warming():
            st = self.state
            for b in _ladder():
                z, no = np.zeros(b, np.int32), np.zeros(b, bool)
                with self._disp():
                    st, _ = k.create_groups(
                        st, self._dev(z), self._dev(z), self._dev(z),
                        self._dev(z), self._dev(no), self._dev(no))
                    st, _ = k.set_cursor(st, self._dev(z), self._dev(z),
                                         self._dev(z), self._dev(no))
                    st, _ = k.accept_p(
                        st, self._dev(np.zeros((6, b), np.int32)))
                    st, _ = k.commit_p(
                        st, self._dev(np.zeros((5, b), np.int32)))
            self.state = st

    def programs(self, *kernels: str) -> str:
        return "+".join(
            "jit_" + getattr(getattr(self._k, name), "__name__", name)
            for name in kernels)

    @property
    def window(self) -> int:
        return self._window

    # -- padding helpers ---------------------------------------------------

    def _dev(self, arr):
        """Host array -> device; replicated over the mesh when sharded
        (batch lanes are the replicated axis of SURVEY §2.7)."""
        if self._repl is not None:
            return self._jax.device_put(arr, self._repl)
        import jax.numpy as jnp
        return jnp.asarray(arr)

    def _pad1(self, arr, fill, dtype=np.int32):
        n = len(arr)
        b = _bucket(n)
        out = np.full(b, fill, dtype)
        out[:n] = arr
        return self._dev(out)

    def _valid(self, n):
        b = _bucket(n)
        v = np.zeros(b, bool)
        v[:n] = True
        return self._dev(v)

    def _np(self, out, n):
        """Device outputs -> host numpy, sliced back to live length."""
        return tuple(np.asarray(x)[:n] for x in out)

    def _packed(self, n, *cols, bucket=None):
        """Stack batch columns into ONE padded [k, bucket] i32 array with
        the valid mask as the last row — a single host->device transfer
        per kernel call (link round trips dominate small batches).
        ``bucket`` lets multi-input fused calls share one padded size so
        their jit cache stays bounded by the ladder, not its square.

        The buffer is a fresh ``np.empty`` per wave, fully overwritten
        (live lanes + padding tail) — that keeps the old np.zeros'
        memset off the hot path WITHOUT reusing buffers.  Reuse rings
        were tried and are unsound here: ``jnp.asarray`` on XLA:CPU
        zero-copies (the device array aliases this numpy buffer) and
        dispatch is asynchronous, so a wave deep enough to wrap any
        fixed-depth ring would overwrite an in-flight chunk's input."""
        b = bucket or _bucket(n)
        with traced("eng.pack", n=n, bytes=4 * (len(cols) + 1) * b):
            out = np.empty((len(cols) + 1, b), np.int32)
            for i, (col, fill) in enumerate(cols):
                row = out[i]
                row[:n] = col
                row[n:] = fill
            out[len(cols), :n] = 1  # valid mask
            out[len(cols), n:] = 0
            return self._dev(out)

    def _disp(self):
        """Dispatch guard: the process-wide one-sharded-launch-at-a-
        time lock when the slab is on a mesh, a no-op otherwise."""
        if self._serialize_dispatch:
            return _MESH_DISPATCH_LOCK
        return contextlib.nullcontext()

    def _submit_span(self, name: str, n: int, chunks, inputs: int = 1):
        """The span of one submit: the ``eng.submit`` total and, while
        spans are on, ``gp.eng.submit`` naming its kernel and bucket.
        Beside it, always on, ``eng.lanes_dispatched`` takes the padded
        lanes launched (:meth:`_submit_done` adds the valid ones per
        kernel)."""
        launched = inputs * sum(_bucket(b - a) for a, b in chunks)
        self.launches += len(chunks)
        DelayProfiler.add_total("eng.lanes_dispatched", 0.0, launched,
                                calls=len(chunks))
        return span("eng.submit", n=n, kernel=self._kpfx + name, lanes=n,
                    bucket=_bucket(chunks[0][1] - chunks[0][0]),
                    chunks=len(chunks), launched=launched)

    def _submit_done(self, name: str, sp, n: int, chunks: int) -> None:
        """``eng.k.<kernel>``: launch wall, a call per chunk, the valid
        lanes."""
        DelayProfiler.add_total("eng.k." + self._kpfx + name,
                                sp.t1 - sp.t0, n, calls=chunks)

    def _collect_now(self, outs, n: int) -> np.ndarray:
        """Collect right behind the submit (the ops with no host work
        to overlap): the same ``gp.eng.collect`` span as
        :meth:`EngineWave.collect`, but no sum, so that ``eng.collect``
        counts what it always counted."""
        with traced("eng.collect", n=n, lanes=n):
            return _collect_cols(outs)

    def _submit1(self, name, n, cols) -> List[Tuple[object, int]]:
        """Launch the packed kernel ``name`` (of the kernel table
        ``self._k``) over <=``_BUCKET_CAP``-lane chunks
        (the bucket-ladder clamp) and start every chunk output's async
        device->host copy; returns the chunk list for _collect_cols.
        Chunks apply sequentially, which is a per-chunk linearization —
        safe for paxos exactly like the batch linearization (kernels.py
        determinism note), and what the scalar engines do per item."""
        kern, chunks = getattr(self._k, name), _chunks(n)
        with self._submit_span(name, n, chunks) as sp:
            cols = [(np.asarray(c), f) for c, f in cols]
            outs = []
            for a, bnd in chunks:
                m = bnd - a
                with self._disp():
                    self.state, o = kern(self.state, self._packed(
                        m, *[(c[a:bnd], f) for c, f in cols]))
                _d2h_start(o)
                outs.append((o, m))
        self._submit_done(name, sp, n, len(chunks))
        return outs

    # -- ops ---------------------------------------------------------------

    def create(self, rows, members, versions, init_bal, self_coord):
        rows, members = np.asarray(rows), np.asarray(members)
        versions, init_bal = np.asarray(versions), np.asarray(init_bal)
        self_coord = np.asarray(self_coord)
        for a, b in _chunks(len(rows)):
            m = b - a
            with self._disp():
                self.state, _ = self._k.create_groups(
                    self.state, self._pad1(rows[a:b], 0),
                    self._pad1(members[a:b], 1),
                    self._pad1(versions[a:b], 0),
                    self._pad1(init_bal[a:b], NO_BALLOT),
                    self._pad1(self_coord[a:b], False, bool),
                    self._valid(m))

    def delete(self, rows):
        rows = np.asarray(rows)
        for a, b in _chunks(len(rows)):
            with self._disp():
                self.state, _ = self._k.delete_groups(
                    self.state, self._pad1(rows[a:b], 0),
                    self._valid(b - a))

    def accept_submit(self, rows, slots, bals, req_ids) -> EngineWave:
        """Non-blocking accept wave: launches the jit call(s) and the
        device->host output copy, returning an :class:`EngineWave` whose
        ``collect()`` yields the :class:`AcceptRes`.  The blocking
        :meth:`accept` is this submit + an immediate collect."""
        n = len(rows)
        lo, hi = _split64(req_ids)
        if self._pallas is not None:
            self.state, (acked, stale, ow, cur_bal) = self._pallas(
                self.state, np.asarray(rows, np.int32),
                np.asarray(slots, np.int32), np.asarray(bals, np.int32),
                lo, hi, np.ones(n, bool))
            res = AcceptRes(acked, stale, ow, cur_bal)
            return EngineWave(lambda: res, n)
        outs = self._submit1("accept_p", n, [
            (rows, 0), (slots, NO_SLOT), (bals, NO_BALLOT), (lo, 0),
            (hi, 0)])

        return EngineWave(lambda: _accept_res(_collect_cols(outs)), n)

    def accept(self, rows, slots, bals, req_ids) -> AcceptRes:
        return self.accept_submit(rows, slots, bals, req_ids).collect()

    def accept_reply_submit(self, rows, slots, bals, senders, acked
                            ) -> EngineWave:
        n = len(rows)
        outs = self._submit1("accept_reply_p", n, [
            (rows, 0), (slots, NO_SLOT), (bals, NO_BALLOT),
            (senders, 0), (np.asarray(acked, np.int32), 0)])

        return EngineWave(lambda: _reply_res(_collect_cols(outs)), n)

    def accept_reply(self, rows, slots, bals, senders, acked
                     ) -> AcceptReplyRes:
        return self.accept_reply_submit(rows, slots, bals, senders,
                                        acked).collect()

    def propose(self, rows, req_ids) -> ProposeRes:
        n = len(rows)
        lo, hi = _split64(req_ids)
        outs = self._submit1("propose_p", n, [
            (rows, 0), (lo, 0), (hi, 0)])
        out = self._collect_now(outs, n)
        granted = out[0] != 0
        return ProposeRes(granted, out[1] != 0, out[2] != 0,
                          np.where(granted, out[3], NO_SLOT), out[4])

    def commit_submit(self, rows, slots, req_ids) -> EngineWave:
        n = len(rows)
        lo, hi = _split64(req_ids)
        outs = self._submit1("commit_p", n, [
            (rows, 0), (slots, NO_SLOT), (lo, 0), (hi, 0)])

        return EngineWave(lambda: _commit_res(_collect_cols(outs)), n)

    def commit(self, rows, slots, req_ids) -> CommitRes:
        return self.commit_submit(rows, slots, req_ids).collect()

    def _submit2(self, name, n1, cols1, n2, cols2):
        """Dual-input fused dispatch, chunked like :meth:`_submit1`
        with BOTH inputs sharing one bucket per chunk (bounds the
        composed kernel's jit cache to the ladder, not its square)."""
        kern, chunks = getattr(self._k, name), _chunks(max(n1, n2))
        with self._submit_span(name, n1 + n2, chunks, inputs=2) as sp:
            cols1 = [(np.asarray(c), f) for c, f in cols1]
            cols2 = [(np.asarray(c), f) for c, f in cols2]
            outs1, outs2 = [], []
            for a, bnd in chunks:
                a1, b1 = min(a, n1), min(bnd, n1)
                a2, b2 = min(a, n2), min(bnd, n2)
                b = _bucket(max(b1 - a1, b2 - a2))
                with self._disp():
                    self.state, o1, o2 = kern(
                        self.state,
                        self._packed(b1 - a1,
                                     *[(c[a1:b1], f) for c, f in cols1],
                                     bucket=b),
                        self._packed(b2 - a2,
                                     *[(c[a2:b2], f) for c, f in cols2],
                                     bucket=b))
                _d2h_start(o1)
                _d2h_start(o2)
                outs1.append((o1, b1 - a1))
                outs2.append((o2, b2 - a2))
        self._submit_done(name, sp, n1 + n2, len(chunks))
        return outs1, outs2

    def accept_commit_submit(self, rows_a, slots_a, bals_a, reqs_a,
                             rows_c, slots_c, reqs_c) -> EngineWave:
        """ONE device dispatch per chunk for the acceptor wave (accepts
        then commits — `kernels.accept_commit_packed`).  Dispatch
        overhead, not kernel time, dominates runtime batches (~0.2-0.3
        ms/call warm), so halving the acceptor's calls is a direct
        latency-path win."""
        na, nc = len(rows_a), len(rows_c)
        if self._pallas is not None:
            # the Pallas accept path owns accepts; keep the calls split
            res = AcceptorBackend.accept_commit(
                self, rows_a, slots_a, bals_a, reqs_a, rows_c, slots_c,
                reqs_c)
            return EngineWave(lambda: res, na + nc)
        lo_a, hi_a = _split64(reqs_a)
        lo_c, hi_c = _split64(reqs_c)
        outs_a, outs_c = self._submit2(
            "accept_commit_p",
            na, [(rows_a, 0), (slots_a, NO_SLOT), (bals_a, NO_BALLOT),
                 (lo_a, 0), (hi_a, 0)],
            nc, [(rows_c, 0), (slots_c, NO_SLOT), (lo_c, 0),
                 (hi_c, 0)])

        return EngineWave(lambda: (_accept_res(_collect_cols(outs_a)),
                                   _commit_res(_collect_cols(outs_c))),
                          na + nc)

    def accept_commit(self, rows_a, slots_a, bals_a, reqs_a,
                      rows_c, slots_c, reqs_c
                      ) -> Tuple[AcceptRes, CommitRes]:
        return self.accept_commit_submit(rows_a, slots_a, bals_a,
                                         reqs_a, rows_c, slots_c,
                                         reqs_c).collect()

    def accept_reply_commit_self(self, rows, slots, bals, senders, acked
                                 ) -> Tuple[AcceptReplyRes, np.ndarray,
                                            np.ndarray]:
        """Fused reply + own commit (ONE device call per chunk; see
        kernels.accept_reply_commit_self_packed).  Returns
        (AcceptReplyRes, applied[B], stale[B]) — the extra columns are
        the coordinator's own commit result for newly-decided lanes
        (execution is re-derived host-side from the decision dict, so
        the device cursor is not surfaced)."""
        n = len(rows)
        outs = self._submit1("accept_reply_commit_self_p", n, [
            (rows, 0), (slots, NO_SLOT), (bals, NO_BALLOT),
            (senders, 0), (np.asarray(acked, np.int32), 0)])
        return _reply_self_res(self._collect_now(outs, n))

    def propose_self(self, rows, req_ids, self_midx):
        """Fused propose + own accept + own vote (ONE device call per
        chunk; see kernels.propose_accept_self_packed).  Returns
        (ProposeRes, self_acked[B], newly_decided[B], preempted[B],
        acc_cur_bal[B]) — the last two surface what the loopback
        self-wave's nack reply used to carry."""
        n = len(rows)
        lo, hi = _split64(req_ids)
        outs = self._submit1("propose_accept_self_p", n, [
            (rows, 0), (lo, 0), (hi, 0), (self_midx, 0)])
        return _propose_self_res(self._collect_now(outs, n))

    def propose_self_reply_submit(self, rows_p, reqs_p, self_midx,
                                  rows_r, slots_r, bals_r, senders_r,
                                  acked_r) -> EngineWave:
        """Fused coordinator wave (ONE device call per chunk;
        kernels.request_reply_p): new proposals + accept replies of the
        same worker batch.  ``collect()`` returns what
        :meth:`propose_self` and :meth:`accept_reply_commit_self`
        return, as a pair."""
        np_, nr = len(rows_p), len(rows_r)
        lo_p, hi_p = _split64(reqs_p)
        outs_p, outs_r = self._submit2(
            "request_reply_p",
            np_, [(rows_p, 0), (lo_p, 0), (hi_p, 0), (self_midx, 0)],
            nr, [(rows_r, 0), (slots_r, NO_SLOT), (bals_r, NO_BALLOT),
                 (senders_r, 0), (np.asarray(acked_r, np.int32), 0)])

        return EngineWave(
            lambda: (_propose_self_res(_collect_cols(outs_p)),
                     _reply_self_res(_collect_cols(outs_r))), np_ + nr)

    def propose_self_reply(self, rows_p, reqs_p, self_midx,
                           rows_r, slots_r, bals_r, senders_r, acked_r):
        return self.propose_self_reply_submit(
            rows_p, reqs_p, self_midx, rows_r, slots_r, bals_r,
            senders_r, acked_r).collect()

    def wave_submit(self, req=None, rep=None, acc=None, com=None
                    ) -> EngineWave:
        """ONE worker batch's hot frames in ONE device dispatch a chunk
        (``kernels.node_wave_p``): ``req`` = (rows, req_ids, self_midx)
        as :meth:`propose_self` takes them, ``rep`` = (rows, slots,
        bals, senders, acked) as :meth:`accept_reply_commit_self`,
        ``acc`` = (rows, slots, bals, req_ids) as :meth:`accept`,
        ``com`` = (rows, slots, req_ids) as :meth:`commit`; a role the
        batch does not have is None and rides as padding, so every
        batch launches the same program.  One staged buffer, one
        host->device copy, one launch and one copy back a chunk: the
        four sections share the bucket of the longest.  ``collect()``
        returns what those four calls return, in that order, None for
        a section that was not given."""
        given = (req, rep, acc, com)
        if self._pallas is not None:
            # the Pallas accept path owns accepts: the calls stay split
            calls = (self.propose_self, self.accept_reply_commit_self,
                     self.accept, self.commit)
            res = tuple(None if sec is None else call(*sec)
                        for call, sec in zip(calls, given))
            return EngineWave(lambda: res, sum(
                len(sec[0]) for sec in given if sec is not None))
        # each section's columns as node_wave_packed stacks them; the
        # fills are _packed's (what a padding lane holds is never read)
        secs: list = [(), (), (), ()]
        if req is not None:
            rows, reqs, midx = req
            secs[0] = ((rows, 0), *((c, 0) for c in _split64(reqs)),
                       (midx, 0))
        if rep is not None:
            rows, slots, bals, senders, acked = rep
            secs[1] = ((rows, 0), (slots, NO_SLOT), (bals, NO_BALLOT),
                       (senders, 0), (np.asarray(acked, np.int32), 0))
        if acc is not None:
            rows, slots, bals, reqs = acc
            secs[2] = ((rows, 0), (slots, NO_SLOT), (bals, NO_BALLOT),
                       *((c, 0) for c in _split64(reqs)))
        if com is not None:
            rows, slots, reqs = com
            secs[3] = ((rows, 0), (slots, NO_SLOT),
                       *((c, 0) for c in _split64(reqs)))
        ns = [len(cols[0][0]) if cols else 0 for cols in secs]
        n, chunks = sum(ns), _chunks(max(ns))
        with self._submit_span("node_wave_p", n, chunks,
                               inputs=len(secs)) as sp:
            sp.note(sections="+".join(
                name for name, m in zip(WAVE_SECTIONS, ns) if m))
            secs = [[(np.asarray(c), f) for c, f in cols]
                    for cols in secs]
            outs = []
            for a, bnd in chunks:
                b = _bucket(bnd - a)
                ms = [min(bnd, m) - min(a, m) for m in ns]
                with traced("eng.pack", n=sum(ms),
                            bytes=4 * _WAVE_ROWS * b):
                    buf = np.empty((_WAVE_ROWS, b), np.int32)
                    for (lo, hi), cols, m, n_s in zip(
                            WAVE_IN_CUTS, secs, ms, ns):
                        if not m:  # no lane of this role in the chunk
                            buf[lo:hi] = 0
                            continue
                        at = min(a, n_s)
                        for row, (col, fill) in zip(buf[lo:hi], cols):
                            row[:m] = col[at:at + m]
                            row[m:] = fill
                        buf[hi - 1, :m] = 1  # valid mask
                        buf[hi - 1, m:] = 0
                    dev = self._dev(buf)
                with self._disp():
                    self.state, o = self._k.node_wave_p(self.state, dev)
                _d2h_start(o)
                outs.append((o, ms))
        self._submit_done("node_wave_p", sp, n, len(chunks))

        # finish holds the outputs and which roles were given, not the
        # batch's columns
        decode = [None if sec is None else dec for sec, dec in zip(
            given, (_propose_self_res, _reply_self_res, _accept_res,
                    _commit_res))]

        def finish():
            hosts = [(np.asarray(o), ms) for o, ms in outs]
            res = []
            for s, ((lo, hi), dec) in enumerate(zip(WAVE_OUT_CUTS,
                                                    decode)):
                cut = [h[lo:hi, :ms[s]] for h, ms in hosts]
                res.append(dec and dec(
                    cut[0] if len(cut) == 1
                    else np.concatenate(cut, axis=1)))
            return tuple(res)
        return EngineWave(finish, n)

    def _dispatch_span(self, kind: str, name: str, n: int, chunks,
                       back: int):
        """The span of one dispatch outside the node wave
        (``gp.eng.prepare`` / ``.install`` / ``.read_index`` /
        ``.confirm`` and the total of the same name): the kernel it
        launches and the jitted function's own name (``program``: a
        device trace lists its runs as ``jit_<program>``), valid lanes,
        first chunk's bucket, chunks, and the ``bytes`` its results copy
        back to the host (``back`` a padded lane)."""
        return span(kind, n=n, kernel=self._kpfx + name,
                    program=getattr(getattr(self._k, name), "__name__",
                                    name), lanes=n,
                    bucket=_bucket(chunks[0][1] - chunks[0][0]),
                    chunks=len(chunks),
                    bytes=back * sum(_bucket(b - a) for a, b in chunks))

    def prepare(self, rows, bals) -> PrepareRes:
        rows, bals = np.asarray(rows), np.asarray(bals)
        n = len(rows)
        chunks = _chunks(n)
        # copied back a lane: acked (a byte), the promise and the
        # cursor, and the four [W] window columns
        with self._dispatch_span("eng.prepare", "prepare", n, chunks,
                                 9 + 16 * self._window):
            outs = []
            for a, b in chunks:
                with self._disp():
                    self.state, o = self._k.prepare(
                        self.state, self._pad1(rows[a:b], 0),
                        self._pad1(bals[a:b], NO_BALLOT),
                        self._valid(b - a))
                for x in o:
                    _d2h_start(x)
                outs.append((o, b - a))
            # materialize OUTSIDE the dispatch lock (the lock's job is
            # serializing sharded program dispatch, not d2h transfers)
            # and behind the last launch: a chunk's copy runs while the
            # next chunk computes
            parts = [self._np(o, m) for o, m in outs]
        acked, cur_bal, cursor, ws, wb, wl, wh = parts[0] \
            if len(parts) == 1 else \
            tuple(np.concatenate(f) for f in zip(*parts))
        # canonicalize the raw slot%W column layout into the SPI contract:
        # live pvalues (slot >= exec_cursor) compacted left, sorted by slot
        live = (ws >= 0) & (ws >= cursor[:, None])
        order = np.argsort(np.where(live, ws, np.iinfo(np.int32).max),
                           axis=1, kind="stable")
        ws2 = np.where(live, ws, NO_SLOT)
        wb2 = np.where(live, wb, NO_BALLOT)
        wl2 = np.where(live, wl, 0)
        wh2 = np.where(live, wh, 0)
        tk = np.take_along_axis
        return PrepareRes(acked, cur_bal, cursor,
                          tk(ws2, order, 1), tk(wb2, order, 1),
                          tk(wl2, order, 1), tk(wh2, order, 1))

    def install_coordinator(self, rows, cbals, next_slots, carry_slot,
                            carry_req) -> None:
        rows, cbals = np.asarray(rows), np.asarray(cbals)
        next_slots = np.asarray(next_slots)
        W = self._window
        m = carry_slot.shape[1]
        lo, hi = _split64(carry_req.reshape(-1))
        lo = lo.reshape(len(rows), m)
        hi = hi.reshape(len(rows), m)
        chunks = _chunks(len(rows))
        with self._dispatch_span("eng.install", "install_coordinator",
                                 len(rows), chunks, 0):
            for a, bnd in chunks:
                n = bnd - a
                b = _bucket(n)
                cs = np.full((b, W), NO_SLOT, np.int32)
                cl = np.zeros((b, W), np.int32)
                ch = np.zeros((b, W), np.int32)
                cs[:n, :m] = carry_slot[a:bnd]
                cl[:n, :m] = lo[a:bnd]
                ch[:n, :m] = hi[a:bnd]
                with self._disp():
                    self.state, _ = self._k.install_coordinator(
                        self.state, self._pad1(rows[a:bnd], 0),
                        self._pad1(cbals[a:bnd], NO_BALLOT),
                        self._pad1(next_slots[a:bnd], 0), self._dev(cs),
                        self._dev(cl), self._dev(ch), self._valid(n))

    def set_cursor(self, rows, cursors, next_slots) -> None:
        rows, cursors = np.asarray(rows), np.asarray(cursors)
        next_slots = np.asarray(next_slots)
        for a, b in _chunks(len(rows)):
            with self._disp():
                self.state, _ = self._k.set_cursor(
                    self.state, self._pad1(rows[a:b], 0),
                    self._pad1(cursors[a:b], 0),
                    self._pad1(next_slots[a:b], 0), self._valid(b - a))

    def gc(self, rows, upto) -> None:
        rows, upto = np.asarray(rows), np.asarray(upto)
        for a, b in _chunks(len(rows)):
            with self._disp():
                self.state, _ = self._k.gc(
                    self.state, self._pad1(rows[a:b], 0),
                    self._pad1(upto[a:b], NO_SLOT), self._valid(b - a))

    def _read_launch(self, kind: str, name: str, cols, back: int
                     ) -> np.ndarray:
        """Launch the read index's packed program ``name`` over
        <=``_BUCKET_CAP``-lane chunks and collect ``[k, n]``, as
        :meth:`_submit1` does but under a span of its own (the state
        comes back unchanged)."""
        kern = getattr(self._k, name)
        n = len(cols[0][0])
        chunks = _chunks(n)
        self.launches += len(chunks)
        with self._dispatch_span(kind, name, n, chunks, back):
            cols = [(np.asarray(c), f) for c, f in cols]
            outs = []
            for a, b in chunks:
                with self._disp():
                    self.state, o = kern(self.state, self._packed(
                        b - a, *[(c[a:b], f) for c, f in cols]))
                _d2h_start(o)
                outs.append((o, b - a))
            return _collect_cols(outs)

    def read_index(self, rows) -> ReadIndexRes:
        # copied back a lane: ok, the ballot and the index
        out = self._read_launch("eng.read_index", "read_index_p",
                                [(rows, 0)], 12)
        return ReadIndexRes(out[0] != 0, out[1], out[2])

    def confirm(self, rows, bals) -> np.ndarray:
        return self._read_launch("eng.confirm", "confirm_p",
                                 [(rows, 0), (bals, NO_BALLOT)], 4)[0] != 0

    def warm_reads(self) -> None:
        """Load ``read_index_p`` and ``confirm_p`` at every bucket of the
        ladder: a batch's reads, and a peer's confirmations, are any
        number of lanes.  All-invalid lanes, under the ledger's warming
        bracket like :meth:`warm_elections`; a node with ``PC.READ_INDEX``
        on calls it at boot."""
        k = self._k
        with EngineLedger.warming():
            for b in _ladder():
                with self._disp():
                    self.state, _ = k.read_index_p(
                        self.state, self._dev(np.zeros((2, b), np.int32)))
                    self.state, _ = k.confirm_p(
                        self.state, self._dev(np.zeros((3, b), np.int32)))

    def cursor_of(self, row: int) -> int:
        return int(self.inspect_rows([row])["exec_cursor"][0])

    def inspect_rows(self, rows) -> Dict[str, np.ndarray]:
        """ONE stacked gather + ONE device->host transfer for the four
        scalar consensus planes — the cheap vectorized extraction the
        ``/groups`` introspection endpoint leans on (snapshot_rows
        hauls the full [W, 4] window planes; this hauls 4 ints/row)."""
        fields = ("bal", "cbal", "next_slot", "exec_cursor")
        words = np.asarray(rows, np.int32)[None, :] * GROUP_WORDS + \
            np.asarray([[COL[f]] for f in fields], np.int32)
        with self._disp():
            import jax
            stacked = jax.device_get(self.state.grp[words])
        return dict(zip(fields, np.asarray(stacked, np.int64)))

    def snapshot_row(self, row: int) -> dict:
        return self.snapshot_rows([row])[0]

    def snapshot_rows(self, rows) -> List[dict]:
        """ONE gather + ONE device->host transfer for the whole sweep."""
        from gigapaxos_tpu.ops.kernels import gather_rows
        import jax
        with self._disp():
            r = gather_rows(self.state, np.asarray(rows, np.int32))
            host = jax.device_get(r)
        return [{f: np.asarray(v[i]) for f, v in zip(host._fields, host)}
                for i in range(len(rows))]

    def restore_row(self, row: int, snap: dict) -> None:
        from gigapaxos_tpu.ops.types import RowState
        from gigapaxos_tpu.ops.kernels import scatter_rows
        # coerce dtypes: snapshots may round-trip through JSON (pause
        # blobs), which turns u32 vote words / bool flags into int lists
        row_state = RowState(
            **{f: self._dev(np.asarray(snap[f]).astype(
                COL_DTYPE.get(f, np.int32))[None])
               for f in RowState._fields})
        with self._disp():
            self.state, _ = scatter_rows(
                self.state, self._dev(np.asarray([row], np.int32)),
                row_state, self._dev(np.asarray([True])))

    def release(self) -> None:
        import jax
        st, self.state = self.state, None
        for leaf in jax.tree_util.tree_leaves(st):
            leaf.delete()

    # -- flight deck: slab accounting + kernel costs -----------------------

    def memory_info(self) -> dict:
        """Per-plane slab bytes from the ACTUAL device arrays (leaf
        ``.nbytes``, not the analytical ``state_nbytes`` estimate),
        bytes/group, and — when the runtime exposes
        ``device.memory_stats()`` — a max-groups-at-current-config
        capacity estimate cross-checked against the device's byte
        limit.  Cold path (introspection scrapes only)."""
        st = self.state
        planes: Dict[str, int] = {}
        total = 0
        for f in st._fields:
            nb = int(getattr(st, f).nbytes)
            plane = _PLANE_OF[f]
            planes[plane] = planes.get(plane, 0) + nb
            total += nb
        per_group = total / float(self.capacity)
        out: dict = {
            "planes": planes,
            "total_bytes": total,
            "capacity": self.capacity,
            "window": self._window,
            "bytes_per_group": per_group,
            "mesh": int(self._mesh.size) if self._mesh is not None
            else 1,
            "platform": self.engine_platform,
        }
        # None on backends that keep no allocator statistics (XLA:CPU)
        ms = next(iter(st.grp.devices())).memory_stats()
        if ms:
            limit = int(ms.get("bytes_limit", 0) or 0)
            out["device_bytes_in_use"] = int(
                ms.get("bytes_in_use", 0) or 0)
            out["device_bytes_limit"] = limit
            if limit and per_group:
                # each mesh device holds capacity/mesh rows, so the
                # fleet capacity is per-device headroom x mesh size
                # (10% reserved for batch buffers + workspace)
                out["max_groups_estimate"] = int(
                    0.9 * limit / per_group) * out["mesh"]
        return out

    def row_ownership(self) -> dict:
        """Active-row count per mesh device (contiguous G/D blocks —
        the layout ``P(GROUP_AXIS)`` produces).  One bool-plane
        transfer; cold path."""
        active = np.asarray(self.state.active)
        d = int(self._mesh.size) if self._mesh is not None else 1
        gs = self.capacity // d
        return {
            "rows_active": int(active.sum()),
            "mesh": [int(active[k * gs:(k + 1) * gs].sum())
                     for k in range(d)],
        }

    def kernel_costs(self) -> Dict[str, dict]:
        """flops / bytes-accessed per hot kernel from the lowered HLO's
        ``cost_analysis()`` at the warm (bucket-8) shapes.  Lowering
        re-traces, so the whole sweep runs inside the ledger's warming
        bracket — a cost scrape must never read as a retrace incident.
        Memoized per backend; best-effort per kernel (a backend whose
        lowering can't cost-analyze reports nulls, not errors)."""
        if self._kcosts is not None:
            return self._kcosts
        k, b = self._k, _bucket(0)

        def z(rows_):
            return self._dev(np.zeros((rows_, b), np.int32))

        prefix = self._kpfx
        sweep = [("propose_p", (z(4),)), ("accept_p", (z(6),)),
                 ("accept_reply_p", (z(6),)), ("commit_p", (z(5),)),
                 ("accept_commit_p", (z(6), z(5))),
                 ("request_reply_p", (z(5), z(6))),
                 ("node_wave_p", (z(_WAVE_ROWS),))]
        out: Dict[str, dict] = {}
        with EngineLedger.warming():
            for name, args in sweep:
                try:
                    ca = getattr(k, name).lower(
                        self.state, *args).cost_analysis()
                    if isinstance(ca, (list, tuple)):
                        ca = ca[0]
                    out[prefix + name] = {
                        "flops": float(ca.get("flops", 0.0)),
                        "bytes_accessed": float(
                            ca.get("bytes accessed", 0.0)),
                    }
                except Exception:
                    out[prefix + name] = {"flops": None,
                                          "bytes_accessed": None}
        self._kcosts = out
        return out


# plane grouping of the ColumnarState fields for the accounting view:
# the window planes' components roll up into the three slabs they make
# (acc_slot .. acc_rhi -> "acc"); the groups' scalars are one table
_PLANE_OF = {
    **{c: view for view, cols in PLANES.items() for c, _ in cols},
    "grp": "groups",
}
