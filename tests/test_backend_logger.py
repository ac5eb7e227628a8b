"""GroupTable, AcceptorBackend SPI (scalar vs columnar equivalence), and
durable logger tests."""

import numpy as np
import pytest

from gigapaxos_tpu.ops.types import NO_BALLOT
from gigapaxos_tpu.ops import pack_ballot
from gigapaxos_tpu.paxos.grouptable import GroupTable
from gigapaxos_tpu.paxos.backend import (ScalarBackend, ColumnarBackend,
                                         _split64, _join64)
from gigapaxos_tpu.paxos.logger import (PaxosLogger, LogEntry,
                                        CheckpointRec, REC_ACCEPT,
                                        REC_DECIDE)
from tests.conftest import tscale

pytestmark = pytest.mark.smoke  # <60s fast-signal subset


def test_grouptable_lifecycle():
    gt = GroupTable(capacity=4)
    a = gt.create("a", (0, 1, 2))
    b = gt.create("b", (0, 1, 2))
    assert a.row != b.row and len(gt) == 2
    assert gt.by_name("a") is a and gt.by_key(a.gkey) is a
    assert gt.by_row(b.row) is b
    with pytest.raises(KeyError):
        gt.create("a", (0, 1, 2))
    gt.delete(a.gkey)
    c = gt.create("c", (0,))
    assert c.row == a.row  # LIFO row reuse
    gt.create("d", (0,))
    gt.create("e", (0,))
    with pytest.raises(MemoryError):
        gt.create("f", (0,))


def _mk_backend(kind, window=8):
    if kind == "scalar":
        return ScalarBackend(window=window)
    return ColumnarBackend(capacity=64, window=window)


@pytest.mark.parametrize("kind", ["scalar", "columnar"])
def test_backend_full_round(kind):
    """Drive one backend through a complete decision round via the SPI."""
    be = _mk_backend(kind)
    rows = np.asarray([0, 1], np.int32)
    b0 = pack_ballot(0, 0)
    be.create(rows, np.asarray([3, 3]), np.asarray([0, 0]),
              np.asarray([b0, b0], np.int32), np.asarray([True, True]))

    reqs = np.asarray([111, 222], np.uint64)
    po = be.propose(rows, reqs)
    assert po.granted.all() and (po.slot == [0, 0]).all()

    ao = be.accept(rows, po.slot, po.cbal, reqs)
    assert ao.acked.all()

    # two acks (self + one follower) -> quorum of 3
    for sender, expect_decide in ((0, False), (1, True)):
        ro = be.accept_reply(rows, po.slot, po.cbal,
                             np.asarray([sender, sender], np.int32),
                             np.asarray([True, True]))
        assert ro.newly_decided.all() == expect_decide
    assert (_join64(ro.req_lo, ro.req_hi) == reqs).all()

    co = be.commit(rows, po.slot, reqs)
    assert co.applied.all() and (co.new_cursor == 1).all()
    assert be.cursor_of(0) == 1 and be.cursor_of(1) == 1


def _drive(be, seed, n_ops=120):
    """Deterministic pseudo-random op stream; returns outputs trace."""
    rng = np.random.default_rng(seed)
    rows_all = np.arange(4, dtype=np.int32)
    b0 = pack_ballot(0, 0)
    be.create(rows_all, np.full(4, 3, np.int32), np.zeros(4, np.int32),
              np.full(4, b0, np.int32),
              np.asarray([True, True, False, False]))
    trace = []
    for step in range(n_ops):
        n = int(rng.integers(1, 5))
        # distinct rows per batch: scalar (sequential) and columnar
        # (batch-max) linearizations only coincide without intra-batch
        # same-group conflicts — which is what the manager's batcher
        # guarantees by coalescing (see kernels.py preconditions)
        rows = rng.permutation(4)[:n].astype(np.int32)
        op = ["accept", "propose", "accept_reply", "commit",
              "prepare"][int(rng.integers(0, 5))]
        slots = rng.integers(0, 6, n).astype(np.int32)
        bals = np.asarray([pack_ballot(int(x), int(x) % 3)
                           for x in rng.integers(0, 3, n)], np.int32)
        reqs = rng.integers(1, 1 << 40, n).astype(np.uint64)
        if op == "accept":
            o = be.accept(rows, slots, bals, reqs)
        elif op == "propose":
            o = be.propose(rows, reqs)
        elif op == "accept_reply":
            o = be.accept_reply(rows, slots, bals,
                                rng.integers(0, 3, n).astype(np.int32),
                                rng.integers(0, 2, n).astype(bool))
        elif op == "commit":
            o = be.commit(rows, slots, reqs)
        else:
            o = be.prepare(rows, bals)
        trace.append((op, tuple(np.asarray(x).tolist() for x in o)))
    return trace


def test_backend_equivalence_random():
    """Scalar and columnar backends produce IDENTICAL outputs for the same
    op stream — the SPI-level version of the kernel/oracle property test."""
    for seed in (0, 1):
        t_s = _drive(_mk_backend("scalar"), seed)
        t_c = _drive(_mk_backend("columnar"), seed)
        for i, ((op_s, o_s), (op_c, o_c)) in enumerate(zip(t_s, t_c)):
            assert op_s == op_c
            assert o_s == o_c, (seed, i, op_s, o_s, o_c)


@pytest.mark.parametrize("kind", ["scalar", "columnar"])
def test_backend_pause_unpause(kind):
    """snapshot_row/restore_row round-trips hot state (pause analog)."""
    be = _mk_backend(kind)
    rows = np.asarray([3], np.int32)
    b0 = pack_ballot(0, 0)
    be.create(rows, np.asarray([3]), np.asarray([0]),
              np.asarray([b0], np.int32), np.asarray([True]))
    po = be.propose(rows, np.asarray([42], np.uint64))
    be.accept(rows, po.slot, po.cbal, np.asarray([42], np.uint64))
    snap = be.snapshot_row(3)

    be2 = _mk_backend(kind)
    be2.restore_row(3, snap)
    assert be2.cursor_of(3) == 0
    # the accepted pvalue survived: prepare at a higher ballot returns it
    pr = be2.prepare(rows, np.asarray([pack_ballot(1, 1)], np.int32))
    assert pr.acked[0]
    live = [(int(s), int(l)) for s, l in
            zip(pr.win_slot[0], pr.win_req_lo[0]) if s >= 0]
    assert (0, 42) in live


def test_split_join64():
    v = np.asarray([0, 1, 0xFFFFFFFF, 0x1_0000_0000, (1 << 64) - 1],
                   np.uint64)
    lo, hi = _split64(v)
    assert (_join64(lo, hi) == v).all()


def test_logger_wal_and_checkpoints(tmp_path):
    lg = PaxosLogger(str(tmp_path / "n0"))
    e1 = LogEntry(REC_ACCEPT, 5, 0, 4096, 101, b"payload-a")
    e2 = LogEntry(REC_DECIDE, 5, 0, 4096, 101)
    e3 = LogEntry(REC_ACCEPT, 9, 2, 0, 333, b"")
    lg.log_batch([e1, e2]).result(timeout=5)   # durable barrier
    lg.log_batch([e3]).result(timeout=5)

    got = list(lg.read_wal())
    assert [(e.rtype, e.gkey, e.slot, e.req_id) for e in got] == [
        (REC_ACCEPT, 5, 0, 101), (REC_DECIDE, 5, 0, 101),
        (REC_ACCEPT, 9, 2, 333)]
    assert got[0].payload == b"payload-a"

    lg.put_group(5, "svc5", 0, (0, 1, 2))
    lg.checkpoint(CheckpointRec(5, "svc5", 0, (0, 1, 2), 0, b"snap"))
    cp = lg.get_checkpoint(5)
    assert cp.slot == 0 and cp.state == b"snap" and cp.members == (0, 1, 2)
    assert lg.all_groups() == [(5, "svc5", 0, (0, 1, 2))]

    # compaction drops entries at/below the checkpointed slot
    lg.compact()
    left = list(lg.read_wal())
    assert [(e.gkey, e.slot) for e in left] == [(9, 2)]

    # pause round-trip
    lg.pause(5, b"hotstate")
    assert lg.unpause(5) == b"hotstate"
    assert lg.unpause(5) is None

    lg.delete_group(5)
    assert lg.get_checkpoint(5) is None and lg.all_groups() == []
    lg.close()


def test_segmented_wal_torn_tail_isolated(tmp_path):
    """A torn tail (partial record, pre-fsync crash) on ONE segment
    must drop only that segment's torn record — its own complete
    prefix and every sibling segment replay fully."""
    import os
    import struct

    d = str(tmp_path / "seg")
    lg = PaxosLogger(d, segments=3)
    for seg, gkey in ((0, 10), (1, 11), (2, 12)):
        lg.log_batch([LogEntry(REC_ACCEPT, gkey, 0, 1, 100 + gkey,
                               b"p"),
                      LogEntry(REC_DECIDE, gkey, 0, 1, 100 + gkey)],
                     seg=seg).result(5)
    lg.close()
    # tear segment 1: append a header claiming a payload that never
    # made it to disk
    rec = struct.Struct("<BQiiQI")
    with open(os.path.join(d, "wal-1.log"), "ab") as f:
        f.write(rec.pack(REC_ACCEPT, 11, 1, 1, 999, 64) + b"xx")
    lg2 = PaxosLogger(d, segments=3)
    got = lg2.read_wal()
    by_gkey = {}
    for e in got:
        by_gkey.setdefault(e.gkey, []).append((e.rtype, e.slot,
                                               e.req_id))
    # seg 1's complete records survive; the torn one is gone
    assert by_gkey[11] == [(REC_ACCEPT, 0, 111), (REC_DECIDE, 0, 111)]
    assert by_gkey[10] == [(REC_ACCEPT, 0, 110), (REC_DECIDE, 0, 110)]
    assert by_gkey[12] == [(REC_ACCEPT, 0, 112), (REC_DECIDE, 0, 112)]
    lg2.close()


def test_segmented_wal_cross_segment_replay_order(tmp_path):
    """Recovery merges every segment; per-group record order (the
    invariant execution-cursor rebuild depends on) is preserved because
    a group's records live in exactly one segment."""
    d = str(tmp_path / "xseg")
    lg = PaxosLogger(d, segments=4)
    # interleave writes across segments, multiple slots per group
    for slot in range(3):
        for seg in range(4):
            gkey = 20 + seg
            lg.log_batch([LogEntry(REC_ACCEPT, gkey, slot, 1,
                                   1000 * gkey + slot)],
                         seg=seg).result(5)
    lg.close()
    lg2 = PaxosLogger(d, segments=4)
    per_group = {}
    for e in lg2.read_wal():
        per_group.setdefault(e.gkey, []).append(e.slot)
    assert set(per_group) == {20, 21, 22, 23}
    for gkey, slots in per_group.items():
        assert slots == [0, 1, 2], (gkey, slots)  # in-order per group
    lg2.close()


def test_torn_tail_with_shard_change_recovery(tmp_path):
    """Chaos-restart dependency (PR 6): a node that crashed mid-write
    under ENGINE_SHARDS=4 restarts as an S=2 node.  Recovery must read
    ALL wal-<k>.log segments on disk — including 2 and 3, which are
    beyond the new layout — drop ONLY the torn record on the old
    segment 2, and preserve per-group record order.  This is the path
    the shard_storm scenario leans on."""
    import os
    import struct

    d = str(tmp_path / "schg")
    lg = PaxosLogger(d, segments=4)
    # two slots per group, one group per old segment
    for slot in range(2):
        for seg in range(4):
            gkey = 40 + seg
            lg.log_batch([LogEntry(REC_ACCEPT, gkey, slot, 1,
                                   1000 * gkey + slot, b"pp")],
                         seg=seg).result(5)
    lg.close()
    # tear OLD segment 2's tail: a header promising bytes that never
    # hit the disk (pre-fsync crash), exactly what a chaos crash-stop
    # leaves behind
    rec = struct.Struct("<BQiiQI")
    with open(os.path.join(d, "wal-2.log"), "ab") as f:
        f.write(rec.pack(REC_ACCEPT, 42, 9, 1, 777, 128) + b"x")

    lg2 = PaxosLogger(d, segments=2)  # the node came back with S=2
    per_group = {}
    for e in lg2.read_wal():
        per_group.setdefault(e.gkey, []).append((e.slot, e.req_id))
    # every complete record from every old segment replays, in order
    for seg in range(4):
        gkey = 40 + seg
        assert per_group.get(gkey) == [
            (0, 1000 * gkey), (1, 1000 * gkey + 1)], \
            (gkey, per_group.get(gkey))
    # the torn record is gone, silently
    assert all(req != 777 for recs in per_group.values()
               for _s, req in recs)
    # new writes land in the S=2 layout; old segments are readable
    # until compaction GCs them (logger._stale_segs covers 2 and 3)
    lg2.log_batch([LogEntry(REC_ACCEPT, 40, 2, 1, 40002)],
                  seg=0).result(5)
    got = [(e.gkey, e.slot) for e in lg2.read_wal() if e.gkey == 40]
    assert got == [(40, 0), (40, 1), (40, 2)]
    lg2.close()


def test_segmented_wal_compaction_isolated(tmp_path):
    """Compacting one segment GCs only its own below-checkpoint
    entries; sibling segments' bytes are untouched."""
    import os

    d = str(tmp_path / "cseg")
    lg = PaxosLogger(d, segments=2)
    lg.log_batch([LogEntry(REC_ACCEPT, 30, s, 1, 3000 + s, b"x" * 8)
                  for s in range(4)], seg=0).result(5)
    lg.log_batch([LogEntry(REC_ACCEPT, 31, s, 1, 3100 + s, b"y" * 8)
                  for s in range(4)], seg=1).result(5)
    # checkpoint BOTH groups past slot 1 — but compact only segment 0
    lg.checkpoint(CheckpointRec(30, "a", 0, (0,), 1, b"s"))
    lg.checkpoint(CheckpointRec(31, "b", 0, (0,), 1, b"s"))
    sib_before = open(os.path.join(d, "wal-1.log"), "rb").read()
    lg.compact_segment(0)
    assert open(os.path.join(d, "wal-1.log"), "rb").read() == sib_before
    by_gkey = {}
    for e in lg.read_wal():
        by_gkey.setdefault(e.gkey, []).append(e.slot)
    assert by_gkey[30] == [2, 3]          # GC'd below checkpoint
    assert by_gkey[31] == [0, 1, 2, 3]    # sibling untouched
    lg.close()


def test_logger_u64_keys(tmp_path):
    """gkeys with the top bit set survive the sqlite signed round-trip."""
    lg = PaxosLogger(str(tmp_path / "n1"))
    big = (1 << 64) - 3
    lg.checkpoint(CheckpointRec(big, "x", 0, (0,), 7, b"s"))
    assert lg.get_checkpoint(big).slot == 7
    lg.close()


def test_logger_recovery_after_reopen(tmp_path):
    d = str(tmp_path / "n2")
    lg = PaxosLogger(d)
    lg.log_batch([LogEntry(REC_ACCEPT, 1, 0, 0, 11, b"x")]).result(5)
    lg.put_group(1, "g", 0, (0, 1, 2))
    lg.close()

    lg2 = PaxosLogger(d)
    assert [(e.gkey, e.req_id) for e in lg2.read_wal()] == [(1, 11)]
    assert lg2.all_groups() == [(1, "g", 0, (0, 1, 2))]
    lg2.close()


def test_wal_compaction_runtime_bounded_and_recovery_exact(tmp_path):
    """Round-2 review Missing #4: compaction must RUN in the live node, not
    just exist.  A solo node with a tiny compaction threshold and a small
    checkpoint interval sustains load; the WAL must stay bounded (GC
    below the checkpointed slot) and a crash-restart must recover the
    exact app state from checkpoint + compacted tail."""
    import os
    import socket

    from gigapaxos_tpu.paxos.client import PaxosClient
    from gigapaxos_tpu.paxos.interfaces import CounterApp
    from gigapaxos_tpu.paxos.manager import PaxosNode
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config

    Config.set(PC.SYNC_WAL, False)
    Config.set(PC.CHECKPOINT_INTERVAL, 25)
    Config.set(PC.WAL_COMPACT_BYTES, 16 * 1024)
    try:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        addr_map = {0: ("127.0.0.1", s.getsockname()[1])}
        s.close()
        d = str(tmp_path / "n0")
        node = PaxosNode(0, addr_map, CounterApp(), d,
                         backend="native", capacity=1 << 8, window=16)
        node.start()
        cli = PaxosClient([addr_map[0]], timeout=tscale(10))
        digest = None
        try:
            assert node.create_group("wal", (0,))
            # ~600 requests x ~40B records >> 16KB threshold several
            # times over; payload padding accelerates the roll-over
            for k in range(600):
                r = cli.send_request("wal", b"p" * 40)
                assert r.status == 0
            import time as _t
            wal0 = os.path.join(d, "wal-0.log")  # segment-0 layout
            deadline = _t.time() + 10
            while _t.time() < deadline and \
                    os.path.getsize(wal0) > 48_000:
                _t.sleep(0.2)  # writer-thread compaction catches up
            size = os.path.getsize(wal0)
            assert size < 48_000, \
                f"WAL grew unbounded: {size}B (threshold 16KB)"
            digest = node.app.digest["wal"]
        finally:
            cli.close()
            node.stop()

        node2 = PaxosNode(0, addr_map, CounterApp(), d,
                          backend="native", capacity=1 << 8, window=16)
        node2.start()
        try:
            assert node2.app.count.get("wal") == 600
            assert node2.app.digest.get("wal") == digest
        finally:
            node2.stop()
    finally:
        Config.set(PC.CHECKPOINT_INTERVAL, 400)
        Config.set(PC.WAL_COMPACT_BYTES, 64 * 1024 * 1024)
