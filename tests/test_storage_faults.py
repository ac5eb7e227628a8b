"""Storage fault plane + durability hardening unit tests.

The fast half of the disk_storm chaos scenario: per-record CRC framing
(quarantine-at-point on mid-segment corruption, v1 compatibility,
torn-tail semantics), fsyncgate rotation (a failed fsync never retries
on the same fd; rotation saves the un-acked batch; a latched device
degrades the node), ENOSPC shedding flags, checksummed checkpoints
with WAL-only-replay fallback, and the injector's seeded determinism
(schedule fingerprints).
"""

import os

import numpy as np
import pytest

from gigapaxos_tpu.chaos.faults import StorageChaos
from gigapaxos_tpu.paxos.backend import ScalarBackend
from gigapaxos_tpu.paxos.logger import (CheckpointRec, LogEntry,
                                        PaxosLogger, REC_ACCEPT,
                                        WalDegradedError, WalFullError,
                                        corrupt_wal_record)
from gigapaxos_tpu.ops import pack_ballot

pytestmark = pytest.mark.smoke  # <60s fast-signal subset


def _entries(n, payload=b"x" * 100):
    return [LogEntry(REC_ACCEPT, 1000 + i, i, 7, 0xABC0 + i, payload)
            for i in range(n)]


def _mk(tmp_path, name="n0", **kw):
    lg = PaxosLogger(str(tmp_path / name), **kw)
    return lg


def _seg0(lg):
    return os.path.join(lg.dir, "wal-0.log")


# -- CRC framing / corruption matrix ----------------------------------


@pytest.mark.parametrize("field", ["len", "header", "payload", "crc"])
def test_corruption_byte_class_quarantines(tmp_path, field):
    """Flip one bit in each byte class of a mid-segment v2 record:
    replay keeps the clean prefix, quarantines from the damage on, and
    surfaces the event in wal_health — never silently replays garbage,
    never truncates acked records before the damage."""
    lg = _mk(tmp_path, wal_crc=True)
    lg.log_batch(_entries(8)).result(timeout=5)
    lg.close()
    corrupt_wal_record(_seg0(lg), 3, field)

    lg2 = _mk(tmp_path, wal_crc=True)
    got = lg2.read_wal()
    # a flipped length word can also misalign the scan past the file
    # end (torn-tail shaped) — either way nothing corrupt replays
    assert len(got) <= 3 or field == "len"
    assert all(e.payload == b"x" * 100 for e in got[:3])
    assert [e.slot for e in got[:3]] == [0, 1, 2]
    h = lg2.wal_health()
    if len(got) == 3:
        assert h["quarantined"], "CRC mismatch must be surfaced"
        # the damaged generation was rotated away: new appends go to a
        # fresh file, never after the corruption
        assert h["rotations"] >= 1
    lg2.close()


def test_v1_log_replays_and_upgrades(tmp_path):
    """Version gate: a headerless (pre-CRC) segment replays with the
    old torn-tail-only semantics, and reopening with WAL_CRC rewrites
    it as v2 frames in place."""
    lg = _mk(tmp_path, wal_crc=False)
    lg.log_batch(_entries(5)).result(timeout=5)
    lg.close()
    with open(_seg0(lg), "rb") as f:
        assert f.read(1) == b"\x01"  # v1: first byte is a record type

    lg2 = _mk(tmp_path, wal_crc=True)  # boot normalizes to v2
    got = lg2.read_wal()
    assert [e.slot for e in got] == [0, 1, 2, 3, 4]
    lg2.close()
    with open(_seg0(lg), "rb") as f:
        assert f.read(6) == b"GPWAL2"


def test_torn_tail_dropped_silently(tmp_path):
    """An incomplete trailing record (pre-fsync crash) is dropped with
    no quarantine — in both frame versions it is a crash artifact, not
    corruption."""
    for crc in (False, True):
        lg = _mk(tmp_path, name=f"n{int(crc)}", wal_crc=crc)
        lg.log_batch(_entries(4)).result(timeout=5)
        lg.close()
        with open(_seg0(lg), "ab") as f:
            f.write(b"\x01partial-record-header")
        lg2 = _mk(tmp_path, name=f"n{int(crc)}", wal_crc=crc)
        got = lg2.read_wal()
        assert [e.slot for e in got] == [0, 1, 2, 3]
        assert not lg2.wal_health()["quarantined"]
        lg2.close()


# -- fsyncgate: poison + rotate, degraded mode ------------------------


def test_transient_eio_rotates_and_saves_batch(tmp_path):
    """A failed fsync poisons the fd; the batch lands durably on a
    fresh generation file and the caller never sees an error — the
    'rotation saves the acks' half of fsyncgate."""
    lg = _mk(tmp_path, sync=True, node_id=0)
    try:
        StorageChaos.configure(seed=3)
        StorageChaos.set_rule(0, None, fsync_eio_p=1.0)
        lg.log_batch(_entries(3)).result(timeout=5)  # must NOT raise
        h = lg.wal_health()
        assert h["rotations"] >= 1 and not h["degraded"]
        assert lg.impaired() is None
        assert os.path.exists(os.path.join(lg.dir, "wal-0.1.log"))
        StorageChaos.clear()
        got = lg.read_wal()
        # the flushed-but-unfsynced copy on the poisoned generation may
        # survive alongside the rotated copy — replay is roll-forward
        # of accept records, so duplicates are idempotent; what must
        # hold is that every record of the batch is present
        assert sorted({e.slot for e in got}) == [0, 1, 2]
    finally:
        StorageChaos.reset()
        lg.close()


def test_persistent_eio_degrades(tmp_path):
    """A latched (whole-device) failure makes the rotated handle fail
    too: WalDegradedError, sticky health flags, fail-fast appends."""
    lg = _mk(tmp_path, sync=True, node_id=0)
    try:
        StorageChaos.configure(seed=3)
        StorageChaos.set_rule(0, None, fsync_eio_p=1.0,
                              fsync_persist=True)
        with pytest.raises(WalDegradedError):
            lg.log_batch(_entries(2)).result(timeout=5)
        assert lg.impaired() == "degraded"
        assert lg.wal_health()["degraded"]
        StorageChaos.clear()  # even with the fault gone...
        with pytest.raises(WalDegradedError):  # ...degraded is sticky
            lg.log_batch(_entries(1)).result(timeout=5)
    finally:
        StorageChaos.reset()
        lg.close()


def test_enospc_flags_and_clears(tmp_path):
    """ENOSPC raises WalFullError (nothing acked), flips the disk-full
    flag the proposal-shedding path reads, and clears on the next
    successful durable append."""
    lg = _mk(tmp_path, sync=True, node_id=0)
    try:
        StorageChaos.configure(seed=3)
        StorageChaos.set_rule(0, None, enospc_p=1.0)
        with pytest.raises(WalFullError):
            lg.log_batch(_entries(2)).result(timeout=5)
        assert lg.impaired() == "disk_full"
        assert lg.wal_health()["disk_full"]
        StorageChaos.clear()  # space comes back
        lg.log_batch(_entries(1)).result(timeout=5)
        assert lg.impaired() is None
        assert not lg.wal_health()["disk_full"]
    finally:
        StorageChaos.reset()
        lg.close()


def test_torn_append_recovers_whole_batch(tmp_path):
    """A torn append (prefix lands, device errors) rotates the whole
    batch to a fresh generation; recovery drops the torn prefix as a
    torn tail and replays every record exactly once."""
    lg = _mk(tmp_path, sync=True, node_id=0)
    try:
        StorageChaos.configure(seed=5)
        StorageChaos.set_rule(0, None, torn_p=1.0)
        lg.log_batch(_entries(4)).result(timeout=5)
        StorageChaos.clear()
        assert lg.wal_health()["rotations"] >= 1
        got = lg.read_wal()
        assert [e.slot for e in got] == [0, 1, 2, 3]
    finally:
        StorageChaos.reset()
        lg.close()


# -- checksummed checkpoints ------------------------------------------


@pytest.mark.parametrize("wal_crc,dedupe", [
    (True, b""), (True, b"\x01\x00\x00\x00ids"), (False, b"ids")])
def test_checkpoint_crc_fallback(tmp_path, wal_crc, dedupe):
    """A checkpoint blob that fails its CRC reads as ABSENT (recovery
    falls back to WAL-only replay / peer transfer), and the drop is
    tallied for the metrics plane.  One that carries dedupe ids is
    checksummed whatever WAL_CRC says (the ids' length has to be
    trusted)."""
    lg = _mk(tmp_path, wal_crc=wal_crc)
    rec = CheckpointRec(42, "g42", 0, (0, 1, 2), 9, b"state-blob", dedupe)
    lg.checkpoint(rec)
    got = lg.get_checkpoint(42)
    assert (got.state, got.dedupe) == (b"state-blob", dedupe)
    # post-crash media corruption: flip one byte of the stored blob
    with lg._db_lock:
        blob = bytearray(lg._db.execute(
            "SELECT state FROM checkpoints WHERE gkey=42").fetchone()[0])
        blob[-1] ^= 0x40
        lg._db.execute("UPDATE checkpoints SET state=? WHERE gkey=42",
                       (bytes(blob),))
        lg._db.commit()
    assert lg.get_checkpoint(42) is None
    assert lg.wal_health()["ckpt_bad"] == 1
    # pre-CRC rows (bare blobs) still pass through the version gate
    lg.wal_crc = False
    lg.checkpoint(CheckpointRec(43, "g43", 0, (0,), 1, b"old-style"))
    lg.wal_crc = True
    assert lg.get_checkpoint(43).state == b"old-style"
    lg.close()


# -- the injector itself ----------------------------------------------


def test_schedule_fingerprint_determinism():
    """Same seed + rules -> same fingerprint; live draws never consume
    the fingerprint's streams; the persistent-EIO latch set folds in."""
    pairs = [(n, s) for n in range(3) for s in range(2)]
    try:
        StorageChaos.configure(seed=7, enabled=True)
        StorageChaos.set_rule(None, None, fsync_eio_p=0.3, torn_p=0.1)
        f1 = StorageChaos.schedule_fingerprint(pairs)
        assert f1 == StorageChaos.schedule_fingerprint(pairs)
        # live consumption draws from per-pair streams, not the
        # fingerprint's fresh ones
        for _ in range(10):
            StorageChaos.on_fsync(0, 0)
            StorageChaos.on_append(1, 1, 512)
        assert StorageChaos.schedule_fingerprint(pairs) == f1
        # latch-only queries draw nothing either
        assert not StorageChaos.is_poisoned(2, 0)
        assert StorageChaos.schedule_fingerprint(pairs) == f1
        StorageChaos.configure(seed=8)
        assert StorageChaos.schedule_fingerprint(pairs) != f1

        # a latched pair changes the fingerprint (identical replays
        # latch identically, diverged ones must not collide)
        StorageChaos.configure(seed=7)
        StorageChaos.set_rule(None, None, fsync_eio_p=1.0,
                              fsync_persist=True)
        f2 = StorageChaos.schedule_fingerprint(pairs)
        StorageChaos.on_fsync(0, 0)  # latches (0, 0)
        assert StorageChaos.is_poisoned(0, 0)
        assert StorageChaos.schedule_fingerprint(pairs) != f2
    finally:
        StorageChaos.reset()


def test_seeded_streams_replay():
    """Per-pair verdict streams replay exactly under the same seed and
    differ across pairs (golden-ratio pair keying)."""
    def drain(node, seg, k=32):
        return [StorageChaos.on_fsync(node, seg)[0] for _ in range(k)]

    try:
        StorageChaos.configure(seed=11, enabled=True)
        StorageChaos.set_rule(None, None, fsync_eio_p=0.5)
        a = drain(0, 0)
        b = drain(1, 0)
        StorageChaos.clear()
        StorageChaos.configure(seed=11, enabled=True)
        StorageChaos.set_rule(None, None, fsync_eio_p=0.5)
        assert drain(0, 0) == a
        assert drain(1, 0) == b
        assert a != b  # astronomically unlikely to collide
    finally:
        StorageChaos.reset()


def test_rule_specificity_and_snapshot():
    """(n,s) beats (n,*) beats (*,s) beats (*,*); /storage snapshot
    carries rules and injected tallies."""
    try:
        StorageChaos.configure(seed=1, enabled=True)
        StorageChaos.set_rule(None, None, fsync_eio_p=1.0)
        StorageChaos.set_rule(0, 0, fsync_delay_s=0.0, enospc_p=1.0)
        fail, _ = StorageChaos.on_fsync(0, 0)   # (0,0) rule: no eio
        assert not fail
        fail, _ = StorageChaos.on_fsync(1, 0)   # wildcard: eio
        assert fail
        full, _ = StorageChaos.on_append(0, 0, 64)
        assert full
        snap = StorageChaos.snapshot()
        assert snap["enabled"] and snap["seed"] == 1
        assert snap["injected"]["fsync_eio"] == 1
        assert snap["injected"]["enospc"] == 1
        assert "0/0" in snap["rules"] and "*/*" in snap["rules"]
    finally:
        StorageChaos.reset()


# -- the acceptor-side nack helper ------------------------------------


def test_gate_acks_withdraws_votes():
    """gate_acks zeroes every ack in an AcceptRes — the accept barrier
    uses it to withdraw votes whose WAL write failed, so peers count
    no phantom quorum member."""
    be = ScalarBackend(window=8)
    rows = np.asarray([0, 1], np.int32)
    b0 = pack_ballot(0, 0)
    be.create(rows, np.asarray([3, 3]), np.asarray([0, 0]),
              np.asarray([b0, b0], np.int32), np.asarray([True, True]))
    po = be.propose(rows, np.asarray([111, 222], np.uint64))
    res = be.accept(rows, po.slot, po.cbal,
                    np.asarray([111, 222], np.uint64))
    assert np.asarray(res.acked).all()
    gated = be.gate_acks(res)
    assert not np.asarray(gated.acked).any()
    # everything else is untouched (ballots still report correctly)
    assert (np.asarray(gated.cur_bal) == np.asarray(res.cur_bal)).all()
