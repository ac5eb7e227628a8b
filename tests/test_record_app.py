"""``RecordApp``: a record of fields per group, binary requests, every reply
led by the group's count of executed requests (the request's place in the
group's one order) -- alone, on a three-node cluster with many requests
outstanding on one group, and as the benchmark's ``ycsb`` driver runs it
against its plain reference."""

import os
import socket
import struct
import time

import pytest

from gigapaxos_tpu.paxos import packets as pkt
from gigapaxos_tpu.paxos.interfaces import KVApp, RecordApp
from tests.conftest import tscale
from tests.test_e2e import make_cluster, shutdown
from tests.test_window_full import _LEN, _responses

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def count_of(reply: bytes) -> int:
    return int.from_bytes(reply[:8], "little")


def test_reads_updates_and_places():
    app = RecordApp()
    assert app.execute("a", 1, b"R") == (1).to_bytes(8, "little") + bytes(1000)
    assert app.execute("a", 2, b"U\x03" + b"x" * 100) == \
        (2).to_bytes(8, "little")
    got = app.execute("a", 3, b"R")
    assert count_of(got) == 3 and len(got) == 1008
    assert got[8:] == bytes(300) + b"x" * 100 + bytes(600)
    # another group has a record and an order of its own
    assert app.execute("b", 4, b"R") == (1).to_bytes(8, "little") + bytes(1000)
    # one field of an update, and the last one wins
    app.execute("a", 5, b"U\x03" + b"y" * 100)
    app.execute("a", 6, b"U\x09" + b"z" * 100)
    rec = app.execute("a", 7, b"R")[8:]
    assert rec == bytes(300) + b"y" * 100 + bytes(500) + b"z" * 100
    assert count_of(app.execute("a", 8, b"R")) == 7  # its own order


@pytest.mark.parametrize("bad", [b"", b"X", b"RR", b"U\x00short",
                                 b"U\x0a" + b"x" * 100,
                                 b"U\x00" + b"x" * 101])
def test_a_malformed_request_takes_its_place_and_changes_nothing(bad):
    app = RecordApp()
    app.execute("a", 1, b"U\x00" + b"k" * 100)
    before = app.checkpoint("a")[8:]
    assert app.execute("a", 2, bad) == (2).to_bytes(8, "little") + b"?"
    assert app.checkpoint("a") == (2).to_bytes(8, "little") + before


def test_checkpoint_and_restore_carry_count_and_record():
    app, other = RecordApp(), RecordApp()
    assert app.checkpoint("a") == b""  # as created
    for k in range(5):
        app.execute("a", k, b"U" + bytes([k]) + bytes([65 + k]) * 100)
    state = app.checkpoint("a")
    assert count_of(state) == 5 and len(state) == 1008
    assert other.restore("a", state) and other.checkpoint("a") == state
    assert other.execute("a", 9, b"R") == (6).to_bytes(8, "little") + state[8:]
    assert not other.restore("a", b"too short")
    assert other.restore("a", b"") and other.checkpoint("a") == b""
    assert other.execute("a", 10, b"R") == (1).to_bytes(8, "little") \
        + bytes(1000)
    # other shapes than YCSB's
    small = RecordApp(fields=2, field_bytes=3)
    small.execute("s", 1, b"U\x01abc")
    assert small.execute("s", 2, b"R")[8:] == b"\x00\x00\x00abc"


def test_kvapp_is_as_it_was():
    app = KVApp()
    assert app.execute("g", 1, b'{"op":"put","k":"a","v":"1"}') == \
        b'{"ok":true}'
    assert app.execute("g", 2, b'{"op":"get","k":"a"}') == \
        b'{"ok": true, "v": "1"}'
    assert app.checkpoint("g") == b'{"a": "1"}'


@pytest.mark.parametrize("backend", ["columnar", "native"])
def test_many_outstanding_on_one_record_take_one_order(tmp_path, backend):
    """48 requests at once on one group, updates of one field and reads in
    turn: every read returns the record as the updates below its place left
    it, and the three replicas end in one state."""
    nodes, addr_map = make_cluster(tmp_path, backend=backend,
                                   app_cls=RecordApp)
    try:
        for nd in nodes:
            nd.create_group("rec", (0, 1, 2))
        gkey = pkt.group_key("rec")
        client = 5151
        ops = {}
        for k in range(48):
            rid = (client << 32) | k
            ops[rid] = b"R" if k % 2 else b"U\x04" + bytes([k]) * 100
        with socket.create_connection(addr_map[gkey % 3],
                                      timeout=tscale(10)) as s:
            s.sendall(_LEN.pack(4) + struct.pack("<i", client))
            time.sleep(0.2)

            def frame(rid):
                body = pkt.Request(client, gkey, rid, 0, ops[rid]).encode()
                return _LEN.pack(len(body)) + body
            s.sendall(b"".join(frame(r) for r in ops))
            got = _responses(s, set(ops), tscale(15))
        assert len(got) == 48
        assert all(len(v) == 1 and v[0].status == 0 for v in got.values())
        by_place = sorted(got, key=lambda r: count_of(got[r][0].payload))
        assert [count_of(got[r][0].payload) for r in by_place] == \
            list(range(1, 49))
        rec = bytes(1000)
        for rid in by_place:
            if ops[rid] == b"R":
                assert got[rid][0].payload[8:] == rec
            else:
                assert len(got[rid][0].payload) == 8
                rec = rec[:400] + ops[rid][2:] + rec[500:]
        deadline = time.time() + tscale(10)
        while time.time() < deadline and len(
                {nd.app.checkpoint("rec") for nd in nodes}) > 1:
            time.sleep(0.05)
        assert {nd.app.checkpoint("rec") for nd in nodes} == \
            {(48).to_bytes(8, "little") + rec}
    finally:
        shutdown(nodes)


def test_the_ycsb_driver_against_its_reference(monkeypatch):
    """The benchmark's tiny ``ycsb`` cell (64 records, 8 threads, Zipf 0.99,
    window 2 so that windows fill, 1 s) through ``drivers/ycsb.py``: every
    number the reference compares is 0, and each control is not correct."""
    from benchmarks import harness
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config
    Config.set(PC.FUSE_WAVES, "on")  # the handlers the chip run takes
    cell = harness.Cell("tiny-ycsb-zipf", root=os.path.join(
        ROOT, "benchmarks", "tests", "tiny_ycsb"))
    driver = cell.driver()
    for const, value in (("RAMP_BURST_S", 0.1), ("WARMUP_BURST_S", 0.3),
                         ("QUIET_BURSTS", 1), ("MAX_BURSTS", 3)):
        monkeypatch.setattr(driver, const, value)
    try:
        run = driver.run(cell, seed=2**31 + 29, seconds=1.0, trace=False,
                         t_start=time.perf_counter())
    finally:
        Config.clear()
    assert run["failed"] == 0 and run["attempted"] > 8
    assert all(v == 0 and lim == 0 for _n, v, lim in run["checks"]), \
        run["checks"]
    assert sum(run["after"]["counters"]["window_full"]) > 0
    for broken, checks in driver.controls(run, 29).items():
        assert any(v > lim for _n, v, lim in checks), broken
