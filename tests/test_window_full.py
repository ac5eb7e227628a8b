"""More requests outstanding on ONE group than its window holds.

``propose`` grants a group at most ``window`` undecided slots and returns
the other lanes as ``throttled`` ("window full: host requeues").  Nothing
read that: a throttled lane was forgotten, and its client waited for its
own retransmit.  A key-value store with a group per key and skewed keys
(YCSB workload A) stands on its hottest group's full window most of the
time.

A throttled lane is parked under its row, with its payload and its waiter,
and proposed again when the group's execute cursor moves: every request is
answered once, in one order, with no retransmit.
"""

import json
import socket
import struct
import time

import pytest

from gigapaxos_tpu.paxos import packets as pkt
from gigapaxos_tpu.utils.profiler import DelayProfiler
from tests.conftest import tscale
from tests.test_e2e import make_cluster, shutdown

_LEN = struct.Struct("<I")
N = 64  # four windows of W=16
CLIENT = 4343


def _frame(gkey: int, rid: int, payload: bytes) -> bytes:
    body = pkt.Request(CLIENT, gkey, rid, 0, payload).encode()
    return _LEN.pack(len(body)) + body


def _responses(sock, want: set, wait_s: float) -> dict:
    """Every Response that arrives for ``want`` until all have one or
    ``wait_s`` pass: req_id -> list of responses (a copy is a fault)."""
    got, buf = {}, b""
    sock.settimeout(0.2)
    deadline = time.time() + wait_s
    while want - set(got) and time.time() < deadline:
        try:
            chunk = sock.recv(1 << 16)
        except socket.timeout:
            continue
        if not chunk:
            break
        buf += chunk
        while len(buf) >= 4:
            (ln,) = _LEN.unpack(buf[:4])
            if len(buf) < 4 + ln:
                break
            obj = pkt.decode(buf[4:4 + ln])
            buf = buf[4 + ln:]
            if isinstance(obj, pkt.Response):
                got.setdefault(obj.req_id, []).append(obj)
    return got


def _settled(nodes, name: str, count: int) -> bool:
    deadline = time.time() + tscale(10)
    while time.time() < deadline:
        if all(nd.app.count.get(name, 0) == count and not nd._proposed
               for nd in nodes):
            return True
        time.sleep(0.05)
    return False


def _counters(nodes, key: str) -> int:
    return sum(nd.metrics(include_profiler=False)["counters"][key]
               for nd in nodes)


def _cluster(tmp_path, backend):
    """``columnar-fused``: PC.FUSE_WAVES on, one engine wave a worker
    batch as on the chip; a flush of parked lanes and a re-offered accept
    stay waves of their own behind it."""
    if backend == "columnar-fused":
        from gigapaxos_tpu.paxos.paxosconfig import PC
        from gigapaxos_tpu.utils.config import Config
        Config.set(PC.FUSE_WAVES, "on")
        backend = "columnar"
    return make_cluster(tmp_path, backend=backend)


@pytest.mark.parametrize("backend",
                         ["columnar", "scalar", "columnar-fused"])
def test_a_full_window_parks_and_answers_every_request(tmp_path, backend):
    nodes, addr_map = _cluster(tmp_path, backend)
    try:
        for nd in nodes:
            nd.create_group("hot", (0, 1, 2))
        gkey = pkt.group_key("hot")
        rids = [(CLIENT << 32) | k for k in range(N)]
        parked0 = _counters(nodes, "parked")
        totals0 = DelayProfiler.totals()
        with socket.create_connection(addr_map[gkey % 3],
                                      timeout=tscale(10)) as s:
            s.sendall(_LEN.pack(4) + struct.pack("<i", CLIENT))
            time.sleep(0.2)  # handshake read on its own
            # all at once, and this client never retransmits
            s.sendall(b"".join(_frame(gkey, r, b"x") for r in rids))
            got = _responses(s, set(rids), tscale(15))
        assert len(got) == N, f"{N - len(got)} of {N} never answered"
        assert all(len(v) == 1 and v[0].status == 0 for v in got.values())
        counts = sorted(json.loads(v[0].payload)["count"]
                        for v in got.values())
        assert counts == list(range(1, N + 1))
        assert _settled(nodes, "hot", N)
        assert len({nd.app.checkpoint("hot") for nd in nodes}) == 1
        for nd in nodes:
            assert int(nd._cur[nd.table.by_name("hot").row]) == N
            assert not nd._parked and not nd._window_parked
        assert _counters(nodes, "window_full") > 0
        assert _counters(nodes, "parked") > parked0
        assert _counters(nodes, "proposed") >= N + _counters(
            nodes, "window_full")
        if backend == "columnar-fused":
            # a batch is one launch but where it flushed parked lanes or
            # offered an accept again: those are launches of their own
            hot = _counters(nodes, "hot_batches")
            assert 0 < _counters(nodes, "one_wave_batches") < hot

        def grew(tag, field):  # wall_s, calls, items of a total
            return DelayProfiler.totals()[tag][field] - totals0.get(
                tag, (0.0, 0, 0))[field]
        # those that found the window full (48, where the burst came in
        # one read), each from its first parking to its grant; every
        # replica executed every request; the accepts went to three
        # write-ahead logs
        assert 16 <= grew("w.window_wait", 2) <= N - 16
        assert grew("w.window_wait", 0) > 0
        assert grew("app.execute", 2) == 3 * N
        assert 0 < grew("app.execute", 1) <= 3 * N
        assert grew("wal.bytes", 2) > 3 * N * 20
        # warm now: a second burst runs at the engine's pace, four rounds
        # of 16.  The followers find each round's accepts beyond their
        # window (the commits that move it come in the same batch) and
        # must offer them again then, not wait for the coordinator's
        # re-drive a second later (three re-drives: over 4 s)
        more = [(CLIENT << 32) | (1000 + k) for k in range(N)]
        with socket.create_connection(addr_map[gkey % 3],
                                      timeout=tscale(10)) as s:
            s.sendall(_LEN.pack(4) + struct.pack("<i", CLIENT))
            time.sleep(0.2)
            t0 = time.time()
            s.sendall(b"".join(_frame(gkey, r, b"x") for r in more))
            got = _responses(s, set(more), tscale(15))
            took = time.time() - t0
        assert len(got) == N and took < tscale(3.0), took
        assert _settled(nodes, "hot", 2 * N)
    finally:
        shutdown(nodes)


@pytest.mark.parametrize("backend",
                         ["columnar", "scalar", "columnar-fused"])
def test_a_retransmit_of_a_parked_request_does_not_run_it_twice(tmp_path,
                                                                 backend):
    nodes, addr_map = _cluster(tmp_path, backend)
    try:
        for nd in nodes:
            nd.create_group("hot", (0, 1, 2))
        gkey = pkt.group_key("hot")
        rids = [(CLIENT << 32) | k for k in range(N)]
        with socket.create_connection(addr_map[gkey % 3],
                                      timeout=tscale(10)) as s:
            s.sendall(_LEN.pack(4) + struct.pack("<i", CLIENT))
            time.sleep(0.2)
            burst = b"".join(_frame(gkey, r, b"x") for r in rids)
            # the burst, and right behind it every request again: the
            # copies of the last 48 find their originals parked (or just
            # proposed, or just executed)
            s.sendall(burst)
            s.sendall(burst)
            got = _responses(s, set(rids), tscale(15))
            assert len(got) == N
            # a copy may be answered a second time from the reply cache,
            # with the same answer: it was not run a second time
            for v in got.values():
                assert {(r.status, r.payload) for r in v} == \
                    {(0, v[0].payload)}
        assert _settled(nodes, "hot", N)
        for nd in nodes:
            assert int(nd._cur[nd.table.by_name("hot").row]) == N, \
                "a copy took a slot of its own"
            assert nd.app.digest == nodes[0].app.digest
            assert not nd._parked and not nd._window_parked
    finally:
        shutdown(nodes)


def test_a_node_does_not_hear_itself(tmp_path):
    """What a node routes to itself (a re-driven accept, a parked proposal's
    answer) is no sign of life of a peer: a node that noted it went on to
    suspect ITSELF a failure timeout later, and held what was parked for
    the groups it leads."""
    nodes, _addr_map = make_cluster(tmp_path, backend="scalar")
    try:
        nd = nodes[0]
        nd.create_group("g", (0, 1, 2))
        nd._route(nd.id, pkt.Response(nd.id, pkt.group_key("g"), 1, 0, b""))
        nd._route(nd.id, pkt.FailureDetect(1, 1, time.time_ns()))
        deadline = time.time() + tscale(5)
        while 1 not in nd._last_heard and time.time() < deadline:
            time.sleep(0.02)
        assert 1 in nd._last_heard and nd.id not in nd._last_heard
        assert nd.id not in nd._suspects
    finally:
        shutdown(nodes)
