"""The generator alone, against a stand-in server on a loopback socket: the
deadline, the failure accounting, one outstanding request per group, and
the same seed giving the same stream."""

import asyncio
import struct

import numpy as np

from benchmarks import loadgen


class FakeServer:
    """Answers every REQUEST with a RESPONSE of the same id after
    ``delay_s``; ``drop(seq)`` true: never answers; ``refuse(seq)`` true:
    answers with status 3."""

    def __init__(self, delay_s=0.001, drop=None, refuse=None):
        self.delay_s, self.drop, self.refuse = delay_s, drop, refuse
        self.seen = []  # (gkey, req_id) in arrival order
        self.in_flight = {}  # gkey -> outstanding now
        self.max_per_group = 0

    async def handle(self, reader, writer):
        await reader.readexactly(8)  # the hello frame
        try:
            while True:
                (blen,) = struct.unpack("<I", await reader.readexactly(4))
                body = await reader.readexactly(blen)
                typ, sender, n, gkey, rid, flags = struct.unpack_from(
                    "<BIIQQB", body)
                assert typ == loadgen.REQUEST and n == 1
                self.seen.append((gkey, rid))
                seq = rid & 0xFFFFFFFF
                if self.drop and self.drop(seq):
                    continue
                self.in_flight[gkey] = self.in_flight.get(gkey, 0) + 1
                self.max_per_group = max(self.max_per_group,
                                         self.in_flight[gkey])
                asyncio.get_running_loop().call_later(
                    self.delay_s, self.reply, writer, gkey, rid, seq)
        except (asyncio.IncompleteReadError, ConnectionError):
            pass

    def reply(self, writer, gkey, rid, seq):
        self.in_flight[gkey] -= 1
        status = 3 if self.refuse and self.refuse(seq) else 0
        pay = b'{"seq": %d}' % seq
        writer.write(struct.pack("<IBIIQQB", 26 + len(pay), loadgen.RESPONSE,
                                 0, 1, gkey, rid, status) + pay)


def drive(server, names, seconds, depth, **kw):
    async def body():
        srv = await asyncio.start_server(server.handle, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        try:
            return await loadgen.run_closed_loop(
                [("127.0.0.1", port)], names, seconds, depth,
                client_id=(1 << 20) + 7, **kw)
        finally:
            srv.close()
    return asyncio.run(body())


NAMES = loadgen.plan_groups(5, 100, 12)


def test_deadline_and_window_accounting():
    srv = FakeServer(delay_s=0.002)
    res = drive(srv, NAMES, 0.4, 8)
    s = loadgen.summarize(res)
    assert s["attempted"] == s["acked"] == res["n_sent"] > 50
    assert s["failed"] == 0
    # nothing is sent after the deadline, and everything sent is waited for
    assert res["t_send"].max() <= res["t0"] + 0.4 + 0.05
    assert (res["t_recv"] >= res["t_send"]).all()
    # the rate is acked requests over first send .. last reply
    span = res["t_recv"].max() - res["t_send"][0]
    assert abs(s["commit_rate"] - s["acked"] / span) < 1e-6
    assert s["commit_p95_ms"] >= s["commit_p50_ms"] >= 2.0
    # explicit ids: client << 32 | seq, seq from 0, round robin over groups
    want = [((1 << 20) + 7) << 32 | k for k in range(res["n_sent"])]
    assert res["req_id"].tolist() == want
    assert [rid for _g, rid in srv.seen] == want
    keys = [loadgen.group_key(g) for g in NAMES]
    assert [g for g, _r in srv.seen] == [keys[k % 12]
                                         for k in range(res["n_sent"])]
    assert res["reply"][3] == b'{"seq": 3}'


def test_a_group_never_has_two_outstanding():
    # depth far above the number of groups: the per-group rule binds
    srv = FakeServer(delay_s=0.003)
    res = drive(srv, NAMES[:4], 0.3, 64)
    assert res["n_sent"] > 20 and srv.max_per_group == 1


def test_unanswered_and_refused_count_as_failed_and_slowest():
    srv = FakeServer(drop=lambda seq: seq == 5,
                     refuse=lambda seq: seq in (9, 10))
    res = drive(srv, NAMES, 0.3, 8, drain_s=1.5)
    s = loadgen.summarize(res)
    assert res["t_recv"][5] < 0 and res["status"][9] == 3
    assert s["failed"] == 3 and s["acked"] == s["attempted"] - 3
    # the unanswered request was retransmitted (same id) and waited for
    assert [rid & 0xFFFFFFFF for _g, rid in srv.seen].count(5) >= 2
    assert res["t_end"] - res["t0"] >= 0.3 + 1.5 - 0.05
    # a failed request is as slow as the whole wait: it is the maximum
    lat_max_ms = 1e3 * (res["t_end"] - res["t0"])
    good = (res["t_recv"] >= 0) & (res["status"] == 0)
    lat = (res["t_recv"] - res["t_send"])[good]
    assert 1e3 * lat.max() < lat_max_ms
    n = res["n_sent"]
    if n < 60:  # three failures are then more than 5% of the requests
        assert s["commit_p95_ms"] >= lat_max_ms - 1e-6


def test_same_seed_same_stream():
    a = loadgen.plan_groups(2**31 + 5, 1000, 64)
    assert a == loadgen.plan_groups(2**31 + 5, 1000, 64)
    assert a != loadgen.plan_groups(2**31 + 6, 1000, 64)
    assert len(set(a)) == 64 and all(
        g[0] == "g" and 0 <= int(g[1:]) < 1000 for g in a)


def test_group_key_and_frames_match_the_programs_wire_format():
    from gigapaxos_tpu.paxos import packets as pkt
    from gigapaxos_tpu.testing import loadgen as theirs
    assert loadgen.group_key("g123") == pkt.group_key("g123")
    gk = np.asarray([pkt.group_key("g1"), pkt.group_key("g2")], np.uint64)
    ids = np.asarray([(7 << 32) | 1, (7 << 32) | 2], np.uint64)
    assert loadgen.frames(7, gk, ids, b"xy") == \
        theirs._frames_vec(7, gk, ids, b"xy")
    assert (loadgen.REQUEST, loadgen.RESPONSE) == \
        (int(pkt.PacketType.REQUEST), int(pkt.PacketType.RESPONSE))


def test_scan_responses_handles_partial_and_foreign_frames():
    def frame(typ, rid, st, pay):
        return struct.pack("<IBIIQQB", 26 + len(pay), typ, 0, 1, 9, rid,
                           st) + pay
    buf = bytearray(frame(2, 11, 0, b"ab") + frame(9, 12, 0, b"")
                    + frame(2, 13, 4, b"") + frame(2, 14, 0, b"abc")[:-1])
    got, used = loadgen.scan_responses(buf)
    assert got == [(11, 0, b"ab"), (13, 4, b"")]
    assert used == len(buf) - len(frame(2, 14, 0, b"abc")) + 1
