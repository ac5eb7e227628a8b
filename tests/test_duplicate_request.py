"""A request and its retransmits in ONE worker wave.

When a node's worker stalls past the clients' retransmit interval (a
cold compile on the chip: seconds), a request and its copies are read
in one batch.  The ``_proposed`` dedupe only knows earlier waves, so
each copy used to take a slot of its own; the first execution consumed
the payload and the later slots — decided, never executable, nothing
for a peer to sync — wedged the group for every later request (found on
the TPU: one request of 1,000 never answered at 250,000 groups).

Copies of one request in one wave take ONE slot.
"""

import socket
import struct
import time

import pytest

from gigapaxos_tpu.paxos import packets as pkt
from tests.conftest import tscale
from tests.test_e2e import make_cluster, shutdown

_LEN = struct.Struct("<I")


def _responses(sock, want: set) -> dict:
    """Read frames until every req_id in ``want`` has been answered."""
    got, buf = {}, b""
    while want - set(got):
        while len(buf) < 4:
            buf += sock.recv(65536)
        (ln,) = _LEN.unpack(buf[:4])
        while len(buf) < 4 + ln:
            buf += sock.recv(65536)
        obj = pkt.decode(buf[4:4 + ln])
        buf = buf[4 + ln:]
        if isinstance(obj, pkt.Response):
            got.setdefault(obj.req_id, obj)
    return got


def _settled(nodes, name, count) -> bool:
    deadline = time.time() + tscale(10)
    while time.time() < deadline:
        if all(nd.app.count.get(name, 0) == count and not nd._proposed
               for nd in nodes):
            return True
        time.sleep(0.05)
    return False


@pytest.mark.parametrize("backend", ["scalar", "native", "columnar"])
def test_copies_in_one_wave_take_one_slot(tmp_path, backend):
    nodes, addr_map = make_cluster(tmp_path, backend=backend)
    try:
        for nd in nodes:
            nd.create_group("dup", (0, 1, 2))
        gkey = pkt.group_key("dup")
        client_id = 4242
        rid1, rid2 = (client_id << 32) | 1, (client_id << 32) | 2

        def frame(rid, payload):
            body = pkt.Request(client_id, gkey, rid, 0, payload).encode()
            return _LEN.pack(len(body)) + body

        with socket.create_connection(addr_map[gkey % 3],
                                      timeout=tscale(10)) as s:
            s.sendall(_LEN.pack(4) + struct.pack("<i", client_id))
            time.sleep(0.2)  # handshake read on its own
            # one write = one read chunk = one wave at the coordinator
            s.sendall(frame(rid1, b"a") * 4)
            assert _responses(s, {rid1})[rid1].status == 0
            assert _settled(nodes, "dup", 1)
            assert sum(nd.n_wave_dups for nd in nodes) == 3
            # the group still serves: this one sat behind the dead
            # slots forever
            s.sendall(frame(rid2, b"b"))
            assert _responses(s, {rid2})[rid2].status == 0
        assert _settled(nodes, "dup", 2)
        for nd in nodes:
            row = nd.table.by_name("dup").row
            assert int(nd._cur[row]) == 2, "a copy took a slot of its own"
            assert nd.app.digest == nodes[0].app.digest
    finally:
        shutdown(nodes)
