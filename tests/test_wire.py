"""Wire-plane aggregation tests (PR 13): FRAG super-frame codec
round-trips, the version handshake, mixed-version fallback, the
byte-for-byte-off guarantee, and the zero-copy receive chunk.
"""

import asyncio
import os
import struct

import numpy as np
import pytest

from gigapaxos_tpu.net.transport import Transport, WireChunk
from gigapaxos_tpu.paxos import packets as pk

_LEN = struct.Struct("<I")


def _arr(vals, dt=np.int32):
    return np.asarray(vals, dt)


def _accept(n, sender=2, gkey=7, slot0=100, seq_blobs=True):
    """AcceptBatch frame in the hot-group steady state: constant gkey,
    consecutive slots, fixed-size near-identical blobs."""
    blobs = [struct.pack("<QQB", 9, (77 << 32) + 1000 + i, 0) + b"x"
             for i in range(n)] if seq_blobs else \
            [os.urandom(8 + (i % 3)) for i in range(n)]
    return pk.AcceptBatch(
        sender=sender, gkey=np.full(n, gkey, np.uint64),
        slot=np.arange(slot0, slot0 + n, dtype=np.int32),
        bal=np.full(n, 3, np.int32),
        req_lo=np.arange(5, 5 + n, dtype=np.int32),
        req_hi=np.arange(9, 9 + n, dtype=np.int32),
        payloads=blobs).encode()


def _reply(n, sender=0):
    return pk.AcceptReplyBatch(
        sender=sender, gkey=np.full(n, 7, np.uint64),
        slot=np.arange(100, 100 + n, dtype=np.int32),
        bal=np.full(n, 3, np.int32),
        acked=np.ones(n, np.uint8)).encode()


def _commit(n, sender=2):
    return pk.CommitBatch(
        sender=sender, gkey=np.full(n, 7, np.uint64),
        slot=np.arange(100, 100 + n, dtype=np.int32),
        bal=np.full(n, 3, np.int32),
        req_lo=np.arange(5, 5 + n, dtype=np.int32),
        req_hi=np.arange(9, 9 + n, dtype=np.int32)).encode()


def _prop(i, sender=1):
    return pk.Proposal(sender=sender, gkey=9, req_id=5000 + i, entry=2,
                       flags=0, payload=b"payload-abc").encode()


def _frag_bytes(sender, frames):
    parts, total = pk.Frag.encode(sender, frames)
    blob = b"".join(parts)
    assert len(blob) == total
    return blob


@pytest.mark.smoke
def test_frag_roundtrip_mixed():
    """A storm-shaped member mix reconstructs byte-for-byte AND
    compresses: packed SoA batches, XOR-sparse proposal runs, and
    incompressible random bodies all in one container."""
    frames = ([_accept(50)] + [_prop(i) for i in range(20)]
              + [_reply(50), _commit(50)]
              + [pk._HDR.pack(int(pk.PacketType.PROPOSAL), 1, 1)
                 + os.urandom(40) for _ in range(4)])
    blob = _frag_bytes(2, frames)
    assert blob[0] == int(pk.PacketType.FRAG)
    assert blob[pk._HDR.size] == pk.WIRE_VERSION
    assert pk.Frag.split(blob) == frames
    raw = sum(len(f) + 4 for f in frames)
    assert len(blob) + 4 < raw / 2  # the storm mix must halve at least


@pytest.mark.smoke
def test_frag_column_packers_roundtrip():
    """Each hot SoA body column-collapses in the steady state and
    reconstructs exactly; broken patterns still round-trip raw."""
    for mk in (_accept, _reply, _commit):
        f = mk(64)
        blob = _frag_bytes(2, [f, f])
        assert pk.Frag.split(blob) == [f, f]
        assert len(blob) < len(f)  # TWO copies smaller than one raw
    # non-steady shapes (mixed gkeys, ragged blobs) stay lossless
    ragged = _accept(16, seq_blobs=False)
    mixed = pk.AcceptBatch(
        sender=2, gkey=_arr([1, 9, 1, 9], np.uint64),
        slot=_arr([4, 9, 2, 7]), bal=_arr([3, 3, 8, 3]),
        req_lo=_arr([5, 1, 0, 2]), req_hi=_arr([0, 0, 3, 0]),
        payloads=[b"a", b"", b"ccc", b"dd"]).encode()
    blob = _frag_bytes(2, [ragged, mixed])
    assert pk.Frag.split(blob) == [ragged, mixed]


def test_frag_xor_and_blob_row_edges():
    # identical bodies -> zero-diff xor member
    f = _prop(1)
    blob = _frag_bytes(1, [f, f, f])
    assert pk.Frag.split(blob) == [f, f, f]
    # uvarint multi-byte edges survive (n_items >= 2**14)
    big_n = (1 << 14) + 3
    hdr = pk._HDR.pack(int(pk.PacketType.PROPOSAL), 1, big_n)
    frames = [hdr + b"ab", hdr + b"cd"]
    out = pk.Frag.split(_frag_bytes(1, frames))
    assert out == frames
    assert pk._read_uvarint(pk._uvarint(big_n), 0) == (big_n, 3)
    # blob-row sparse codec: direct pack/unpack round-trip
    n, size = 40, 17
    rows = np.zeros((n, size), np.uint8)
    rows[:, 3] = np.arange(n)          # one drifting byte per row
    packed = pk._pack_blob_rows(n, size, memoryview(rows.tobytes()))
    assert packed is not None and len(packed) < n * size
    got_size, raw, _o = pk._unpack_blob_rows(n, memoryview(packed), 0)
    assert got_size == size and raw == rows.tobytes()
    # dense random rows refuse to "pack" (never grow the frame)
    rnd = os.urandom(n * size)
    assert pk._pack_blob_rows(n, size, memoryview(rnd)) is None


@pytest.mark.smoke
def test_registered_packer_unpacker_pairs_roundtrip():
    """Every registered column codec round-trips against its inverse
    BY NAME — _pack_accept/_unpack_accept, _pack_reply/_unpack_reply,
    _pack_commit/_unpack_commit — plus the XOR body delta pair
    _xor_sparse/_xor_apply.  The wiresym analysis rule requires each
    helper to appear in a round-trip test, so this is the rule's
    anchor: drop a codec from here and the sweep fails."""
    h = pk._HDR.size
    for mk, pack, unpack in (
            (_accept, pk._pack_accept, pk._unpack_accept),
            (_reply, pk._pack_reply, pk._unpack_reply),
            (_commit, pk._pack_commit, pk._unpack_commit)):
        f = mk(48)
        n = pk._HDR.unpack_from(f, 0)[2]
        body = memoryview(f)[h:]
        packed = pack(n, body)
        assert packed is not None and len(packed) < len(body)
        assert unpack(n, memoryview(packed)) == bytes(body)
    # the registries mirror each other (wiresym checks this statically
    # too; this keeps the symmetry executable)
    assert set(pk._FRAG_PACKERS) == set(pk._FRAG_UNPACKERS)
    # XOR-sparse member delta: near-identical bodies ship positions
    # only, and apply reconstructs exactly
    prev, cur = _prop(1), _prop(2)
    d = pk._xor_sparse(prev, cur)
    assert d is not None and len(d) < len(cur)
    assert pk._xor_apply(prev, d) == cur
    # everywhere-different bodies refuse to delta (never grow)
    assert pk._xor_sparse(prev, bytes(255 - b for b in prev)) is None


def test_frag_malformed_raises():
    f = _prop(0)
    blob = bytearray(_frag_bytes(1, [f, _accept(8, sender=1)]))
    with pytest.raises(ValueError):
        pk.Frag.split(bytes(blob[:len(blob) - 3]))  # truncated member
    blob = bytearray(_frag_bytes(1, [f, f]))
    newer = bytearray(blob)
    newer[pk._HDR.size] = pk.WIRE_VERSION + 1
    with pytest.raises(ValueError):
        pk.Frag.split(bytes(newer))                 # newer wire version
    # xor member with no predecessor (flags byte forged on member 0)
    one = bytearray(_frag_bytes(1, [f]))
    one[pk._HDR.size + 1] |= pk._M_XOR
    with pytest.raises(ValueError):
        pk.Frag.split(bytes(one))


@pytest.mark.smoke
def test_wire_hello_and_packable():
    h = pk.wire_hello(3)
    assert pk.parse_wire_hello(h) == (3, pk.WIRE_VERSION)
    with pytest.raises(ValueError):
        pk.parse_wire_hello(_prop(0))
    # lone-frame FRAG eligibility: big batches yes, scalars/n=1 no
    assert pk.packable(_reply(32))
    assert pk.packable(_accept(32))
    assert not pk.packable(_prop(0))
    assert not pk.packable(_accept(1))


@pytest.mark.smoke
def test_wirechunk_columns():
    frames = [_prop(0), _reply(4), _commit(3)]
    blob = b"".join(frames)
    offs = np.cumsum([0] + [len(f) for f in frames[:-1]]).astype(
        np.int64)
    lens = np.asarray([len(f) for f in frames], np.int64)
    ck = WireChunk(blob, offs, lens)
    assert len(ck) == 3
    assert list(ck.types) == [int(pk.PacketType.PROPOSAL),
                              int(pk.PacketType.ACCEPT_REPLY_BATCH),
                              int(pk.PacketType.COMMIT_BATCH)]
    for i, f in enumerate(frames):
        assert bytes(ck.view(i)) == f


async def _wait(cond, timeout=5.0):
    t0 = asyncio.get_event_loop().time()
    while not cond():
        if asyncio.get_event_loop().time() - t0 > timeout:
            raise TimeoutError
        await asyncio.sleep(0.005)


def test_off_wire_byte_identical():
    """WIRE_COALESCE off is BYTE-FOR-BYTE the pre-PR-13 wire: a raw
    socket server sees exactly id-handshake + length-prefixed frames,
    with no FRAG/HELLO frame types anywhere in the stream."""
    async def main():
        captured = bytearray()
        got = asyncio.Event()
        frames = [_prop(i) for i in range(5)] + [_accept(8)]

        async def handle(reader, writer):
            want = 8 + sum(len(f) + 4 for f in frames)
            while len(captured) < want:
                data = await reader.read(1 << 16)
                if not data:
                    break
                captured.extend(data)
            got.set()
            # py3.12: Server.wait_closed() waits for every accepted
            # connection, and this raw server owns its side of the one
            # the Transport opened — close it or the test never ends
            writer.close()

        srv = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        t = Transport(1, ("127.0.0.1", 0), {0: ("127.0.0.1", port)},
                      on_frame=lambda f: None, wire_coalesce=False)
        await t.start()
        t.send_many([(0, f, False, 1) for f in frames])
        await asyncio.wait_for(got.wait(), 10)
        await t.stop()
        srv.close()
        await srv.wait_closed()

        want = _LEN.pack(4) + struct.pack("<i", 1)
        for f in frames:
            want += _LEN.pack(len(f)) + f
        assert bytes(captured) == want
        # and no aggregation frame types on the old wire
        o = 8
        while o < len(captured):
            (ln,) = _LEN.unpack_from(captured, o)
            assert captured[o + 4] not in (int(pk.PacketType.FRAG),
                                           int(pk.PacketType.WIRE_HELLO))
            o += 4 + ln
        assert t.tx_frags == 0

    asyncio.run(main())


def _mk(node_id, addr_map, inbox, **kw):
    return Transport(node_id, ("127.0.0.1", 0), addr_map,
                     on_frame=lambda f: inbox.append(bytes(f)), **kw)


def test_mixed_version_cluster_falls_back():
    """A coalescing node never sends FRAGs to a peer that didn't
    announce a wire version (old node), and the old node's traffic is
    untouched — the rolling-upgrade contract."""
    async def main():
        in_new, in_old = [], []
        old = _mk(0, {}, in_old, wire_coalesce=False)
        await old.start()
        new = _mk(1, {0: ("127.0.0.1", old.port)}, in_new,
                  wire_coalesce=True, coalesce_min=2)
        await new.start()
        old.addr_map[1] = ("127.0.0.1", new.port)

        frames = [_prop(i) for i in range(6)] + [_accept(8)]
        new.send_many([(0, f, False, 1) for f in frames])
        await _wait(lambda: len(in_old) == len(frames))
        # the hello is swallowed at the transport layer; the frames
        # themselves arrive canonical and in order
        assert in_old == frames
        assert new.tx_frags == 0  # no hello back => no coalescing
        assert new.peer_wire == {}

        back = [_prop(i, sender=0) for i in range(4)]
        for f in back:
            old.send(1, f)
        await _wait(lambda: len(in_new) == len(back))
        assert in_new == back and new.rx_frags == 0
        await new.stop()
        await old.stop()

    asyncio.run(main())


def test_hello_negotiation_enables_coalescing():
    """Both sides coalescing: the hello is consumed by the transport
    (peer_wire learned, never delivered upward), groups >= coalesce_min
    travel as ONE FRAG, and the receiver hands decode the canonical
    member frames."""
    async def main():
        in0, in1 = [], []
        t0 = _mk(0, {}, in0, wire_coalesce=True)
        await t0.start()
        t1 = _mk(1, {0: ("127.0.0.1", t0.port)}, in1,
                 wire_coalesce=True, coalesce_min=2)
        await t1.start()
        t0.addr_map[1] = ("127.0.0.1", t1.port)

        # prime the connection so the hello round-trips first
        t1.send(0, _prop(99))
        await _wait(lambda: len(in0) == 1)
        await _wait(lambda: t1.peer_wire.get(0) == pk.WIRE_VERSION
                    or t0.peer_wire.get(1) == pk.WIRE_VERSION)
        # the reverse hello needs t0's outbound connection
        t0.send(1, _prop(98, sender=0))
        await _wait(lambda: len(in1) == 1)
        await _wait(lambda: t1.peer_wire.get(0) == pk.WIRE_VERSION)

        frames = [_prop(i) for i in range(8)] + [_accept(16)]
        t1.send_many([(0, f, False, 1) for f in frames])
        await _wait(lambda: len(in0) == 2)
        # ONE FRAG container on the wire; the node layer splits it
        # (transport hands handlers the raw frame)
        assert in0[1][0] == int(pk.PacketType.FRAG)
        assert pk.Frag.split(in0[1]) == frames
        assert t1.tx_frags == 1
        assert t1.tx_frag_members == len(frames)
        assert t0.rx_frags == 1 and t0.rx_frag_members == len(frames)
        assert t1.sent_frames >= len(frames) + 1  # members, not frags
        # hellos are transport-internal, never delivered upward
        assert not any(f[0] == int(pk.PacketType.WIRE_HELLO)
                       for f in in0)
        await t1.stop()
        await t0.stop()

    asyncio.run(main())


def test_rx_chunks_delivers_wirechunk():
    """WIRE_SOA_RX receive path: the scan loop hands the batch handler
    WireChunk columns (zero-copy views over the read blob) instead of
    per-frame bytes."""
    async def main():
        chunks = []
        t0 = Transport(0, ("127.0.0.1", 0), {},
                       on_frame=lambda f: None,
                       on_frames=lambda items: chunks.extend(items),
                       wire_coalesce=True, rx_chunks=True)
        await t0.start()
        t1 = _mk(1, {0: ("127.0.0.1", t0.port)}, [], wire_coalesce=True)
        await t1.start()
        frames = [_prop(i) for i in range(3)]
        for f in frames:
            t1.send(0, f)
        await _wait(lambda: sum(len(c) for c in chunks
                                if isinstance(c, WireChunk))
                    >= len(frames))
        got = []
        for c in chunks:
            assert isinstance(c, WireChunk)
            for i in range(len(c)):
                got.append(bytes(c.view(i)))
        assert got == frames      # hello consumed before chunking
        assert t0.rx_reads >= 1
        await t1.stop()
        await t0.stop()

    asyncio.run(main())
