"""Reads without a round (``PC.READ_INDEX``; Raft's ReadIndex): a read at
its group's active coordinator takes no slot; the coordinator notes its
``next_slot``, a majority, itself included, confirms that no higher ballot
was promised, and the read is answered once every slot below the noted one
has executed.

The two device programs against the oracle's plain forms (and their mesh
twins), the coordinator's book, a deposed coordinator that must never
answer from its own state, a mixed concurrent run checked read by read,
and the guard: with the setting off the serving programs and the wire are
what they were."""

import asyncio
import hashlib
import socket
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapaxos_tpu.chaos.faults import ChaosPlane
from gigapaxos_tpu.ops import kernels
from gigapaxos_tpu.ops.oracle import OracleGroup
from gigapaxos_tpu.ops.types import (NODE_MASK, make_state, pack_ballot,
                                     with_columns)
from gigapaxos_tpu.paxos import packets as pkt
from gigapaxos_tpu.paxos.interfaces import CounterApp, RecordApp
from gigapaxos_tpu.paxos.paxosconfig import PC
from gigapaxos_tpu.paxos.readindex import PendingReads, Read
from gigapaxos_tpu.testing.harness import PaxosEmulation
from gigapaxos_tpu.utils.config import Config
from gigapaxos_tpu.utils.profiler import DelayProfiler
from tests.conftest import tscale

_LEN = struct.Struct("<I")
FB = 100  # RecordApp's field bytes


def count_of(reply: bytes) -> int:
    return int.from_bytes(reply[:8], "little")


def update(field: int, byte: int) -> bytes:
    return b"U" + bytes([field]) + bytes([byte]) * FB


def ri_total(tag: str) -> int:
    return DelayProfiler.totals().get(tag, (0.0, 0, 0))[2]


# -- the two programs ---------------------------------------------------

def random_state(G, W, seed):
    """A state whose group table is random where the read index looks:
    live or not, coordinating or not, phase 1 done or not, and ballots
    that now and then agree."""
    rng = np.random.default_rng(seed)
    bal = rng.integers(-1, 4, G).astype(np.int32)
    cols = dict(active=rng.random(G) < 0.9, is_coord=rng.random(G) < 0.8,
                coord_active=rng.random(G) < 0.8, bal=bal,
                cbal=np.where(rng.random(G) < 0.6, bal,
                              rng.integers(-1, 4, G)).astype(np.int32),
                next_slot=rng.integers(0, 1 << 20, G).astype(np.int32))
    st = with_columns(make_state(G, W),
                      **{k: jnp.asarray(v) for k, v in cols.items()})
    return st, cols


def oracle_of(cols, r, W):
    og = OracleGroup(members=3, window=W)
    og.bal, og.cbal = int(cols["bal"][r]), int(cols["cbal"][r])
    og.is_coord = bool(cols["is_coord"][r])
    og.coord_active = bool(cols["coord_active"][r])
    og.next_slot = int(cols["next_slot"][r])
    return og


def lanes(G, bucket, seed, cols):
    """``bucket`` lanes, the last few padding; each asks about its row's
    own promise, one above or one below it."""
    rng = np.random.default_rng(seed + 1)
    g = rng.integers(0, G, bucket).astype(np.int32)
    valid = (np.arange(bucket) < bucket - 3).astype(np.int32)
    asked = (cols["bal"][g] + rng.integers(-1, 2, bucket)).astype(np.int32)
    return g, valid, asked


def expected(cols, g, valid, asked, W):
    ok_r, cbal, nxt, ok_c = [], [], [], []
    for i, r in enumerate(g.tolist()):
        og = oracle_of(cols, r, W)
        ok, cb, ns = og.read_index()
        live = bool(valid[i]) and bool(cols["active"][r])
        ok_r.append(live and ok)
        cbal.append(cb)
        nxt.append(ns)
        ok_c.append(live and og.confirm(int(asked[i])))
    return (np.asarray(ok_r), np.asarray(cbal), np.asarray(nxt),
            np.asarray(ok_c))


@pytest.mark.parametrize("bucket", [8, 64, 512])
def test_programs_match_the_oracle(bucket):
    G, W = 256, 8
    st, cols = random_state(G, W, bucket)
    g, valid, asked = lanes(G, bucket, bucket, cols)
    ok_r, cbal, nxt, ok_c = expected(cols, g, valid, asked, W)
    table = np.asarray(st.grp)
    st, out = kernels.read_index_p(st, jnp.asarray(np.stack([g, valid])))
    out = np.asarray(out)
    live = valid != 0
    np.testing.assert_array_equal(out[0] != 0, ok_r)
    np.testing.assert_array_equal(out[1][live], cbal[live])
    np.testing.assert_array_equal(out[2][live], nxt[live])
    st, conf = kernels.confirm_p(st, jnp.asarray(
        np.stack([g, asked, valid])))
    conf = np.asarray(conf)
    assert conf.shape == (1, bucket)
    np.testing.assert_array_equal(conf[0] != 0, ok_c)
    # both programs hand the state back as it came
    np.testing.assert_array_equal(np.asarray(st.grp), table)
    if bucket >= 64:  # both answers, of both programs, were asked for
        assert ok_r.any() and (~ok_r & live).any() and ok_c.any() \
            and (~ok_c & live).any()


@pytest.mark.parametrize("bucket", [8, 64])
def test_mesh_twins_match_the_single_device_programs(bucket):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from gigapaxos_tpu.ops.meshkernels import GROUP_AXIS, MeshKernels
    G, W = 256, 8
    mesh = Mesh(np.asarray(jax.devices()[:4]), (GROUP_AXIS,))
    mk = MeshKernels(mesh)
    st, cols = random_state(G, W, 7 + bucket)
    sharded = jax.device_put(st, jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, PartitionSpec(GROUP_AXIS)), st))
    repl = NamedSharding(mesh, PartitionSpec())
    g, valid, asked = lanes(G, bucket, 7 + bucket, cols)
    for name, packed in (("read_index_p", np.stack([g, valid])),
                         ("confirm_p", np.stack([g, asked, valid]))):
        st, one = getattr(kernels, name)(st, jnp.asarray(packed))
        sharded, four = getattr(mk, name)(
            sharded, jax.device_put(packed, repl))
        one, four = np.asarray(one), np.asarray(four)
        live = valid != 0
        np.testing.assert_array_equal(one[:, live], four[:, live],
                                      err_msg=name)


def test_the_columnar_engine_answers_as_the_scalar_one():
    """``ColumnarBackend.read_index`` / ``confirm`` (the device programs
    behind the SPI) and the native store's (its rows' snapshots) as
    ``ScalarBackend``'s (the oracle) on the same groups: created, one of
    them taken over at a higher ballot."""
    from gigapaxos_tpu.paxos.backend import (ColumnarBackend, NativeBackend,
                                             ScalarBackend)
    W, G = 8, 64
    rows = np.arange(G, dtype=np.int32)
    init = np.asarray([pack_ballot(0, r % 3) for r in rows], np.int32)
    mine = rows % 3 == 0
    engines = [ColumnarBackend(G, W, mesh="off"), ScalarBackend(W),
               NativeBackend(G, W)]
    for bk in engines:
        bk.create(rows, np.full(G, 3, np.int32), np.zeros(G, np.int32),
                  init, mine)
        bk.prepare(rows[:4], np.full(4, pack_ballot(1, 1), np.int32))
    got = [(bk.read_index(rows), bk.confirm(rows, init)) for bk in engines]
    (rs, cs) = got[1]
    for rc, cc in got[::2]:
        np.testing.assert_array_equal(rc.ok, rs.ok)
        np.testing.assert_array_equal(rc.cbal[rs.ok], rs.cbal[rs.ok])
        np.testing.assert_array_equal(rc.next_slot, rs.next_slot)
        np.testing.assert_array_equal(cc, cs)
    assert rs.ok.sum() == mine.sum() - 2  # rows 0 and 3 were taken over
    assert not cs[:4].any() and cs[4:].all()


def test_confirm_frames_round_trip():
    q = pkt.ReadConfirm(2, 77, np.asarray([5, 1 << 63], np.uint64),
                        np.asarray([4097, -1], np.int32))
    back = pkt.decode(q.encode())
    assert isinstance(back, pkt.ReadConfirm) and back.xid == 77 \
        and back.sender == 2
    np.testing.assert_array_equal(back.gkey, q.gkey)
    np.testing.assert_array_equal(back.bal, q.bal)
    r = pkt.ReadConfirmReply(1, 77, np.asarray([1, 0, 1], np.uint8))
    back = pkt.decode(r.encode())
    assert back.xid == 77 and back.ok.tolist() == [1, 0, 1]


# -- the app and the book ------------------------------------------------

def test_a_read_takes_no_place_in_the_record():
    app = RecordApp()
    app.execute("a", 1, update(2, 7))
    assert app.is_read(b"R") and not app.is_read(update(2, 7)) \
        and not app.is_read(b"RR")
    got = app.read("a", b"R")
    assert count_of(got) == 1 and got[8 + 2 * FB:8 + 3 * FB] == b"\x07" * FB
    assert app.read("a", b"R") == got  # it changes nothing
    assert count_of(app.execute("a", 2, b"R")) == 2  # as a round: a place
    assert count_of(app.read("never", b"R")) == 0
    # apps that say nothing are served as they always were
    assert not CounterApp().is_read(b"R")
    with pytest.raises(NotImplementedError):
        CounterApp().read("a", b"R")


def test_the_book_waits_for_a_majority_and_the_index():
    book = PendingReads()
    for rid, need in ((1, 1), (2, 1), (3, 0)):
        book.add(Read(rid, 5, 99, 0, 0, True, 0, b"R", index=3, need=need,
                      t0=10.0))
    book.open_frame(7, 10.0, [[1, 2]], {1: np.asarray([0]),
                                        2: np.asarray([0])})
    cur = np.zeros(8, np.int32)
    assert [r.rid for r in book.take_ready(cur)] == []  # index not reached
    assert book.on_reply(7, 1, np.asarray([1], np.uint8)) == []
    assert sorted(book.ready) == [1, 2, 3]
    # a second answer, a late refusal, an unknown peer: nothing changes
    assert book.on_reply(7, 1, np.asarray([0], np.uint8)) == []
    assert book.on_reply(7, 2, np.asarray([0], np.uint8)) == []
    assert 7 not in book.frames
    cur[5] = 2
    assert book.take_ready(cur) == []
    cur[5] = 3
    assert sorted(r.rid for r in book.take_ready(cur)) == [1, 2, 3]
    assert not book.reads and not book.ready
    # a refusal before a majority sends the read to the round path
    book.add(Read(4, 5, 99, 0, 0, True, 0, b"R", index=3, need=1, t0=10.0))
    book.open_frame(8, 10.0, [[4]], {1: np.asarray([0])})
    assert [r.rid for r in book.on_reply(8, 1, np.asarray([0], np.uint8))] \
        == [4]
    assert not book.reads
    # and time does too
    book.add(Read(6, 5, 99, 0, 0, True, 0, b"R", index=9, need=1, t0=10.0))
    assert book.expire(10.5) == []
    assert [r.rid for r in book.expire(11.5)] == [6]


# -- on a cluster --------------------------------------------------------

def cluster(tmp_path, read_index=True, **kw):
    Config.set(PC.READ_INDEX, read_index)
    Config.set(PC.FUSE_WAVES, "on")  # the handlers the chip runs
    return PaxosEmulation(str(tmp_path), n_nodes=3, n_groups=0,
                          app_cls=RecordApp, capacity=1 << 10, **kw)


def led_by(emu, node, n=1, prefix="rec"):
    """``n`` group names whose initial coordinator is ``node``."""
    out = []
    for i in range(1000):
        name = f"{prefix}{i}"
        if pkt.group_key(name) % 3 == node:
            out.append(name)
            if len(out) == n:
                break
    emu.create_groups(0, names=out)
    return out


class Client:
    """A raw client connection to one node: requests out, every Response
    in with its arrival time."""

    def __init__(self, emu, node, cid):
        self.cid, self.seq = cid, 0
        self.s = socket.create_connection(emu.addr_map[node],
                                          timeout=tscale(10))
        self.s.sendall(_LEN.pack(4) + struct.pack("<i", cid))
        self.buf, self.got = b"", {}
        time.sleep(0.1)

    def send(self, name, payload, rid=None):
        if rid is None:
            self.seq += 1
            rid = (self.cid << 32) | self.seq
        body = pkt.Request(self.cid, pkt.group_key(name), rid, 0,
                           payload).encode()
        self.s.sendall(_LEN.pack(len(body)) + body)
        return rid

    def poll(self, until: float):
        self.s.settimeout(0.05)
        while time.time() < until:
            try:
                chunk = self.s.recv(1 << 16)
            except socket.timeout:
                continue
            if not chunk:
                break
            self.buf += chunk
            while len(self.buf) >= 4:
                (ln,) = _LEN.unpack(self.buf[:4])
                if len(self.buf) < 4 + ln:
                    break
                obj = pkt.decode(self.buf[4:4 + ln])
                self.buf = self.buf[4 + ln:]
                if isinstance(obj, pkt.Response):
                    self.got.setdefault(obj.req_id, []).append(
                        (time.time(), obj))

    def wait(self, rid, secs):
        deadline = time.time() + secs
        while rid not in self.got and time.time() < deadline:
            self.poll(min(deadline, time.time() + 0.1))
        return self.got.get(rid)

    def close(self):
        self.s.close()


def test_reads_at_the_coordinator_take_no_round(tmp_path):
    emu = cluster(tmp_path)
    try:
        (name,) = led_by(emu, 0)
        c = Client(emu, 0, 4101)
        u = c.send(name, update(1, 9))
        assert c.wait(u, tscale(10))[0][1].status == 0
        wal0 = ri_total("wal.bytes")
        rids = [c.send(name, b"R") for _ in range(20)]
        for rid in rids:
            (t, resp), = c.wait(rid, tscale(10))
            assert resp.status == 0 and count_of(resp.payload) == 1
            assert resp.payload[8 + FB:8 + 2 * FB] == b"\x09" * FB
        c.close()
        assert ri_total("ri.reads") == 20 and ri_total("ri.fallbacks") == 0
        assert ri_total("ri.confirm_frames") >= 2
        assert ri_total("wal.bytes") == wal0  # no append for a read
        # no replica gave a read a place
        for nd in emu.nodes.values():
            assert nd.app.count.get(name) == 1
    finally:
        emu.stop()


def test_a_deposed_coordinator_never_answers_from_its_own_state(tmp_path):
    """Node 0 leads the group and is cut off; node 1 or 2 takes it over
    at a higher ballot and commits an update.  A read at node 0 is never
    answered with the record as node 0 holds it: it cannot be confirmed,
    goes as a round that cannot be decided, and once the cut heals it is
    answered with the update in."""
    emu = cluster(tmp_path, ping_interval_s=0.15, failure_timeout_s=1.0)
    try:
        (name,) = led_by(emu, 0)
        c0 = Client(emu, 0, 4102)
        u1 = c0.send(name, update(0, 1))
        assert c0.wait(u1, tscale(10))[0][1].status == 0
        row = emu.nodes[1].table.by_name(name).row
        ChaosPlane.partition([{0}, {1, 2}])
        deadline = time.time() + tscale(15)
        while time.time() < deadline and \
                int(emu.nodes[1]._bal[row]) & NODE_MASK == 0:
            time.sleep(0.05)
        new = int(emu.nodes[1]._bal[row]) & NODE_MASK
        assert new in (1, 2), "nobody took the group over"
        cn = Client(emu, new, 4103)
        u2 = cn.send(name, update(3, 2))
        (_t, r2), = cn.wait(u2, tscale(10))
        assert r2.status == 0 and count_of(r2.payload) == 2
        fell0 = ri_total("ri.fallbacks")
        r = c0.send(name, b"R")
        c0.poll(time.time() + tscale(3))
        assert r not in c0.got, "a cut-off coordinator answered a read"
        assert ri_total("ri.fallbacks") > fell0
        ChaosPlane.heal()
        deadline = time.time() + tscale(20)
        while r not in c0.got and time.time() < deadline:
            c0.send(name, b"R", rid=r)  # the client's retransmit
            c0.poll(time.time() + 1.0)
        (_t, resp), = c0.got[r][:1]
        assert resp.status == 0 and count_of(resp.payload) == 2
        assert resp.payload[8:8 + FB] == b"\x01" * FB
        assert resp.payload[8 + 3 * FB:8 + 4 * FB] == b"\x02" * FB
        c0.close()
        cn.close()
    finally:
        ChaosPlane.reset()
        emu.stop()


def test_a_read_waits_for_the_undecided_slot_below_its_index(tmp_path):
    """An update's slot is accepted but not yet decided at the
    coordinator (its peer's replies are slow and the third replica is cut
    off) when a read arrives: the read is answered only after that slot
    executed, with the update in."""
    emu = cluster(tmp_path)
    try:
        (name,) = led_by(emu, 0)
        c = Client(emu, 0, 4104)
        warm = c.send(name, update(5, 3))
        assert c.wait(warm, tscale(10))[0][1].status == 0
        ChaosPlane.partition([{0, 1}, {2}])
        ChaosPlane.set_link(1, 0, delay_s=0.4)
        u = c.send(name, update(5, 4))
        time.sleep(0.15)
        node0 = emu.nodes[0]
        row = node0.table.by_name(name).row
        assert int(node0._cur[row]) == 1, "the update executed too soon"
        r = c.send(name, b"R")
        (tu, ru), = c.wait(u, tscale(10))
        (tr, rr), = c.wait(r, tscale(10))
        assert ru.status == 0 and rr.status == 0
        assert count_of(rr.payload) == 2 and tr >= tu
        assert rr.payload[8 + 5 * FB:8 + 6 * FB] == b"\x04" * FB
        c.close()
    finally:
        ChaosPlane.reset()
        emu.stop()


def check_record(ops, seed_rec=bytes(10 * FB)):
    """The rules of a record with reads by read index, on one record's
    answered operations ``(t_send, t_recv, payload, reply)``: the updates
    take places 1, 2, 3 .. once each, in an order that does not run
    against real time; a read stating count c returns the seed record
    with the first c updates applied, c is at least the place of every
    update answered before the read was sent, and the first c updates
    were all sent before the read was answered."""
    errs = []
    ups = sorted((o for o in ops if o[2] != b"R"),
                 key=lambda o: count_of(o[3]))
    states, sent_by = [seed_rec], [float("-inf")]
    latest = float("-inf")
    for place, (ts, tr, pay, rep) in enumerate(ups, start=1):
        if count_of(rep) != place:
            errs.append(f"update at place {count_of(rep)}, not {place}")
        if tr < latest:
            errs.append(f"update at place {place} against real time")
        latest = max(latest, ts)
        rec = states[-1]
        at = pay[1] * FB
        states.append(rec[:at] + pay[2:] + rec[at + FB:])
        sent_by.append(max(sent_by[-1], ts))
    for ts, tr, pay, rep in (o for o in ops if o[2] == b"R"):
        c = count_of(rep)
        if c >= len(states) or rep[8:] != states[c]:
            errs.append(f"read stating {c} returned another record")
            continue
        before = [p for p, u in enumerate(ups, start=1) if u[1] < ts]
        if before and c < max(before):
            errs.append(f"read stating {c} missed place {max(before)}")
        if sent_by[c] > tr:
            errs.append(f"read stating {c} saw an update sent after it")
    return errs


@pytest.mark.parametrize("backend", ["columnar", "scalar", "native"])
def test_concurrent_reads_and_updates_are_linearizable(tmp_path, backend):
    """24 clients, one operation outstanding each, on six records, two
    reads in three; the answers checked read by read."""
    emu = cluster(tmp_path, backend=backend)
    try:
        names = led_by(emu, 0, 3) + led_by(emu, 1, 3, prefix="other")
        servers = [emu.addr_map[i] for i in range(3)]
        ops = {n: [] for n in names}

        async def client(k):
            from gigapaxos_tpu.paxos.client import PaxosClientAsync
            rng = np.random.default_rng(k)
            cli = PaxosClientAsync(5000 + k, servers, timeout=tscale(20))
            try:
                for _ in range(12):
                    name = names[int(rng.integers(0, len(names)))]
                    pay = b"R" if rng.random() < 0.67 else update(
                        int(rng.integers(0, 10)), int(rng.integers(0, 256)))
                    ts = time.time()
                    resp = await cli.send_request(name, pay)
                    assert resp.status == 0
                    ops[name].append((ts, time.time(), pay, resp.payload))
            finally:
                await cli.close()

        async def main():
            await asyncio.gather(*[client(k) for k in range(24)])
        asyncio.run(main())
        errs = [f"{n}: {e}" for n in names for e in check_record(ops[n])]
        assert not errs, errs[:10]
        assert ri_total("ri.reads") > 100
        # every replica holds every record as its last update left it
        deadline = time.time() + tscale(10)
        final = {n: len([o for o in ops[n] if o[2] != b"R"])
                 for n in names}
        while time.time() < deadline and any(
                nd.app.count.get(n, 0) != final[n]
                for nd in emu.nodes.values() for n in names):
            time.sleep(0.05)
        for n in names:
            assert {nd.app.checkpoint(n) for nd in emu.nodes.values()} \
                .__len__() == 1
            assert count_of(emu.nodes[0].app.checkpoint(n) or bytes(8)) \
                == final[n]
    finally:
        emu.stop()


# -- the guard: off, nothing changes -------------------------------------

# sha256 (first 16 hex) of each serving program's jaxpr at G=64, W=8 and
# a 16-lane bucket, as the tree had them before the read index came
SERVING_JAXPRS = {
    "accept_commit_p": "3b6eebfe8ad5e75c",
    "accept_p": "38858fad100bb502",
    "accept_reply_commit_self_p": "193478b25e6d0e4a",
    "accept_reply_p": "74740a3a4c6b8648",
    "commit_p": "642b666ff5af7a0c",
    "node_wave_p": "80178506cffd746b",
    "propose_accept_self_p": "cd39c9adcd0ba324",
    "propose_p": "b14f1a21684d76c2",
    "request_reply_p": "47615ea02c225724",
}
SERVING_ROWS = {
    "propose_p": (4,), "accept_p": (6,), "accept_reply_p": (6,),
    "commit_p": (5,), "propose_accept_self_p": (5,),
    "accept_reply_commit_self_p": (6,), "accept_commit_p": (6, 5),
    "request_reply_p": (5, 6), "node_wave_p": (22,),
}


@pytest.mark.parametrize("name", sorted(SERVING_JAXPRS))
def test_the_serving_programs_are_as_they_were(name):
    st = jax.eval_shape(lambda: make_state(64, 8))
    text = str(jax.make_jaxpr(getattr(kernels, name))(
        st, *[jax.ShapeDtypeStruct((k, 16), jnp.int32)
              for k in SERVING_ROWS[name]]))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        SERVING_JAXPRS[name]


def test_off_reads_are_rounds_and_nothing_is_confirmed(tmp_path,
                                                       monkeypatch):
    sent = []
    real = pkt.ReadConfirm.encode
    monkeypatch.setattr(pkt.ReadConfirm, "encode",
                        lambda self: sent.append(self) or real(self))
    emu = cluster(tmp_path, read_index=False)
    try:
        (name,) = led_by(emu, 0)
        c = Client(emu, 0, 4105)
        rids = [c.send(name, b"R") for _ in range(5)]
        counts = sorted(count_of(c.wait(r, tscale(10))[0][1].payload)
                        for r in rids)
        assert counts == [1, 2, 3, 4, 5]  # each read took its place
        c.close()
        assert not sent
        assert not any(k.startswith("ri.") for k in DelayProfiler.totals())
        assert all(nd._ri is None for nd in emu.nodes.values())
    finally:
        emu.stop()


def test_boot_loads_the_read_programs_at_every_bucket(tmp_path):
    from gigapaxos_tpu.paxos.backend import _ladder
    from gigapaxos_tpu.utils.engineledger import EngineLedger
    emu = cluster(tmp_path)
    try:
        (name,) = led_by(emu, 0)
        k0 = {k: dict(v) for k, v in EngineLedger.kernels().items()}
        c = Client(emu, 0, 4106)
        for n in (1, 30, 100):
            rids = [c.send(name, b"R") for _ in range(n)]
            for r in rids:
                assert c.wait(r, tscale(10))
        c.close()
        for k in ("read_index_p", "confirm_p", "mesh.read_index_p",
                  "mesh.confirm_p"):
            got = EngineLedger.kernels().get(k)
            if got is None:
                continue
            assert got["compiles"] == k0[k]["compiles"], k
            assert got["retraces"] == 0, k
        assert any(k in EngineLedger.kernels() for k in
                   ("read_index_p", "mesh.read_index_p"))
        assert len(list(_ladder())) == 4
    finally:
        emu.stop()
