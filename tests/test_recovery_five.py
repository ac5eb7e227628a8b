"""Five replicas on the columnar engine, one crash-stopped and started again
from its own log: the deployment of the benchmark's ``recovery-5r-100k`` at a
size a test holds.  What the restarted node recovers, what the frontier
exchange brings it, and what its dedupe tables know afterwards are compared
with the plain reference (``benchmarks/reference/recovery_rsm.py``) over ALL
FIVE replicas.  Every wait is on the event meant (no election open, the
catch-up ended, every replica at the acknowledged count), never on a
wall-clock window (ROADMAP D12)."""

import asyncio
import threading
import time

import numpy as np
import pytest

from benchmarks import loadgen, loadgen_failover
from benchmarks.drivers import failover as fo
from benchmarks.drivers import recovery as drv
from benchmarks.reference import recovery_rsm
from gigapaxos_tpu.paxos import packets as pkt
from gigapaxos_tpu.paxos.interfaces import CounterApp
from gigapaxos_tpu.paxos.manager import PaxosNode
from gigapaxos_tpu.testing.harness import PaxosEmulation

from tests.conftest import tscale

R, LIVE, ACTIVE, DEPTH = 5, 400, 60, 16
CLIENT = 1 << 20


def boot(path, n_groups=LIVE):
    return PaxosEmulation(
        str(path), n_nodes=R, n_groups=n_groups, group_size=R,
        backend="columnar", app_cls=CounterApp, capacity=1024,
        ping_interval_s=0.1, failure_timeout_s=tscale(0.8))


def drive(emu, names, seconds, client, depth=DEPTH, **kw):
    servers = [emu.addr_map[i] for i in sorted(emu.addr_map)]
    return asyncio.run(loadgen_failover.run_closed_loop_kill(
        servers, names, seconds, depth, client_id=client,
        retransmit_after_s=tscale(0.3), drain_s=tscale(40), **kw))


def wait_taken_over(emu, victim):
    """Until the survivors have installed themselves for every group the
    victim led and no election is open (bounded)."""
    n_led = len(fo.led_by(LIVE, victim, R))
    alive = [nd for i, nd in emu.nodes.items()
             if i != victim and nd is not None]
    deadline = time.monotonic() + tscale(60)
    while time.monotonic() < deadline and (
            sum(nd.n_installs for nd in alive) < n_led
            or any(nd.open_elections for nd in alive)):
        time.sleep(0.02)
    assert sum(nd.n_installs for nd in alive) >= n_led
    return alive


def forget_what_was_queued_for(emu, victim):
    """A node down for longer than its peers' send budget: what they had
    queued for it is gone when it comes back (each peer's loop drops its
    own queue, as the byte budget would have)."""
    done = []
    for nd in emu.nodes.values():
        if nd is None:
            continue
        ev = threading.Event()

        def drop(nd=nd, ev=ev):
            peer = nd.transport._peers.get(victim)
            if peer is not None:
                peer.queue.clear()
                peer.bytes_queued = 0
            ev.set()
        nd._loop.call_soon_threadsafe(drop)
        done.append(ev)
    assert all(ev.wait(tscale(5)) for ev in done)


def wait_level(nd):
    """Until the restarted node's frontier exchange has ended (on a
    program without one: nothing to wait for)."""
    deadline = time.monotonic() + tscale(30)
    while time.monotonic() < deadline and getattr(nd, "catching_up", False):
        time.sleep(0.02)
    assert not getattr(nd, "catching_up", False)


def compare(emu, victim, names, results, before, after, wait_s=15):
    """The run against the plain reference over all five, as the
    benchmark's driver compares a window."""
    ids = sorted(emu.nodes)
    nodes = [emu.nodes[i] for i in ids]
    streams = [fo.stream_of(names, r) for r in results]
    acked = {}
    for st, r in zip(streams, results):
        for (g, _rid), t in zip(st, r["t_recv"]):
            acked[g] = acked.get(g, 0) + int(t >= 0)
    states, spurious = fo.survivor_states(nodes, set(names), acked,
                                          tscale(wait_s))
    assert spurious == 0
    led = fo.led_by(LIVE, victim, R)
    ballots, cbals = fo.coordinator_views(nodes, led)
    cks = recovery_rsm.check(
        streams, [fo.parsed(r) for r in results], states,
        ids.index(victim), drv.recovery_facts(LIVE, before, after),
        ballots, cbals, ids)
    return {n: v for n, v, _lim in cks}


@pytest.mark.parametrize("victim", [2, 4])
def test_a_restarted_node_ends_level_on_groups_that_go_idle(tmp_path,
                                                            victim):
    """Killed, four survivors decide more on sixty groups, and what they
    queued for the dead node is lost; restarted, and NOTHING more is sent:
    no later commit ever shows a gap on those groups.  The frontier
    exchange at the end of recovery brings every one of them level, and
    tells the node who coordinates the groups it once led."""
    emu = boot(tmp_path)
    try:
        names = loadgen.plan_groups(11 + victim, LIVE, ACTIVE)
        first = drive(emu, names, tscale(0.5), CLIENT + 1)
        before = drv.frontier_of(emu.nodes[victim])
        emu.kill(victim)
        wait_taken_over(emu, victim)
        second = drive(emu, names, tscale(0.5), CLIENT + 2)
        assert second["n_sent"] >= ACTIVE // 2
        forget_what_was_queued_for(emu, victim)
        back = emu.restart(victim)
        after = drv.frontier_of(back)
        wait_level(back)
        got = compare(emu, victim, names, [first, second], before, after,
                      wait_s=5)
        assert not any(got.values()), got
        assert len(back.table) == LIVE
        # the groups it once led: promised to their new coordinator now
        led = fo.led_by(LIVE, victim, R)
        with back._engine_lock:
            rows = back.table.rows_for_keys(led)
            coord = back._bal[rows] & 0xFFF
        assert (coord == (victim + 1) % R).all()
    finally:
        emu.stop()


def test_a_copy_that_crosses_a_restart_and_a_checkpoint_is_executed_once(
        tmp_path, monkeypatch):
    """ROADMAP U3's route, made deterministic.  A request is executed by
    the four survivors while the victim is dead.  The victim restarts
    (with the frontier exchange held off, so that the checkpoint transfer
    alone has to carry the dedupe key), is handed the copy its peers had
    for it, finds itself coordinator by its boot ballot, is refused, runs
    for coordinator, wins, catches up by CHECKPOINT TRANSFER, and has the
    copy again: it must answer it from its tables and propose nothing."""
    monkeypatch.setattr(PaxosNode, "_frontier_tick",
                        lambda self, now: None, raising=False)
    # node 4: its first ballot, (1, 4), outranks the (1, 0) its groups
    # were taken over at, so its election is won at the first attempt
    victim = 4
    emu = boot(tmp_path, n_groups=40)
    try:
        g = next(n for n in emu.groups
                 if pkt.group_key(n) % R == victim)
        first = drive(emu, [g], tscale(0.3), CLIENT + 1, depth=1)
        emu.kill(victim)
        alive = [nd for nd in emu.nodes.values() if nd is not None]
        deadline = time.monotonic() + tscale(60)
        while time.monotonic() < deadline and (
                not any(nd.n_installs for nd in alive)
                or any(nd.open_elections for nd in alive)):
            time.sleep(0.02)
        second = drive(emu, [g], tscale(0.3), CLIENT + 2, depth=1)
        n_ids = first["n_sent"] + second["n_sent"]
        assert second["n_sent"] > 0 and (second["t_recv"] >= 0).all()
        forget_what_was_queued_for(emu, victim)
        back = emu.restart(victim)
        meta = back.table.by_name(g)
        assert int(back._cur[meta.row]) < n_ids  # behind, and idle
        rid = int(second["req_id"][-1])
        entry = (victim + 1) % R
        copy = pkt.Proposal(entry, meta.gkey, rid, entry, 0, b"x")
        back._inq.put(copy)   # refused: the node runs for coordinator
        deadline = time.monotonic() + tscale(30)
        while time.monotonic() < deadline and not (
                back.n_elections_started or back.open_elections):
            time.sleep(0.01)
        assert back.n_elections_started
        back._inq.put(copy)   # parked, or proposed once it has caught up
        deadline = time.monotonic() + tscale(30)
        while time.monotonic() < deadline and (
                back.open_elections or back._catchup_barrier
                or back._parked
                or int(back._cur[meta.row]) < n_ids):
            time.sleep(0.02)
        back._inq.put(copy)   # and once more, level and coordinating
        deadline = time.monotonic() + tscale(10)
        while time.monotonic() < deadline and len(
                {nd.app.count.get(g) for nd in emu.nodes.values()}) > 1:
            time.sleep(0.02)
        time.sleep(tscale(0.5))  # a second decision would land by now
        counts = [nd.app.count.get(g, 0) for nd in emu.nodes.values()]
        assert counts == [n_ids] * R, (counts, n_ids)
        assert back._was_executed(rid)
    finally:
        emu.stop()


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_killed_and_restarted_under_load_against_the_reference(tmp_path,
                                                               seed):
    """The cell's own run at a test's size: a closed loop with ring
    retransmits through the kill, the outage, the recovery and the
    catch-up; all five replicas against the plain reference."""
    victim = 2
    emu = boot(tmp_path)
    try:
        names = loadgen.plan_groups(seed, LIVE, ACTIVE)
        fault = {}

        def restart():
            time.sleep(tscale(1.2))
            fault["after"] = drv.frontier_of(emu.restart(victim))

        def kill():
            fault["before"] = drv.frontier_of(emu.nodes[victim])
            emu.kill(victim)
            fault["thread"] = threading.Thread(target=restart, daemon=True)
            fault["thread"].start()
        res = drive(emu, names, tscale(3.0), CLIENT + 1, kill=kill,
                    kill_at_s=tscale(0.4))
        fault["thread"].join(tscale(30))
        back = emu.nodes[victim]
        assert back is not None and res["n_resent"] > 0
        wait_level(back)
        got = compare(emu, victim, names, [res], fault["before"],
                      fault["after"])
        assert not any(got.values()), got
    finally:
        emu.stop()


# -- the frontier frames ----------------------------------------------------

def test_frontier_request_round_trips_and_chunks_at_16384_rows():
    n = 2 * pkt.FRONTIER_ROWS + 5
    rng = np.random.default_rng(1)
    gkey = rng.integers(0, 1 << 63, n, dtype=np.uint64)
    cursor = rng.integers(0, 1 << 20, n).astype(np.int32)
    bal = rng.integers(0, 1 << 20, n).astype(np.int32)
    import itertools
    frames = pkt.FrontierRequest.frames(7, itertools.count(40), gkey,
                                        cursor, bal)
    assert [len(f.gkey) for f in frames] == [16384, 16384, 5]
    assert [f.xid for f in frames] == [40, 41, 42]
    back = [pkt.decode(f.encode()) for f in frames]
    assert all(type(b) is pkt.FrontierRequest and b.sender == 7
               for b in back)
    assert (np.concatenate([b.gkey for b in back]) == gkey).all()
    assert (np.concatenate([b.cursor for b in back]) == cursor).all()
    assert (np.concatenate([b.bal for b in back]) == bal).all()
    assert len(frames[0].encode()) == 9 + 8 + 16 * 16384


@pytest.mark.parametrize("nb,nd,nc", [(0, 0, 0), (3, 0, 2), (2, 5, 0),
                                      (4, 3, 6)])
def test_frontier_reply_round_trips(nb, nd, nc):
    rng = np.random.default_rng(nb + 10 * nd + 100 * nc)

    def keys(n):
        return rng.integers(0, 1 << 63, n, dtype=np.uint64)

    def words(n):
        return rng.integers(-5, 1 << 30, n).astype(np.int32)
    dedupes = [pkt.pack_dedupe([(int(k), i % 5, b"r" * i)
                                for i, k in enumerate(keys(j))])
               for j in range(nc)]
    o = pkt.FrontierReply(
        3, 99, 1, keys(nb), words(nb), keys(nd), words(nd), words(nd),
        words(nd), [bytes([i]) + b"p" * i for i in range(nd)], keys(nc),
        words(nc), [b"s" * (i * 7) for i in range(nc)], dedupes)
    b = pkt.decode(o.encode())
    assert (b.sender, b.xid, b.last) == (3, 99, 1)
    for f in ("b_gkey", "b_bal", "d_gkey", "d_slot", "d_req_lo",
              "d_req_hi", "c_gkey", "c_slot"):
        assert (getattr(b, f) == getattr(o, f)).all(), f
    assert b.d_payloads == o.d_payloads and b.c_states == o.c_states
    assert b.c_dedupes == dedupes
    assert [pkt.unpack_dedupe(d) for d in b.c_dedupes] == \
        [pkt.unpack_dedupe(d) for d in dedupes]


def test_dedupe_ids_ride_a_checkpoint_reply_and_a_checkpoint_record(
        tmp_path):
    from gigapaxos_tpu.paxos.logger import CheckpointRec, PaxosLogger
    items = [(2**63 + 5, 0, b'{"count": 3}'), (17, 4, b""), (18, 0, b"x")]
    blob = pkt.pack_dedupe(items)
    assert pkt.unpack_dedupe(blob) == items and pkt.pack_dedupe([]) == b""
    assert pkt.unpack_dedupe(blob[:-1]) == items[:2]  # cut short: the whole
    o = pkt.decode(pkt.CheckpointReply(1, 12, 400, b"snap", blob).encode())
    assert (o.gkey, o.slot, o.state, o.dedupe) == (12, 400, b"snap", blob)
    bare = pkt.decode(pkt.CheckpointReply(1, 12, 400, b"snap").encode())
    assert bare.state == b"snap" and bare.dedupe == b""
    for crc in (True, False):
        lg = PaxosLogger(str(tmp_path / f"crc{crc}"), sync=False,
                         wal_crc=crc)
        try:
            lg.checkpoint(CheckpointRec(5, "g5", 0, (0, 1, 2), 9, b"st",
                                        blob))
            lg.checkpoint(CheckpointRec(6, "g6", 0, (0, 1, 2), 9, b"st"))
            got = {r.gkey: r for r in lg.checkpoints_for([5, 6])}
            assert (got[5].state, got[5].dedupe) == (b"st", blob)
            assert (got[6].state, got[6].dedupe) == (b"st", b"")
        finally:
            lg.close()


# -- spans and counters -------------------------------------------------------

def test_recovery_spans_reach_the_ring_and_the_totals(tmp_path):
    """With spans on, a restart leaves ``gp.rec.boot`` and its parts, the
    catch-up and the peers' ``gp.rec.serve`` in the ring with the
    attributes the benchmark's readers take, and their sums and the
    counters in the profiler."""
    from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
    from gigapaxos_tpu.utils.profiler import DelayProfiler
    victim = 2
    emu = boot(tmp_path)
    try:
        names = loadgen.plan_groups(5, LIVE, ACTIVE)
        first = drive(emu, names, tscale(0.5), CLIENT + 1)
        dead = emu.nodes[victim].backend
        emu.kill(victim)
        # a crashed node's slab is given back at once, not when a
        # collection happens to free it beside its successor's
        assert dead.state is None
        wait_taken_over(emu, victim)
        second = drive(emu, names, tscale(0.5), CLIENT + 2)
        # the groups that decided anything while the victim was dead
        moved = len(set(second["seq_group"].tolist()))
        assert moved >= ACTIVE // 2
        forget_what_was_queued_for(emu, victim)
        RI.enabled = True
        back = emu.restart(victim)
        wait_level(back)
        by = {}
        for s in RI.spans_snapshot():
            by.setdefault(s["kind"], []).append(s)
        (b,) = by["rec.boot"]
        assert b["groups"] == LIVE and b["node"] == victim
        for kind in ("rec.groups", "rec.table", "rec.install",
                     "rec.checkpoints", "rec.wal"):
            (s,) = by[kind]
            assert s["parent"] == b["id"] and b["t0"] <= s["t0"] \
                and s["t1"] <= b["t1"], kind
        assert by["rec.groups"][0]["rows"] == LIVE
        (s,) = by["rec.install"]
        assert s["rows"] == LIVE and s["bytes"] > LIVE * 700
        assert s["programs"].startswith("jit_")
        (s,) = by["rec.checkpoints"]
        assert s["rows"] == LIVE and s["bytes"] > 0 and "restored" in s
        (s,) = by["rec.wal"]
        assert s["records"] == s["accepts"] + s["decisions"] > 0
        assert s["bytes"] >= 29 * s["records"]
        assert s["programs"].count("jit_") == 2
        (c,) = by["rec.catchup"]
        assert c["rows_behind"] == c["rows_level"] == moved
        assert c["frames"] >= R - 1 and c["bytes"] > 16 * LIVE
        assert c["by_checkpoint"] + c["by_decisions"] >= moved
        assert c["t0"] >= b["t1"] and c["left_behind"] == 0
        serve = by["rec.serve"]
        assert {s["node"] for s in serve} == set(range(R)) - {victim}
        assert sum(s["rows"] for s in serve) >= LIVE
        assert sum(s["ahead"] for s in serve) == moved
        tot = DelayProfiler.totals()
        for tag in ("rec.boot", "rec.groups", "rec.table", "rec.install",
                    "rec.checkpoints", "rec.wal", "rec.catchup",
                    "rec.serve"):
            assert tot[tag][1] > 0 and tot[tag][0] > 0, tag
        assert tot["rec.groups_recovered"][2] == LIVE
        assert tot["rec.rows_behind"][2] == tot["rec.rows_level"][2] \
            == moved
        assert tot["rec.catchup_frames"][2] == c["frames"]
        # every request the survivors executed meanwhile, with its answer
        assert tot["rec.dedupe_ids_loaded"][2] >= second["n_sent"]
    finally:
        RI.enabled = False
        emu.stop()


# -- what a recovery reads -------------------------------------------------

def test_the_logger_reads_back_groups_and_checkpoints_with_their_ids(
        tmp_path):
    """``all_groups`` and ``checkpoints_for`` give back what was written:
    blobs, dedupe ids and signed keys included, and the bytes a checkpoint
    transaction wrote are on the profiler's ``ckpt.bytes``."""
    from gigapaxos_tpu.utils.profiler import DelayProfiler
    from gigapaxos_tpu.paxos.logger import CheckpointRec, PaxosLogger
    lg = PaxosLogger(str(tmp_path), sync=False)
    try:
        big = (1 << 63) + 11  # stored signed
        items = [(big, "g'\"big", 2, (0, 1, 2, 3, 4))] + [
            (k, f"g{k}", 0, (0, 1, 2)) for k in range(1, 1201)]
        lg.put_groups(items)
        blob = pkt.pack_dedupe([(9, 0, b"resp")])
        wrote = DelayProfiler.totals().get("ckpt.bytes", (0, 0, 0))[2]
        lg.checkpoint_many(
            [CheckpointRec(big, "g'\"big", 2, (0, 1, 2, 3, 4), 41,
                           b"\x00\xff state", blob)]
            + [CheckpointRec(k, f"g{k}", 0, (0, 1, 2), -1, b"")
               for k in range(1, 1201)])
        assert sorted(lg.all_groups()) == sorted(items)
        got = {r.gkey: r for r in lg.checkpoints_for(
            [big] + list(range(1, 1201)) + [777777])}
        assert len(got) == 1201
        assert (got[big].name, got[big].slot, got[big].state,
                got[big].dedupe, got[big].members) == (
            "g'\"big", 41, b"\x00\xff state", blob, (0, 1, 2, 3, 4))
        assert got[5].state == b"" and got[5].slot == -1
        wrote = DelayProfiler.totals()["ckpt.bytes"][2] - wrote
        assert wrote >= len(b"\x00\xff state") + len(blob)
    finally:
        lg.close()
