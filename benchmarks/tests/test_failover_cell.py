"""The failover cell's own parts on the CPU at tiny sizes: the generator's
ring retransmit and dead-server rule against stand-in servers, the plain
reference against its three controls, the five readers on a ring made by
hand, the election byte count, and the driver through a whole run with a
node killed inside the window."""

import asyncio
import collections
import json
import os
import time

import numpy as np
import pytest

from benchmarks import loadgen, loadgen_failover, roofline_elections
from benchmarks.reference import failover_rsm
from benchmarks.reference.counter_rsm import run_group
from benchmarks.tests.test_loadgen import FakeServer

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tiny_failover")
NAMES = loadgen.plan_groups(5, 100, 20)
NEW = ("service_gap_ms", "detect_ms", "takeover_ms",
       "election_lanes_per_dispatch", "election_kernels_roofline")


# -- the generator --------------------------------------------------------

def drive(fakes, seconds, depth, kill_server=None, **kw):
    """Three stand-in servers; ``kill_server`` closes that one's listener
    and its open connections when the generator calls ``kill``."""
    async def body():
        writers = [[] for _ in fakes]

        def handler(i):
            async def handle(reader, writer):
                writers[i].append(writer)
                await fakes[i].handle(reader, writer)
            return handle
        srvs = [await asyncio.start_server(handler(i), "127.0.0.1", 0)
                for i in range(len(fakes))]
        loop = asyncio.get_running_loop()

        def kill():  # called from the generator's executor thread
            def close():
                srvs[kill_server].close()
                for w in writers[kill_server]:
                    w.transport.abort()
            loop.call_soon_threadsafe(close)
        try:
            return await loadgen_failover.run_closed_loop_kill(
                [("127.0.0.1", s.sockets[0].getsockname()[1])
                 for s in srvs], NAMES, seconds, depth,
                client_id=(1 << 20) + 9,
                kill=kill if kill_server is not None else None, **kw)
        finally:
            for s in srvs:
                s.close()
    return asyncio.run(body())


def test_with_nobody_dead_it_is_the_closed_loop():
    fakes = [FakeServer(0.002) for _ in range(3)]
    opened = []
    res = drive(fakes, 0.4, 8, on_start=lambda: opened.append(
        time.perf_counter()))
    assert len(opened) == 1 and 0 <= res["t0"] - opened[0] < 0.01
    assert res["n_sent"] > 50 and res["n_resent"] == 0
    assert (res["t_recv"] >= res["t_send"]).all() and res["t_kill"] is None
    # each request went once, to its group's home, and no group had two
    assert len(res["sends"]) == res["n_sent"]
    assert (res["sends"][:, 1] == res["home"]).all()
    assert max(f.max_per_group for f in fakes) == 1
    assert loadgen.summarize(res)["failed"] == 0


def test_unanswered_requests_go_round_the_ring_with_the_same_id():
    # server 0 never answers: what is homed there is answered by server 1,
    # a retransmit interval later, under the id it was first sent with
    fakes = [FakeServer(0.002, drop=lambda seq: True),
             FakeServer(0.002), FakeServer(0.002)]
    res = drive(fakes, 0.5, 4, retransmit_after_s=0.1, drain_s=5)
    assert loadgen.summarize(res)["failed"] == 0
    stuck = np.flatnonzero(res["home"] == 0)
    assert len(stuck) and res["n_resent"] >= len(stuck)
    for k in stuck:
        mine = res["sends"][res["sends"][:, 0] == k]
        assert mine[:2, 1].tolist() == [0, 1]          # home, then next
        assert mine[1, 2] - mine[0, 2] >= 0.1          # not before its time
    assert {rid for _g, rid in fakes[1].seen} >= {
        int(res["req_id"][k]) for k in stuck}


def test_a_closed_server_is_skipped_by_retransmits_and_new_requests():
    fakes = [FakeServer(0.002) for _ in range(3)]
    res = drive(fakes, 1.0, 4, kill_server=1, kill_at_s=0.3,
                retransmit_after_s=0.1, drain_s=5)
    assert loadgen.summarize(res)["failed"] == 0
    assert abs(res["t_kill"] - res["t0"] - 0.3) < 0.05
    assert res["closed_at"][1] is not None and res["closed_at"][0] is None
    seen_close = res["closed_at"][1]
    late = res["sends"][res["sends"][:, 2] > seen_close + 0.01]
    assert len(late) and not (late[:, 1] == 1).any()
    # a group homed on the dead server goes first to the next in ring
    homed = np.flatnonzero((res["home"] == 1)
                           & (res["t_send"] > seen_close + 0.01))
    assert len(homed)
    first = {int(k): res["sends"][res["sends"][:, 0] == k][0, 1]
             for k in homed}
    assert set(first.values()) == {2.0}
    out = loadgen_failover.outage(res)
    assert out["service_gap_s"] > 0 and out["rate_before_kill"] > 0


# -- the reference and its controls ---------------------------------------

def record(n_groups=6, per_group=5, victim=2, replicas=5):
    """A window by hand: ``per_group`` requests to each group in turn, all
    answered as the counter answers; group j is homed on node j % R; the
    kill falls after the second round, and the third round's requests to
    the victim's groups were sent twice."""
    groups = [f"g{j}" for j in range(n_groups)]
    stream, t_send, home = [], [], []
    for r in range(per_group):
        for j, g in enumerate(groups):
            stream.append((g, (7 << 32) | len(stream)))
            t_send.append(float(r) + j / 100)
            home.append(j % replicas)
    n = len(stream)
    t_send, home = np.asarray(t_send), np.asarray(home)
    t_kill = 1.5
    sends = [(k, home[k], t_send[k]) for k in range(n)]
    sends += [(k, (home[k] + 1) % replicas, t_send[k] + 1.0)
              for k in range(n) if home[k] == victim and 2 <= t_send[k] < 3]
    answers, _st, _ids = failover_rsm.replay(
        [stream], [np.ones(n, bool)])
    res = {"n_sent": n, "t_send": t_send, "t_recv": t_send + 0.1,
           "status": np.zeros(n, np.int16), "reply": list(answers[0]),
           "home": home, "victim": victim, "t_kill": t_kill,
           "sends": np.asarray(sends, np.float64)}
    final = {g: run_group([rid for gg, rid in stream if gg == g])[-1]
             for g in groups}
    survivors = [i for i in range(replicas) if i != victim]
    led = 3  # groups the victim led: all at ballot (1, next in ring)
    ballots = np.full((len(survivors), led), (1 << 12) | 3)
    cbals = np.where(np.asarray(survivors)[:, None] == 3, ballots, -1)
    return [stream], [res], [dict(final) for _ in survivors], ballots, \
        cbals, survivors


def test_a_sound_record_is_correct():
    streams, results, states, ballots, cbals, survivors = record()
    cks = failover_rsm.check(streams, results, states, ballots, cbals,
                             survivors)
    assert [n for n, _v, _l in cks] == [
        "answers_wrong", "answers_refused", "never_answered",
        "executed_twice", "replica_groups_diverged",
        "groups_without_coordinator"]
    assert all(v == 0 for _n, v, _l in cks), cks


@pytest.mark.parametrize("broken,outside", [
    ("lost_carryover", {"replica_groups_diverged", "answers_wrong"}),
    ("doubled_retransmit", {"executed_twice", "replica_groups_diverged",
                            "answers_wrong"}),
    ("two_coordinators", {"groups_without_coordinator"})])
def test_each_control_is_not_correct(broken, outside):
    streams, results, states, ballots, cbals, survivors = record()
    rng = np.random.default_rng([3, 0xC0])
    victim = failover_rsm.pick_victim(broken, results, rng)
    if broken == "lost_carryover":
        k = victim  # acknowledged before the kill, homed on the victim
        assert results[0]["home"][k] == 2 and results[0]["t_recv"][k] < 1.5
    if broken == "doubled_retransmit":
        assert (results[0]["sends"][:, 0] == victim).sum() == 2
    fake, st, b, c = failover_rsm.broken_run(
        broken, streams, results, ballots, cbals, survivors, victim)
    cks = {n: v for n, v, _l in failover_rsm.check(streams, fake, st, b, c,
                                                   survivors)}
    assert {n for n, v in cks.items() if v} == outside, cks
    if broken == "doubled_retransmit":
        assert cks["executed_twice"] == len(survivors)


def test_what_the_comparison_lets_pass_and_what_not():
    streams, results, states, ballots, cbals, survivors = record()

    def bad(**kw):
        args = dict(streams=streams, results=results,
                    replica_states=states, ballots=ballots, cbals=cbals,
                    survivors=survivors)
        args.update(kw)
        return {n for n, v, _l in failover_rsm.check(**args) if v}
    # a group's LAST request never answered may be executed or not ...
    res = dict(results[0], t_recv=results[0]["t_recv"].copy())
    last = len(streams[0]) - 1
    res["t_recv"][last] = -1.0
    g = streams[0][last][0]
    without = [dict(s, **{g: run_group(
        [rid for gg, rid in streams[0] if gg == g][:-1])[-1]})
        for s in states]
    assert bad(results=[res]) == {"never_answered"}
    assert bad(results=[res], replica_states=without) == {"never_answered"}
    # ... but the same way on every survivor
    assert bad(results=[res], replica_states=without[:1] + states[1:]) == {
        "never_answered", "replica_groups_diverged"}
    # one survivor a write short; a refusal; no coordinator; a dead one
    short = [dict(states[0], g0=(1, 1))] + states[1:]
    assert "replica_groups_diverged" in bad(replica_states=short)
    refused = dict(results[0], status=results[0]["status"].copy())
    refused["status"][3] = 1
    assert bad(results=[refused]) == {"answers_refused"}
    assert bad(cbals=np.full_like(cbals, -1)) == {
        "groups_without_coordinator"}
    dead = np.full_like(ballots, (1 << 12) | 2)   # the victim itself
    assert bad(ballots=dead, cbals=dead) == {"groups_without_coordinator"}
    stale = np.full_like(ballots, 2)              # the victim's ballot 0
    assert bad(ballots=stale) == {"groups_without_coordinator"}


# -- the byte count and the readers ---------------------------------------

def test_election_bytes_follow_the_state_layout():
    # a promise: active 1 + bal 4 + cursor 4 + W entries of 16, bal written
    # 4, and the lane's own 17; an install: two flags, two words, lane 12
    assert roofline_elections.promise_bytes(16) == 1 + 4 + 4 + 256 + 4 + 17
    assert roofline_elections.install_bytes() == 2 + 8 + 12
    assert roofline_elections.install_bytes(3) == 22 + 48
    assert roofline_elections.election_bytes(80000, 20000, 0, 16) == \
        80000 * 286 + 20000 * 22
    assert roofline_elections.election_bytes(4, 1, 2, 16) == \
        4 * 286 + 22 + 32


def _span(kind, t0, t1, node=3, **attrs):
    return dict(kind=kind, node=node, tid=1, wave=1, parent=0, t0=t0, t1=t1,
                **attrs)


@pytest.fixture
def ring(monkeypatch):
    from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
    RI.reset()

    def put(spans):
        monkeypatch.setattr(RI, "_spans",
                            collections.deque(spans, maxlen=1000))
    yield put
    RI.reset()


def readers():
    from benchmarks import harness
    return {name: harness.load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py")) for name in NEW}


def test_the_five_readers_on_a_ring_made_by_hand(ring, monkeypatch):
    """The kill at 0; node 4 suspects node 2 at 3.0 s and node 3 at 3.1;
    node 3 starts 20,000 elections at 3.11; four acceptors promise in five
    chunks each; the installs end at 3.35 (one row carried 2 slots)."""
    from benchmarks import roofline
    t = time.monotonic()
    spans = [
        _span("fo.suspect", t + 3.0, t + 3.001, node=4, dead=2),
        _span("fo.suspect", t + 3.1, t + 3.2, dead=2),
        _span("fo.suspect", t + 2.0, t + 2.1, dead=1),  # not the victim
        _span("fo.elect_start", t + 3.11, t + 3.15, items=20000),
        _span("fo.install", t + 3.3, t + 3.34, items=19999, carried=0),
        _span("fo.install", t + 3.34, t + 3.35, items=1, carried=2),
        _span("eng.install", t + 3.3, t + 3.33, lanes=19999, chunks=5,
              bucket=4096, program="install_coordinator_batch"),
        _span("eng.install", t + 3.34, t + 3.35, lanes=1, chunks=1,
              bucket=8, program="install_coordinator_batch"),
    ] + [_span("eng.prepare", t + 3.2, t + 3.25, node=n, lanes=20000,
               chunks=5, bucket=4096, program="prepare_batch")
         for n in (0, 1, 3, 4)]
    ring(spans)
    monkeypatch.setattr(roofline, "load_peaks",
                        lambda kind=None: {"hbm_bytes_per_s": 819e9})
    run = {"window": {"service_gap_s": 4.25, "t_kill_ring": t, "victim": 2},
           "config": {"window": 16},
           "trace": {"module_s": {"jit_prepare_batch": 0.002,
                                  "jit_install_coordinator_batch": 0.0005,
                                  "jit_accept_packed": 9.0}}}
    got = {name: mod.read(run) for name, mod in readers().items()}
    assert got["service_gap_ms"] == pytest.approx(4250.0)
    assert got["detect_ms"] == pytest.approx(3000.0)
    assert got["takeover_ms"] == pytest.approx(240.0)
    assert got["election_lanes_per_dispatch"] == pytest.approx(
        100000 / 26)
    least = 80000 * 286 + 20000 * 22 + 2 * 16
    assert got["election_kernels_roofline"] == pytest.approx(
        100 * least / 819e9 / 0.0025)
    assert got["election_kernels_roofline"] < 100


def test_the_readers_find_nothing_where_nothing_is(ring):
    """No election in the traced seconds, a run with no kill, or the
    parent's program (no such span): None, and nothing raised."""
    ring([])
    bare = {"window": {}, "config": {"window": 16}, "trace": None}
    assert all(mod.read(bare) is None for mod in readers().values())
    t = time.monotonic()
    ring([_span("w.tick", t, t + 0.001)])
    run = {"window": {"service_gap_s": None, "t_kill_ring": None,
                      "victim": 2}, "config": {"window": 16},
           "trace": {"module_s": {"jit_accept_packed": 1.0}}}
    assert all(mod.read(run) is None for mod in readers().values())
    # spans of an election whose programs the trace does not hold
    ring([_span("eng.prepare", t, t + 0.01, lanes=8, chunks=1, bucket=8,
                program="prepare_batch")])
    got = {name: mod.read(run) for name, mod in readers().items()}
    assert got["election_kernels_roofline"] is None
    assert got["election_lanes_per_dispatch"] == 8


# -- the driver -----------------------------------------------------------

@pytest.fixture
def failover_cell(monkeypatch):
    from benchmarks import harness
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config
    Config.set(PC.FUSE_WAVES, "on")  # the handlers the chip run takes
    # the traced seconds hold the suspicion and the takeover: 1 s .. 3 s
    monkeypatch.setattr(harness, "TRACE_S", 2.0)
    cell = harness.Cell("tiny-failover-kill1", root=TINY)
    for const, value in (("RAMP_BURST_S", 0.1), ("WARMUP_BURST_S", 0.3),
                         ("QUIET_BURSTS", 1), ("MAX_BURSTS", 3)):
        monkeypatch.setattr(cell.driver(), const, value)
    return cell


def test_the_cell_kills_a_node_and_is_correct(failover_cell, measure, capfd):
    line = measure(failover_cell, seed=2**31 + 77, seconds=4.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 32
    assert set(line["metrics"]) == {"commit_rate", "commit_p50_ms",
                                    "setup_s"}
    assert set(line["checks"]) >= {
        "answers_wrong", "never_answered", "executed_twice",
        "replica_groups_diverged", "groups_without_coordinator",
        "kill_off_schedule"}
    out = capfd.readouterr().out
    window = json.loads(next(ln for ln in out.splitlines()
                             if '"phase": "window"' in ln))
    assert window["victim_stayed_dead"] and window["resent"] > 0
    # the next in ring took every group the victim led, in one batch
    led = window["installs"][3]
    assert led > 64 and window["installs"] == [0, 0, 0, led, 0]
    assert window["elections_won"] == window["elections_started"] \
        == window["installs"]
    # (a tiny warm-up may leave a serving bucket to the window; never these)
    assert not {"prepare", "install_coordinator"} \
        & set(window["kernels_traced_in_window"])


def test_a_traced_run_reports_the_new_metrics_and_its_controls_fail(
        failover_cell, ring):
    """One run for both: the four readers a CPU can feed (no device plane,
    so no roofline) beside the served ones, and each control through the
    comparison at the run's own size (``chip_control.py``'s way)."""
    driver = failover_cell.driver()
    run = driver.run(failover_cell, seed=23, seconds=4.0, trace=True,
                     t_start=time.perf_counter())
    assert all(v <= lim for _n, v, lim in run["checks"]), run["checks"]
    got = {m["name"]: failover_cell.reader(m["name"]).read(run)
           for m in failover_cell.per_layer()}
    assert {k for k, v in got.items() if v is None} <= {
        "paxos_kernels_roofline", "election_kernels_roofline"}, got
    assert 600 - 150 <= got["detect_ms"] <= 600 + 100 + 1500
    assert 0 < got["takeover_ms"] < 5000
    assert got["election_lanes_per_dispatch"] > 8
    ctl = driver.controls(run, 23)
    assert set(ctl) == set(driver.CONTROLS)
    for broken, checks in ctl.items():
        assert any(v > lim for _n, v, lim in checks), broken
