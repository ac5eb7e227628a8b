"""The recovery cell's own parts on the CPU at tiny sizes: the plain
reference against its two controls, the six readers on a ring and on totals
made by hand, the recovery byte count, and the driver through a whole run
with a node killed and restarted inside the window."""

import collections
import json
import os
import time

import numpy as np
import pytest

from benchmarks import loadgen, roofline_recovery
from benchmarks.reference import recovery_rsm
from benchmarks.reference.counter_rsm import run_group

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tiny_recovery")
NAMES = loadgen.plan_groups(5, 100, 20)
NEW = ("recover_ms", "recovery_wal_ms", "catchup_ms",
       "catchup_rows_per_frame", "recovery_rate_dip",
       "recovery_kernels_roofline")
NODES = [0, 1, 2, 3, 4]


# -- the reference ----------------------------------------------------------

def record(n=60, resent=(7, 23), unanswered_last=True):
    """A window's record as the generator keeps it: request k on group
    k % 20, all answered by the reference but (optionally) the last."""
    req_id = (np.uint64(77 << 32) | np.arange(n, dtype=np.uint64))
    seq_group = np.arange(n) % len(NAMES)
    stream = [(NAMES[g], int(r)) for g, r in zip(seq_group, req_id)]
    answers = {}
    for g in set(seq_group.tolist()):
        ks = np.flatnonzero(seq_group == g)
        for k, out in zip(ks, run_group([int(req_id[k]) for k in ks])):
            answers[int(k)] = out
    t_recv = np.arange(n) * 0.01 + 0.005
    if unanswered_last:
        t_recv[-1] = -1.0
    sends = [(k, k % 5, k * 0.01) for k in range(n)] + \
        [(k, (k + 1) % 5, k * 0.01 + 1.0) for k in resent]
    res = {"n_sent": n, "seq_group": seq_group, "req_id": req_id,
           "t_recv": t_recv, "status": np.zeros(n, np.int16),
           "reply": [answers[k] for k in range(n)],
           "sends": np.asarray(sends, np.float64)}
    return [stream], [res]


def sound(streams, results):
    """What five sound replicas hold, and a sound restart's facts."""
    answered = [np.asarray(r["t_recv"]) >= 0 for r in results]
    _ans, states, _ids = recovery_rsm.replay(streams, answered)
    final = {g: sts[0] for g, sts in states.items()}
    facts = {"live_groups": 100, "groups_recovered": 100,
             "checkpoint_slot": [-1] * 90 + [3] * 10,
             "cursor_after_boot": [0] * 90 + [4] * 10}
    bal = np.full((5, 4), (1 << 12) | 3)
    cbal = np.where(np.arange(5)[:, None] == 3, bal, -2)
    return [dict(final) for _ in NODES], facts, bal, cbal


def checks(streams, results, states, facts, bal, cbal):
    return {n: v for n, v, _l in recovery_rsm.check(
        streams, results, states, 2, facts, bal, cbal, NODES)}


def test_a_sound_record_is_correct():
    streams, results = record()
    got = checks(streams, results, *sound(streams, results))
    assert set(got) == {
        "answers_wrong", "answers_refused", "never_answered",
        "executed_twice", "state_mismatch", "restarted_rows_behind",
        "groups_not_recovered", "rolled_back_below_checkpoint",
        "coordinators_missing"}
    assert got.pop("never_answered") == 1 and not any(got.values()), got


@pytest.mark.parametrize("broken", recovery_rsm.CONTROLS)
def test_each_control_is_not_correct(broken):
    streams, results = record()
    states, facts, bal, cbal = sound(streams, results)
    rng = np.random.default_rng(3)
    victim = recovery_rsm.pick_victim(broken, results, rng)
    fake, bad = recovery_rsm.broken_run(broken, streams, results, 5, 2,
                                        victim)
    got = checks(streams, fake, bad, facts, bal, cbal)
    if broken == "stale_replica":
        # one replica left stale on one group: behind, and not the others'
        assert got["restarted_rows_behind"] == 1
        assert got["state_mismatch"] == 1 and got["executed_twice"] == 0
        assert victim not in (7, 23)
    else:
        # one request applied twice, on all five
        assert got["executed_twice"] == 5 and victim in (7, 23)
        assert got["restarted_rows_behind"] == 0


def test_what_the_restart_itself_is_held_to():
    streams, results = record(unanswered_last=False)
    states, facts, bal, cbal = sound(streams, results)
    ok = checks(streams, results, states, facts, bal, cbal)
    assert not any(ok.values()), ok
    lost = dict(facts, groups_recovered=97)
    assert checks(streams, results, states, lost, bal,
                  cbal)["groups_not_recovered"] == 3
    back = dict(facts, cursor_after_boot=[0] * 90 + [4] * 8 + [3, 0])
    assert checks(streams, results, states, back, bal,
                  cbal)["rolled_back_below_checkpoint"] == 2
    # the restarted node still promised to itself on one group it led
    old = bal.copy()
    old[2, 1] = 2
    assert checks(streams, results, states, facts, old,
                  cbal)["coordinators_missing"] == 1
    # ... or behind on a group whose requests were all acknowledged
    stale = [dict(s) for s in states]
    g = streams[0][5][0]
    stale[2][g] = run_group([rid for gg, rid in streams[0] if gg == g])[-2]
    got = checks(streams, results, stale, facts, bal, cbal)
    assert got["restarted_rows_behind"] == 1 and got["state_mismatch"] == 1


# -- the byte count -----------------------------------------------------------

def test_recovery_bytes_follow_the_state_layout():
    # an install: three flags and eight words of scalars = 35, a slot word
    # for each of 16 entries of three windows = 192, the lane's 17
    assert roofline_recovery.install_bytes(16) == 35 + 192 + 17 == 244
    # a cursor: exec_cursor written, next_slot read and written, lane 12
    assert roofline_recovery.cursor_bytes() == 4 + 8 + 12
    # a replayed accept: roofline's 29 and a lane of 20 in, 7 out; a
    # commit: 29 and 16 in, 3 out
    assert roofline_recovery.replay_bytes(1, 0) == 29 + 27
    assert roofline_recovery.replay_bytes(0, 1) == 29 + 19
    assert roofline_recovery.recovery_bytes(100000, 8192, 13000, 12000,
                                            16) == \
        100000 * 244 + 8192 * 24 + 13000 * 56 + 12000 * 48


# -- the readers --------------------------------------------------------------

def _span(kind, t0, t1, node=2, **attrs):
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


def totals(**tags):
    return {"totals": {k: {"wall_s": w, "calls": c, "items": i}
                       for k, (w, c, i) in tags.items()}}


def test_the_six_readers_on_a_run_made_by_hand(ring, monkeypatch):
    """The restart at 100.0 s on the ring's clock; the boot takes 6 s, of
    it the WAL 1.5 s; the catch-up 2.5 s in 8 frames for 4,400 rows; the
    traced seconds are 99.5 .. 103.5 and hold the install and the
    checkpoints, not the roll-forward."""
    from benchmarks import roofline
    peaks = roofline.load_peaks("TPU v5 lite")
    monkeypatch.setattr(roofline, "load_peaks", lambda kind=None: peaks)
    ring([_span("rec.install", 101.0, 102.0, rows=100000, bytes=73900000,
                programs="jit_create_groups_batch"),
          _span("rec.checkpoints", 102.0, 103.0, rows=100000, restored=8000,
                bytes=4000000, programs="jit_set_cursor_batch"),
          _span("rec.wal", 103.0, 104.5, records=25000, accepts=13000,
                decisions=12000, bytes=800000,
                programs="jit_accept_packed+jit_commit_packed"),
          _span("rec.boot", 100.0, 106.0, groups=100000)])
    before = totals(**{"rec.boot": (1.0, 1, 5)})
    after = totals(**{"rec.boot": (7.0, 2, 100005),
                      "rec.wal": (1.5, 1, 25000),
                      "rec.catchup": (2.5, 1, 4400),
                      "rec.catchup_frames": (0.0, 2, 8),
                      "rec.rows_level": (0.0, 8, 4400)})
    # 800 a second acknowledged through the outage, 1,000 a second from
    # the takeover's last install (56.0) to the restart (58.0), 500 a
    # second while the boot is open (58.0 .. 64.0), 1,000 after it
    t = np.concatenate([np.arange(0, 6, 0.00125),
                        np.arange(6, 8, 0.001),
                        np.arange(8, 14, 0.002),
                        np.arange(14, 20, 0.001)]) + 50.0
    run = {"before": before, "after": after, "config": {"window": 16},
           "trace": {"module_s": {"jit_create_groups_batch": 0.05,
                                  "jit_set_cursor_batch": 0.001,
                                  "jit_accept_packed": 0.5,
                                  "jit_node_wave_packed": 0.2}},
           "window": {"t_recv": t, "status": np.zeros(len(t), np.int16),
                      "t_all_installed": 56.0, "t_restart": 58.0,
                      "t_restart_done": 64.0, "boot_s": 6.0,
                      "t_deadline": 70.0, "rate_after_takeover": 800.0,
                      "trace_ring": (99.5, 103.5)}}
    got = {name: mod.read(run) for name, mod in readers().items()}
    assert got["recover_ms"] == pytest.approx(6000.0)
    assert got["recovery_wal_ms"] == pytest.approx(1500.0)
    assert got["catchup_ms"] == pytest.approx(2500.0)
    assert got["catchup_rows_per_frame"] == pytest.approx(550.0)
    assert got["recovery_rate_dip"] == pytest.approx(50.0, abs=0.1)
    # a boot that outlasts the window is read as far as the client sent;
    # one that opened after it, or a takeover still open at the restart,
    # is not read at all
    dip = readers()["recovery_rate_dip"]
    cut = dict(run["window"], t_restart_done=75.0, boot_s=17.0,
               t_deadline=62.0)
    assert dip.read(dict(run, window=cut)) == pytest.approx(50.0, abs=0.1)
    assert dip.read(dict(run, window=dict(cut, t_deadline=57.0))) is None
    assert dip.read(dict(run, window=dict(cut, t_all_installed=59.0))) \
        is None
    least = 100000 * 244 + 8000 * 24
    assert got["recovery_kernels_roofline"] == pytest.approx(
        100 * least / 819e9 / 0.051)
    assert got["recovery_kernels_roofline"] < 100


def test_the_readers_find_nothing_where_nothing_is(ring):
    """A run in which no node recovered, or the parent's program (no such
    span, no such total): None, and nothing raised."""
    ring([])
    bare = {"before": {}, "after": {}, "window": {},
            "config": {"window": 16}, "trace": None}
    assert all(mod.read(bare) is None for mod in readers().values())
    t = time.monotonic()
    ring([_span("w.tick", t, t + 0.001)])
    run = {"before": totals(**{"w.decode": (1.0, 5, 5)}),
           "after": totals(**{"w.decode": (2.0, 9, 9)}),
           "window": {"t_restart": None, "boot_s": None,
                      "rate_after_takeover": 900.0,
                      "trace_ring": (t - 1, t + 1),
                      "t_recv": np.zeros(3), "status": np.zeros(3)},
           "config": {"window": 16},
           "trace": {"module_s": {"jit_accept_packed": 1.0}}}
    assert all(mod.read(run) is None for mod in readers().values())
    # a recovery whose programs the trace does not hold
    ring([_span("rec.install", t, t + 0.01, rows=8, bytes=8 * 739,
                programs="jit_create_groups_batch")])
    assert readers()["recovery_kernels_roofline"].read(run) is None


# -- the driver ---------------------------------------------------------------

@pytest.fixture
def recovery_cell(monkeypatch):
    from benchmarks import harness
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config
    Config.set(PC.FUSE_WAVES, "on")  # the handlers the chip run takes
    monkeypatch.setattr(harness, "TRACE_S", 2.0)
    cell = harness.Cell("tiny-recovery-restart1", root=TINY)
    for const, value in (("RAMP_BURST_S", 0.1), ("WARMUP_BURST_S", 0.3),
                         ("QUIET_BURSTS", 1), ("MAX_BURSTS", 3)):
        monkeypatch.setattr(cell.driver(), const, value)
    return cell


def test_the_cell_kills_and_restarts_a_node_and_is_correct(
        recovery_cell, measure, capfd):
    line = measure(recovery_cell, seed=2**31 + 79, seconds=4.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 32
    assert set(line["metrics"]) == {"commit_rate", "commit_p50_ms",
                                    "setup_s"}
    assert set(line["checks"]) >= {
        "answers_wrong", "never_answered", "executed_twice",
        "state_mismatch", "restarted_rows_behind", "groups_not_recovered",
        "rolled_back_below_checkpoint", "coordinators_missing",
        "kill_off_schedule", "restart_off_schedule", "compiles_in_window"}
    out = capfd.readouterr().out
    window = json.loads(next(ln for ln in out.splitlines()
                             if '"phase": "window"' in ln))
    assert window["victim_came_back"] and window["resent"] > 0
    assert 0.5 <= window["killed_at_s"] <= 1.1
    assert 1.9 <= window["restarted_at_s"] <= 2.5
    tot = window["recovery_totals"]
    assert tot["rec.boot"][0] == 1 and tot["rec.groups_recovered"][1] == 400
    assert tot["rec.catchup"][0] == 1 and tot["rec.catchup_frames"][1] >= 4
    assert tot["rec.rows_level"][1] == tot["rec.rows_behind"][1]
    # nothing a recovery launches was first traced inside the window
    assert not {"create_groups", "set_cursor", "accept_p", "commit_p",
                "prepare", "install_coordinator"} \
        & set(window["kernels_traced_in_window"])
    ref = json.loads(next(ln for ln in out.splitlines()
                          if '"phase": "reference"' in ln))
    assert ref["replicas_compared"] == 5


def test_a_traced_run_reports_the_new_metrics_and_its_controls_fail(
        recovery_cell, ring):
    """One run for both: the five readers a CPU can feed (no device plane,
    so no roofline) beside the served ones, and each control through the
    comparison at the run's own size (``chip_control.py``'s way)."""
    driver = recovery_cell.driver()
    run = driver.run(recovery_cell, seed=29, seconds=4.0, trace=True,
                     t_start=time.perf_counter())
    assert all(v <= lim for _n, v, lim in run["checks"]), run["checks"]
    got = {m["name"]: recovery_cell.reader(m["name"]).read(run)
           for m in recovery_cell.per_layer()}
    assert {k for k, v in got.items() if v is None} <= {
        "paxos_kernels_roofline", "recovery_kernels_roofline"}, got
    assert 0 < got["recovery_wal_ms"] < got["recover_ms"] < 20000
    assert 0 < got["catchup_ms"] < 20000
    assert got["catchup_rows_per_frame"] >= 1
    assert got["recovery_rate_dip"] < 100
    # the tracer came on as the recovery began: its parts opened in the
    # traced seconds and reached the ring (the boot's own span opened
    # before them and reached only its sum)
    from benchmarks import span_ring
    kinds = {s["kind"] for s in span_ring.session() or []}
    assert {"rec.install", "rec.checkpoints", "rec.wal", "rec.serve",
            "rec.catchup"} <= kinds
    ctl = driver.controls(run, 29)
    assert set(ctl) == set(driver.CONTROLS)
    for broken, cks in ctl.items():
        assert any(v > lim for _n, v, lim in cks), broken


def test_a_program_without_the_exchange_is_refused_at_once(
        recovery_cell, monkeypatch):
    """On the parent's program the driver says what is missing and exits,
    before it boots anything."""
    from gigapaxos_tpu.paxos.manager import PaxosNode
    monkeypatch.delattr(PaxosNode, "catching_up")
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as exc:
        recovery_cell.driver().run(recovery_cell, seed=1, seconds=1.0,
                                   trace=False, t_start=t0)
    assert "frontier exchange" in str(exc.value.code)
    assert time.perf_counter() - t0 < 1.0
