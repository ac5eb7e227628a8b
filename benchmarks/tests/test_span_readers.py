"""The six readers of the program's stage spans (``gp.*``, PR 26): each on a
ring made by hand gives the value computed by hand, nothing on an empty ring
or one that dropped spans, and all six come out of a traced tiny cell."""

import collections
import json
import os
import shutil
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
SIX = {"queue_wait_ms": 2.0, "worker_nap_ms": 1.5, "engine_submit_ms": 2.5,
       "padded_lane_share": 62.5, "no_wave_in_flight_share": 25.0,
       "emit_ms": 0.5}


def _span(kind, t0, t1, **attrs):
    return dict(kind=kind, node=0, tid=1, wave=1, parent=0, t0=t0, t1=t1,
                **attrs)


def hand():
    """A session of 20 ms on the ring's clock, fresh (the ring evicts by
    age).  By hand: queue_wait_ms (1 + 3) / 2; worker_nap_ms (1 + 2 + 3)
    / 4 submits; engine_submit_ms (3 + 3 + 2 + 2) / 4; padded_lane_share
    1 - 30 / 80; emit_ms (0.25 + 0.75) / 2; in flight [0, 2) the wave from
    before the session, [3, 10) two waves that overlap, [12, 15),
    [17, 20) = 15 ms of 20."""
    t = time.monotonic()

    def ms(x):
        return t + x / 1e3
    return [
        # a wave in flight when the session began: its collect ends at 2
        _span("eng.collect", ms(0), ms(2), lanes=8),
        _span("w.wait", ms(0), ms(1)),
        _span("w.wait", ms(2), ms(4)),
        _span("w.coalesce", ms(4), ms(7), prev_items=40),
        _span("w.decode", ms(7), ms(7.5), frames=4, queue_wait_s=0.001),
        _span("w.decode", ms(12), ms(12.5), frames=4, queue_wait_s=0.003),
        _span("eng.submit", ms(3), ms(6), kernel="accept_p", lanes=6,
              bucket=8, chunks=1, launched=8),
        _span("eng.submit", ms(5), ms(8), kernel="request_reply_p",
              lanes=6, bucket=8, chunks=1, launched=16),
        _span("eng.collect", ms(8), ms(9), lanes=6),
        _span("eng.collect", ms(9), ms(10), lanes=6),
        _span("eng.submit", ms(12), ms(14), kernel="accept_commit_p",
              lanes=10, bucket=8, chunks=2, launched=32),
        _span("eng.collect", ms(14), ms(15), lanes=10),
        _span("eng.submit", ms(17), ms(19), kernel="commit_p", lanes=8,
              bucket=8, chunks=1, launched=24),
        _span("eng.collect", ms(19), ms(20), lanes=8),
        _span("w.emit", ms(10), ms(10.25), frames=3),
        _span("w.emit", ms(15), ms(15.75), frames=5),
    ]


@pytest.fixture
def ring(monkeypatch):
    from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
    RI.reset()

    def put(spans, maxlen=1000):
        monkeypatch.setattr(RI, "_spans",
                            collections.deque(spans, maxlen=maxlen))
    yield put
    RI.reset()


def readers():
    from benchmarks import harness
    return {name: harness.load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py")) for name in SIX}


def test_each_reader_on_a_ring_made_by_hand(ring):
    ring(hand())
    for name, mod in readers().items():
        assert mod.read({}) == pytest.approx(SIX[name]), name


def test_nothing_to_read(ring, monkeypatch):
    from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
    ring([])
    assert all(mod.read({}) is None for mod in readers().values())
    # a ring that pushed spans out has lost the window's beginning
    ring(hand())
    monkeypatch.setattr(RI, "n_span_dropped", 1)
    assert all(mod.read({}) is None for mod in readers().values())
    # spans of other kinds only: every reader finds none of its own
    monkeypatch.setattr(RI, "n_span_dropped", 0)
    now = time.monotonic()
    ring([_span("w.tick", now, now + 0.001)])
    assert all(mod.read({}) is None for mod in readers().values())
    # a program from before the ring had an accessor (the parent commit)
    monkeypatch.delattr(RI, "spans_snapshot")
    assert all(mod.read({}) is None for mod in readers().values())


def test_a_traced_tiny_cell_reports_the_six(tmp_path, monkeypatch, measure,
                                            ring):
    """A benchmark of its own (the tiny one plus six entries, as a later PR
    adds them): the harness finds the readers by name, and a traced run of
    three nodes on the CPU fills the ring they read."""
    from benchmarks import harness
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config
    from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
    shutil.copytree(TINY, tmp_path / "tiny")
    path = tmp_path / "tiny" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    mine = [m for m in harness.load_json(os.path.join(
        ROOT, "BENCHMARK.json"))["per_layer"] if m["name"] in SIX]
    assert len(mine) == 6
    for m in mine:
        assert m["source"] == "program_span"
        bench["per_layer"].append(dict(m, workloads=["tiny-served-d16"]))
    path.write_text(json.dumps(bench))

    Config.set(PC.FUSE_WAVES, "on")  # the handlers the chip run takes
    monkeypatch.setattr(harness, "TRACE_S", 0.3)
    cell = harness.Cell("tiny-served-d16", root=str(tmp_path / "tiny"))
    for const, value in (("RAMP_BURST_S", 0.1), ("WARMUP_BURST_S", 0.3),
                         ("QUIET_BURSTS", 1), ("MAX_BURSTS", 3)):
        monkeypatch.setattr(cell.driver(), const, value)
    line = measure(cell, seed=11, seconds=1.0, trace=True)
    assert line["correct"] is True
    assert set(SIX) <= set(line["metrics"]), line["metrics"]
    got = {k: line["metrics"][k]["value"] for k in SIX}
    assert all(v >= 0 for v in got.values()), got
    assert got["padded_lane_share"] < 100
    assert got["no_wave_in_flight_share"] <= 100
    st = RI.span_stats()
    assert st["dropped"] == 0 and st["begun"] == st["ended"] > 0
    # the ring took the traced 0.3 s and nothing of the second around it
    lo = min(s["t0"] for s in RI.spans_snapshot())
    hi = max(s["t1"] for s in RI.spans_snapshot())
    assert 0.25 <= hi - lo <= 0.6
