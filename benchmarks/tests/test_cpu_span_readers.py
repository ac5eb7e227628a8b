"""The four readers of the spans' thread clocks and of the handler and
event-loop spans (PR 38): each on a ring made by hand gives the value
computed by hand, nothing on an empty ring, a ring that dropped spans or
records without ``c0`` (the parent's), and a traced tiny cell reports them
beside the six readers of PR 26."""

import collections
import json
import os
import shutil
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
FOUR = {"process_off_cpu_share": 50.0, "submit_off_cpu_ms": 1.75,
        "handler_python_ms": 2.875, "loop_cpu_share": 20.0}
SIX = ("queue_wait_ms", "worker_nap_ms", "engine_submit_ms",
       "padded_lane_share", "no_wave_in_flight_share", "emit_ms")


def hand():
    """A session of 20 ms on the ring's clock, fresh (the ring evicts by
    age); times and thread clocks in ms.  By hand:

    process_off_cpu_share: batch 1 is 10 wall, 4 CPU, 1 in fsync (under
    wal under w.post under w.node_wave): 5 off; batch 2 is 8 wall, 4 CPU:
    4 off; (5 + 4) / 18.
    submit_off_cpu_ms: (2 - 0.5) and (3 - 1) off, over two submits.
    handler_python_ms: w.node_wave 8 - (2 + 2 + 1 + 2.5), w.pre 2, w.post
    2.5 - (2 + 0.25), w.requests 6 - (3 + 1) (a nested eng.submit under a
    split handler), w.upper.Foo 1: 5.75 over two batches.
    loop_cpu_share: loop thread 2 ran 12.5 - 10, thread 3 21.5 - 20, over
    the session's 20."""
    t = time.monotonic()
    ids = iter(range(1, 100))

    def sp(kind, t0, t1, c0, c1, parent=0, tid=1, **attrs):
        return dict(kind=kind, node=0, tid=tid, wave=1, parent=parent,
                    id=next(ids), t0=t + t0 / 1e3, t1=t + t1 / 1e3,
                    c0=c0 / 1e3, c1=c1 / 1e3, **attrs)
    p1 = sp("w.process", 0, 10, 0, 4)
    nw = sp("w.node_wave", 1, 9, 0.5, 3.5, p1["id"])
    post = sp("w.post", 6, 8.5, 2, 3, nw["id"])
    wal = sp("wal", 6, 8, 2, 2.5, post["id"])
    p2 = sp("w.process", 12, 20, 5, 9)
    req = sp("w.requests", 12, 18, 5, 8, p2["id"])
    return [
        sp("w.pre", 1, 3, 0.5, 1.5, nw["id"]),
        sp("eng.submit", 3, 5, 1.5, 2, nw["id"], lanes=4, launched=8),
        sp("eng.collect", 5, 6, 2, 2, nw["id"]),
        sp("wal.fsync", 6.5, 7.5, 2.2, 2.3, wal["id"]),
        wal,
        sp("app.execute", 8, 8.25, 2.5, 2.7, post["id"]),
        post, nw,
        sp("w.emit", 9, 9.5, 3.5, 3.9, p1["id"]),
        p1,
        sp("eng.submit", 13, 16, 6, 7, req["id"], lanes=4, launched=8),
        sp("eng.collect", 16, 17, 7, 7.1, req["id"]),
        req,
        sp("w.upper.Foo", 18, 19, 8, 8.9, p2["id"]),
        p2,
        sp("net.read", 0, 1, 10, 10.5, tid=2, frames=3, bytes=90),
        sp("net.read", 2, 3, 20, 20.5, tid=3, frames=1, bytes=30),
        sp("net.write", 4, 5, 11, 11.25, tid=2, frames=2, bytes=60),
        sp("net.read", 15, 16, 12, 12.5, tid=2, frames=1, bytes=30),
        sp("net.write", 18, 20, 21, 21.5, tid=3, frames=4, bytes=120),
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
        ROOT, "benchmarks", "layer_metrics", name + ".py")) for name in FOUR}


def test_each_reader_on_a_ring_made_by_hand(ring):
    ring(hand())
    for name, mod in readers().items():
        assert mod.read({}) == pytest.approx(FOUR[name]), name


def test_nothing_to_read(ring, monkeypatch):
    from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
    ring([])
    assert all(mod.read({}) is None for mod in readers().values())
    # a ring that pushed spans out has lost the window's beginning
    ring(hand())
    monkeypatch.setattr(RI, "n_span_dropped", 1)
    assert all(mod.read({}) is None for mod in readers().values())
    # the parent's records: no thread clock on any of them
    monkeypatch.setattr(RI, "n_span_dropped", 0)
    ring([{k: v for k, v in s.items() if k not in ("c0", "c1")}
          for s in hand()])
    assert all(mod.read({}) is None for mod in readers().values())


def test_a_traced_tiny_cell_reports_the_ten(tmp_path, monkeypatch, measure,
                                            ring):
    """The tiny benchmark plus the ten span metrics' entries: a traced run
    of three nodes on the CPU gives every one a number, and the ring holds
    the traced seconds whole."""
    from benchmarks import harness
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config
    from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
    ten = set(FOUR) | set(SIX)
    shutil.copytree(TINY, tmp_path / "tiny")
    path = tmp_path / "tiny" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    mine = [m for m in harness.load_json(os.path.join(
        ROOT, "BENCHMARK.json"))["per_layer"] if m["name"] in ten]
    assert len(mine) == 10
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
    assert ten <= set(line["metrics"]), line["metrics"]
    got = {k: line["metrics"][k]["value"] for k in FOUR}
    assert all(v >= 0 for v in got.values()), got
    assert got["process_off_cpu_share"] <= 100
    st = RI.span_stats()
    assert st["dropped"] == 0 and st["begun"] == st["ended"] > 0
