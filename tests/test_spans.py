"""The stage-span primitive (``utils/instrument.span``): always-on sums,
and — under the operator's switch or a JAX profiler session — ``gp.*``
events on the profiler's clock plus the completed spans in the ring,
with parent, wave and thread (ISSUE 26, ROADMAP T1)."""

import collections
import glob
import threading
import time

import pytest

from gigapaxos_tpu.paxos.manager import _StampedQueue
from gigapaxos_tpu.paxos.paxosconfig import PC
from gigapaxos_tpu.utils import prom
from gigapaxos_tpu.utils.config import Config
from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
from gigapaxos_tpu.utils.instrument import span, traced
from gigapaxos_tpu.utils.profiler import DelayProfiler

TOP = ("w.wait", "w.coalesce", "w.decode", "w.process", "w.tick")


@pytest.fixture(autouse=True)
def _fresh_rings():
    RI.reset()
    DelayProfiler.clear()
    yield


@pytest.mark.smoke
def test_off_moves_the_sums_and_nothing_else():
    assert not RI.tracing()
    with span("t.stage", node=1, n=3, frames=3) as sp:
        assert not sp.on
        sp.note(late=1)  # goes nowhere while off
    with span("t.stage", n=2, total="t.other"):
        pass
    tot = DelayProfiler.totals()
    assert tot["t.stage"][1:3] == (1, 3) and tot["t.stage"][0] >= 0
    assert tot["t.other"][1:3] == (1, 2)  # total= names the sum
    st = RI.span_stats()
    assert st["begun"] == st["ended"] == st["dropped"] == 0
    assert st["kinds"] == {} and RI.spans_snapshot() == []
    assert sp.t1 >= sp.t0
    # traced(): the same span with no sum; off, no object of its own
    with traced("t.inner", node=1, bytes=8) as a:
        a.note(late=1)
    with traced("t.inner") as b:
        pass
    assert a is b and not a.on
    assert "t.inner" not in DelayProfiler.totals()


@pytest.mark.smoke
def test_on_records_parent_wave_thread_and_late_attributes():
    RI.enabled = True
    RI.set_wave(RI.next_wave())
    wave = RI.current_wave()
    with span("t.outer", node=2, n=5, items=5) as outer:
        assert outer.on
        with span("t.inner", kernel="k") as inner:
            inner.n = 7           # counted late: the sums and the ring
            inner.note(chunks=2)  # known late: the ring
        with traced("t.nowave", wave=0) as t:
            assert t.on  # on: a span like any other, but for the sum
    with span("t.next"):
        pass
    got = {s["kind"]: s for s in RI.spans_snapshot()}
    assert list(got) == ["t.inner", "t.nowave", "t.outer", "t.next"]
    o, i = got["t.outer"], got["t.inner"]
    assert o["parent"] == 0 and i["parent"] == o["id"]
    assert got["t.nowave"]["parent"] == o["id"]
    assert got["t.next"]["parent"] == 0  # the stack unwound
    assert o["wave"] == i["wave"] == wave and got["t.nowave"]["wave"] == 0
    assert {s["tid"] for s in got.values()} == {threading.get_ident()}
    assert (o["node"], o["n"], o["items"]) == (2, 5, 5)
    assert (i["n"], i["kernel"], i["chunks"]) == (7, "k", 2)
    assert o["t0"] <= i["t0"] <= i["t1"] <= o["t1"]
    assert DelayProfiler.totals()["t.inner"][1:3] == (1, 7)
    assert "t.nowave" not in DelayProfiler.totals()
    st = RI.span_stats()
    assert st["begun"] == st["ended"] == 4 and st["open"] == 0
    # an exception passes through and still ends the span
    with pytest.raises(KeyError):
        with span("t.boom"):
            raise KeyError("x")
    st = RI.span_stats()
    assert st["begun"] == st["ended"] == 5
    assert RI._tls.stack == []


@pytest.mark.smoke
def test_the_ring_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(RI, "_spans", collections.deque(maxlen=4))
    RI.enabled = True
    for _ in range(6):
        with span("t.many"):
            pass
    st = RI.span_stats()
    assert st["dropped"] == 2 and st["ended"] == 6
    assert len(RI.spans_snapshot()) == 4
    assert "gp_spans_dropped_total 2" in prom.render_prometheus(
        {"spans": st})
    RI.clear()
    assert RI.span_stats()["dropped"] == 0


@pytest.mark.smoke
def test_queue_wait_pairs_puts_with_gets_under_two_putting_threads():
    q = _StampedQueue()
    n = 2000

    def put(base):
        for k in range(n):
            q.put((base, k, time.monotonic()))

    threads = [threading.Thread(target=put, args=(b,)) for b in (0, 1)]
    for t in threads:
        t.start()
    seen = {0: -1, 1: -1}
    last = 0.0
    for _ in range(2 * n):
        base, k, t_before = q.get(timeout=5)
        # what comes out is what went in, in each thread's own order,
        # and the stamp is that item's: taken inside its put
        assert k == seen[base] + 1
        seen[base] = k
        assert t_before <= q.t_put <= time.monotonic()
        assert q.t_put >= last  # stamped under the queue's own mutex
        last = q.t_put
    for t in threads:
        t.join()
    assert q.qsize() == 0 and q.empty()
    q.put(None)  # the stop sentinel is an item like any other
    assert q.get_nowait() is None


def _events(trace_dir):
    """name -> list of stats dicts of the ``gp.*`` host events."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("gp."):
                    out[ev.name].append(dict(ev.stats))
    return out


def test_a_profiler_session_turns_the_spans_on(tmp_path):
    """A tiny three-node emulation under ``jax.profiler.start_trace``:
    the ring and the ``.xplane.pb`` hold the same spans, every engine
    and WAL span hangs under the ``w.process`` of its wave and thread,
    and the five top-level worker spans tile each worker thread."""
    import jax

    from gigapaxos_tpu.testing.harness import PaxosEmulation
    Config.set(PC.FUSE_WAVES, "on")  # the handlers the chip run takes
    emu = PaxosEmulation(str(tmp_path / "wal"), n_nodes=3, n_groups=16,
                         backend="columnar", capacity=256, sync_wal=True)
    try:
        res = emu.run_load_fast(100, concurrency=16)
        assert res["ok"] == 100
        # off: the sums moved, the ring and its counters did not
        st = RI.span_stats()
        assert st["begun"] == 0 and st["kinds"] == {}
        assert DelayProfiler.totals()["w.process"][1] > 0

        # followers execute behind the acknowledgement: let them end, so
        # that no span opens under a w.process begun before the session
        time.sleep(0.3)
        trace_dir = str(tmp_path / "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            assert RI.tracing() and not RI.enabled
            res = emu.run_load_fast(300, concurrency=16,
                                    client_id=(1 << 20) + 1)
            time.sleep(0.1)  # only waits and ticks are in flight now
        finally:
            jax.profiler.stop_trace()
        assert res["ok"] == 300
    finally:
        emu.stop()
    assert not RI.tracing()
    # the always-on sums (read with the nodes stopped: exact)
    tot = DelayProfiler.totals()
    for tag in ("w.decode", "w.process", "w.emit", "w.queue_wait",
                "eng.submit", "eng.collect", "eng.lanes_dispatched"):
        assert tot[tag][1] > 0, tag
    # the boundaries inside a summed stage are spans only
    assert not {"w.wait", "w.tick", "eng.pack", "wal", "wal.fsync"} & set(tot)
    per_kernel = {k: v for k, v in tot.items() if k.startswith("eng.k.")}
    assert per_kernel
    # valid lanes per kernel sum to eng.submit's, with padding on top;
    # a call per chunk launched
    assert sum(v[2] for v in per_kernel.values()) == \
        tot["eng.submit"][2] <= tot["eng.lanes_dispatched"][2]
    assert sum(v[1] for v in per_kernel.values()) == \
        tot["eng.lanes_dispatched"][1] >= tot["eng.submit"][1]
    assert ',stage="eng.k"} ' in prom.render_prometheus(
        {"profiler": DelayProfiler.snapshot(buckets=False)})

    st = RI.span_stats()
    assert st["begun"] == st["ended"] > 0, st
    assert st["dropped"] == st["orphaned"] == st["open"] == 0, st
    spans = RI.spans_snapshot()
    assert len(spans) == st["ended"]

    # the same spans as events of the profile, by kind; a wait or a tick
    # in flight when the session stopped ends after it, so the profile
    # lacks at most one per worker thread
    events = _events(trace_dir)
    ring = collections.Counter("gp." + s["kind"] for s in spans)
    assert set(events) == set(ring)
    for name, n in ring.items():
        slack = 3 if name in ("gp.w.wait", "gp.w.tick") else 0
        assert 0 <= n - len(events[name]) <= slack, (name, n)
    sub = events["gp.eng.submit"][0]
    assert {"node", "wave", "n", "kernel", "lanes", "bucket", "chunks",
            "launched"} <= set(sub)
    assert {"frames", "queue_wait_s"} <= set(events["gp.w.decode"][0])

    by_id = {s["id"]: s for s in spans}
    workers = {s["tid"] for s in spans if s["kind"] == "w.decode"}
    assert len(workers) == 3
    kinds = collections.Counter(s["kind"] for s in spans)
    for kind in TOP[2:] + ("w.emit", "eng.submit", "eng.pack",
                           "eng.collect", "wal", "wal.fsync"):
        assert kinds[kind] > 0, kinds
    for s in spans:
        k = s["kind"]
        if k in TOP:
            assert s["parent"] == 0, s
        elif k in ("eng.submit", "eng.collect", "wal", "w.emit"):
            assert s["parent"], s
            p = by_id[s["parent"]]
            assert p["kind"] == "w.process", (s, p)
            assert (p["wave"], p["tid"]) == (s["wave"], s["tid"])
            assert p["t0"] <= s["t0"] and s["t1"] <= p["t1"]
        elif k == "eng.pack":
            assert by_id[s["parent"]]["kind"] == "eng.submit"
        elif k == "wal.fsync":
            # under the inline append; the logger's own writer thread
            # (checkpoints, creates) syncs under no span
            assert s["parent"] == 0 and s["tid"] not in workers or \
                by_id[s["parent"]]["kind"] == "wal", s
        if k == "eng.submit":
            assert s["kernel"] and s["bucket"] in (8, 64, 512, 4096)
            assert s["lanes"] <= s["launched"]
        if k == "w.wait":
            assert s["wave"] == 0

    # the five top-level spans tile each worker thread's time
    for tid in workers:
        mine = sorted((s for s in spans
                       if s["tid"] == tid and s["kind"] in TOP),
                      key=lambda s: s["t0"])
        covered = sum(s["t1"] - s["t0"] for s in mine)
        whole = mine[-1]["t1"] - mine[0]["t0"]
        assert 0.98 * whole <= covered <= whole, (covered, whole)
        for a, b in zip(mine, mine[1:]):
            assert a["t1"] <= b["t0"]  # siblings, never nested
