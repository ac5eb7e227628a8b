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
HANDLERS = ("w.node_wave", "w.requests", "w.acc_com", "w.accepts",
            "w.replies", "w.commits", "w.prepare_batch",
            "w.prepare_reply_batch")


def _handler(kind):
    return kind in HANDLERS or kind.startswith(("w.upper.", "w.ar.",
                                                "w.rc."))


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
def test_off_reads_no_thread_clock(monkeypatch):
    calls = []
    clock = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: calls.append(1) or clock())
    assert not RI.tracing()
    for _ in range(3):
        with span("t.stage", node=1, n=2):
            with traced("t.inner", bytes=8):
                pass
    assert calls == [] and DelayProfiler.totals()["t.stage"][1] == 3
    # on: two reads a span, at its open and its close
    RI.enabled = True
    with span("t.stage"):
        with traced("t.inner"):
            pass
    assert len(calls) == 4


@pytest.mark.smoke
def test_on_each_record_has_the_threads_cpu_clock():
    RI.enabled = True
    best_sleep, best_busy = 0.0, 0.0
    for _ in range(5):  # a loaded host may preempt a busy loop: best of 5
        with span("t.sleep") as a:
            time.sleep(0.05)
        with traced("t.busy") as b:
            end = time.monotonic() + 0.05
            while time.monotonic() < end:
                pass
        sa, sb = RI.spans_snapshot()[-2:]
        assert (sa["kind"], sb["kind"]) == ("t.sleep", "t.busy")
        best_sleep = max(best_sleep, (sa["t1"] - sa["t0"])
                         - (sa["c1"] - sa["c0"]))
        best_busy = max(best_busy, (sb["c1"] - sb["c0"])
                        / (sb["t1"] - sb["t0"]))
        if best_sleep >= 0.04 and best_busy >= 0.8:
            break
    assert a.on and b.on
    assert best_sleep >= 0.04, best_sleep   # off CPU
    assert best_busy >= 0.8, best_busy      # on CPU
    assert all(s["c0"] <= s["c1"] for s in RI.spans_snapshot())


async def _transport(node_id, addr_map, inbox):
    from gigapaxos_tpu.net.transport import Transport
    from gigapaxos_tpu.paxos import packets as pk
    t = Transport(node_id, ("127.0.0.1", 0), addr_map,
                  on_frame=lambda f: inbox.append(pk.decode(f)))
    await t.start()
    return t


def test_the_event_loop_spans_its_reads_and_writes():
    """A loopback pair on an event loop of its own thread: every read and
    every write another thread hands the loop is a ``gp.net.*`` span on
    the loop's thread, and the reads' frames and bytes are the receiver's
    counters."""
    import asyncio

    from gigapaxos_tpu.paxos import packets as pk
    RI.enabled = True
    loop = asyncio.new_event_loop()
    th = threading.Thread(target=loop.run_forever, daemon=True)
    th.start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(5)
    in0, in1 = [], []
    try:
        t0 = run(_transport(0, {}, in0))
        t1 = run(_transport(1, {0: ("127.0.0.1", t0.port)}, in1))
        t0.addr_map[1] = ("127.0.0.1", t1.port)
        frames = [pk.Prepare(1, k, k).encode() for k in range(40)]
        t1.send_many_threadsafe([(0, f, False, 1) for f in frames[:30]])
        for f in frames[30:]:
            t1.send_threadsafe(0, f)
        t0.send_threadsafe(1, pk.FailureDetect(0, 0, 42).encode())
        deadline = time.monotonic() + 5
        while (len(in0), len(in1)) != (40, 1):
            assert time.monotonic() < deadline, (len(in0), len(in1))
            time.sleep(0.005)
        run(t0.stop())
        run(t1.stop())
    finally:
        loop.call_soon_threadsafe(loop.stop)
        th.join(5)
    assert not th.is_alive()
    net = [s for s in RI.spans_snapshot() if s["kind"].startswith("net.")]
    assert {s["tid"] for s in net} == {th.ident}
    assert all(s["c0"] <= s["c1"] for s in net)

    def total(kind, node, attr):
        return sum(s[attr] for s in net
                   if s["kind"] == kind and s["node"] == node)
    assert total("net.read", 0, "frames") == t0.rcvd_frames == 40
    assert total("net.read", 0, "bytes") == t0.rcvd_bytes > 0
    assert total("net.read", 1, "frames") == t1.rcvd_frames == 1
    assert total("net.read", 1, "bytes") == t1.rcvd_bytes
    assert total("net.write", 1, "frames") == 40
    assert total("net.write", 1, "bytes") == sum(len(f) for f in frames)
    assert total("net.write", 0, "frames") == 1


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
        before = DelayProfiler.totals()
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
        t_stop, after = time.monotonic(), DelayProfiler.totals()
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
    # lacks at most one per worker thread (and a loop's read of a ping
    # at most one per loop)
    events = _events(trace_dir)
    ring = collections.Counter("gp." + s["kind"] for s in spans)
    assert set(events) == set(ring)
    for name, n in ring.items():
        slack = 3 if name in ("gp.w.wait", "gp.w.tick", "gp.net.read",
                              "gp.net.write") else 0
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
        elif k in ("eng.submit", "eng.collect", "wal", "w.emit") or \
                _handler(k):
            assert s["parent"], s
            p = by_id[s["parent"]]
            while p["kind"] != "w.process":  # through the handler spans
                assert _handler(p["kind"]) or p["kind"] == "w.post", (s, p)
                p = by_id[p["parent"]]
            assert (p["wave"], p["tid"]) == (s["wave"], s["tid"])
            assert p["t0"] <= s["t0"] and s["t1"] <= p["t1"]
        elif k == "eng.pack":
            assert by_id[s["parent"]]["kind"] == "eng.submit"
        elif k in ("w.pre", "w.post"):
            assert by_id[s["parent"]]["kind"] == "w.node_wave", s
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

    # every record has its thread's CPU clock; the fused handler holds its
    # pre and post halves between which the wave's submit and collect sit
    assert all(s["c0"] <= s["c1"] for s in spans)
    # (a batch whose every frame the pre halves drop launches nothing)
    assert kinds["w.node_wave"] == kinds["w.pre"] >= kinds["w.post"] > 0
    for s in spans:
        if s["kind"] in ("eng.submit", "eng.collect") and \
                by_id[s["parent"]]["kind"] == "w.node_wave":
            pre = [c for c in spans if c["parent"] == s["parent"]
                   and c["kind"] == "w.pre"][0]
            post = [c for c in spans if c["parent"] == s["parent"]
                    and c["kind"] == "w.post"][0]
            assert pre["t1"] <= s["t0"] and s["t1"] <= post["t0"]
    # the handlers' sums count what their spans count: a call a record,
    # the items their n
    done = [s for s in spans if _handler(s["kind"]) and s["t1"] <= t_stop]
    assert {s["kind"] for s in done} >= {"w.node_wave"}
    for kind in {s["kind"] for s in done}:
        mine = [s for s in done if s["kind"] == kind]
        b = before.get(kind, (0.0, 0, 0))
        assert after[kind][1] - b[1] == len(mine), kind
        assert after[kind][2] - b[2] == sum(s["n"] for s in mine), kind

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
