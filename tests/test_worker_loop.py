"""The node's one worker loop: ``_worker_loop`` -> ``_next_batch`` ->
``_decode_batch`` -> ``_process`` -> ``_emit_bundle`` -> ``_tick``.

The contract every caller of the loop leans on, driven on a node that
was never started (no sockets, no device): an empty intake becomes a
tick, the stop sentinel ends the loop, a batch that raises is lost and
the worker is not, the engine clock is the batch's decode stamp inside
``_process`` and the wall clock in ``_tick``, the per-batch buffers are
``None`` outside ``_process``, and frames that arrive inside the
coalescing nap ride the batch up to ``BATCH_SIZE``.  Then the loop's
end-to-end contract on a live three-node cluster, and what a
deployment is told when it still sets one of the settings that chose
another loop or engine.
"""

import logging
import threading
import time

import pytest

from gigapaxos_tpu.paxos.interfaces import CounterApp, Replicable
from gigapaxos_tpu.paxos.manager import PaxosNode
from gigapaxos_tpu.paxos.paxosconfig import PC, removed_settings
from gigapaxos_tpu.testing.harness import PaxosEmulation
from gigapaxos_tpu.utils.config import Config
from tests.conftest import tscale

BUFFERS = ("_resp_out", "_out_buf", "_self_buf", "_window_moved",
           "_acc_ahead")


@pytest.fixture
def node(tmp_path):
    """A node that is built and never started: its loop is driven by
    the test.  Frames are opaque here (``_decode_batch`` passes them
    through and each test puts its own ``_process_inner`` in)."""
    nd = PaxosNode(0, {0: ("127.0.0.1", 1)}, CounterApp(),
                   str(tmp_path / "n0"), backend="scalar", capacity=64,
                   window=8)
    nd.batch_timeout = 0.01
    nd._decode_batch = list
    try:
        yield nd
    finally:
        nd.stop()


def _run_loop(nd):
    th = threading.Thread(target=nd._worker_loop, daemon=True)
    th.start()
    return th


def _wait(cond, what, seconds=10.0):
    deadline = time.time() + tscale(seconds)
    while not cond():
        assert time.time() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


def test_empty_intake_times_out_into_a_tick(node):
    """No frame for ``BATCH_TIMEOUT_S``: the loop ticks (failure
    detection, elections and re-drives need no traffic) and processes
    nothing."""
    ticks, batches = [], []
    node._tick = lambda: ticks.append(1)
    node._process_inner = batches.append
    th = _run_loop(node)
    _wait(lambda: len(ticks) >= 3, "three ticks of an idle loop")
    assert not batches
    node._inq.put(None)
    th.join(tscale(5))
    assert not th.is_alive()


def test_stop_sentinel_ends_the_loop(node):
    """The sentinel ends the loop wherever the drain meets it: what
    was queued before it is served, what was queued behind it is
    not."""
    seen = []
    node._process_inner = lambda batch: seen.extend(batch)
    for item in (b"before", None, b"behind"):
        node._inq.put(item)
    th = _run_loop(node)
    th.join(tscale(5))
    assert not th.is_alive()
    assert seen == [b"before"]
    assert node._inq.get_nowait() == b"behind"


def test_batch_that_raises_is_lost_and_the_worker_is_not(node, caplog):
    """A handler that raises loses its batch, by name in the log; the
    next batch is served by the same thread and starts from empty
    buffers, not from what the dead batch had buffered."""
    served, entry_state = [], []

    def inner(batch):
        entry_state.append({b: getattr(node, b) for b in BUFFERS})
        if b"bad" in batch:
            node._out_buf.append((1, b"frame of the dead batch", False, 1))
            node._resp_out.setdefault(9, []).append((0, 1, 0, b""))
            raise RuntimeError("handler fault")
        served.extend(batch)

    node._process_inner = inner
    th = _run_loop(node)
    with caplog.at_level(logging.ERROR, logger="gp.node"):
        node._inq.put(b"bad")
        _wait(lambda: len(entry_state) == 1 and node._out_buf is None,
              "the failing batch")
        node._inq.put(b"good")
        _wait(lambda: served == [b"good"], "the batch after the fault")
    assert th.is_alive()
    assert any("worker batch failed" in r.getMessage()
               for r in caplog.records)
    assert entry_state[1] == {"_resp_out": {}, "_out_buf": [],
                              "_self_buf": [], "_window_moved": [],
                              "_acc_ahead": []}
    node._inq.put(None)
    th.join(tscale(5))


def test_now_is_the_decode_stamp_in_process_and_the_wall_clock_in_tick(
        node):
    """Inside ``_process`` every ``_now()`` is the one stamp taken when
    the batch was decoded (replay re-pins it); a tick runs unpinned;
    and another thread of the node reads the wall clock even while a
    batch is in flight — the one thing ``_wtls`` still holds."""
    reads = {}

    def inner(batch):
        a = node._now()
        time.sleep(0.02)
        reads["process"] = (a, node._now(), node._wtls.now)
        other = []
        th2 = threading.Thread(target=lambda: other.append(node._now()))
        th2.start()
        th2.join()
        reads["other_thread"] = other[0]

    def tick():
        reads.setdefault("tick", (node._wtls.now, node._now(),
                                  time.time()))

    node._process_inner = inner
    node._tick = tick
    t_before = time.time()
    node._inq.put(b"frame")
    th = _run_loop(node)
    _wait(lambda: "tick" in reads and "process" in reads, "a batch + tick")
    node._inq.put(None)
    th.join(tscale(5))
    a, b, pinned = reads["process"]
    assert a == b == pinned and pinned >= t_before
    assert reads["other_thread"] > pinned  # unpinned: 20 ms later
    tick_pin, tick_now, wall = reads["tick"]
    assert tick_pin == 0.0 and abs(tick_now - wall) < 1.0


@pytest.mark.parametrize("fault", [False, True], ids=["ok", "raises"])
def test_per_batch_buffers_are_none_after_process(node, fault):
    """The per-batch buffers are plain attributes: live (fresh, empty)
    inside ``_process`` and ``None`` again after it, also when the
    batch raised — a buffer left live would swallow the frames of the
    next caller of ``_route`` outside a batch."""
    inside = {}

    def inner(batch):
        inside.update({b: getattr(node, b) for b in BUFFERS})
        inside["t0"] = node._batch_t0
        if fault:
            node._self_buf.append(b"left over")  # requeued, not kept
            raise RuntimeError("handler fault")

    node._process_inner = inner
    assert all(getattr(node, b) is None for b in BUFFERS)
    t_before = time.time()
    if fault:
        with pytest.raises(RuntimeError):
            node._process([b"frame"])
        # the pass's leftovers went back to the intake, not into limbo
        assert node._inq.get_nowait() == b"left over"
    else:
        node._process([b"frame"])
    assert inside["_resp_out"] == {} and inside["_out_buf"] == []
    assert inside["t0"] >= t_before
    assert all(getattr(node, b) is None for b in BUFFERS), \
        {b: getattr(node, b) for b in BUFFERS}


def test_frames_inside_the_coalescing_nap_join_the_batch(
        node, monkeypatch):
    """Under load (the previous batch had ``BATCH_BUSY_ITEMS`` frames)
    the worker naps ``BATCH_COALESCE_S`` after the first item, and what
    arrives meanwhile rides the same batch — up to ``BATCH_SIZE``
    FRAMES, a read chunk counting each frame it holds."""
    node.batch_busy, node.batch_coalesce, node.batch_size = 8, 0.25, 4
    naps = []

    def nap(seconds):  # the frames arrive while the worker sleeps
        naps.append(seconds)
        for f in (b"c", b"d", b"e", b"f"):
            node._inq.put(f)

    monkeypatch.setattr(time, "sleep", nap)
    node._inq.put([b"a", b"b"])  # one read chunk of two frames
    batch, n_frames, qwait = node._next_batch(prev_items=8)
    assert naps == [0.25]
    assert batch == [[b"a", b"b"], b"c", b"d"] and n_frames == 4
    assert qwait >= 0.0
    assert [node._inq.get_nowait() for _ in range(2)] == [b"e", b"f"]
    # trickle traffic (a small previous batch) takes what is there
    # and does not nap
    node._inq.put(b"g")
    batch, n_frames, _ = node._next_batch(prev_items=7)
    assert batch == [b"g"] and n_frames == 1 and naps == [0.25]


def test_a_tick_follows_every_batch(node):
    """Ticks are not only the idle path: each served batch is followed
    by one (under load the loop never times out, and elections,
    re-drives and the parked flush must still run)."""
    order = []
    node.batch_timeout = 30.0  # no idle tick inside this test
    node._tick = lambda: order.append("tick")
    node._process_inner = lambda batch: order.append("batch")
    th = _run_loop(node)
    for k in range(3):
        node._inq.put(b"f%d" % k)
        _wait(lambda: order.count("tick") == k + 1, f"tick {k + 1}")
    node._inq.put(None)
    th.join(tscale(5))
    assert order == ["batch", "tick"] * 3


def test_self_routed_packets_ride_the_same_pass_up_to_its_cap(node):
    """A packet a handler routes to its own node is served as a
    follow-up wave of the same ``_process`` pass; a chain that never
    ends is cut after eight waves and what is left goes back to the
    intake instead of being lost."""
    waves = []

    def inner(batch):
        waves.append(list(batch))
        node._route(node.id, b"hop%d" % len(waves))

    node._process_inner = inner
    node._process([b"first"])
    assert waves == [[b"first"]] + [[b"hop%d" % k] for k in range(1, 9)]
    assert node._inq.get_nowait() == b"hop9"
    assert node._self_buf is None
    # outside a pass the same call goes through the intake queue
    node._route(node.id, b"outside")
    assert node._inq.get_nowait() == b"outside"


# -- the loop on a live cluster ----------------------------------------------


class _RecordingApp(Replicable):
    """Per-node execution journal: name -> [req_id] in apply order."""

    def __init__(self):
        self.seq = {}

    def execute(self, name, req_id, payload, is_stop=False) -> bytes:
        self.seq.setdefault(name, []).append(req_id)
        return b"ok"

    def checkpoint(self, name) -> bytes:
        return b""

    def restore(self, name, state) -> bool:
        return True


def test_worker_loop_per_group_order_exactly_once(tmp_path):
    """The loop (decode | engine+WAL | emit, one thread) keeps the
    per-group in-order execution contract: every replica applies the
    same per-group request sequence, exactly once — and ``w.emit``
    carried this load's outbound batches."""
    from gigapaxos_tpu.utils.profiler import DelayProfiler
    emu = PaxosEmulation(str(tmp_path), n_nodes=3, n_groups=8,
                         backend="columnar", app_cls=_RecordingApp)
    try:
        # snapshot AFTER boot, so the assertion below proves THIS
        # load's batches rode w.emit (the profiler is process-global)
        emit_before = DelayProfiler.totals().get("w.emit",
                                                 (0, 0, 0, 0))[1]
        n = 120
        stats = emu.run_load(n, concurrency=24, timeout=tscale(40))
        assert stats["ok"] == n, stats
        apps = [emu.nodes[i].app for i in range(3)]
        # wait for stragglers' catch-up commits to apply everywhere
        deadline = time.time() + tscale(25)
        while time.time() < deadline:
            if len({sum(map(len, a.seq.values())) for a in apps}) == 1:
                break
            time.sleep(0.05)
        groups = set()
        for a in apps:
            groups |= set(a.seq)
        for g in groups:
            seqs = [tuple(a.seq.get(g, ())) for a in apps]
            assert seqs[0] == seqs[1] == seqs[2], \
                f"group {g} diverged across replicas: {seqs}"
            assert len(set(seqs[0])) == len(seqs[0]), \
                f"group {g} executed a request twice: {seqs[0]}"
        totals = DelayProfiler.totals()
        assert totals.get("w.emit", (0, 0, 0, 0))[1] > emit_before, \
            f"w.emit never carried this load: {sorted(totals)}"
        # one worker thread a node: nothing beside it runs _process
        names = [t.name for t in threading.enumerate()]
        assert not [x for x in names if "-lane" in x or "-proc" in x
                    or "-emit" in x], names
    finally:
        emu.stop()


# -- one engine wave a worker batch (PC.FUSE_WAVES on) -----------------------


def _fused_load(tmp_path, fuse):
    """120 requests over 8 groups on three columnar nodes: each node's
    counters and each replica's per-group journal."""
    Config.set(PC.FUSE_WAVES, fuse)
    emu = PaxosEmulation(str(tmp_path / fuse), n_nodes=3, n_groups=8,
                         backend="columnar", app_cls=_RecordingApp)
    try:
        assert [nd._fuse_waves for nd in emu.nodes.values()] \
            == [fuse == "on"] * 3
        n = 120
        stats = emu.run_load_fast(n, concurrency=24, timeout=tscale(40))
        assert stats["ok"] == n, stats
        apps = [emu.nodes[i].app for i in range(3)]
        deadline = time.time() + tscale(25)
        while time.time() < deadline and not all(
                sum(map(len, a.seq.values())) == n for a in apps):
            time.sleep(0.05)
        ctr = [nd.metrics(include_profiler=False)["counters"]
               for nd in emu.nodes.values()]
        return ctr, [{g: list(v) for g, v in a.seq.items()} for a in apps]
    finally:
        emu.stop()


def test_every_hot_batch_is_one_wave_and_ends_where_the_split_run_ends(
        tmp_path):
    """Whole-wave fusion: every worker batch that holds a hot frame,
    whatever roles it holds, is ONE engine launch (``one_wave_batches`` ==
    ``hot_batches``: this load parks nothing and offers no accept twice),
    and the three replicas end with the per-group journals the same load
    leaves under the split handlers."""
    fused, seq_on = _fused_load(tmp_path, "on")
    split, seq_off = _fused_load(tmp_path, "off")
    for c in fused:
        assert c["hot_batches"] > 0
        assert c["one_wave_batches"] == c["hot_batches"], c
    for c in split:
        assert c["hot_batches"] > 0
    assert seq_on[0] == seq_on[1] == seq_on[2]
    assert seq_off[0] == seq_off[1] == seq_off[2]
    # the same requests on each group, each once (a request id's low
    # word is its place in the load; their order within a group is the
    # arrival order of a concurrent load)
    def places(seq):
        return {g: sorted(r & 0xFFFFFFFF for r in v) for g, v in seq.items()}

    assert places(seq_on[0]) == places(seq_off[0])
    assert all(len(set(v)) == len(v) for v in seq_on[0].values())


class _DroppingApp(_RecordingApp):
    """Deletes its own group when it executes ``drop``: what an app may
    do from inside ``execute`` (the engine lock is re-entrant)."""

    node = None

    def execute(self, name, req_id, payload, is_stop=False) -> bytes:
        super().execute(name, req_id, payload, is_stop)
        if payload == b"drop":
            self.node.delete_group(name)
        return b"ok"


def test_a_group_deleted_inside_the_batch_drops_its_accept_lanes(tmp_path):
    """One wave a batch resolves an accept's row BEFORE the coordinator's
    posts run; a request that executes in ``_rep_post`` and deletes the
    group frees that row under the accept.  The lane ran on the device
    on the row as it was (the delete followed it there); its host half
    must leave the freed row's mirrors alone and answer nothing, as the
    split order's later row lookup did.  The group ends deleted on all
    three replicas."""
    from gigapaxos_tpu.ops.types import NO_BALLOT, pack_ballot
    from gigapaxos_tpu.paxos import packets as pkt
    from tests.test_e2e import make_cluster, shutdown
    import numpy as np

    Config.set(PC.FUSE_WAVES, "on")
    nodes, _addr = make_cluster(tmp_path, app_cls=_DroppingApp)
    try:
        for nd in nodes:
            nd.app.node = nd
            assert nd.create_group("g", (0, 1, 2))
        gkey = pkt.group_key("g")
        c = nodes[gkey % 3]
        peers = [i for i in range(3) if i != c.id]
        row = c.table.by_name("g").row
        rid = 77 << 32
        keys = np.asarray([gkey], np.uint64)

        def i32(x):
            return np.asarray([x], np.int32)

        with c._engine_lock:  # the worker waits; these two are its batches
            c._process([pkt.Request(4242, gkey, rid, 0, b"drop")])
            assert rid in c._proposed and c.table.by_name("g") is not None
            sent = c.transport.metrics()["tx_bytes"]
            hot0 = c.n_hot_batches
            # a peer's ack decides slot 0; beside it another regime's
            # accept for slot 1 of the same group
            c._process([
                pkt.AcceptReplyBatch(peers[0], keys, i32(0),
                                     i32(pack_ballot(0, c.id)),
                                     np.asarray([1], np.uint8)),
                pkt.AcceptBatch(peers[1], keys, i32(1),
                                i32(pack_ballot(1, peers[1])), i32(9),
                                i32(0), payloads=[b"\x00late"])])
            assert c.n_hot_batches == hot0 + 1
            assert c.n_one_wave_batches >= 1
            assert c.table.by_name("g") is None
            assert c.app.seq["g"] == [rid]
            # the freed row is as a free row is: the accept's post did
            # not stamp it, store its payload or keep it in flight
            assert c._bal[row] == NO_BALLOT and c._acc_hi[row] == -1
            assert row not in c._dec and not c._proposed
            assert c._payload_get(9) is None
        deadline = time.time() + tscale(15)
        while time.time() < deadline and any(
                nd.table.by_name("g") is not None for nd in nodes):
            time.sleep(0.05)
        for nd in nodes:
            assert nd.table.by_name("g") is None, nd.id
            assert nd.app.seq["g"] == [rid]
        assert sent <= c.transport.metrics()["tx_bytes"]
    finally:
        shutdown(nodes)


# -- settings that chose another loop or engine ------------------------------


def _props(tmp_path, line):
    from gigapaxos_tpu.server import read_extras
    p = tmp_path / "gp.properties"
    p.write_text("active.node0=127.0.0.1:2000\n# comment\n" + line + "\n")
    return read_extras(str(p))


@pytest.mark.parametrize("line,name", [
    ("ENGINE_SHARDS=4", "ENGINE_SHARDS"),
    ("PIPELINE_WORKER=true", "PIPELINE_WORKER"),
], ids=["ENGINE_SHARDS", "PIPELINE_WORKER"])
def test_removed_setting_in_properties_is_answered_by_name(
        tmp_path, line, name):
    """A properties file that still asks for engine lanes or the
    pipelined loop is told, by the key's name, that the setting is
    gone — and a file that sets the value that changed nothing is
    not."""
    msgs = removed_settings(_props(tmp_path, line))
    assert len(msgs) == 1 and msgs[0].startswith(line), msgs
    assert "no longer a setting" in msgs[0] and "MIGRATING" in msgs[0]
    assert not hasattr(PC, name)
    harmless = {"ENGINE_SHARDS": "ENGINE_SHARDS=1",
                "PIPELINE_WORKER": "PIPELINE_WORKER=false"}[name]
    assert removed_settings(_props(tmp_path, harmless)) == []
    # the same key in Config.load's form (PC.<KEY>=)
    p = tmp_path / "lib.properties"
    p.write_text("PC." + line + "\n")
    Config.load(str(p))
    assert [m.split(" ")[0] for m in removed_settings()] == [line]


def test_removed_setting_in_environment_is_logged_at_start_up(
        tmp_path, monkeypatch, caplog):
    """``GP_PC_ENGINE_SHARDS=4`` reaches no ``Config.get`` any more
    (the key is gone), so the node itself answers it when it is
    built — in the log, by name — and runs its one lane."""
    monkeypatch.setenv("GP_PC_ENGINE_SHARDS", "4")
    with caplog.at_level(logging.WARNING, logger="gp.node"):
        nd = PaxosNode(0, {0: ("127.0.0.1", 1)}, CounterApp(),
                       str(tmp_path / "n0"), backend="scalar",
                       capacity=64, window=8)
    try:
        hits = [r.getMessage() for r in caplog.records
                if "ENGINE_SHARDS=4" in r.getMessage()]
        assert len(hits) == 1 and "environment" in hits[0], hits
        assert "ignored" in hits[0]
    finally:
        nd.stop()
    monkeypatch.setenv("GP_PC_ENGINE_SHARDS", "1")
    assert removed_settings() == []


# -- the README against the code ---------------------------------------------


def _readme():
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "README.md")) as f:
        return f.read()


def test_readme_knob_table_is_the_code(tmp_path):
    """The README's configuration table names exactly the keys of
    ``PC`` — none that left, none missing — and every default it
    prints is the code's."""
    import re

    rows = dict(re.findall(r"^\| `([A-Z][A-Z0-9_]*)` \| ([^|]*) \|",
                           _readme(), re.M))
    assert set(rows) == {k.name for k in PC}, (
        sorted(set(rows) ^ {k.name for k in PC}))
    assert len(PC) == 54
    units = {"MiB": 1 << 20}
    for key in PC:
        shown, want = rows[key.name].strip(), key.default
        if isinstance(want, bool):
            assert shown == str(want), (key.name, shown)
        elif isinstance(want, (int, float)):
            m = re.fullmatch(r"(-?[0-9.]+)(?: (\w+))?", shown)
            assert m, (key.name, shown)
            got = float(m.group(1)) * units.get(m.group(2), 1)
            assert got == want, (key.name, shown, want)
        else:
            assert shown.strip('"') == want, (key.name, shown)


def test_readme_states_no_timing_of_its_own():
    """Speed lives in ``PERF.md`` and the ledger, measured on the chip.
    The README may point there; it may not carry a rate or a latency
    reading (they were CPU timings under device metrics' names until
    PR 31), nor the scripts and artifacts that rendered them."""
    import re

    text = _readme()
    reading = re.compile(
        r"\d[\d.,]*\s?[KM]?\s?(req/s|ops/s|creates/s|installs/s|"
        r"decisions/s|ms\b|K/s)")
    hits = [ln for ln in text.splitlines()
            if reading.search(ln) and "PERF.md" not in ln]
    assert not hits, hits
    for gone in ("render_perf", "bench.py", "BENCH_FULL", "BENCH_LATENCY",
                 "BENCH_WIRE", "ENGINE_SHARDS", "PIPELINE_WORKER",
                 "--pipeline", "--engine-shards"):
        assert gone not in text, gone
    assert "PERF.md" in text and "PERF_LEDGER.jsonl" in text
    assert "benchmarks/run.py" in text


def test_server_no_longer_takes_engine_shards(capsys):
    """``--engine-shards`` left the server's command line with the
    lanes: argparse refuses it by name."""
    from gigapaxos_tpu.server import main
    with pytest.raises(SystemExit) as ei:
        main(["--config", "x.properties", "--id", "0",
              "--engine-shards", "4"])
    assert ei.value.code == 2
    assert "--engine-shards" in capsys.readouterr().err
