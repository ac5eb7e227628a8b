"""Metrics plane: histogram percentiles vs a sorted-sample oracle,
mergeable snapshots, windowed rates, snapshot round-trips under
concurrent writers, structured node metrics(), and pipeline-stage span
begin/end pairing across the 3-stage worker."""

import json
import threading
import time

import numpy as np

from gigapaxos_tpu.paxos.client import PaxosClient
from gigapaxos_tpu.paxos.paxosconfig import PC
from gigapaxos_tpu.utils.config import Config
from gigapaxos_tpu.utils.instrument import RequestInstrumenter
from gigapaxos_tpu.utils.profiler import (DelayProfiler, _Hist, _Rate,
                                          hist_percentile,
                                          merge_hist_snapshots)
from tests.conftest import tscale
from tests.test_e2e import make_cluster, shutdown


def test_histogram_percentiles_vs_oracle():
    """Log-bucketed percentiles track a sorted-sample oracle within the
    bucket ladder's relative error bound (2^(1/4) buckets, geometric
    midpoints: ≤ ~10%; assert 15% for slack)."""
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=-6.0, sigma=1.2, size=20_000)
    h = _Hist()
    for s in samples:
        h.record(float(s))
    assert h.count == len(samples)
    assert abs(h.sum - samples.sum()) < 1e-6 * samples.sum() + 1e-9
    for q in (50, 90, 99, 99.9):
        est = h.percentile(q)
        exact = float(np.percentile(samples, q))
        assert abs(est - exact) <= 0.15 * exact, (q, est, exact)
    # clamped to observed extremes
    assert h.percentile(0.001) >= h.min
    assert h.percentile(99.999) <= h.max


def test_histogram_tiny_and_edge_samples():
    h = _Hist()
    h.record(0.0)        # below BASE -> bucket 0
    h.record(1e-9)
    h.record(1e6)        # beyond the ladder -> clamped top bucket
    assert h.count == 3
    assert h.percentile(50) is not None
    assert _Hist().percentile(50) is None  # empty -> None


def test_histogram_snapshot_merge():
    """Snapshots merge bucket-wise: merging two halves reproduces the
    full histogram's percentiles exactly (same bucket counts)."""
    rng = np.random.default_rng(11)
    samples = rng.lognormal(mean=-7.0, sigma=1.5, size=10_000)
    full, h1, h2 = _Hist(), _Hist(), _Hist()
    for s in samples:
        full.record(float(s))
    for s in samples[:5000]:
        h1.record(float(s))
    for s in samples[5000:]:
        h2.record(float(s))
    merged = merge_hist_snapshots(h1.snapshot(), h2.snapshot())
    assert merged["count"] == full.count
    for q in (50, 90, 99):
        assert abs(hist_percentile(merged, q)
                   - full.percentile(q)) < 1e-12
    # merged snapshots survive a JSON round trip and stay mergeable
    again = merge_hist_snapshots(json.loads(json.dumps(merged)),
                                 _Hist().snapshot())
    assert again["count"] == full.count


def test_rate_is_windowed_not_lifetime():
    """The satellite fix: per_sec measures the sliding window, so a
    stopped stream reads ~0 instead of decaying toward the lifetime
    average; the cumulative count is kept separately."""
    r = _Rate(window_s=0.4, nslots=8)
    for _ in range(100):
        r.update()
    assert r.count == 100
    assert r.per_sec > 100  # 100 events landed well inside the window
    time.sleep(0.6)  # > window: every slot expires
    assert r.per_sec < 1.0, "rate still reflects expired events"
    assert r.count == 100  # cumulative count unaffected
    r.update(10)
    assert r.count == 110
    assert r.per_sec > 1.0


def test_snapshot_under_concurrent_writers():
    """snapshot() is consistent and JSON-serializable while writer
    threads hammer every update path; final counts add up exactly."""
    DelayProfiler.clear()
    N, WRITES = 4, 2000
    t0 = time.monotonic() - 0.002

    def writer(k):
        for _ in range(WRITES):
            DelayProfiler.update_delay(f"d{k % 2}", t0)
            DelayProfiler.update_rate("r")
            DelayProfiler.update_total("w", t0)

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(N)]
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads):
        snap = DelayProfiler.snapshot()
        json.dumps(snap)  # mid-flight snapshots serialize cleanly
    for t in threads:
        t.join()
    final = DelayProfiler.snapshot()
    assert sum(h["count"]
               for h in final["histograms"].values()) == N * WRITES
    assert final["rates"]["r"]["count"] == N * WRITES
    assert final["totals"]["w"]["calls"] == N * WRITES
    assert json.loads(json.dumps(final))["delays"]["d0"]["count"] > 0


def test_stats_dumper_appends_and_stops(tmp_path):
    """The periodic dumper appends parseable JSONL snapshots and its
    stop() returns promptly (regression: an attribute named _stop
    shadowed threading.Thread's internal _stop and broke join())."""
    from gigapaxos_tpu.utils.statsdump import StatsDumper
    path = str(tmp_path / "stats.jsonl")
    d = StatsDumper(lambda: ("line", {"n": 1}), 0.05, path)
    d.start()
    deadline = time.time() + tscale(5)
    while time.time() < deadline:
        try:
            if len(open(path).readlines()) >= 2:
                break
        except OSError:
            pass
        time.sleep(0.05)
    t0 = time.time()
    d.stop()
    assert time.time() - t0 < 3.0
    assert not d.is_alive()
    recs = [json.loads(ln) for ln in open(path)]
    assert len(recs) >= 2 and recs[0]["n"] == 1 and "ts" in recs[0]


def test_node_metrics_structured(tmp_path):
    """PaxosNode.metrics() replaces string-scraping: nested dict with
    counters/engine/net/profiler/spans; stats() renders from it."""
    nodes, addr_map = make_cluster(tmp_path, backend="native")
    try:
        for nd in nodes:
            assert nd.create_group("met", (0, 1, 2))
        cli = PaxosClient([addr_map[i] for i in range(3)],
                          timeout=tscale(10))
        for k in range(5):
            assert cli.send_request("met", f"m{k}".encode()).status == 0
        cli.close()
        ms = [nd.metrics() for nd in nodes]
        assert sum(m["counters"]["decided"] for m in ms) >= 5
        m = ms[0]
        assert {"counters", "engine", "net", "profiler",
                "spans"} <= set(m)
        assert {"submit_s", "collect_s", "overlap_s"} <= set(m["engine"])
        assert isinstance(m["net"]["tx_frames"], int)
        assert {"congestion", "peer_gone", "write_error",
                "test"} <= set(m["net"]["drops"])
        assert "node.batch" in m["profiler"]["histograms"]
        json.dumps(m, default=str)  # the /stats payload
        line = nodes[0].stats()
        assert "exec=" in line and "net[" in line and "recon=" in line
    finally:
        shutdown(nodes)


def test_spans_pair_across_3stage_worker(tmp_path):
    """With the pipelined worker + tracing on: w.decode|w.process|w.emit
    (and wal) spans are stamped per wave, begin/end counts pair up, and a
    traced request decomposes into its stages via the instrument API
    (the acceptance-criteria decomposition)."""
    Config.set(PC.PIPELINE_WORKER, True)
    Config.set(PC.TRACE_REQUESTS, True)
    RequestInstrumenter.clear()
    nodes, addr_map = make_cluster(tmp_path, backend="native")
    try:
        for nd in nodes:
            assert nd.create_group("sp", (0, 1, 2))
        cli = PaxosClient([addr_map[i] for i in range(3)],
                          timeout=tscale(10))
        rid = None
        for k in range(5):
            r = cli.send_request("sp", f"s{k}".encode())
            assert r.status == 0
            rid = r.req_id
        cli.close()
        deadline = time.time() + tscale(5)
        bd = {}
        while time.time() < deadline:
            bd = RequestInstrumenter.request_breakdown(rid)
            st = RequestInstrumenter.span_stats()
            if {"w.decode", "w.process", "w.emit"} <= set(bd):
                break
            time.sleep(0.05)
        # the request decomposes into its pipeline stages
        assert {"w.decode", "w.process", "w.emit"} <= set(bd), bd
        assert "wal" in bd, bd  # the append slice (lock, write, sync)
        assert all(v >= 0 for v in bd.values())
        st = RequestInstrumenter.span_stats()
        # every begin has its end, but for the spans in flight right now
        # (the waits are spans too: each live worker thread holds one)
        assert st["begun"] - st["ended"] == st["open"] <= 9, st
        assert st["orphaned"] == 0 and st["dropped"] == 0, st
        assert st["kinds"]["w.process"]["count"] >= 1
        # every completed span is well-formed and wave-stamped
        for sp in RequestInstrumenter.request_spans(rid):
            assert sp["t1"] >= sp["t0"] and sp["wave"] > 0
        # span aggregates surface in the node metrics snapshot
        assert "w.process" in nodes[0].metrics()["spans"]["kinds"]
    finally:
        RequestInstrumenter.enabled = False
        RequestInstrumenter.clear()
        shutdown(nodes)


def test_columnar_wave_spans():
    """The columnar backend's submit/collect halves stamp eng.submit /
    eng.collect spans carrying lane/chunk counts and the submit->collect
    overlap (the device-vs-host split of a wave)."""
    from gigapaxos_tpu.paxos.backend import ColumnarBackend
    RequestInstrumenter.enabled = True
    RequestInstrumenter.clear()
    try:
        be = ColumnarBackend(16, window=4)
        rows = np.arange(4, dtype=np.int32)
        be.create(rows, np.full(4, 3, np.int32),
                  np.zeros(4, np.int32), np.zeros(4, np.int32),
                  np.ones(4, bool))
        RequestInstrumenter.set_wave(RequestInstrumenter.next_wave())
        wave = be.accept_submit(rows, np.zeros(4, np.int32),
                                np.ones(4, np.int32),
                                np.arange(1, 5).astype(np.uint64))
        wave.collect()
        wid = RequestInstrumenter.current_wave()
        spans = RequestInstrumenter.wave_spans(wid)
        kinds = [s["kind"] for s in spans]
        assert "eng.submit" in kinds and "eng.collect" in kinds, kinds
        sub = next(s for s in spans if s["kind"] == "eng.submit")
        col = next(s for s in spans if s["kind"] == "eng.collect")
        assert sub["lanes"] == 4 and sub["chunks"] >= 1
        assert sub["kernel"] == be._kpfx + "accept_p"
        assert sub["launched"] == sub["bucket"] * sub["chunks"] >= 4
        assert col["overlap_s"] >= 0 and col["wave"] == wid
    finally:
        RequestInstrumenter.enabled = False
        RequestInstrumenter.clear()
