"""Cluster tracing plane (PR 5 tentpole): cross-node trace
reconstruction over a real 3-node in-process cluster, deterministic
sampling, age-based ring eviction + orphan accounting, and the
slow-request log."""

import time

import pytest

pytestmark = pytest.mark.smoke  # <60s fast-signal subset (runs ~1s)

from gigapaxos_tpu.paxos.client import PaxosClient
from gigapaxos_tpu.paxos.packets import Request, group_key
from gigapaxos_tpu.paxos.paxosconfig import PC
from gigapaxos_tpu.utils.config import Config
from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI

from tests.conftest import tscale
from tests.test_e2e import make_cluster, shutdown


def _forwarded_name(entry: int, n: int = 3) -> str:
    """A group name whose deterministic initial coordinator is NOT the
    entry node — so the trace crosses entry -> coordinator -> quorum."""
    for k in range(64):
        name = f"ct-{k}"
        if group_key(name) % n != entry:
            return name
    raise AssertionError("unreachable")


@pytest.mark.parametrize("backend", ["native", "columnar"])
def test_cluster_breakdown_stitches_cross_node_trace(tmp_path, backend):
    """A sampled request through a 3-node cluster yields a stitched
    cluster_breakdown(trace_id): entry recv/fwd, coordinator prop +
    accept fan-out, quorum acc on >= majority nodes, dec, commit
    fan-out, exec on every replica — with monotonic causality and
    non-negative network hops.  Both engines: the columnar dec/acc
    stamp sites live on different handler paths than the fused native
    ones (a `sel`-shadowing bug on the columnar path got past a
    native-only version of this test)."""
    Config.set(PC.TRACE_SAMPLE, 1.0)
    RI.clear()
    nodes, addr_map = make_cluster(tmp_path, backend=backend)
    cli = None
    try:
        # client connects to node 0 first -> entry node is 0
        name = _forwarded_name(entry=0)
        for nd in nodes:
            assert nd.create_group(name, (0, 1, 2))
        cli = PaxosClient([addr_map[i] for i in range(3)],
                          timeout=tscale(10))
        r = cli.send_request(name, b"trace-me")
        assert r.status == 0
        rid = r.req_id

        need = {"recv", "fwd", "prop", "acc.tx", "acc", "dec",
                "com.tx", "exec"}
        deadline = time.time() + tscale(8)
        bd = None
        while time.time() < deadline:
            bd = RI.cluster_breakdown(rid)
            stages = {p["stage"] for p in bd["path"]}
            execs = {p["node"] for p in bd["path"]
                     if p["stage"] == "exec"}
            if need <= stages and len(execs) == 3:
                break
            time.sleep(0.05)
        stages = {p["stage"] for p in bd["path"]}
        assert need <= stages, stages
        assert bd["trace_id"] == rid
        assert bd["total_s"] > 0

        # monotonic causality over the merged path
        ts = [p["t_ms"] for p in bd["path"]]
        assert ts == sorted(ts)
        by_stage = {}
        for p in bd["path"]:
            by_stage.setdefault(p["stage"], []).append(p)
        coord = group_key(name) % 3
        assert by_stage["prop"][0]["node"] == coord
        assert by_stage["recv"][0]["node"] == 0
        # entry stamp precedes the coordinator grant precedes quorum
        assert by_stage["recv"][0]["t_ms"] <= by_stage["prop"][0]["t_ms"]
        assert by_stage["prop"][0]["t_ms"] <= by_stage["dec"][0]["t_ms"]
        accs = {p["node"] for p in by_stage["acc"]}
        assert len(accs) >= 2, f"quorum not visible: {accs}"
        assert {p["node"] for p in by_stage["exec"]} == {0, 1, 2}

        # network hops: every recorded hop is non-negative and the
        # accept fan-out hop reaches a non-coordinator node
        assert bd["hops"], "no hops stitched"
        assert all(h["s"] >= 0 for h in bd["hops"])
        acc_hops = [h for h in bd["hops"]
                    if h["stage"] == "acc.tx->acc"]
        assert acc_hops and all(h["from"] == coord for h in acc_hops)

        # per-node span breakdown: every node shows pipeline stages;
        # the WAL span (stamped node-less by the logger) is resolved
        # through its wave to a real node
        for n in (0, 1, 2):
            assert "w.process" in bd["nodes"][n], bd["nodes"]
        assert -1 not in bd["nodes"] or \
            not bd["nodes"][-1], "unresolved spans"
        assert any("wal" in kinds for kinds in bd["nodes"].values())

        # export/merge path (what /cluster/traces does): splitting the
        # ring into per-node exports and merging reproduces the story
        ex = RI.export_trace(rid)
        per_node = []
        for n in (0, 1, 2):
            per_node.append({
                "trace_id": rid,
                "events": [e for e in ex["events"] if e[1] == n],
                "spans": [s for s in ex["spans"]
                          if s.get("node") == n]})
        bd2 = RI.cluster_breakdown(rid, per_node)
        assert {p["stage"] for p in bd2["path"]} == stages
        assert bd2["total_s"] == pytest.approx(bd["total_s"])
        cli.close()
        cli = None
    finally:
        if cli is not None:
            cli.close()
        shutdown(nodes)


def test_unsampled_requests_leave_zero_ring_entries(tmp_path):
    """PC.TRACE_SAMPLE=0 (the default): tracing stays disabled — a
    request leaves NO ring entries and no spans (the
    hot path pays one attribute check per hook)."""
    RI.reset()
    nodes, addr_map = make_cluster(tmp_path, backend="native")
    cli = None
    try:
        for nd in nodes:
            assert nd.create_group("quiet", (0, 1, 2))
        assert RI.enabled is False
        cli = PaxosClient([addr_map[i] for i in range(3)],
                          timeout=tscale(10))
        r = cli.send_request("quiet", b"x")
        assert r.status == 0
        time.sleep(0.2)
        assert RI.trace(r.req_id) == []
        assert len(RI._ring) == 0
        assert len(RI._spans) == 0
        ex = RI.export_trace(r.req_id)
        assert ex["events"] == [] and ex["spans"] == []
        bd = RI.cluster_breakdown(r.req_id)
        assert bd["total_s"] is None and bd["path"] == []
    finally:
        if cli is not None:
            cli.close()
        shutdown(nodes)


@pytest.mark.smoke
def test_sampling_is_deterministic_and_proportional():
    """The sampling verdict is a pure function of the trace id (every
    node agrees with zero propagated bytes) and hits ~the configured
    rate; the FLAG_SAMPLED force bit overrides a negative verdict."""
    RI.enabled = True
    RI.configure(sample_rate=0.25)
    verdicts = [RI.sampled(i) for i in range(8000)]
    assert verdicts == [RI.sampled(i) for i in range(8000)]
    frac = sum(verdicts) / len(verdicts)
    assert 0.2 < frac < 0.3, frac
    neg = verdicts.index(False)
    assert RI.sampled(neg, force=True)
    # record() filters by the same verdict
    RI.clear()
    for i in range(100):
        RI.record(i, "recv", 0)
    assert len(RI._ring) == sum(verdicts[:100])
    # rate 0 records nothing without force; force still records
    RI.configure(sample_rate=0.0)
    RI.clear()
    RI.record(7, "recv", 0)
    assert len(RI._ring) == 0
    RI.record(7, "recv", 0, force=True)
    assert len(RI._ring) == 1


@pytest.mark.smoke
def test_age_eviction_and_orphaned_spans():
    """Satellite: size-only eviction let spans from long-dead waves
    linger and the begun/ended pairing drift.  Age eviction drops old
    events/spans, and a span whose end never arrives becomes an
    explicit `orphaned` count instead of permanent pairing skew."""
    RI.reset()
    RI.enabled = True
    RI.configure(max_age_s=60.0)
    RI.set_wave(RI.next_wave())
    RI.record(1, "recv", 0)
    done = RI.span_begin("engine", node=0)
    RI.span_end(done)
    leaked = RI.span_begin("decode", node=0)
    assert leaked is not None  # never ended: the lost-end case
    st = RI.span_stats()
    assert st["begun"] == 2 and st["ended"] == 1
    assert st["open"] == 1 and st["orphaned"] == 0

    # jump past the horizon: everything ages out, the open span
    # becomes orphaned
    evicted = RI.evict(now=time.monotonic() + 120.0)
    assert evicted == 3  # 1 ring event + 1 completed span + 1 orphan
    assert len(RI._ring) == 0 and len(RI._spans) == 0
    st = RI.span_stats()
    assert st["orphaned"] == 1 and st["open"] == 0
    assert st["kinds"] == {}

    # a LATE end on an orphan-evicted span undoes the orphan verdict
    # (the end arrived after all — a permanent false "lost end" would
    # never clear) and keeps the completed record
    RI.span_end(leaked)
    st = RI.span_stats()
    assert st["orphaned"] == 0 and st["ended"] == 2
    assert len(RI._spans) == 1

    # max_age_s=0 disables age eviction entirely
    RI.configure(max_age_s=0.0)
    RI.record(2, "recv", 0)
    assert RI.evict(now=time.monotonic() + 1e6) == 0
    assert len(RI._ring) == 1


@pytest.mark.smoke
def test_slow_trace_log_topk():
    """The slow-request log keeps the top-K sampled traces over the
    threshold, slowest first, with monotone seqs for the dumper."""
    RI.reset()
    RI.enabled = True
    RI.configure(slow_threshold_s=0.010, slow_k=3)
    RI.note_done(1, 0.005)          # under threshold: ignored
    for tid, total in ((2, 0.020), (3, 0.050), (4, 0.030),
                       (5, 0.040)):
        RI.note_done(tid, total)
    slow = RI.slow_traces()
    assert [s["trace_id"] for s in slow] == [3, 5, 4]  # top-3 desc
    assert slow[0]["total_s"] == pytest.approx(0.050)
    seqs = [s["seq"] for s in slow]
    assert len(set(seqs)) == 3
    # disabled threshold: nothing recorded
    RI.configure(slow_threshold_s=0.0)
    RI.clear()
    RI.note_done(9, 99.0)
    assert RI.slow_traces() == []


@pytest.mark.smoke
def test_wire_flag_sampled_is_a_known_bit():
    """The client-forced trace bit must not collide with the wire stop
    bit or the node-internal NOOP/MISSING markers (MIGRATING: old
    nodes ignore it; the flags byte always existed)."""
    from gigapaxos_tpu.paxos import manager
    assert Request.FLAG_SAMPLED == 8
    assert Request.FLAG_SAMPLED != Request.FLAG_STOP
    assert Request.FLAG_SAMPLED not in (manager.FLAG_NOOP,
                                        manager.FLAG_MISSING)
    assert manager.FLAG_SAMPLED == Request.FLAG_SAMPLED
