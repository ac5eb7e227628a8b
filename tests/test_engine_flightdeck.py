"""Engine flight deck (PR 18): the forced-retrace alarm (ledger count +
flight-recorder trigger, exactly once per new signature), the
``GET /engine`` / ``/engine/kernels`` schema with exact slab-memory
math, the ``gp_engine_*`` prometheus families, and the
``/cluster/engine`` fan-out merge over real per-node stats listeners."""

import asyncio
import json
import time
import urllib.request

import numpy as np
import pytest

from gigapaxos_tpu.paxos.interfaces import NoopApp
from gigapaxos_tpu.paxos.manager import PaxosNode
from gigapaxos_tpu.paxos.paxosconfig import PC
from gigapaxos_tpu.testing.harness import free_ports
from gigapaxos_tpu.utils.config import Config
from gigapaxos_tpu.utils.engineledger import EngineLedger

from tests.conftest import tscale
from tests.test_e2e import make_cluster, shutdown
from tests.test_metrics_format import _get, _validate_exposition

# every /engine scrape must carry at least these top-level sections
ENGINE_KEYS = {"node", "platform", "engine_mesh",
               "ledger", "cache", "memory", "balance", "waves"}
LEDGER_KEYS = {"kernels", "compiles", "retraces", "compile_s",
               "cache_hits", "cache_misses", "monitoring", "warmed"}
# plane grouping of the columnar slab accounting view
PLANE_KEYS = {"groups", "acc", "dec", "prop"}


def _columnar_node(tmp_path):
    Config.set(PC.STATS_PORT, 0)
    addr = {0: ("127.0.0.1", free_ports(1)[0])}
    node = PaxosNode(0, addr, NoopApp(), str(tmp_path),
                     backend="columnar", capacity=64, window=4)
    node.start()
    return node


def _kname(node, base):
    """Ledger name of a kernel on this backend: the conftest mesh (8
    virtual CPU devices) routes the columnar engine through
    meshkernels, whose ledger entries carry the ``mesh.`` prefix."""
    return ("mesh." if node.backend.engine_mesh != "off" else "") + base


# --------------------------------------------------------------------------
# forced retrace: ledger counter + blackbox trigger, exactly once
# --------------------------------------------------------------------------


def test_forced_retrace_fires_ledger_and_trigger(tmp_path):
    """A static-shape excursion after warm-up (a batch wider than any
    bucket the ladder compiled) must count exactly one retrace against
    the kernel, fire every registered trigger exactly once with the
    ``engine_retrace:<kernel>`` reason, and dump the flight recorder.
    The identical second call hits the jit cache: no new trace, no
    second alarm."""
    Config.set(PC.BLACKBOX_MB, 4)
    Config.set(PC.BLACKBOX_S, 0.0)  # keep slow-trace dumps out
    node = _columnar_node(tmp_path)
    calls = []
    try:
        assert node.blackbox is not None
        kn = _kname(node, "accept_p")
        base_dumps = node.blackbox.snapshot()["dumps"]
        led0 = EngineLedger.snapshot()
        assert led0["warmed"], "columnar boot must mark the ledger warm"
        assert EngineLedger.retraces(kn) == 0
        EngineLedger.add_trigger(calls.append)

        b = node.backend
        # width 17 is outside every bucket the 64-row warm-up compiled;
        # thread the returned state back (the jit donates its buffers)
        odd = b._dev(np.zeros((6, 17), np.int32))
        b.state, _ = b._k.accept_p(b.state, odd)

        assert EngineLedger.retraces(kn) == 1
        assert calls == [f"engine_retrace:{kn}"]
        # the node registered its blackbox trigger at boot
        # (PC.ENGINE_RETRACE_TRIGGER default-on); the dump runs on a
        # daemon thread, so poll
        deadline = time.time() + tscale(10)
        while time.time() < deadline:
            if node.blackbox.snapshot()["dumps"] > base_dumps:
                break
            time.sleep(0.05)
        assert node.blackbox.snapshot()["dumps"] == base_dumps + 1

        # same signature again: cached dispatch, wrapper never re-runs
        odd = b._dev(np.zeros((6, 17), np.int32))
        b.state, _ = b._k.accept_p(b.state, odd)
        assert EngineLedger.retraces(kn) == 1
        assert calls == [f"engine_retrace:{kn}"]
    finally:
        EngineLedger.remove_trigger(calls.append)
        node.stop()


def test_retrace_trigger_knob_off(tmp_path):
    """ENGINE_RETRACE_TRIGGER=0: the ledger still counts the retrace,
    but no flight-recorder dump fires."""
    Config.set(PC.BLACKBOX_MB, 4)
    Config.set(PC.BLACKBOX_S, 0.0)
    Config.set(PC.ENGINE_RETRACE_TRIGGER, 0)
    node = _columnar_node(tmp_path)
    try:
        kn = _kname(node, "accept_p")
        base_dumps = node.blackbox.snapshot()["dumps"]
        before = EngineLedger.retraces(kn)
        b = node.backend
        b.state, _ = b._k.accept_p(
            b.state, b._dev(np.zeros((6, 23), np.int32)))
        assert EngineLedger.retraces(kn) == before + 1
        time.sleep(tscale(0.3))
        assert node.blackbox.snapshot()["dumps"] == base_dumps
    finally:
        node.stop()


# --------------------------------------------------------------------------
# GET /engine + /engine/kernels schema, /metrics gp_engine_* families
# --------------------------------------------------------------------------


@pytest.mark.smoke
def test_engine_endpoints_schema(tmp_path):
    """Single columnar node: /engine carries the full flight-deck
    schema with EXACT slab memory math, /engine/kernels joins the
    per-kernel ledger rows with the HLO cost analysis, and the
    gp_engine_* families render on /metrics."""
    node = _columnar_node(tmp_path)
    try:
        port = node.stats_http.port

        st, body = _get(port, "/engine")
        assert st == 200
        d = json.loads(body)
        assert ENGINE_KEYS <= set(d), set(d)
        led = d["ledger"]
        assert LEDGER_KEYS <= set(led), set(led)
        assert led["kernels"] >= 1 and led["compiles"] >= led["kernels"]
        assert led["warmed"] is True
        assert isinstance(d["cache"], dict) and "active" in d["cache"]

        mem = d["memory"]
        assert set(mem["planes"]) == PLANE_KEYS
        # the accounting must be exact, not approximate: planes sum to
        # the slab total and the per-group rate divides it evenly
        assert sum(mem["planes"].values()) == mem["total_bytes"]
        assert mem["bytes_per_group"] * mem["capacity"] == \
            mem["total_bytes"]
        assert mem["capacity"] == 64 and mem["window"] == 4

        bal = d["balance"]
        assert bal["rows_active"] == 0  # no groups created yet
        assert "mesh" in bal
        assert {"submit_s", "collect_s", "overlap_s"} <= set(d["waves"])

        st, body = _get(port, "/engine/kernels")
        assert st == 200
        ks = json.loads(body)
        assert ks["node"] == 0
        assert ks["kernels"], "per-kernel ledger rows missing"
        for name, row in ks["kernels"].items():
            assert {"compiles", "retraces", "compile_s",
                    "hot"} <= set(row), (name, row)
        # the warm-up ladder kernels are marked hot (retrace-alarmed)
        kn = _kname(node, "accept_p")
        assert ks["kernels"][kn]["hot"] is True
        assert set(ks["costs"]) == {
            _kname(node, n) for n in
            ("propose_p", "accept_p", "accept_reply_p", "commit_p",
             "accept_commit_p", "request_reply_p", "node_wave_p")}
        for row in ks["costs"].values():
            assert {"flops", "bytes_accessed"} == set(row)

        st, body = _get(port, "/metrics")
        series = _validate_exposition(body.decode())
        assert f'gp_engine_compiles_total{{kernel="{kn}"}}' in series
        assert f'gp_engine_retraces_total{{kernel="{kn}"}}' in series
        assert "gp_engine_compile_seconds_total" in series
        assert "gp_engine_cache_active" in series
        assert series['gp_engine_slab_bytes{plane="acc"}'] == \
            mem["planes"]["acc"]
        assert series["gp_engine_slab_bytes_total"] == \
            mem["total_bytes"]
        assert series["gp_engine_bytes_per_group"] == \
            mem["bytes_per_group"]
        assert series["gp_engine_capacity_rows"] == 64
        assert series["gp_engine_rows_active"] == 0
    finally:
        node.stop()


# --------------------------------------------------------------------------
# /cluster/engine fan-out merge
# --------------------------------------------------------------------------


def test_cluster_engine_fanout(tmp_path):
    """scrape /engine off every node's real stats listener and merge:
    dead peers read up=0, ledger counters sum across the fleet, and
    per-node detail rides along under ``nodes``."""
    Config.set(PC.STATS_PORT, 0)
    nodes, _addr_map = make_cluster(tmp_path, backend="native")
    try:
        for nd in nodes:
            assert nd.create_group("ce", (0, 1, 2))
        peers = {i: ("127.0.0.1", nd.stats_http.port)
                 for i, nd in enumerate(nodes)}
        peers[9] = ("127.0.0.1", 1)  # dead peer must not break merge

        from gigapaxos_tpu.net.cluster import (merge_cluster_engine,
                                               scrape_cluster)

        async def body():
            per_node = await scrape_cluster(peers, "/engine",
                                            timeout=tscale(5))
            merged = merge_cluster_engine(per_node)
            assert merged["cluster"]["nodes"][9] == 0
            assert all(merged["cluster"]["nodes"][i] == 1
                       for i in range(3))
            assert set(merged["nodes"]) == {0, 1, 2}
            # the ledger is process-global, so the fleet sum is exactly
            # the per-node sums (all three scrapes see the same ledger)
            want = sum(per_node[i]["ledger"]["compiles"]
                       for i in range(3))
            assert merged["ledger"]["compiles"] == want
            assert merged["ledger"]["retraces"] == sum(
                per_node[i]["ledger"]["retraces"] for i in range(3))
            for i in range(3):
                assert LEDGER_KEYS <= set(per_node[i]["ledger"])
                assert "waves" in per_node[i]
            # native backend: no device slabs, so /engine answers with
            # memory null and the merge never invents an estimate
            assert per_node[0]["memory"] is None
            assert "max_groups_estimate" not in \
                (merged.get("memory") or {})
        asyncio.run(body())
    finally:
        shutdown(nodes)
