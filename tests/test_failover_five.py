"""Five replicas on the columnar engine, one crash-stopped: the deployment
of the benchmark's ``failover-5r-100k`` at a size a test holds.  A closed
loop with ring retransmits (``benchmarks/loadgen_failover.py``) runs through
the kill, or on both sides of it, and what the clients were answered and
what the four survivors hold is compared with the plain reference
(``benchmarks/reference/failover_rsm.py``).  Every wait is on the event
meant (installs reached, no election open, every request answered), never
on a wall-clock window (ROADMAP D12)."""

import asyncio
import time

import pytest

from benchmarks import loadgen, loadgen_failover
from benchmarks.drivers import failover as drv
from benchmarks.reference import failover_rsm
from gigapaxos_tpu.paxos.interfaces import CounterApp
from gigapaxos_tpu.paxos.paxosconfig import PC
from gigapaxos_tpu.testing.harness import PaxosEmulation
from gigapaxos_tpu.utils.config import Config
from gigapaxos_tpu.utils.engineledger import EngineLedger

from tests.conftest import tscale

R, LIVE, ACTIVE, DEPTH = 5, 400, 60, 16
CLIENT = 1 << 20


def boot(path, capacity=1024, n_groups=LIVE):
    return PaxosEmulation(
        str(path), n_nodes=R, n_groups=n_groups, group_size=R,
        backend="columnar", app_cls=CounterApp, capacity=capacity,
        ping_interval_s=0.1, failure_timeout_s=tscale(0.8))


def drive(emu, names, seconds, client, **kw):
    servers = [emu.addr_map[i] for i in sorted(emu.addr_map)]
    return asyncio.run(loadgen_failover.run_closed_loop_kill(
        servers, names, seconds, DEPTH, client_id=client,
        retransmit_after_s=tscale(0.3), drain_s=tscale(40), **kw))


def wait_taken_over(emu, victim, n_led):
    """Until the survivors have installed themselves for every group the
    victim led and no election is open (bounded)."""
    alive = [nd for i, nd in emu.nodes.items() if i != victim]
    deadline = time.monotonic() + tscale(60)
    while time.monotonic() < deadline and (
            sum(nd.n_installs for nd in alive) < n_led
            or any(nd.open_elections for nd in alive)):
        time.sleep(0.02)
    assert sum(nd.n_installs for nd in alive) >= n_led, (
        [nd.n_installs for nd in alive],
        [nd.open_elections for nd in alive])
    return alive


def compare(emu, victim, names, results):
    """The run against the plain reference, as the benchmark's driver
    compares a window."""
    led = drv.led_by(LIVE, victim, R)
    alive = wait_taken_over(emu, victim, len(led))
    streams = [drv.stream_of(names, r) for r in results]
    acked = {}
    for st, r in zip(streams, results):
        for (g, _rid), t in zip(st, r["t_recv"]):
            acked[g] = acked.get(g, 0) + int(t >= 0)
    states, spurious = drv.survivor_states(alive, set(names), acked,
                                           tscale(30))
    ballots, cbals = drv.coordinator_views(alive, led)
    survivors = [i for i in range(R) if i != victim]
    cks = failover_rsm.check(streams, [drv.parsed(r) for r in results],
                             states, ballots, cbals, survivors)
    assert spurious == 0
    return {n: v for n, v, _lim in cks}, alive


@pytest.mark.parametrize("victim", [0, 2, 4])
@pytest.mark.parametrize("when", ["in_flight", "idle"])
def test_one_of_five_killed_under_a_closed_loop(tmp_path, victim, when):
    emu = boot(tmp_path)
    try:
        names = loadgen.plan_groups(7 + victim, LIVE, ACTIVE)
        for nd in emu.nodes.values():
            assert nd.n_elections_started == 0
        if when == "in_flight":
            # killed with requests outstanding on the groups it led; the
            # generator returns when every request has its answer
            res = drive(emu, names, tscale(1.5), CLIENT + 1,
                        kill=lambda: emu.kill(victim),
                        kill_at_s=tscale(0.4))
            assert res["t_kill"] is not None and res["n_resent"] > 0
            results = [res]
        else:
            # idle at the kill: one stream before it, one after the
            # survivors have taken its groups over
            before = drive(emu, names, tscale(0.5), CLIENT + 1)
            emu.kill(victim)
            wait_taken_over(emu, victim,
                            len(drv.led_by(LIVE, victim, R)))
            after = drive(emu, names, tscale(0.5), CLIENT + 2)
            assert after["closed_at"][victim] is not None
            results = [before, after]
        got, alive = compare(emu, victim, names, results)
        assert not any(got.values()), got
        # the next in ring ran the elections, batched, and won them all
        nxt = emu.nodes[(victim + 1) % R]
        n_led = len(drv.led_by(LIVE, victim, R))
        assert nxt.n_elections_started >= n_led > 64
        assert nxt.n_elections_won == nxt.n_installs >= n_led
        assert all(nd.n_elections_preempted == 0 for nd in alive)
        c = nxt.metrics(include_profiler=False)["counters"]
        assert c["elections_won"] == nxt.n_elections_won
        assert c["elections_started"] == nxt.n_elections_started
        assert c["elections_preempted"] == 0
    finally:
        emu.stop()


def test_boot_loads_the_election_programs_at_every_bucket(tmp_path):
    """A node with peers traces ``prepare``, ``install_coordinator`` and
    the re-proposal wave ``propose_accept_self_p`` at boot, at every step
    of the bucket ladder, so a takeover (a batch of the victim's rows, then
    single rows, then whatever was parked in one wave) compiles and
    retraces nothing.  Found on the chip (PR 34): a flush of more than
    eight parked requests traced ``propose_accept_self_p`` at a bucket no
    traffic had used, inside the outage."""
    Config.set(PC.ENGINE_MESH, "off")
    before = {k: EngineLedger.kernels().get(k, {}).get("compiles", 0)
              for k in ("prepare", "install_coordinator",
                        "propose_accept_self_p")}
    emu = boot(tmp_path, capacity=1040)  # a state shape of this test's own
    try:
        booted = EngineLedger.kernels()
        for k in before:
            assert booted[k]["compiles"] - before[k] == 4, (k, booted[k])
            assert booted[k]["hot"]
        names = loadgen.plan_groups(3, LIVE, ACTIVE)
        res = drive(emu, names, tscale(1.5), CLIENT + 1,
                    kill=lambda: emu.kill(1), kill_at_s=tscale(0.4))
        got, _alive = compare(emu, 1, names, [res])
        assert not any(got.values()), got
        after = EngineLedger.kernels()
        for k in before:
            assert after[k]["compiles"] == booted[k]["compiles"], k
            assert after[k]["retraces"] == 0
    finally:
        emu.stop()


def test_a_node_alone_loads_no_election_program(tmp_path):
    before = {k: EngineLedger.kernels().get(k, {}).get("compiles", 0)
              for k in ("prepare", "install_coordinator")}
    Config.set(PC.ENGINE_MESH, "off")
    emu = PaxosEmulation(str(tmp_path), n_nodes=1, n_groups=4,
                         group_size=1, backend="columnar", capacity=1048)
    try:
        now = EngineLedger.kernels()
        assert all(now.get(k, {}).get("compiles", 0) == before[k]
                   for k in before)
    finally:
        emu.stop()


def test_election_spans_reach_the_ring(tmp_path):
    """With spans on, a takeover leaves ``gp.fo.*`` and ``gp.eng.prepare``
    / ``gp.eng.install`` in the ring with the attributes the benchmark's
    readers take, and their totals in the profiler."""
    from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
    from gigapaxos_tpu.utils.profiler import DelayProfiler
    emu = boot(tmp_path)
    try:
        RI.enabled = True
        victim = 3
        n_led = len(drv.led_by(LIVE, victim, R))
        emu.kill(victim)
        wait_taken_over(emu, victim, n_led)

        def all_in_the_ring():
            # a span reaches the ring, and its sum the profiler, when it
            # ENDS: every survivor's suspicion (the scan runs inside it),
            # the installs for every group the victim led, and the reply
            # frames around them (an install nests in the reply that
            # brought its quorum) -- not when a counter they bump moves
            tot = DelayProfiler.totals()
            return (sum(s["kind"] == "fo.suspect"
                        for s in RI.spans_snapshot()) >= R - 1
                    and tot.get("fo.install", (0, 0, 0))[2] >= n_led
                    and tot.get("fo.reply", (0, 0, 0))[2] >= 3 * n_led
                    and tot.get("fo.prepare", (0, 0, 0))[2]
                    >= (R - 1) * n_led)
        deadline = time.monotonic() + tscale(20)
        while time.monotonic() < deadline and not all_in_the_ring():
            time.sleep(0.02)
        spans = RI.spans_snapshot()
        by = {}
        for s in spans:
            by.setdefault(s["kind"], []).append(s)
        assert {s["dead"] for s in by["fo.suspect"]} == {victim}
        assert len(by["fo.suspect"]) == R - 1
        assert sum(s["items"] for s in by["fo.elect_start"]) >= n_led
        assert sum(s["lanes"] for s in by["fo.prepare"]) >= (R - 1) * n_led
        assert sum(s["lanes"] for s in by["fo.reply"]) >= 3 * n_led
        assert all("slow_rows" in s for s in by["fo.reply"])
        assert sum(s["items"] for s in by["fo.install"]) == n_led
        assert all("carried" in s for s in by["fo.install"])
        for kind in ("eng.prepare", "eng.install"):
            for s in by[kind]:
                assert s["lanes"] > 0 and s["chunks"] == 1
                assert s["bucket"] in (8, 64, 512, 4096)
                assert s["program"] and "bytes" in s
        assert all(s["bytes"] == 0 for s in by["eng.install"])
        assert all(s["bytes"] == s["bucket"] * (9 + 16 * 16)
                   for s in by["eng.prepare"])
        tot = DelayProfiler.totals()
        for tag in ("fo.scan", "fo.elect_start", "fo.prepare", "fo.reply",
                    "fo.install", "eng.prepare", "eng.install"):
            assert tot[tag][1] > 0, tag
    finally:
        emu.stop()
