"""Driver of the failover configurations: R ``PaxosNode`` replicas of every
group in this process over real loopback sockets, the closed-loop generator
of ``loadgen_failover.py`` in front of them, and one node crash-stopped a
stated share into the window (``PaxosEmulation.kill``), measured from the
client's side.

Boot, creates, the warm-up rule, the tracer and the counters read as
differences around the window are ``drivers/served.py``'s (imported, not
copied, where they are functions); the one thing this driver does that a
served run does not is call ``emu.kill(victim)`` at the stated time.  What
differs around that: the snapshots keep every total and every counter and
outlive a dead node, the comparison is with ``reference/failover_rsm.py``
over the SURVIVORS, and after the drain every group the victim led is
looked up on every survivor.  ``drivers/failover.md`` says what a mix may
set and what ``correct`` compares.
"""

from __future__ import annotations

import asyncio
import json
import shutil
import tempfile
import threading
import time
from typing import Dict, Optional

import numpy as np

from benchmarks import harness, loadgen, loadgen_failover
from benchmarks.drivers.served import (CLIENT_BASE, MAX_BURSTS, QUIET_BURSTS,
                                       RAMP_BURST_S, WARMUP_BURST_S,
                                       stream_of)
from benchmarks.reference import failover_rsm

SHOWN_COUNTERS = ("installs", "elections_started", "elections_won",
                  "elections_preempted", "ballot_changes", "redriven",
                  "parked", "park_dropped", "wave_dups", "paused", "shed",
                  "wal_nacked")
ELECTION_TAGS = ("fo.scan", "fo.elect_start", "fo.prepare", "fo.reply",
                 "fo.install", "eng.prepare", "eng.install")
KILL_WITHIN_S = 0.1   # of its time, or the run is off its schedule
SETTLE_S = 60.0       # most the survivors get to end their elections
CONTROLS = failover_rsm.CONTROLS


def snapshot(emu, gone: Optional[dict] = None) -> dict:
    """The program's counters, all cumulative: every ``DelayProfiler``
    total, and each node's counters by node id.  ``gone``: what a dead node
    read when it was last alive (its wire bytes still count)."""
    from gigapaxos_tpu.utils.engineledger import EngineLedger
    from gigapaxos_tpu.utils.profiler import DelayProfiler
    fs = DelayProfiler.snapshot(buckets=False)["histograms"].get(
        "wal.fsync", {})
    per_node = {i: node_facts(nd) for i, nd in emu.nodes.items()
                if nd is not None}
    per_node.update(gone or {})
    ids = sorted(emu.nodes)
    keys = next(iter(per_node.values()))["counters"]
    return {
        "totals": {k: {"wall_s": v[0], "calls": v[1], "items": v[2]}
                   for k, v in DelayProfiler.totals().items()},
        "wal_fsync": {"count": fs.get("count", 0),
                      "sum_s": fs.get("sum_s", 0.0)},
        "ledger": harness.ledger(),
        "kernels": {k: v["compiles"]
                    for k, v in EngineLedger.kernels().items()},
        "net": {k: sum(f["net"][k] for f in per_node.values())
                for k in ("tx_bytes", "rx_bytes", "tx_writes", "rx_reads")},
        "counters": {k: [per_node[i]["counters"][k] if i in per_node
                         else None for i in ids]
                     for k in keys if isinstance(keys[k], int)},
    }


def node_facts(nd) -> dict:
    return {"net": nd.transport.metrics(),
            "counters": nd.metrics(include_profiler=False)["counters"]}


class TakeoverWatch:
    """From the kill on, a thread reads the survivors' election counters
    every few milliseconds: when the first election began and when the
    last install ended, on the host's clock, in every run (the per-layer
    readers have the same from the spans, in a traced run only)."""

    def __init__(self, survivors, n_led: int):
        self.nodes, self.n_led = survivors, n_led
        self.t_first_election = self.t_all_installed = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(0.005):
            now = time.perf_counter()
            if self.t_first_election is None and any(
                    getattr(nd, "n_elections_started", 0)
                    or nd.open_elections for nd in self.nodes):
                self.t_first_election = now
            if sum(nd.n_installs for nd in self.nodes) >= self.n_led \
                    and not any(nd.open_elections for nd in self.nodes):
                self.t_all_installed = now
                return

    def stop(self) -> None:
        """Safe to call twice, and on a watch that never started."""
        self._stop.set()
        if self._thread.ident is not None:
            self._thread.join()


def election_totals(before: dict, after: dict) -> dict:
    """What the takeover cost by the program's own sums (always on): for
    each election tag the calls, items and seconds added in the window."""
    out = {}
    for tag in ELECTION_TAGS:
        a = after["totals"].get(tag)
        if a is not None:
            b = before["totals"].get(tag, {})
            out[tag] = [a["calls"] - b.get("calls", 0),
                        a["items"] - b.get("items", 0),
                        round(a["wall_s"] - b.get("wall_s", 0.0), 5)]
    return out


def election_spans(t_kill_ring: Optional[float]) -> Optional[dict]:
    """A traced run's election spans by kind: how many, their seconds, and
    from the first's start to the last's end in seconds after the kill."""
    from benchmarks import span_ring
    spans = [s for s in span_ring.session() or []
             if s["kind"] in ELECTION_TAGS or s["kind"] == "fo.suspect"]
    if not spans or t_kill_ring is None:
        return None
    out = {}
    for s in spans:
        o = out.setdefault(s["kind"], [0, 0.0, s["t0"], s["t1"]])
        o[0] += 1
        o[1] += s["t1"] - s["t0"]
        o[2], o[3] = min(o[2], s["t0"]), max(o[3], s["t1"])
    return {k: [n, round(sec, 5), round(lo - t_kill_ring, 4),
                round(hi - t_kill_ring, 4)]
            for k, (n, sec, lo, hi) in out.items()}


def survivor_states(nodes, groups, wait_for: Dict[str, int],
                    timeout_s: float = 60.0):
    """Each survivor's (count, digest) of ``groups``, once every one of
    them has executed what ``wait_for`` says was acknowledged and has no
    election open (bounded); and the writes to groups nobody addressed."""
    deadline = time.monotonic() + timeout_s
    while True:
        states = []
        for nd in nodes:
            with nd._engine_lock:
                states.append({g: (nd.app.count.get(g, 0),
                                   nd.app.digest.get(g, 0))
                               for g in groups if nd.app.count.get(g, 0)})
        if (all(st.get(g, (0, 0))[0] >= c for st in states
                for g, c in wait_for.items())
                and not any(nd.open_elections for nd in nodes)) \
                or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    spurious = sum(1 for nd in nodes for g, c in list(nd.app.count.items())
                   if c and g not in groups)
    return states, spurious


def led_by(n_live: int, victim: int, replicas: int) -> np.ndarray:
    """Keys of the live groups whose initial coordinator is ``victim``."""
    keys = np.asarray([loadgen.group_key(f"g{i}") for i in range(n_live)],
                      np.uint64)
    return keys[keys % np.uint64(replicas) == np.uint64(victim)]


def coordinator_views(nodes, gkeys: np.ndarray):
    """Every survivor's promised ballot and the ballot it coordinates at,
    for the groups ``gkeys``, read from its engine."""
    bals, cbals = [], []
    for nd in nodes:
        with nd._engine_lock:
            rows = nd.table.rows_for_keys(gkeys)
            got = nd.backend.inspect_rows(np.where(rows >= 0, rows, 0))
        bals.append(np.where(rows >= 0, got["bal"], -1))
        cbals.append(np.where(rows >= 0, got["cbal"], -2))
    return np.stack(bals), np.stack(cbals)


def parsed(res: dict) -> dict:
    """A generator's result with each reply as (count, digest), or None."""
    out = []
    for pay in res["reply"]:
        try:
            body = json.loads(pay)
            out.append((body["count"], body["digest"]))
        except (ValueError, KeyError, TypeError):
            out.append(None)
    return dict(res, reply=out)


def controls(run: dict, seed: int) -> Dict[str, list]:
    """The CONTROLS at the run's own size: the reference with one guarantee
    of a failover taken away, put in the program's place on the run's own
    record, through the same comparison."""
    streams, results, ballots, cbals, survivors = run["for_control"]
    out = {}
    for broken in CONTROLS:
        rng = np.random.default_rng([int(seed), 0xC0])
        victim = failover_rsm.pick_victim(broken, results, rng)
        fake, states, b, c = failover_rsm.broken_run(
            broken, streams, results, ballots, cbals, survivors, victim)
        out[broken] = failover_rsm.check(streams, fake, states, b, c,
                                         survivors)
    return out


def run(cell, *, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    import jax

    from gigapaxos_tpu.paxos.interfaces import CounterApp
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.testing.harness import PaxosEmulation
    from gigapaxos_tpu.utils.config import Config

    cfg, mix = cell.config, cell.traffic
    R, depth, victim = int(cfg["replicas"]), int(mix["depth"]), \
        int(mix["victim"])
    kill_at_s = float(mix["kill_at_share"]) * seconds
    for key, val in cfg.get("pc", {}).items():
        Config.set(getattr(PC, key), val)
    logdir = tempfile.mkdtemp(prefix="gp_bench_wal_")
    harness.say("settings", pc=cfg.get("pc", {}), wal_dir=logdir,
                wal_filesystem=harness.filesystem_of(logdir),
                sync_wal=cfg["guarantees"]["sync_wal"])
    emu = watch = None
    try:
        t0 = time.perf_counter()
        emu = PaxosEmulation(
            logdir, n_nodes=R, n_groups=0, group_size=R,
            backend=cfg["backend"], app_cls=CounterApp,
            capacity=int(cfg["capacity_rows"]), window=int(cfg["window"]),
            sync_wal=bool(cfg["guarantees"]["sync_wal"]))
        t_boot = time.perf_counter() - t0
        t0 = time.perf_counter()
        emu.create_groups(int(cfg["live_groups"]))
        t_create = time.perf_counter() - t0

        names = loadgen.plan_groups(seed, int(cfg["live_groups"]),
                                    int(mix["active_groups"]))
        servers = [emu.addr_map[i] for i in sorted(emu.nodes)]
        payload = b"x" * int(mix["payload_bytes"])
        note = harness.annotate if trace else None
        led = led_by(int(cfg["live_groups"]), victim, R)
        survivors = [i for i in sorted(emu.nodes) if i != victim]

        # the warm-up: drivers/served.py's, on the same generator
        t0 = time.perf_counter()
        led0 = harness.ledger()
        results, bursts, quiet = [], [], 0
        ramp = [1 << k for k in range(depth.bit_length()) if 1 << k < depth]
        while quiet < QUIET_BURSTS and len(bursts) < len(ramp) + MAX_BURSTS:
            i = len(bursts)
            d = ramp[i] if i < len(ramp) else depth
            before = harness.ledger()
            res = asyncio.run(loadgen.run_closed_loop(
                servers, names,
                RAMP_BURST_S if d < depth else WARMUP_BURST_S, d,
                client_id=CLIENT_BASE + 1 + i, payload=payload))
            after = harness.ledger()
            lost = int(((res["t_recv"] < 0) | (res["status"] != 0)).sum())
            if lost:
                raise RuntimeError(
                    f"warm-up burst {i}: {lost} of {res['n_sent']} requests "
                    "never answered or refused")
            results.append(res)
            fresh = sum(after[k] - before[k] for k in after)
            bursts.append({"depth": d, "sent": res["n_sent"],
                           "programs_loaded": fresh})
            quiet = quiet + 1 if d == depth and not fresh else 0
        t_warm = time.perf_counter() - t0
        led1 = harness.ledger()

        fault: dict = {}
        watch = TakeoverWatch([emu.nodes[i] for i in survivors], len(led))

        def kill():
            fault["gone"] = {victim: node_facts(emu.nodes[victim])}
            fault["t_ring"] = time.monotonic()  # the span ring's clock
            watch.start()
            emu.kill(victim)

        tracer = harness.tracer_for(seconds) if trace else None
        snap0 = snapshot(emu)
        setup_s = time.perf_counter() - t_start
        harness.say("setup", setup_s=round(setup_s, 3),
                    boot_s=round(t_boot, 3), creates_s=round(t_create, 3),
                    warmup_s=round(t_warm, 3), warmup_bursts=bursts,
                    warmup_quiet=quiet >= QUIET_BURSTS,
                    groups_led_by_victim=len(led),
                    **{k: led1[k] - led0[k] for k in led1})
        # the tracer starts with the window, connections made, so that the
        # profiler's own start-up never falls on the kill
        res = asyncio.run(loadgen_failover.run_closed_loop_kill(
            servers, names, seconds, depth, client_id=CLIENT_BASE + 64,
            payload=payload, drain_s=float(mix["drain_s"]),
            retransmit_after_s=float(mix["retransmit_after_s"]),
            kill=kill, kill_at_s=kill_at_s, annotate=note,
            on_start=tracer.start if tracer else None))
        snap1 = snapshot(emu, fault.get("gone"))
        res["victim"] = victim
        results.append(res)
        red = tracer.finish() if tracer else None
        peak = harness.memory_peak_bytes()
        summary = loadgen.summarize(res)
        seen = loadgen_failover.outage(res, victim)

        # what the window is compared on: every request's answer, the
        # state of every SURVIVOR once it has caught up and its elections
        # are over, and who coordinates the groups the victim led
        streams = [stream_of(names, r) for r in results]
        acked: Dict[str, int] = {}
        for st, r in zip(streams, results):
            ok = (r["t_recv"] >= 0) & (r["status"] == 0)
            for (g, _rid), good in zip(st, ok):
                acked[g] = acked.get(g, 0) + int(good)
        alive = [emu.nodes[i] for i in survivors]
        states, spurious = survivor_states(alive, set(names), acked,
                                           SETTLE_S)
        watch.stop()
        ballots, cbals = coordinator_views(alive, led)
        platform = jax.devices()[0].platform
        settings = {
            "engine_platforms": [getattr(nd.backend, "engine_platform", None)
                                 for nd in alive],
            "waves_fused": [bool(nd._fuse_waves) for nd in alive],
            "sync_wal": bool(Config.get(PC.SYNC_WAL)),
            "pause_idle_s": Config.get(PC.PAUSE_IDLE_S),
            "failure_timeout_s": Config.get(PC.FAILURE_TIMEOUT_S),
            "ping_interval_s": Config.get(PC.PING_INTERVAL_S),
            "groups_created": len(emu.groups),
            "victim_stayed_dead": emu.nodes[victim] is None,
            "open_elections": [nd.open_elections for nd in alive],
            "slab_bytes": [(nd.backend.memory_info() or {}).get(
                "total_bytes") for nd in alive],
        }
    finally:
        if watch is not None:
            watch.stop()
        if emu is not None:
            emu.stop()
        shutil.rmtree(logdir, ignore_errors=True)

    delta = {k: [None if a is None or b is None else b - a
                 for a, b in zip(snap0["counters"][k], snap1["counters"][k])]
             for k in SHOWN_COUNTERS if k in snap1["counters"]}
    t_kill = res["t_kill"]

    def since_kill(t):
        return round(t - t_kill, 4) if t is not None and t_kill else None
    harness.say(
        "window", **{k: (round(v, 4) if isinstance(v, float) else v)
                     for k, v in {**summary, **seen}.items()},
        drain_s=round(res["t_end"] - res["t0"] - seconds, 3),
        acked_by_second=np.bincount(
            (res["t_recv"][res["t_recv"] >= 0] - res["t0"]).astype(int))
        .tolist(),
        killed_at_s=round(t_kill - res["t0"], 4) if t_kill else None,
        kill_took_s=since_kill(res["t_kill_done"]),
        client_saw_close_s=since_kill(res["closed_at"][victim]),
        detect_s=since_kill(watch.t_first_election),
        all_installed_s=since_kill(watch.t_all_installed),
        resent=res["n_resent"],
        election_totals=election_totals(snap0, snap1),
        election_spans=election_spans(fault.get("t_ring")) if trace
        else None,
        unanswered_at_kill=int(((res["t_send"] < t_kill) & (
            (res["t_recv"] < 0) | (res["t_recv"] > t_kill))).sum())
        if t_kill else None,
        kernels_traced_in_window={
            k: v - snap0["kernels"].get(k, 0)
            for k, v in snap1["kernels"].items()
            if v != snap0["kernels"].get(k, 0)},
        memory_peak_bytes=peak, **settings, **delta,
        compiles_in_window={k: snap1["ledger"][k] - snap0["ledger"][k]
                            for k in snap1["ledger"]})
    t0 = time.perf_counter()
    shown = [parsed(r) for r in results]
    cks = failover_rsm.check(streams, shown, states, ballots, cbals,
                             survivors)
    off_schedule = int(t_kill is None
                       or abs(t_kill - res["t0"] - kill_at_s) > KILL_WITHIN_S
                       or not settings["victim_stayed_dead"])
    off_chip = sum(p != platform for p in settings["engine_platforms"])
    cks += [("kill_off_schedule", off_schedule, 0),
            ("writes_nobody_sent", spurious, 0),
            ("engines_off_the_device", off_chip, 0),
            ("sync_wal_off", int(not settings["sync_wal"]
                                 == bool(cfg["guarantees"]["sync_wal"])), 0),
            ("groups_paged_out",
             int(sum(d or 0 for d in delta.get("paused", []))), 0)]
    harness.say("reference", seconds=round(time.perf_counter() - t0, 3),
                requests_compared=sum(r["n_sent"] for r in results),
                groups_compared=len(acked), groups_led_by_victim=len(led))
    return {
        "attempted": summary["attempted"], "failed": summary["failed"],
        "end_to_end": {"commit_rate": summary["commit_rate"],
                       "commit_p50_ms": summary["commit_p50_ms"],
                       "commit_p95_ms": summary["commit_p95_ms"],
                       "setup_s": setup_s},
        "memory_peak_bytes": peak, "trace": red, "checks": cks,
        "config": cfg, "traffic": mix,
        "window": dict(summary, **seen, replicas=R, victim=victim,
                       t_recv=res["t_recv"], status=res["status"],
                       t_kill=t_kill, t_kill_ring=fault.get("t_ring")),
        "before": snap0, "after": snap1,
        "for_control": (streams, shown, ballots, cbals, survivors),
    }
