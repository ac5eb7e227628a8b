"""Driver of the recovery configurations: ``drivers/failover.py``'s
deployment (R ``PaxosNode`` replicas of every group in this process over
real loopback sockets, ``loadgen_failover.py``'s closed loop in front of
them, one node crash-stopped a stated share into the window) with the other
half of a crash: the victim is started again inside the window from its own
WAL and checkpoint directory (``PaxosEmulation.restart``, the product's own
boot path), recovers, and is brought level by the frontier exchange, all
under the stream.

Boot, creates, the generator, the counters' snapshots, the takeover watch
and the readers' inputs are ``drivers/failover.py``'s and
``drivers/served.py``'s (imported where they are functions).  This driver's
own: the restart thread, the tracer placed on the recovery, the wait for the
restarted node to report itself level, and the comparison with
``reference/recovery_rsm.py`` over ALL R replicas.  ``drivers/recovery.md``
says what a mix may set and what ``correct`` compares.
"""

from __future__ import annotations

import asyncio
import shutil
import tempfile
import threading
import time
from typing import Dict, Optional

import numpy as np

from benchmarks import harness, loadgen, loadgen_failover
from benchmarks.drivers import failover as fo
from benchmarks.drivers.served import (CLIENT_BASE, MAX_BURSTS, QUIET_BURSTS,
                                       RAMP_BURST_S, WARMUP_BURST_S,
                                       stream_of)
from benchmarks.reference import recovery_rsm

SHOWN_COUNTERS = fo.SHOWN_COUNTERS
RECOVERY_TAGS = ("rec.boot", "rec.groups", "rec.table", "rec.install",
                 "rec.checkpoints", "rec.wal", "rec.catchup", "rec.serve",
                 "rec.boots_begun",
                 "rec.groups_recovered", "rec.rows_behind", "rec.rows_level",
                 "rec.catchup_frames", "rec.dedupe_ids_loaded")
FAULT_WITHIN_S = 0.5  # of its time, or the run is off its schedule
SETTLE_S = 60.0       # most the replicas get to end their elections
CONTROLS = recovery_rsm.CONTROLS


def warm_up(servers, names, depth: int, payload: bytes):
    """``drivers/served.py``'s warm-up rule on this generator: the cell's
    own stream at every power of two below its depth, then at its depth
    until QUIET_BURSTS bursts in a row loaded nothing.  Returns (results,
    bursts, whether it went quiet)."""
    results, bursts, quiet = [], [], 0
    ramp = [1 << k for k in range(depth.bit_length()) if 1 << k < depth]
    while quiet < QUIET_BURSTS and len(bursts) < len(ramp) + MAX_BURSTS:
        i = len(bursts)
        d = ramp[i] if i < len(ramp) else depth
        before = harness.ledger()
        res = asyncio.run(loadgen.run_closed_loop(
            servers, names, RAMP_BURST_S if d < depth else WARMUP_BURST_S,
            d, client_id=CLIENT_BASE + 1 + i, payload=payload))
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
    return results, bursts, quiet >= QUIET_BURSTS


def frontier_of(nd) -> Dict[int, tuple]:
    """Each of a node's groups by key: (first slot not executed, slot of
    its last checkpoint), read under the node's engine lock."""
    with nd._engine_lock:
        rows = np.flatnonzero(nd._bal >= 0)
        return dict(zip(nd._row_gkey[rows].tolist(),
                        zip(nd._cur[rows].tolist(),
                            nd._ckpt[rows].tolist())))


def slab_bytes(nd) -> Optional[int]:
    """A node's device slab, read under its engine lock: the restarted
    node may still be digesting what its peers had queued for it, and a
    launch donates the arrays ``memory_info`` looks at."""
    with nd._engine_lock:
        return (nd.backend.memory_info() or {}).get("total_bytes")


def recovery_facts(live_groups: int, before: Dict[int, tuple],
                   after: Optional[Dict[int, tuple]]) -> dict:
    """What ``recovery_rsm.check`` takes of the restart: the groups the
    node came up with, and for each it had, its last checkpoint before the
    kill beside its cursor when recovery returned (a group it lost reads
    as rolled back to nothing)."""
    after = after or {}
    keys = list(before)
    return {"live_groups": live_groups, "groups_recovered": len(after),
            "checkpoint_slot": [before[k][1] for k in keys],
            "cursor_after_boot": [after.get(k, (-(1 << 31), 0))[0]
                                  for k in keys]}


def both_lives(gone: dict, back: dict) -> dict:
    """A node's facts over its two incarnations: what it read when it died
    plus what it has read since it came back (its counters and its wire
    bytes began again at 0), so that after minus before stays the
    window's."""
    return {"net": {k: gone["net"][k] + v for k, v in back["net"].items()
                    if isinstance(v, (int, float))},
            "counters": {k: (gone["counters"].get(k, 0) + v
                             if isinstance(v, int) else v)
                         for k, v in back["counters"].items()}}


def recovery_totals(before: dict, after: dict) -> dict:
    """What the restart cost by the program's own sums (always on): for
    each recovery tag the calls, items and seconds added in the window."""
    out = {}
    for tag in RECOVERY_TAGS:
        a = after["totals"].get(tag)
        if a is not None:
            b = before["totals"].get(tag, {})
            out[tag] = [a["calls"] - b.get("calls", 0),
                        a["items"] - b.get("items", 0),
                        round(a["wall_s"] - b.get("wall_s", 0.0), 5)]
    return out


def recovery_spans(t_restart_ring: Optional[float]) -> Optional[dict]:
    """A traced run's recovery spans by kind: how many, their seconds,
    from the first's start to the last's end in seconds after the restart,
    and the attributes of the last."""
    from benchmarks import span_ring
    spans = [s for s in span_ring.session() or []
             if s["kind"] in RECOVERY_TAGS]
    if not spans or t_restart_ring is None:
        return None
    out: dict = {}
    for s in spans:
        o = out.setdefault(s["kind"], [0, 0.0, s["t0"], s["t1"], {}])
        o[0] += 1
        o[1] += s["t1"] - s["t0"]
        o[2], o[3] = min(o[2], s["t0"]), max(o[3], s["t1"])
        o[4] = {k: v for k, v in s.items()
                if k not in ("kind", "node", "tid", "wave", "parent", "t0",
                             "t1", "id")}
    return {k: [n, round(sec, 5), round(lo - t_restart_ring, 4),
                round(hi - t_restart_ring, 4), attrs]
            for k, (n, sec, lo, hi, attrs) in out.items()}


def boots_begun() -> int:
    """Recoveries begun in this process so far (the program's sum
    ``rec.boots_begun``, a call a recovery)."""
    from gigapaxos_tpu.utils.profiler import DelayProfiler
    return DelayProfiler.totals().get("rec.boots_begun", (0, 0, 0))[1]


class TraceTheRecovery:
    """Starts ``tracer`` the moment a recovery has read its groups and
    loaded its programs (the program's sum ``rec.boots_begun``; the
    restarted node's engine is built before that), from a thread of its own
    that reads the sum every 2 ms: the profiler comes on while the table is
    rebuilt, so the traced seconds begin with the device install and hold
    as much of the checkpoints and the roll-forward as ends inside them."""

    def __init__(self, tracer):
        self.tracer, self.started = tracer, False
        self._seen = boots_begun()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(0.002):
            if boots_begun() > self._seen:
                self.started = True
                self.tracer.start()
                return

    def finish(self) -> dict:
        """The reduced trace; of seconds after the drain where no
        recovery ever began (the run is off its schedule then)."""
        self._stop.set()
        if self._thread.ident is not None:
            self._thread.join()
        if not self.started:
            self.tracer.start()
        return self.tracer.finish()


def wait_level(nd, timeout_s: float) -> Optional[float]:
    """Until the restarted node reports its catch-up ended (bounded):
    the seconds waited, or None where it had not."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if not nd.catching_up:
            return time.monotonic() - t0
        time.sleep(0.02)
    return None


def controls(run: dict, seed: int) -> Dict[str, list]:
    """The CONTROLS at the run's own size: the reference's final states
    with one guarantee taken away, put in the program's place on the run's
    own record, through the same comparison."""
    streams, results, nodes, restarted, facts, ballots, cbals = \
        run["for_control"]
    out = {}
    for broken in CONTROLS:
        rng = np.random.default_rng([int(seed), 0xC0])
        victim = recovery_rsm.pick_victim(broken, results, rng)
        fake, states = recovery_rsm.broken_run(
            broken, streams, results, len(nodes), restarted, victim)
        out[broken] = recovery_rsm.check(streams, fake, states, restarted,
                                         facts, ballots, cbals, nodes)
    return out


def run(cell, *, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    import jax

    from gigapaxos_tpu.paxos.interfaces import CounterApp
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.testing.harness import PaxosEmulation
    from gigapaxos_tpu.utils.config import Config

    from gigapaxos_tpu.paxos.manager import PaxosNode
    if not hasattr(PaxosNode, "catching_up"):
        # a program without the frontier exchange cannot say when a
        # restarted node is level, and leaves its idle groups behind
        raise SystemExit(
            "benchmarks/drivers/recovery.py: this program has no frontier "
            "exchange (PaxosNode.catching_up); it cannot run the "
            f"configuration {cell.config.get('name')!r}")

    cfg, mix = cell.config, cell.traffic
    R, depth, victim = int(cfg["replicas"]), int(mix["depth"]), \
        int(mix["victim"])
    kill_at_s = float(mix["kill_at_share"]) * seconds
    restart_at_s = float(mix["restart_at_share"]) * seconds
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
        ids = sorted(emu.nodes)
        servers = [emu.addr_map[i] for i in ids]
        payload = b"x" * int(mix["payload_bytes"])
        note = harness.annotate if trace else None
        led = fo.led_by(int(cfg["live_groups"]), victim, R)
        survivors = [i for i in ids if i != victim]

        t0 = time.perf_counter()
        led0 = harness.ledger()
        results, bursts, quiet = warm_up(servers, names, depth, payload)
        # what a recovery launches, at sizes no traffic reaches, loaded
        # before the window as a restarted process finds them in its
        # compile cache: the restarted node is a new engine in THIS
        # process, and what this one loads, that one has
        with emu.nodes[victim]._engine_lock:
            emu.nodes[victim].backend.warm_recovery()
        t_warm = time.perf_counter() - t0
        led1 = harness.ledger()

        fault: dict = {}
        watch = fo.TakeoverWatch([emu.nodes[i] for i in survivors],
                                 len(led))
        # in a traced run the traced seconds start with the recovery's
        # own device work (drivers/recovery.md, Tracing); every span that
        # opens in them reaches the ring when it ends
        tracer = TraceTheRecovery(harness.Tracer(
            0.0, min(harness.TRACE_S, seconds / 2))) if trace else None

        def kill():
            nd = emu.nodes[victim]
            fault["gone"] = {victim: fo.node_facts(nd)}
            fault["before"] = frontier_of(nd)
            fault["t_ring"] = time.monotonic()  # the span ring's clock
            watch.start()
            emu.kill(victim)

        def restart():
            due = fault["t_window"] + restart_at_s
            time.sleep(max(due - time.perf_counter(), 0))
            # the kill has returned (bounded)
            give_up = due + 2.0
            while time.perf_counter() < give_up \
                    and emu.nodes[victim] is not None:
                time.sleep(0.005)
            if emu.nodes[victim] is not None:
                return  # no kill to come back from: off schedule
            fault["t_restart"] = time.perf_counter()
            fault["t_restart_ring"] = time.monotonic()
            nd = emu.restart(victim)
            fault["t_restart_done"] = time.perf_counter()
            fault["after"] = frontier_of(nd)

        restarter = threading.Thread(target=restart, daemon=True)

        def on_start():
            fault["t_window"] = time.perf_counter()
            restarter.start()
            if tracer is not None:
                tracer.start()

        snap0 = fo.snapshot(emu)
        setup_s = time.perf_counter() - t_start
        harness.say("setup", setup_s=round(setup_s, 3),
                    boot_s=round(t_boot, 3), creates_s=round(t_create, 3),
                    warmup_s=round(t_warm, 3), warmup_bursts=bursts,
                    warmup_quiet=quiet, groups_led_by_victim=len(led),
                    **{k: led1[k] - led0[k] for k in led1})
        res = asyncio.run(loadgen_failover.run_closed_loop_kill(
            servers, names, seconds, depth, client_id=CLIENT_BASE + 64,
            payload=payload, drain_s=float(mix["drain_s"]),
            retransmit_after_s=float(mix["retransmit_after_s"]),
            kill=kill, kill_at_s=kill_at_s, annotate=note,
            on_start=on_start))
        restarter.join(SETTLE_S)
        back = emu.nodes[victim]
        level_s = wait_level(back, float(mix["catchup_wait_s"])) \
            if back is not None else None
        gone = fault.get("gone")
        if gone and back is not None:
            gone = {victim: both_lives(gone[victim], fo.node_facts(back))}
        snap1 = fo.snapshot(emu, gone)
        res["victim"] = victim
        results.append(res)
        red = tracer.finish() if tracer else None
        peak = harness.memory_peak_bytes()
        summary = loadgen.summarize(res)
        seen = loadgen_failover.outage(res, victim)

        # what the window is compared on: every request's answer, the
        # state of EVERY replica once it has caught up and its elections
        # are over, and who coordinates the groups the victim led
        streams = [stream_of(names, r) for r in results]
        acked: Dict[str, int] = {}
        for st, r in zip(streams, results):
            ok = (r["t_recv"] >= 0) & (r["status"] == 0)
            for (g, _rid), good in zip(st, ok):
                acked[g] = acked.get(g, 0) + int(good)
        alive_ids = [i for i in ids if emu.nodes[i] is not None]
        alive = [emu.nodes[i] for i in alive_ids]
        states, spurious = fo.survivor_states(
            alive, set(names), acked,
            float(mix["catchup_wait_s"]) if level_s is not None else 1.0)
        watch.stop()
        ballots, cbals = fo.coordinator_views(alive, led)
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
            "victim_came_back": back is not None,
            "open_elections": [nd.open_elections for nd in alive],
            "slab_bytes": [slab_bytes(nd) for nd in alive],
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
    t_kill, t_win = res["t_kill"], res["t0"]
    t_restart = fault.get("t_restart")

    def since(t, ref):
        return round(t - ref, 4) if t is not None and ref else None
    boot = recovery_totals(snap0, snap1).get("rec.boot")
    by_second = np.bincount(
        (res["t_recv"][res["t_recv"] >= 0] - t_win).astype(int))
    harness.say(
        "window", **{k: (round(v, 4) if isinstance(v, float) else v)
                     for k, v in {**summary, **seen}.items()},
        drain_s=round(res["t_end"] - t_win - seconds, 3),
        acked_by_second=by_second.tolist(),
        killed_at_s=since(t_kill, t_win),
        kill_took_s=since(res["t_kill_done"], t_kill),
        client_saw_close_s=since(res["closed_at"][victim], t_kill),
        detect_s=since(watch.t_first_election, t_kill),
        all_installed_s=since(watch.t_all_installed, t_kill),
        restarted_at_s=since(t_restart, t_win),
        restart_took_s=since(fault.get("t_restart_done"), t_restart),
        level_after_drain_s=None if level_s is None else round(level_s, 3),
        resent=res["n_resent"],
        recovery_totals=recovery_totals(snap0, snap1),
        recovery_spans=recovery_spans(fault.get("t_restart_ring"))
        if trace else None,
        election_totals=fo.election_totals(snap0, snap1),
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
    shown = [fo.parsed(r) for r in results]
    facts = recovery_facts(int(cfg["live_groups"]), fault.get("before", {}),
                           fault.get("after"))
    restarted = alive_ids.index(victim) if victim in alive_ids else 0
    cks = recovery_rsm.check(streams, shown, states, restarted, facts,
                             ballots, cbals, alive_ids)
    off_kill = int(t_kill is None
                   or abs(t_kill - t_win - kill_at_s) > FAULT_WITHIN_S)
    off_restart = int(t_restart is None or back is None
                      or abs(t_restart - t_win - restart_at_s)
                      > FAULT_WITHIN_S)
    led_w = snap1["ledger"]
    compiles = (led_w["compiles"] - snap0["ledger"]["compiles"]
                + led_w["retraces"] - snap0["ledger"]["retraces"])
    off_chip = sum(p != platform for p in settings["engine_platforms"])
    cks += [("kill_off_schedule", off_kill, 0),
            ("restart_off_schedule", off_restart, 0),
            ("compiles_in_window", compiles, 0),
            ("writes_nobody_sent", spurious, 0),
            ("engines_off_the_device", off_chip, 0),
            ("sync_wal_off", int(not settings["sync_wal"]
                                 == bool(cfg["guarantees"]["sync_wal"])), 0),
            ("groups_paged_out",
             int(sum(d or 0 for d in delta.get("paused", []))), 0)]
    harness.say("reference", seconds=round(time.perf_counter() - t0, 3),
                requests_compared=sum(r["n_sent"] for r in results),
                groups_compared=len(acked), groups_led_by_victim=len(led),
                replicas_compared=len(alive_ids))
    # the trace's ends on the ring's clock, for the readers that count
    # what fell inside the traced seconds
    ring_off = time.monotonic() - time.perf_counter()
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
                       t0=t_win, t_kill=t_kill,
                       t_kill_ring=fault.get("t_ring"),
                       t_restart=t_restart,
                       t_restart_ring=fault.get("t_restart_ring"),
                       t_restart_done=fault.get("t_restart_done"),
                       boot_s=boot[2] if boot else None,
                       t_all_installed=watch.t_all_installed,
                       t_deadline=t_win + seconds,
                       trace_ring=(red["t_lo"] + ring_off,
                                   red["t_hi"] + ring_off)
                       if red and red.get("t_lo") is not None else None),
        "before": snap0, "after": snap1,
        "for_control": (streams, shown, alive_ids, restarted, facts,
                        ballots, cbals),
    }
