"""Driver of the served configurations: three ``PaxosNode`` replicas in
this process over real loopback sockets, the benchmark's closed-loop
generator in front of them, measured from the client's side.

Started as a copy of ``chip_smoke.run_stream`` (boot, creates, placement,
waiting for the followers, reading each replica's state) with the
benchmark's own generator, a deadline, a warm-up and counters read as
differences around the window.  What the window's requests were answered
with, and the state the three replicas end in, are compared with the plain
reference (``reference/counter_rsm.py``) once the window has closed.
"""

from __future__ import annotations

import asyncio
import json
import shutil
import tempfile
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmarks import harness, loadgen
from benchmarks.reference import counter_rsm

CLIENT_BASE = 1 << 20  # warm-up bursts take BASE+1.., the window BASE+64
# The warm-up is traffic and nothing else: the cell's own stream at every
# power of two below its depth (a wave's size picks the program it runs, and
# waves of every size up to the depth come in a window), then at its own
# depth until QUIET_BURSTS bursts in a row compiled and loaded nothing.
RAMP_BURST_S = 0.25
WARMUP_BURST_S = 1.0
QUIET_BURSTS = 2
MAX_BURSTS = 12
NODE_COUNTERS = ("installs", "ballot_changes", "redriven", "wave_dups",
                 "paused", "unpaused", "shed", "wal_nacked")


def snapshot(emu) -> dict:
    """The program's counters, all cumulative: read before and after the
    window and subtracted by the readers."""
    from gigapaxos_tpu.utils.profiler import DelayProfiler
    tot = DelayProfiler.totals()
    fs = DelayProfiler.snapshot(buckets=False)["histograms"].get(
        "wal.fsync", {})
    nets = [nd.transport.metrics() for nd in emu.nodes.values()]
    ctr = [nd.metrics(include_profiler=False)["counters"]
           for nd in emu.nodes.values()]
    return {
        "totals": {k: {"wall_s": v[0], "calls": v[1], "items": v[2]}
                   for k, v in tot.items()
                   if k in ("eng.submit", "eng.collect", "eng.overlap")},
        "wal_fsync": {"count": fs.get("count", 0),
                      "sum_s": fs.get("sum_s", 0.0)},
        "ledger": harness.ledger(),
        "net": {k: sum(n[k] for n in nets)
                for k in ("tx_bytes", "rx_bytes", "tx_writes", "rx_reads")},
        "counters": {k: [c.get(k, 0) for c in ctr] for k in NODE_COUNTERS},
    }


def stream_of(names: Sequence[str], res: dict) -> List[Tuple[str, int]]:
    return [(names[g], int(r)) for g, r in zip(res["seq_group"],
                                               res["req_id"])]


def replica_states(emu, groups, wait_for: Dict[str, int],
                   timeout_s: float = 60.0):
    """Each replica's (count, digest) of ``groups``.  Followers execute
    behind the acknowledgement, so wait until every replica has executed
    what ``wait_for`` says was acknowledged (bounded)."""
    deadline = time.monotonic() + timeout_s
    while True:
        states = []
        for nd in emu.nodes.values():
            with nd._engine_lock:
                states.append({g: (nd.app.count.get(g, 0),
                                   nd.app.digest.get(g, 0))
                               for g in groups if nd.app.count.get(g, 0)})
        if all(st.get(g, (0, 0))[0] >= c for st in states
               for g, c in wait_for.items()) \
                or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    spurious = sum(1 for nd in emu.nodes.values()
                   for g, c in list(nd.app.count.items())
                   if c and g not in groups)
    return states, spurious


def compare(streams, results, states, spurious: int, replicas: int):
    """The numbers compared, each with its limit (exact: 0).  ``results``:
    for each stream the generator's result; ``states``: each replica's
    final (count, digest) per group."""
    want_ans, want_states = counter_rsm.replay(streams, replicas=replicas)
    res = results[-1]  # the window
    wrong = never = refused = 0
    for k, want in enumerate(want_ans[-1]):
        if res["t_recv"][k] < 0:
            never += 1
        elif res["status"][k] != 0:
            refused += 1
        else:
            try:
                body = json.loads(res["reply"][k])
                got = (body["count"], body["digest"])
            except (ValueError, KeyError, TypeError):
                got = None
            wrong += got != want
    diverged = 0
    for r in range(replicas):
        have = states[r] if r < len(states) else {}
        for g in set(want_states[r]) | set(have):
            diverged += have.get(g) != want_states[r].get(g)
    return [("answers_wrong", wrong, 0),
            ("answers_refused", refused, 0),
            ("never_answered", never, 0),
            ("replica_groups_diverged", diverged, 0),
            ("writes_nobody_sent", spurious, 0)]


CONTROLS = ("lost_write", "reordered", "doubled", "stale_answer")


def controls(run: dict, seed: int) -> Dict[str, list]:
    """The CONTROL at the run's own size: the reference with one stated
    guarantee taken away, put in the program's place on the run's own
    streams (the same requests, the same ids, answered as the broken
    reference answers them), through the same comparison."""
    streams, results = run["for_control"]
    # a victim whose group has another write (in the window or in the
    # warm-up before it), so that "reordered" has two writes to swap
    writes: Dict[str, int] = {}
    for st in streams:
        for g, _rid in st:
            writes[g] = writes.get(g, 0) + 1
    twice = [k for k, (g, _rid) in enumerate(streams[-1]) if writes[g] > 1]
    rng = np.random.default_rng([int(seed), 0xC0])
    victim = int(rng.choice(twice)) if twice else \
        int(rng.integers(0, len(streams[-1])))
    out = {}
    for broken in CONTROLS:
        ans, states = counter_rsm.replay(
            streams, replicas=run["window"]["replicas"], broken=broken,
            victim=victim)
        fake = [dict(r, reply=[json.dumps({"count": c, "digest": d}).encode()
                               for c, d in a])
                for r, a in zip(results, ans)]
        out[broken] = compare(streams, fake, states, 0,
                              run["window"]["replicas"])
    return out


def run(cell, *, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    import jax

    from gigapaxos_tpu.paxos.interfaces import CounterApp
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.testing.harness import PaxosEmulation
    from gigapaxos_tpu.utils.config import Config

    cfg, mix = cell.config, cell.traffic
    R, depth = int(cfg["replicas"]), int(mix["depth"])
    for key, val in cfg.get("pc", {}).items():
        Config.set(getattr(PC, key), val)
    logdir = tempfile.mkdtemp(prefix="gp_bench_wal_")
    harness.say("settings", pc=cfg.get("pc", {}), wal_dir=logdir,
                wal_filesystem=harness.filesystem_of(logdir),
                sync_wal=cfg["guarantees"]["sync_wal"])
    emu = None
    try:
        t0 = time.perf_counter()
        emu = PaxosEmulation(
            logdir, n_nodes=R, n_groups=0, backend=cfg["backend"],
            app_cls=CounterApp, capacity=int(cfg["capacity_rows"]),
            window=int(cfg["window"]),
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

        def drive(secs, client, at_depth=depth, **kw):
            return asyncio.run(loadgen.run_closed_loop(
                servers, names, secs, at_depth, client_id=client,
                payload=payload, **kw))

        t0 = time.perf_counter()
        led0 = harness.ledger()
        results, bursts, quiet = [], [], 0
        ramp = [1 << k for k in range(depth.bit_length()) if 1 << k < depth]
        while quiet < QUIET_BURSTS and len(bursts) < len(ramp) + MAX_BURSTS:
            i = len(bursts)
            d = ramp[i] if i < len(ramp) else depth
            before = harness.ledger()
            res = drive(RAMP_BURST_S if d < depth else WARMUP_BURST_S,
                        CLIENT_BASE + 1 + i, at_depth=d)
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

        tracer = harness.tracer_for(seconds) if trace else None
        snap0 = snapshot(emu)
        setup_s = time.perf_counter() - t_start
        harness.say("setup", setup_s=round(setup_s, 3),
                    boot_s=round(t_boot, 3), creates_s=round(t_create, 3),
                    warmup_s=round(t_warm, 3), warmup_bursts=bursts,
                    warmup_quiet=quiet >= QUIET_BURSTS,
                    **{k: led1[k] - led0[k] for k in led1})
        if tracer:
            tracer.start()
        res = drive(seconds, CLIENT_BASE + 64, annotate=note)
        snap1 = snapshot(emu)
        results.append(res)
        red = tracer.finish() if tracer else None
        peak = harness.memory_peak_bytes()
        summary = loadgen.summarize(res)

        # what the window is compared on: every request's answer, and the
        # state of every replica once the followers have caught up
        streams = [stream_of(names, r) for r in results]
        acked: Dict[str, int] = {}
        for st, r in zip(streams, results):
            ok = (r["t_recv"] >= 0) & (r["status"] == 0)
            for (g, _rid), good in zip(st, ok):
                acked[g] = acked.get(g, 0) + int(good)
        states, spurious = replica_states(emu, set(names), acked)
        platform = jax.devices()[0].platform
        settings = {
            "engine_platforms": [getattr(nd.backend, "engine_platform", None)
                                 for nd in emu.nodes.values()],
            "waves_fused": [bool(nd._fuse_waves)
                            for nd in emu.nodes.values()],
            "sync_wal": bool(Config.get(PC.SYNC_WAL)),
            "pause_idle_s": Config.get(PC.PAUSE_IDLE_S),
            "failure_timeout_s": Config.get(PC.FAILURE_TIMEOUT_S),
            "groups_created": len(emu.groups),
            "slab_bytes": [(nd.backend.memory_info() or {}).get(
                "total_bytes") for nd in emu.nodes.values()],
        }
    finally:
        if emu is not None:
            emu.stop()
        shutil.rmtree(logdir, ignore_errors=True)

    delta = {k: [b - a for a, b in zip(snap0["counters"][k],
                                       snap1["counters"][k])]
             for k in NODE_COUNTERS}
    harness.say("window", **{k: (round(v, 4) if isinstance(v, float) else v)
                             for k, v in summary.items()},
                drain_s=round(res["t_end"] - res["t0"] - seconds, 3),
                acked_by_second=np.bincount(
                    (res["t_recv"][res["t_recv"] >= 0]
                     - res["t0"]).astype(int)).tolist(),
                memory_peak_bytes=peak, **settings, **delta,
                compiles_in_window={k: snap1["ledger"][k] - snap0["ledger"][k]
                                    for k in snap1["ledger"]})
    t0 = time.perf_counter()
    cks = compare(streams, results, states, spurious, R)
    off_chip = sum(p != platform for p in settings["engine_platforms"])
    cks += [("engines_off_the_device", off_chip, 0),
            ("sync_wal_off", int(not settings["sync_wal"]
                                 == bool(cfg["guarantees"]["sync_wal"])), 0),
            ("groups_paged_out", int(sum(delta["paused"])), 0)]
    harness.say("reference", seconds=round(time.perf_counter() - t0, 3),
                requests_compared=res["n_sent"],
                groups_compared=len(acked))
    return {
        "attempted": summary["attempted"], "failed": summary["failed"],
        "end_to_end": {"commit_rate": summary["commit_rate"],
                       "commit_p50_ms": summary["commit_p50_ms"],
                       "commit_p95_ms": summary["commit_p95_ms"],
                       "setup_s": setup_s},
        "memory_peak_bytes": peak, "trace": red, "checks": cks,
        "config": cfg, "traffic": mix,
        "window": dict(summary, replicas=R, t_recv=res["t_recv"],
                       status=res["status"]),
        "before": snap0, "after": snap1,
        "for_control": (streams, results),
    }
