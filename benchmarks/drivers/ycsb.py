"""Driver of the record-store configurations (YCSB core workloads over a
record per group): three ``PaxosNode`` replicas in this process over real
loopback sockets, ``RecordApp`` on each, the YCSB-style client threads of
``loadgen_ycsb.py`` in front of them, measured from the client's side.

Started as a copy of ``drivers/served.py``: boot, creates, the warm-up rule,
the tracer, counters read as differences around the window, and the same
keys in what ``run`` returns, so the served cells' readers read these cells
unchanged.  What differs: records are loaded before the window (no round
each), client threads draw their keys independently (no per-group rule), the
comparison is with ``reference/record_store.py``, and ``snapshot`` keeps
every total and every counter the program has.  ``drivers/ycsb.md`` says what
a mix may set and what ``correct`` compares.
"""

from __future__ import annotations

import asyncio
import functools
import shutil
import tempfile
import time
from typing import Dict, List

import numpy as np

from benchmarks import harness, loadgen, loadgen_ycsb
from benchmarks.reference import record_store

CLIENT_BASE = 1 << 20  # warm-up bursts take BASE+1.., the window BASE+64
# The warm-up is traffic and nothing else, as in drivers/served.py: the
# cell's own stream at every power of two below its threads and once at
# OVER times its threads (a wave's size picks the program it runs, and a hot
# record now and then makes a wave, or a flush of parked lanes, one bucket
# above what 1 s of the stream shows), then at its own threads until
# QUIET_BURSTS bursts in a row compiled and loaded nothing.  A burst is not
# quiet while the busiest record is short of the node's checkpoint interval
# and the window would take it past: the first checkpoint of a group cuts its
# log with a program of its own, and a hot record reaches it.
OVER = 2
RAMP_BURST_S = 0.25
WARMUP_BURST_S = 1.0
QUIET_BURSTS = 2
MAX_BURSTS = 12
SHOWN_COUNTERS = ("window_full", "parked", "park_dropped", "installs",
                  "ballot_changes", "redriven", "wave_dups", "paused",
                  "unpaused", "shed", "wal_nacked")
CONTROLS = record_store.CONTROLS


def snapshot(emu) -> dict:
    """The program's counters, all cumulative: read before and after the
    window and subtracted by the readers.  EVERY ``DelayProfiler`` total and
    every numeric node counter, so a reader of a later PR finds its own."""
    from gigapaxos_tpu.utils.engineledger import EngineLedger
    from gigapaxos_tpu.utils.profiler import DelayProfiler
    fs = DelayProfiler.snapshot(buckets=False)["histograms"].get(
        "wal.fsync", {})
    nets = [nd.transport.metrics() for nd in emu.nodes.values()]
    ctr = [nd.metrics(include_profiler=False)["counters"]
           for nd in emu.nodes.values()]
    return {
        "totals": {k: {"wall_s": v[0], "calls": v[1], "items": v[2]}
                   for k, v in DelayProfiler.totals().items()},
        "wal_fsync": {"count": fs.get("count", 0),
                      "sum_s": fs.get("sum_s", 0.0)},
        "ledger": harness.ledger(),
        "kernels": {k: v["compiles"]
                    for k, v in EngineLedger.kernels().items()},
        "net": {k: sum(n[k] for n in nets)
                for k in ("tx_bytes", "rx_bytes", "tx_writes", "rx_reads")},
        "counters": {k: [c.get(k, 0) for c in ctr] for k in ctr[0]
                     if isinstance(ctr[0][k], int)},
    }


def load_records(emu, initial: np.ndarray) -> None:
    """The load phase, untimed by YCSB too: every replica's app is given
    every record through ``Replicable.restore``, with count 0."""
    states = [record_store.checkpoint_of(0, row.tobytes()) for row in initial]
    for nd in emu.nodes.values():
        with nd._engine_lock:
            for i, state in enumerate(states):
                if not nd.app.restore(f"g{i}", state):
                    raise RuntimeError(f"record g{i} not restored")


def all_ops(results: List[dict]) -> Dict:
    """Every operation sent since the load, warm-up bursts first, as
    ``record_store.check`` takes them."""
    return {"record": np.concatenate([r["record"] for r in results]),
            "t_send": np.concatenate([r["t_send"] for r in results]),
            "t_recv": np.concatenate([r["t_recv"] for r in results]),
            "status": np.concatenate([r["status"] for r in results]),
            "payload": [p for r in results for p in r["payload"]],
            "reply": [p for r in results for p in r["reply"]]}


def replica_checkpoints(emu, initial: np.ndarray, ops: Dict,
                        timeout_s: float = 60.0):
    """Each replica's checkpoint of every record addressed, and how many of
    the others are not as loaded on some replica.  Followers execute behind
    the acknowledgement, so wait until every replica has executed what was
    acknowledged of each record (bounded)."""
    ok = (ops["t_recv"] >= 0) & (ops["status"] == 0)
    acked: Dict[int, int] = {}
    for rec, good in zip(ops["record"].tolist(), ok.tolist()):
        acked[rec] = acked.get(rec, 0) + int(good)
    touched = {rec: f"g{rec}" for rec in acked}
    deadline = time.monotonic() + timeout_s
    while True:
        behind = 0
        for nd in emu.nodes.values():
            with nd._engine_lock:
                count = nd.app.count
                behind += sum(count.get(touched[rec], 0) < c
                              for rec, c in acked.items())
        if not behind or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    cps, rest = [], []
    for nd in emu.nodes.values():
        with nd._engine_lock:
            cps.append({rec: nd.app.checkpoint(name)
                        for rec, name in touched.items()})
            rest.append({i: nd.app.checkpoint(f"g{i}")
                         for i in range(len(initial)) if i not in touched})
    changed = 0
    for i in rest[0]:
        seed_state = record_store.checkpoint_of(0, initial[i].tobytes())
        changed += sum(r[i] != seed_state for r in rest)
    return cps, changed, len(touched)


def controls(run: dict, seed: int) -> Dict[str, list]:
    """The CONTROL at the run's own size: the reference with one stated
    guarantee taken away, put in the program's place on the run's own
    operations, in the order the run took, through the same comparison."""
    initial, ops, fb, R = run["for_control"]
    order = record_store.order_of(ops)
    out = {}
    for broken in CONTROLS:
        rng = np.random.default_rng([int(seed), 0xC0])
        victim = record_store.pick_victim(initial, ops, fb, order, broken,
                                          rng)
        replies, cps = record_store.simulate(initial, ops, fb, R, order,
                                             broken, victim)
        out[broken] = record_store.check(initial, dict(ops, reply=replies),
                                         fb, cps, 0)
    return out


def run(cell, *, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    import jax

    from gigapaxos_tpu.paxos.interfaces import RecordApp
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.testing.harness import PaxosEmulation
    from gigapaxos_tpu.utils.config import Config

    cfg, mix = cell.config, cell.traffic
    R, threads = int(cfg["replicas"]), int(mix["threads"])
    n_rec, fields, fb = int(cfg["live_groups"]), int(cfg["fields"]), \
        int(cfg["field_bytes"])
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
            app_cls=functools.partial(RecordApp, fields, fb),
            capacity=int(cfg["capacity_rows"]), window=int(cfg["window"]),
            sync_wal=bool(cfg["guarantees"]["sync_wal"]))
        t_boot = time.perf_counter() - t0
        t0 = time.perf_counter()
        emu.create_groups(n_rec)
        t_create = time.perf_counter() - t0
        t0 = time.perf_counter()
        initial = record_store.initial_records(seed, n_rec, fields, fb)
        load_records(emu, initial)
        t_load = time.perf_counter() - t0

        servers = [emu.addr_map[i] for i in sorted(emu.nodes)]
        note = harness.annotate if trace else None

        def drive(secs, client, at_threads=threads, **kw):
            return asyncio.run(loadgen_ycsb.run_threads(
                servers, n_rec, secs, mix, seed=seed, client_id=client,
                fields=fields, field_bytes=fb, threads=at_threads, **kw))

        t0 = time.perf_counter()
        led0 = harness.ledger()
        results, bursts, quiet = [], [], 0
        sent_to = np.zeros(n_rec, np.int64)  # operations a record, so far
        cut = int(Config.get(PC.CHECKPOINT_INTERVAL)) + 1
        ramp = [1 << k for k in range(threads.bit_length())
                if 1 << k < threads] + [OVER * threads]
        while quiet < QUIET_BURSTS and len(bursts) < len(ramp) + MAX_BURSTS:
            i = len(bursts)
            d = ramp[i] if i < len(ramp) else threads
            before = harness.ledger()
            res = drive(WARMUP_BURST_S if d >= threads else RAMP_BURST_S,
                        CLIENT_BASE + 1 + i, at_threads=d, burst=i + 1)
            after = harness.ledger()
            lost = int(((res["t_recv"] < 0) | (res["status"] != 0)).sum())
            if lost:
                raise RuntimeError(
                    f"warm-up burst {i}: {lost} of {res['n_sent']} requests "
                    f"never answered ({int((res['t_recv'] < 0).sum())}) or "
                    "refused (statuses "
                    f"{sorted(set(res['status'][res['t_recv'] >= 0]))})")
            results.append(res)
            fresh = sum(after[k] - before[k] for k in after)
            here = np.bincount(res["record"], minlength=n_rec)
            sent_to += here
            due = bool(sent_to.max() <= cut < sent_to.max() + here.max()
                       * seconds / (res["t_end"] - res["t0"]))
            bursts.append({"threads": d, "sent": res["n_sent"],
                           "programs_loaded": fresh,
                           "busiest_record": int(sent_to.max())})
            quiet = quiet + 1 if d == threads and not fresh and not due \
                else 0
        t_warm = time.perf_counter() - t0
        led1 = harness.ledger()

        tracer = harness.tracer_for(seconds) if trace else None
        snap0 = snapshot(emu)
        setup_s = time.perf_counter() - t_start
        harness.say("setup", setup_s=round(setup_s, 3),
                    boot_s=round(t_boot, 3), creates_s=round(t_create, 3),
                    load_s=round(t_load, 3), warmup_s=round(t_warm, 3),
                    warmup_bursts=bursts, warmup_quiet=quiet >= QUIET_BURSTS,
                    **{k: led1[k] - led0[k] for k in led1})
        if tracer:
            tracer.start()
        res = drive(seconds, CLIENT_BASE + 64, annotate=note)
        snap1 = snapshot(emu)
        results.append(res)
        red = tracer.finish() if tracer else None
        peak = harness.memory_peak_bytes()
        summary = loadgen.summarize(res)

        # what the window is compared on: every operation's answer, and
        # every replica's checkpoint once the followers have caught up
        ops = all_ops(results)
        cps, changed, n_touched = replica_checkpoints(emu, initial, ops)
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
             for k in SHOWN_COUNTERS if k in snap1["counters"]}
    hot = np.bincount(res["record"]) if res["n_sent"] else np.zeros(1, int)
    reads = sum(p == b"R" for p in res["payload"])
    on_hot = res["record"] == hot.argmax()
    lat = np.where(res["t_recv"] >= 0, res["t_recv"], res["t_end"]) \
        - res["t_send"]
    harness.say("window", **{k: (round(v, 4) if isinstance(v, float) else v)
                             for k, v in summary.items()},
                drain_s=round(res["t_end"] - res["t0"] - seconds, 3),
                acked_by_second=np.bincount(
                    (res["t_recv"][res["t_recv"] >= 0]
                     - res["t0"]).astype(int)).tolist(),
                reads=int(reads), updates=int(res["n_sent"] - reads),
                resent=res["n_resent"], records_addressed=n_touched,
                hottest_record_ops=int(hot.max()),
                hottest_record_p50_ms=round(1e3 * float(np.median(
                    lat[on_hot])), 3) if res["n_sent"] else None,
                over_1s=int((lat > 1.0).sum()),
                over_1s_on_hottest=int((lat[on_hot] > 1.0).sum()),
                kernels_traced_in_window={
                    k: v - snap0["kernels"].get(k, 0)
                    for k, v in snap1["kernels"].items()
                    if v != snap0["kernels"].get(k, 0)},
                memory_peak_bytes=peak, **settings, **delta,
                compiles_in_window={k: snap1["ledger"][k] - snap0["ledger"][k]
                                    for k in snap1["ledger"]})
    t0 = time.perf_counter()
    cks = record_store.check(initial, ops, fb, cps, changed)
    off_chip = sum(p != platform for p in settings["engine_platforms"])
    cks += [("engines_off_the_device", off_chip, 0),
            ("sync_wal_off", int(not settings["sync_wal"]
                                 == bool(cfg["guarantees"]["sync_wal"])), 0),
            ("groups_paged_out", int(sum(delta.get("paused", [0]))), 0)]
    harness.say("reference", seconds=round(time.perf_counter() - t0, 3),
                requests_compared=len(ops["reply"]),
                groups_compared=n_touched)
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
        "for_control": (initial, ops, fb, R),
    }
