"""Driver of the read-mostly record store (``ycsb-b-3r-100k``): YCSB core
workload B over a record per group, with reads answered by the read index
(``PC.READ_INDEX``: a read takes no slot; the group's coordinator has a
majority confirm its ballot and answers once it has executed every slot
below its ``next_slot`` at the read's arrival).

The deployment, the generator and the set-up are ``drivers/ycsb.py``'s,
imported where they are functions (``snapshot``, ``load_records``,
``all_ops``, ``replica_checkpoints``; ``loadgen_ycsb.run_threads``), so a
change to either path is measured on both.  This driver's own: the look for
a program that can run the configuration, the comparison with
``reference/record_store_ri.py`` (updates take places, reads state how many
they saw), the replicas' checkpoints over the records an update addressed,
and the read index's counters on the ``window`` line.
``drivers/ycsb_ri.md`` says what a mix may set and what ``correct``
compares.
"""

from __future__ import annotations

import asyncio
import functools
import shutil
import tempfile
import time
from typing import Dict

import numpy as np

from benchmarks import harness, loadgen, loadgen_ycsb
from benchmarks.drivers import ycsb
from benchmarks.reference import record_store_ri as ref

CLIENT_BASE = ycsb.CLIENT_BASE
OVER, RAMP_BURST_S, WARMUP_BURST_S = ycsb.OVER, ycsb.RAMP_BURST_S, \
    ycsb.WARMUP_BURST_S
QUIET_BURSTS, MAX_BURSTS = ycsb.QUIET_BURSTS, ycsb.MAX_BURSTS
SHOWN_COUNTERS = ycsb.SHOWN_COUNTERS
RI_TOTALS = ("ri.reads", "ri.fallbacks", "ri.confirm_frames")
CONTROLS = ref.CONTROLS


def can_run() -> str:
    """Why the program cannot run this configuration, or "" where it
    can: reads by read index need the ``PC.READ_INDEX`` setting and an app
    boundary that can answer a read (``Replicable.read``)."""
    from gigapaxos_tpu.paxos.interfaces import Replicable
    from gigapaxos_tpu.paxos.paxosconfig import PC
    if not hasattr(PC, "READ_INDEX"):
        return "the program has no PC.READ_INDEX: reads would go as rounds"
    if not hasattr(Replicable, "read"):
        return "the program's Replicable has no read()"
    return ""


def only(ops: Dict, mask: np.ndarray) -> Dict:
    """The operations of ``mask``, in the form ``all_ops`` gives."""
    idx = np.flatnonzero(mask)
    return {k: ([v[i] for i in idx] if isinstance(v, list) else v[mask])
            for k, v in ops.items()}


def controls(run: dict, seed: int) -> Dict[str, list]:
    """The CONTROL at the run's own size: the reference with one stated
    guarantee taken away, put in the program's place on the run's own
    operations, in the order the run took, through the same comparison."""
    initial, ops, fb, R = run["for_control"]
    order = ref.order_of(ops)
    out = {}
    for broken in CONTROLS:
        rng = np.random.default_rng([int(seed), 0xC0])
        victim = ref.pick_victim(initial, ops, fb, order, broken, rng)
        replies, cps = ref.simulate(initial, ops, fb, R, order, broken,
                                    victim)
        out[broken] = ref.check(initial, dict(ops, reply=replies), fb, cps,
                                0)
    return out


def run(cell, *, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    why = can_run()
    if why:
        raise SystemExit(f"benchmarks/drivers/ycsb_ri.py: cannot run "
                         f"{cell.name}: {why}")
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
        initial = ref.initial_records(seed, n_rec, fields, fb)
        ycsb.load_records(emu, initial)
        t_load = time.perf_counter() - t0

        servers = [emu.addr_map[i] for i in sorted(emu.nodes)]
        note = harness.annotate if trace else None

        def drive(secs, client, at_threads=threads, **kw):
            return asyncio.run(loadgen_ycsb.run_threads(
                servers, n_rec, secs, mix, seed=seed, client_id=client,
                fields=fields, field_bytes=fb, threads=at_threads, **kw))

        # ycsb.py's warm-up rule: the stream at every power of two below
        # its threads and once at OVER times them, then at its threads
        # until QUIET_BURSTS bursts in a row loaded nothing
        t0 = time.perf_counter()
        led0 = harness.ledger()
        results, bursts, quiet = [], [], 0
        sent_to = np.zeros(n_rec, np.int64)  # updates a record, so far
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
            # a checkpoint cut is a program of its own: only updates take
            # slots here, so only they bring a record to its first cut
            upd = np.asarray([p[:1] == b"U" for p in res["payload"]], bool)
            here = np.bincount(res["record"][upd], minlength=n_rec)
            sent_to += here
            due = bool(sent_to.max() <= cut < sent_to.max() + here.max()
                       * seconds / (res["t_end"] - res["t0"]))
            bursts.append({"threads": d, "sent": res["n_sent"],
                           "programs_loaded": fresh,
                           "busiest_record_updates": int(sent_to.max())})
            quiet = quiet + 1 if d == threads and not fresh and not due \
                else 0
        t_warm = time.perf_counter() - t0
        led1 = harness.ledger()

        tracer = harness.tracer_for(seconds) if trace else None
        snap0 = ycsb.snapshot(emu)
        setup_s = time.perf_counter() - t_start
        harness.say("setup", setup_s=round(setup_s, 3),
                    boot_s=round(t_boot, 3), creates_s=round(t_create, 3),
                    load_s=round(t_load, 3), warmup_s=round(t_warm, 3),
                    warmup_bursts=bursts, warmup_quiet=quiet >= QUIET_BURSTS,
                    **{k: led1[k] - led0[k] for k in led1})
        if tracer:
            tracer.start()
        res = drive(seconds, CLIENT_BASE + 64, annotate=note)
        snap1 = ycsb.snapshot(emu)
        results.append(res)
        red = tracer.finish() if tracer else None
        peak = harness.memory_peak_bytes()
        summary = loadgen.summarize(res)

        # what the window is compared on: every operation's answer, and
        # every replica's checkpoint of each record an update addressed
        # once the followers have executed what was acknowledged of it
        ops = ycsb.all_ops(results)
        updates = np.asarray([ref.is_update(p) for p in ops["payload"]],
                             bool)
        cps, changed, n_touched = ycsb.replica_checkpoints(
            emu, initial, only(ops, updates))
        platform = jax.devices()[0].platform
        settings = {
            "engine_platforms": [getattr(nd.backend, "engine_platform", None)
                                 for nd in emu.nodes.values()],
            "waves_fused": [bool(nd._fuse_waves)
                            for nd in emu.nodes.values()],
            "sync_wal": bool(Config.get(PC.SYNC_WAL)),
            "read_index": bool(Config.get(PC.READ_INDEX)),
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
    ri = {k: harness.delta_total({"before": snap0, "after": snap1}, k,
                                 "items") or 0 for k in RI_TOTALS}
    hot = np.bincount(res["record"]) if res["n_sent"] else np.zeros(1, int)
    reads = sum(p == b"R" for p in res["payload"])
    on_hot = res["record"] == hot.argmax()
    lat = np.where(res["t_recv"] >= 0, res["t_recv"], res["t_end"]) \
        - res["t_send"]
    is_read = np.asarray([p == b"R" for p in res["payload"]], bool)
    harness.say("window", **{k: (round(v, 4) if isinstance(v, float) else v)
                             for k, v in summary.items()},
                drain_s=round(res["t_end"] - res["t0"] - seconds, 3),
                acked_by_second=np.bincount(
                    (res["t_recv"][res["t_recv"] >= 0]
                     - res["t0"]).astype(int)).tolist(),
                reads=int(reads), updates=int(res["n_sent"] - reads),
                read_p50_ms=round(1e3 * float(np.median(lat[is_read])), 3)
                if is_read.any() else None,
                update_p50_ms=round(1e3 * float(np.median(lat[~is_read])), 3)
                if (~is_read).any() else None,
                reads_by_read_index=ri["ri.reads"],
                reads_as_rounds=ri["ri.fallbacks"],
                confirm_frames=ri["ri.confirm_frames"],
                resent=res["n_resent"], records_updated=n_touched,
                hottest_record_ops=int(hot.max()),
                hottest_record_p50_ms=round(1e3 * float(np.median(
                    lat[on_hot])), 3) if res["n_sent"] else None,
                over_1s=int((lat > 1.0).sum()),
                kernels_traced_in_window={
                    k: v - snap0["kernels"].get(k, 0)
                    for k, v in snap1["kernels"].items()
                    if v != snap0["kernels"].get(k, 0)},
                memory_peak_bytes=peak, **settings, **delta,
                compiles_in_window={k: snap1["ledger"][k] - snap0["ledger"][k]
                                    for k in snap1["ledger"]})
    t0 = time.perf_counter()
    cks = ref.check(initial, ops, fb, cps, changed)
    off_chip = sum(p != platform for p in settings["engine_platforms"])
    cks += [("engines_off_the_device", off_chip, 0),
            ("sync_wal_off", int(not settings["sync_wal"]
                                 == bool(cfg["guarantees"]["sync_wal"])), 0),
            ("read_index_off", int(not settings["read_index"]
                                   or not ri["ri.reads"]), 0),
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
