#!/usr/bin/env python
"""On-chip smoke: the storm kernel and the served path, once, on the TPU.

One process, no platform override, no child that needs the chip.  It
drives the two hot paths through the entry points a user calls, at the
size a deployment holds on one v5e, and checks what comes out against
the repo's plain references:

- **storm** (BASELINE config 3): ``make_fleet(G=2**20, W=16, R=3)`` and a
  few synced ``storm`` steps at ``B=2**18``; a seeded sample of groups is
  replayed lane by lane through ``ops/oracle.py`` and compared field by
  field, and the decided count is held to the lanes admitted.
- **served** (BASELINE config 2): three ``PaxosNode`` replicas in this
  process over real loopback sockets, columnar engine at capacity 2**20
  with 100,000 live groups, ``sync_wal`` on, a seeded closed-loop stream
  through ``run_load_fast``; every request acknowledged, every node's
  engine on the TPU, then the guarantees: acked writes present on all
  replicas, per-group order and digests equal across replicas
  (``chaos/invariants.py``) and equal to the same stream replayed
  through a ``backend="scalar"`` emulation on the host.
- ``--mesh`` (four chips, run by hand): only the served stream with the
  slab sharded over every local device, against the same stream with
  ``ENGINE_MESH="off"`` and the scalar replay (2**18 rows, 250,000 live
  groups, so that live rows reach all four shards).

Anything that fails raises: there is no fallback and no ``"ok": true``
without every check.  This is a smoke, not a benchmark: the wall times
it prints say where a cold run spends its minutes, nothing else.

Last stdout line on success::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from gigapaxos_tpu.utils.jaxcache import (cache_metrics,
                                          enable_persistent_cache)



def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def _peak_bytes() -> int | None:
    import jax
    ms = jax.devices()[0].memory_stats()
    return int(ms["peak_bytes_in_use"]) if ms else None


def _ledger() -> dict:
    from gigapaxos_tpu.utils.engineledger import EngineLedger
    snap = EngineLedger.snapshot()
    cm = cache_metrics()
    return {"compiles": snap["compiles"], "retraces": snap["retraces"],
            "cache_hits": cm["hits"], "cache_misses": cm["misses"]}


# --------------------------------------------------------------------------
# storm phase
# --------------------------------------------------------------------------


def _oracle_storm_step(fleet, lanes) -> int:
    """One storm step for ONE group through the scalar oracle, phase by
    phase in lane order exactly as ``decide_storm_step`` batches them.
    ``fleet`` = the group's R oracle replicas (0 coordinates);
    ``lanes`` = this step's request ids for the group.  Returns how
    many lanes were decided."""
    coord = fleet[0]
    granted = []
    for req in lanes:
        status, slot, bal = coord.propose(req)
        if status == "granted":
            granted.append((slot, bal, req))
    acks = [[og.accept(slot, bal, req)[0] for slot, bal, req in granted]
            for og in fleet]
    newly = [False] * len(granted)
    for r in range(len(fleet)):
        for i, (slot, bal, _req) in enumerate(granted):
            newly[i] |= coord.accept_reply(slot, bal, r, acks[r][i])[0]
    for og in fleet:
        for (slot, _bal, req), dec in zip(granted, newly):
            if dec:
                og.commit(slot, req)
    return sum(newly)


def _check_rows_against_oracle(rows, host_states, oracles, W) -> int:
    """Field-by-field comparison of gathered device rows with the
    oracle replicas; returns the number of values compared."""
    from gigapaxos_tpu.ops.types import (ACC_BAL, ACC_RHI, ACC_RLO,
                                         ACC_SLOT, DEC_RHI, DEC_RLO,
                                         DEC_SLOT, join_req_id)
    checked = 0
    for r, hs in enumerate(host_states):
        for i, g in enumerate(rows):
            og = oracles[int(g)][r]
            want = {"bal": og.bal, "exec_cursor": og.exec_cursor}
            if r == 0:
                want.update(next_slot=og.next_slot, cbal=og.cbal,
                            is_coord=og.is_coord)
            for f, v in want.items():
                got = getattr(hs, f)[i]
                assert int(got) == int(v), \
                    f"storm: replica {r} group {g} {f}={got} oracle={v}"
                checked += 1
            # the rings keep the newest pvalue per window column
            for slot, pv in og.accepted.items():
                if slot + W in og.accepted:
                    continue
                a = hs.acc[i, slot % W]
                got = (int(a[ACC_SLOT]), int(a[ACC_BAL]),
                       join_req_id(a[ACC_RLO], a[ACC_RHI]))
                assert got == (slot, pv.bal, pv.req_id), \
                    f"storm: replica {r} group {g} acc[{slot}]={got} " \
                    f"oracle={(slot, pv.bal, pv.req_id)}"
                checked += 1
            for slot, req in og.decided.items():
                if slot + W in og.decided:
                    continue
                d = hs.dec[i, slot % W]
                got = (int(d[DEC_SLOT]), join_req_id(d[DEC_RLO],
                                                     d[DEC_RHI]))
                assert got == (slot, req), \
                    f"storm: replica {r} group {g} dec[{slot}]={got} " \
                    f"oracle={(slot, req)}"
                checked += 1
    return checked


def storm_phase(G: int, W: int, B: int, steps: int, sample: int,
                seed: int, R: int = 3) -> dict:
    """The fused decide-storm at (G, W, B): every step synced, the
    decided count held to the lanes admitted, a seeded sample of groups
    held to the oracle.  Groups are independent, so replaying only the
    sample's lanes (in batch order) is exact."""
    import jax
    import jax.numpy as jnp

    from gigapaxos_tpu.ops.kernels import gather_rows
    from gigapaxos_tpu.ops.oracle import make_oracle_group
    from gigapaxos_tpu.ops.storm import make_fleet, storm
    from gigapaxos_tpu.ops.types import join_req_id

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    states = make_fleet(G, W, R=R)
    jax.block_until_ready(states)
    t_fleet = time.perf_counter() - t0

    rows = np.sort(rng.choice(G, size=min(sample, G), replace=False))
    in_sample = np.zeros(G, bool)
    in_sample[rows] = True
    oracles = {int(g): [make_oracle_group(R, W, 0, r == 0)
                        for r in range(R)] for g in rows}
    valid = jnp.ones((B,), bool)
    step_s, decided, admitted, oracle_decided = [], 0, 0, 0
    for _ in range(steps):
        g = rng.integers(0, G, B, dtype=np.int32)
        rlo = rng.integers(0, 1 << 31, B, dtype=np.int32)
        rhi = rng.integers(0, 1 << 31, B, dtype=np.int32)
        t1 = time.perf_counter()
        states, n = storm(states, jnp.asarray(g), jnp.asarray(rlo),
                          jnp.asarray(rhi), valid)
        n = int(n)  # the step's sync
        step_s.append(round(time.perf_counter() - t1, 3))
        decided += n
        # every step decides all it admits, so each group enters a step
        # with an empty window and admits up to W of its lanes
        admitted += int(np.minimum(np.bincount(g, minlength=G), W).sum())
        per_group: dict = {}
        for i in np.flatnonzero(in_sample[g]):
            per_group.setdefault(int(g[i]), []).append(
                join_req_id(rlo[i], rhi[i]))
        for gi, lanes in per_group.items():
            oracle_decided += _oracle_storm_step(oracles[gi], lanes)
    assert decided == admitted, \
        f"storm: decided {decided} != lanes admitted {admitted}"

    jrows = jnp.asarray(rows.astype(np.int32))
    host_states = [jax.device_get(gather_rows(st, jrows)) for st in states]
    sample_decided = int(sum(
        len(og[0].decided) for og in oracles.values()))
    assert sample_decided == oracle_decided
    checked = _check_rows_against_oracle(rows, host_states, oracles, W)
    del states
    out = {"G": G, "W": W, "B": B, "R": R, "steps": steps,
           "fleet_build_s": round(t_fleet, 2),
           "step_wall_s_first_includes_compile": step_s,
           "decided": decided, "lanes_admitted": admitted,
           "oracle_sample_groups": len(rows),
           "oracle_sample_decided": sample_decided,
           "oracle_values_compared": checked,
           "peak_bytes_in_use": _peak_bytes(), **_ledger()}
    say("storm", **out)
    return out


# --------------------------------------------------------------------------
# served phase
# --------------------------------------------------------------------------


def make_plan(seed: int, n_groups: int, n_active: int, rounds: int):
    """The seeded request stream: ``rounds`` rounds, each naming
    ``n_active`` distinct groups in a seeded order.  A round is one
    closed-loop ``run_load_fast`` call that sends every named group ONE
    request, and a round ends before the next begins — so no group ever
    has two requests outstanding and its order is the seed's, whatever
    the interleaving of waves."""
    rng = np.random.default_rng(seed)
    n_active = min(n_active, n_groups)
    return [[f"g{i}" for i in rng.choice(n_groups, n_active,
                                         replace=False)]
            for _ in range(rounds)]


def _per_device_bytes(backend) -> dict:
    out: dict = {}
    for leaf in backend.state:
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def _check_placement(emu, platform: str, mesh: str,
                     spread_over: int | None) -> dict:
    """Every node's engine state on ``platform``; no mesh where the knob
    says ``"off"``; where a mesh formed, 1/mesh of the slab on each of
    its devices; ``spread_over`` (the four-chip phase) demands a mesh of
    that many devices with live rows on every shard.  On the TPU the
    allocator must hold the slabs."""
    import jax
    slab_total, facts = 0, {"mesh": None}
    for i, nd in emu.nodes.items():
        be = nd.backend
        assert be.engine_platform == platform, \
            f"node {i}: engine on {be.engine_platform}, not {platform}"
        assert {d.platform for d in be.state.bal.devices()} == {platform}
        total = be.memory_info()["total_bytes"]
        slab_total += total
        per_dev = _per_device_bytes(be)
        n = be._mesh.size if be._mesh is not None else 1
        assert mesh != "off" or n == 1, f"node {i}: mesh despite 'off'"
        assert set(per_dev.values()) == {total // n} and \
            len(per_dev) == n, f"node {i}: per-device bytes {per_dev}"
        if spread_over is not None:
            own = be.row_ownership()["mesh"]
            assert n == spread_over == len(own) and all(own), \
                f"node {i}: mesh of {n}, live rows per shard {own}"
            facts["rows_per_shard"] = own
        if be._mesh is not None:
            facts["mesh"] = n
        facts["bytes_per_device"] = total // n
    if platform == "tpu":
        in_use = int(jax.devices()[0].memory_stats()["bytes_in_use"])
        floor = slab_total // (facts["mesh"] or 1)
        assert in_use >= floor, \
            f"device holds {in_use} B, slabs need {floor} B"
        facts["device_bytes_in_use"] = in_use
    facts["slab_bytes_total"] = slab_total
    return facts


def _lost_request_report(emu, group: str, rid: int) -> dict:
    """What every node knows of a request that was never answered: its
    trace (when ``GP_PC_TRACE_REQUESTS=1``), the host's in-flight and
    dedupe tables, the group's host mirrors and device row, and the
    node's counters — enough to tell a request that never arrived from
    one that was proposed, decided or executed and then dropped."""
    from gigapaxos_tpu.utils.instrument import RequestInstrumenter

    def plain(v):
        return v.tolist() if isinstance(v, np.ndarray) else v

    out: dict = {"group": group, "req_id": rid,
                 "trace": RequestInstrumenter.format(rid)}
    for i, nd in emu.nodes.items():
        info = nd.group_info(group) or {}
        row = info.get("row")
        fl = nd._proposed.get(rid)
        facts = {
            "group": info,
            "proposed": None if fl is None else {
                "row": fl.row, "slot": fl.slot, "bal": fl.bal,
                "age_s": round(time.time() - fl.proposed, 1),
                "redriven_s_ago": round(time.time() - fl.redriven, 1)},
            "client_wait": rid in nd._client_wait,
            "payload": [rid in nd._payloads, rid in nd._payloads_old],
            "executed": [rid in nd._executed_recent,
                         rid in nd._executed_old],
            "app_count": nd.app.count.get(group),
            "counters": nd.metrics(include_profiler=False)["counters"],
            "net_drops": nd.transport.metrics()["drops"],
            "inq": nd._inq.qsize(),
        }
        if row is not None:
            facts["decided_pending"] = {
                int(s): int(r) for s, r in nd._dec.get(row, {}).items()}
            facts["election"] = row in nd._elections or nd._mass_has(row)
            facts["parked"] = len(nd._parked.get(row, ()))
            facts["barrier"] = row in nd._catchup_barrier
            facts["in_flight_on_row"] = [
                (r, f.slot) for r, f in nd._proposed.items()
                if f.row == row]
            if hasattr(nd.backend, "snapshot_rows"):
                with nd._engine_lock:
                    snap = nd.backend.snapshot_rows([row])[0]
                facts["device_row"] = {k: plain(v) for k, v in snap.items()
                                       if k != "members"}
        out[f"node{i}"] = facts
    return out


def _drive(emu, plan, concurrency: int, label: str,
           request_timeout: float):
    """Send the plan round by round; every request must be acked.
    Returns (hist, acks, round_s): per group the acked history in
    ``chaos/invariants.py``'s record form, per round the (position,
    digest-after-execute) each request was answered with, and the
    rounds' wall times."""
    hist: dict = {}
    acks, round_s = [], []
    for rnd in plan:
        t0 = time.perf_counter()
        res = emu.run_load_fast(len(rnd), concurrency=concurrency,
                                timeout=request_timeout, groups=rnd,
                                capture=True)
        round_s.append(round(time.perf_counter() - t0, 3))
        cap = res.pop("capture")
        bad = np.flatnonzero(cap["status"] != 0)[:5]
        for k in bad:
            say("lost-request", engine=label, status=int(cap["status"][k]),
                **_lost_request_report(emu, rnd[k], int(cap["req_id"][k])))
        assert res["ok"] == len(rnd) and not res["errors"], \
            f"{label}: requests lost (status -1) or refused: {res}, " \
            f"first (group, status): " \
            f"{[(rnd[k], int(cap['status'][k])) for k in bad]}"
        row = []
        for k, g in enumerate(rnd):
            body = json.loads(cap["payload"][k])
            row.append((body["count"], body["digest"]))
            hist.setdefault(g, []).append(
                (cap["t_send"][k], cap["t_recv"][k],
                 int(cap["req_id"][k]), body["count"]))
        acks.append(row)
    return hist, acks, round_s


def _replica_state(nd, groups):
    """(counts, digests) of ``groups`` on one replica, wherever it keeps
    them: resident in the app, or — a group the deactivator paged out
    as idle (``PC.PAUSE_IDLE_S``) — in its durable pause record, which
    is what the next request to it is served from."""
    import base64

    from gigapaxos_tpu.paxos.packets import group_key
    counts, digests = {}, {}
    with nd._engine_lock:  # no pause or unpause under the reading
        for g in groups:
            if g in nd.app.count:
                counts[g], digests[g] = nd.app.count[g], nd.app.digest[g]
            elif group_key(g) in nd._paused:
                st = json.loads(base64.b64decode(json.loads(
                    nd.logger.peek_pause(group_key(g)))["app"]))
                if st["count"]:
                    counts[g], digests[g] = st["count"], st["digest"]
        spurious = [g for g, c in nd.app.count.items()
                    if c and g not in groups]
    assert not spurious, f"writes nobody sent: {spurious[:5]}"
    return counts, digests


def _check_guarantees(emu, hist, label: str):
    """Every acked write on all replicas, one order per group, digests
    converged (``chaos/invariants.py``).  Followers execute behind the
    ack, so first wait until every replica has executed every acked
    request.  Returns node 0's (counts, digests)."""
    from gigapaxos_tpu.chaos import invariants as inv
    want = {g: len(recs) for g, recs in hist.items()}
    deadline = time.monotonic() + 120.0
    while True:
        state = {i: _replica_state(nd, want)
                 for i, nd in emu.nodes.items()}
        # a group may answer every request and still be wedged for the
        # next one (a decided slot that can never execute): no node may
        # keep a decision it has not executed or a proposal in flight
        stuck = {i: (sum(s >= nd._cur[row] for row, d in nd._dec.items()
                         for s in d), len(nd._proposed))
                 for i, nd in emu.nodes.items()}
        if all(c == want for c, _d in state.values()) and \
                not any(any(v) for v in stuck.values()):
            break
        assert time.monotonic() < deadline, \
            f"{label}: replicas did not converge on the acked writes, " \
            f"or kept (unexecuted decisions, proposals in flight): {stuck}"
        time.sleep(0.1)
    counts = {i: c for i, (c, _d) in state.items()}
    digests = {i: d for i, (_c, d) in state.items()}
    errs = inv.no_lost_acks(hist, counts) + inv.digests_converged(digests)
    for g, recs in hist.items():
        errs += [f"group {g}: {e}" for e in inv.check_single_order(recs)]
        # one outstanding request per group: the k-th acked request of
        # a group was executed k-th
        if [r[3] for r in recs] != list(range(1, len(recs) + 1)):
            errs.append(f"group {g}: positions {[r[3] for r in recs]}")
    assert not errs, f"{label}: {errs[:10]}"
    return counts[0], digests[0]


def run_stream(backend: str, plan, *, n_groups: int, capacity: int,
               window: int, concurrency: int, mesh: str = "auto",
               request_timeout: float = 300.0,
               platform: str | None = None,
               spread_over: int | None = None,
               only_touched_groups: bool = False) -> dict:
    """Boot a 3-replica emulation (``sync_wal`` on), drive ``plan``
    through ``run_load_fast``, hold the run to its guarantees and return
    what a comparison with another engine needs."""
    from gigapaxos_tpu.paxos.interfaces import CounterApp
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.testing import loadgen
    from gigapaxos_tpu.testing.harness import PaxosEmulation
    from gigapaxos_tpu.utils.config import Config
    from gigapaxos_tpu.utils.profiler import DelayProfiler

    def submits():
        return DelayProfiler.totals().get("eng.submit", (0.0, 0, 0, 0.0))

    # req_id = client_id << 32 | seq and CounterApp's digest mixes it in:
    # every engine's stream must carry the same ids (fresh servers each
    # time, so their dedup caches cannot see a reused id)
    loadgen._next_client = None
    label = backend if backend != "columnar" else f"columnar/mesh={mesh}"
    prior_mesh = Config.get(PC.ENGINE_MESH)
    Config.set(PC.ENGINE_MESH, mesh)
    logdir = tempfile.mkdtemp(prefix="gp_chip_smoke_")
    emu = None
    try:
        led0 = _ledger()
        t0 = time.perf_counter()
        emu = PaxosEmulation(logdir, n_nodes=3, n_groups=0,
                             backend=backend, app_cls=CounterApp,
                             capacity=capacity, window=window,
                             sync_wal=True)
        t_boot = time.perf_counter() - t0
        t0 = time.perf_counter()
        if only_touched_groups:
            emu.create_groups(0, names=sorted(
                {g for rnd in plan for g in rnd}, key=lambda s: int(s[1:])))
        else:
            emu.create_groups(n_groups)
        t_create = time.perf_counter() - t0
        placement = {} if platform is None else \
            _check_placement(emu, platform, mesh, spread_over)

        sub0 = submits()
        hist, acks, round_s = _drive(emu, plan, concurrency, label,
                                     request_timeout)
        sub1 = submits()
        counts, digests = _check_guarantees(emu, hist, label)

        led1 = _ledger()
        calls, items = sub1[1] - sub0[1], sub1[2] - sub0[2]
        facts = {
            "engine": label, "nodes": 3, "capacity": capacity,
            "window": window, "sync_wal": bool(Config.get(PC.SYNC_WAL)),
            "pause_idle_s": Config.get(PC.PAUSE_IDLE_S),
            "failure_timeout_s": Config.get(PC.FAILURE_TIMEOUT_S),
            "groups_created": len(emu.groups),
            "requests": sum(len(r) for r in plan),
            "acked": sum(len(r) for r in acks),
            "concurrency": concurrency,
            "boot_s": round(t_boot, 2),
            "create_groups_s": round(t_create, 2),
            "round_wall_s_first_includes_compiles": round_s,
            "waves_fused": [bool(nd._fuse_waves)
                            for nd in emu.nodes.values()],
            "engine_dispatches": calls,
            "lanes_per_dispatch": round(items / calls, 1) if calls else None,
            "platforms": [nd.backend.engine_platform
                          for nd in emu.nodes.values()],
            # what the nodes did beside serving: coordinator installs
            # and ballot changes (a peer suspected while it sat in the
            # compiler), accept re-drives, copies of a request read in
            # one wave (retransmits piled up behind a stall), groups
            # paged out as idle
            **{k: [nd.metrics(include_profiler=False)["counters"][k]
                   for nd in emu.nodes.values()]
               for k in ("installs", "ballot_changes", "redriven",
                         "wave_dups", "paused", "unpaused")},
            **placement,
            **{k: led1[k] - led0[k] for k in led1},
            "peak_bytes_in_use": _peak_bytes(),
        }
        say("served", **facts)
        return {"acks": acks, "counts": counts, "digests": digests,
                "facts": facts}
    finally:
        if emu is not None:
            emu.stop()
        Config.set(PC.ENGINE_MESH, prior_mesh)
        shutil.rmtree(logdir, ignore_errors=True)


def assert_same_results(a: dict, b: dict, what: str) -> None:
    """Two engines, one seeded stream: every request must have been
    given the same position and left the same digest, and the apps must
    end in the same state."""
    assert a["acks"] == b["acks"], f"{what}: per-request results differ"
    assert a["counts"] == b["counts"], f"{what}: final counts differ"
    assert a["digests"] == b["digests"], f"{what}: final digests differ"


def served_phase(*, n_groups: int, capacity: int, window: int,
                 n_active: int, rounds: int, concurrency: int, seed: int,
                 platform: str, mesh_ab: bool = False,
                 request_timeout: float = 300.0) -> dict:
    """The served path as a node with no configuration builds it — on
    the default device, ``ENGINE_MESH="auto"`` (a mesh over every local
    device when there are several) — against the scalar replay.
    ``mesh_ab`` is the four-chip phase: the mesh must form over every
    local device with live rows on every shard, and the same stream also
    runs with ``ENGINE_MESH="off"``."""
    import jax
    plan = make_plan(seed, n_groups, n_active, rounds)
    common = dict(n_groups=n_groups, capacity=capacity, window=window,
                  concurrency=concurrency, request_timeout=request_timeout)
    col = run_stream(
        "columnar", plan, mesh="auto", platform=platform,
        spread_over=len(jax.local_devices()) if mesh_ab else None,
        **common)
    if mesh_ab:
        off = run_stream("columnar", plan, mesh="off", platform=platform,
                         **common)
        assert_same_results(col, off, "mesh vs ENGINE_MESH=off")
    # the plain reference: the host's scalar engine (no JAX).  Groups
    # are independent and idle ones have no state to compare, so it
    # creates only the groups the stream touches.
    ref = run_stream("scalar", plan, only_touched_groups=True, **common)
    assert_same_results(col, ref, "columnar vs scalar replay")
    out = {"requests": col["facts"]["requests"],
           "groups_touched": len(col["counts"]),
           "mesh": col["facts"]["mesh"],
           "replicas_agree": True, "scalar_replay_agrees": True}
    if mesh_ab:
        out["mesh_off_agrees"] = True
    say("served-verdict", **out)
    return out


# --------------------------------------------------------------------------


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=22)
    p.add_argument("--mesh", action="store_true",
                   help="four chips: run ONLY the served stream with the "
                        "slab sharded over every local device, against "
                        "ENGINE_MESH=off and the scalar replay")
    p.add_argument("--storm-groups", type=int, default=1 << 20)
    p.add_argument("--storm-batch", type=int, default=1 << 18)
    p.add_argument("--storm-steps", type=int, default=4)
    p.add_argument("--storm-sample", type=int, default=1024)
    p.add_argument("--window", type=int, default=16)
    p.add_argument("--groups", type=int, default=None,
                   help="live groups per node (default 100000; --mesh "
                        "250000 so that live rows reach every shard)")
    p.add_argument("--capacity", type=int, default=None,
                   help="slab rows (default 2**20; --mesh 2**18)")
    p.add_argument("--active", type=int, default=1000,
                   help="groups named in each round of the stream")
    p.add_argument("--rounds", type=int, default=5,
                   help="rounds of --active requests (the first also "
                        "absorbs the cold compiles)")
    p.add_argument("--concurrency", type=int, default=256)
    p.add_argument("--request-timeout", type=float, default=300.0,
                   help="seconds of once-a-second retransmits after "
                        "which an unanswered request counts as lost")
    return p


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    enable_persistent_cache()
    import jax

    from gigapaxos_tpu import native

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX gave this process {device}; the smoke "
              "runs on a TPU or not at all", file=sys.stderr)
        return 2
    if not native.have_native():
        print("chip_smoke: native/_hotpath.so did not build; the served "
              "path's host code must be the C++ one", file=sys.stderr)
        return 3
    if args.mesh and device["count"] != 4:
        print(f"chip_smoke: --mesh needs four chips, found "
              f"{device['count']}", file=sys.stderr)
        return 4
    say("start", device=device, cache=cache_metrics(), seed=args.seed,
        native=True)

    t_all = time.perf_counter()
    if args.mesh:
        groups, capacity = args.groups or 250_000, args.capacity or 1 << 18
        say("cuts", live_groups=groups, slab_rows=capacity, note=(
            "rows are handed out from 0 up, so live rows reach all four "
            "shards only in a slab they nearly fill: 250K of 2^18 rows "
            "here, where 2^20 rows would need >786K creates per node"))
    else:
        t0 = time.perf_counter()
        storm_phase(args.storm_groups, args.window, args.storm_batch,
                    args.storm_steps, args.storm_sample, args.seed)
        say("storm-wall", seconds=round(time.perf_counter() - t0, 1))
        groups, capacity = args.groups or 100_000, args.capacity or 1 << 20
        say("cuts", live_groups=groups, slab_rows=capacity, note=(
            "100K live groups in a 2^20-row slab (BASELINE config 2); "
            "1M live groups would add ~4.5 min of host-side creates"))
    t0 = time.perf_counter()
    served_phase(n_groups=groups, capacity=capacity, window=args.window,
                 n_active=args.active, rounds=args.rounds,
                 concurrency=args.concurrency, seed=args.seed,
                 platform="tpu", mesh_ab=args.mesh,
                 request_timeout=args.request_timeout)
    say("served-wall", seconds=round(time.perf_counter() - t0, 1))
    say("done", total_wall_s=round(time.perf_counter() - t_all, 1),
        peak_bytes_in_use=_peak_bytes(), **_ledger())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
