"""Driver of the storm configurations: ``make_fleet`` and ``ops/storm.storm``
on the chip, every step synced, inputs double-buffered.

Started as a copy of ``chip_smoke.storm_phase`` (the comparison with the
oracle over a seeded sample of groups) and ``bench.bench_columnar`` (step k
is dispatched, step k+1's lanes are drawn and copied while it runs, then k
is synced), with a deadline instead of a step count.  The rate is the
decisions of all synced steps over the whole window.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import harness
from benchmarks.reference import paxos_oracle

WARMUP_STEPS = 2  # the first compiles or loads the program, the second runs

# the state's layout (``ops/types.py``): columns of the window planes
ACC_SLOT, ACC_BAL, ACC_RLO, ACC_RHI = 0, 1, 2, 3
DEC_SLOT, DEC_RLO, DEC_RHI = 0, 1, 2


def join_req_id(lo, hi) -> int:
    return ((int(hi) & 0xFFFFFFFF) << 32) | (int(lo) & 0xFFFFFFFF)


class Lanes:
    """The seeded lane stream: step k's groups and request ids are the
    k-th draw, so a replay from the same seed sees the same steps."""

    def __init__(self, seed: int, G: int, B: int):
        self.rng = np.random.default_rng([int(seed), 0x5707])
        self.G, self.B = G, B

    def draw(self):
        g = self.rng.integers(0, self.G, self.B, dtype=np.int32)
        rlo = self.rng.integers(0, 1 << 31, self.B, dtype=np.int32)
        rhi = self.rng.integers(0, 1 << 31, self.B, dtype=np.int32)
        return g, rlo, rhi


def sample_rows(seed: int, G: int, n: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0x5A3F])
    return np.sort(rng.choice(G, size=min(n, G), replace=False))


def replay(seed: int, G: int, W: int, B: int, R: int, steps: int,
           rows: np.ndarray, broken: Optional[str] = None):
    """The reference: the same ``steps`` steps from the same seed, for the
    sampled groups only (groups are independent, so that is exact), lane by
    lane in batch order.  Returns (oracles, decided in the sample, lanes
    admitted over ALL groups)."""
    lanes = Lanes(seed, G, B)
    in_sample = np.zeros(G, bool)
    in_sample[rows] = True
    oracles = {int(g): paxos_oracle.make_fleet(R, W) for g in rows}
    decided = admitted = 0
    for k in range(steps):
        g, rlo, rhi = lanes.draw()
        # every step decides all it admits, so each group enters a step
        # with an empty window and admits up to W of its lanes
        admitted += int(np.minimum(np.bincount(g, minlength=G), W).sum())
        per_group: Dict[int, List[int]] = {}
        for i in np.flatnonzero(in_sample[g]):
            per_group.setdefault(int(g[i]), []).append(
                join_req_id(rlo[i], rhi[i]))
        for gi, ids in per_group.items():
            decided += paxos_oracle.storm_step(
                oracles[gi], ids,
                broken=broken if k == steps - 1 else None)
    return oracles, decided, admitted


def rows_from_oracles(oracles, rows, R: int, W: int) -> List[dict]:
    """The oracle's replicas in the shape the device's rows are read in:
    what the CONTROL puts in the program's place."""
    out = []
    for r in range(R):
        n = len(rows)
        hs = {"bal": np.zeros(n, np.int64),
              "exec_cursor": np.zeros(n, np.int64),
              "next_slot": np.zeros(n, np.int64),
              "cbal": np.zeros(n, np.int64),
              "is_coord": np.zeros(n, bool),
              "acc": np.full((n, W, 4), -1, np.int64),
              "dec": np.full((n, W, 3), -1, np.int64)}
        for i, g in enumerate(rows):
            og = oracles[int(g)][r]
            hs["bal"][i], hs["exec_cursor"][i] = og.bal, og.exec_cursor
            hs["next_slot"][i], hs["cbal"][i] = og.next_slot, og.cbal
            hs["is_coord"][i] = og.is_coord
            for slot in sorted(og.accepted):
                bal, req = og.accepted[slot]
                hs["acc"][i, slot % W] = (slot, bal, req & 0xFFFFFFFF,
                                          req >> 32)
            for slot in sorted(og.decided):
                req = og.decided[slot]
                hs["dec"][i, slot % W] = (slot, req & 0xFFFFFFFF, req >> 32)
        out.append(hs)
    return out


def compare_rows(rows, host_states, oracles, W: int):
    """Field by field: the rows read back against the reference's
    replicas.  Returns (fields that differ, fields compared)."""
    wrong = compared = 0
    for r, hs in enumerate(host_states):
        for i, g in enumerate(rows):
            og = oracles[int(g)][r]
            want = {"bal": og.bal, "exec_cursor": og.exec_cursor}
            if r == 0:
                want.update(next_slot=og.next_slot, cbal=og.cbal,
                            is_coord=og.is_coord)
            for f, v in want.items():
                wrong += int(hs[f][i]) != int(v)
                compared += 1
            # the rings keep the newest pvalue per window column
            for slot, (bal, req) in og.accepted.items():
                if slot + W in og.accepted:
                    continue
                a = hs["acc"][i, slot % W]
                got = (int(a[ACC_SLOT]), int(a[ACC_BAL]),
                       join_req_id(a[ACC_RLO], a[ACC_RHI]))
                wrong += got != (slot, bal, req)
                compared += 1
            for slot, req in og.decided.items():
                if slot + W in og.decided:
                    continue
                d = hs["dec"][i, slot % W]
                got = (int(d[DEC_SLOT]), join_req_id(d[DEC_RLO], d[DEC_RHI]))
                wrong += got != (slot, req)
                compared += 1
    return wrong, compared


def checks(seed, G, W, B, R, steps, rows, host_states, decided: int):
    """The numbers compared, each with its limit (exact: 0)."""
    oracles, sample_decided, admitted = replay(seed, G, W, B, R, steps, rows)
    wrong, compared = compare_rows(rows, host_states, oracles, W)
    harness.say("compared", steps=steps, sample_groups=len(rows),
                sample_decided=sample_decided, fields_compared=compared,
                decided=decided, lanes_admitted=admitted)
    return [("decided_minus_admitted", abs(decided - admitted), 0),
            ("sample_fields_wrong", wrong, 0),
            ("sample_fields_missing", int(compared < 5 * len(rows)), 0)]


CONTROLS = ("lost_commit", "no_quorum")


def controls(run: dict, seed: int) -> Dict[str, list]:
    """The CONTROL at the run's own size: the reference with one stated
    guarantee taken away in the run's last step, put in the program's
    place (its replicas read as the device's rows are), through the same
    comparison with the sound reference."""
    a = run["for_control"]
    sound, _d, admitted = replay(a["seed"], a["G"], a["W"], a["B"], a["R"],
                                 a["steps"], a["rows"])
    out = {}
    for broken in CONTROLS:
        oracles, _dec, _adm = replay(a["seed"], a["G"], a["W"], a["B"],
                                     a["R"], a["steps"], a["rows"],
                                     broken=broken)
        host = rows_from_oracles(oracles, a["rows"], a["R"], a["W"])
        wrong, compared = compare_rows(a["rows"], host, sound, a["W"])
        out[broken] = [("decided_minus_admitted", 0, 0),
                       ("sample_fields_wrong", wrong, 0),
                       ("sample_fields_missing",
                        int(compared < 5 * len(a["rows"])), 0)]
    return out


def read_rows(states, rows) -> List[dict]:
    import jax
    import jax.numpy as jnp
    jrows = jnp.asarray(rows.astype(np.int32))
    out = []
    for st in states:
        got = jax.device_get({f: getattr(st, f)[jrows] for f in (
            "bal", "exec_cursor", "next_slot", "cbal", "is_coord", "acc",
            "dec")})
        out.append(got)
    return out


def run(cell, *, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    import jax
    import jax.numpy as jnp

    from gigapaxos_tpu.ops import storm as storm_mod

    cfg, mix = cell.config, cell.traffic
    G, W, R = int(cfg["groups"]), int(cfg["window"]), int(cfg["replicas"])
    B = int(mix["lanes_per_step"])
    led0 = harness.ledger()

    t0 = time.perf_counter()
    states = storm_mod.make_fleet(G, W, R=R)
    jax.block_until_ready(states)
    t_fleet = time.perf_counter() - t0

    lanes = Lanes(seed, G, B)
    valid = jnp.ones((B,), bool)

    def make_inputs():
        with harness.annotate("bench.storm.inputs"):
            return tuple(jnp.asarray(a) for a in lanes.draw())

    def step(states, inputs):
        with harness.annotate("bench.storm.dispatch"):
            return storm_mod.storm(states, *inputs, valid)

    def sync(n) -> int:
        with harness.annotate("bench.storm.sync"):
            return int(n)

    # warm-up: the cell's own step at its own shape (the first compiles or
    # loads the program from the cache)
    t0 = time.perf_counter()
    decided = steps = 0
    warm_s = []
    for _ in range(WARMUP_STEPS):
        t1 = time.perf_counter()
        states, n = step(states, make_inputs())
        decided += sync(n)
        steps += 1
        warm_s.append(round(time.perf_counter() - t1, 4))
    nxt = make_inputs()
    jax.block_until_ready(nxt)
    t_warm = time.perf_counter() - t0
    led1 = harness.ledger()
    tracer = harness.tracer_for(seconds) if trace else None
    if tracer:
        tracer.start()

    # the window: every step synced, at most one in flight
    t_win = time.perf_counter()
    setup_s = t_win - t_start
    harness.say("setup", setup_s=round(setup_s, 3),
                fleet_build_s=round(t_fleet, 3), warmup_s=round(t_warm, 3),
                warmup_step_s=warm_s,
                **{k: led1[k] - led0[k] for k in led1})
    step_t0, step_s, step_n = [], [], []
    while True:
        t1 = time.perf_counter()
        if t1 - t_win >= seconds:
            break
        states, n = step(states, nxt)
        nxt = make_inputs()  # overlaps the step in flight
        n = sync(n)
        step_t0.append(t1)
        step_s.append(time.perf_counter() - t1)
        step_n.append(n)
    window_s = time.perf_counter() - t_win
    led2 = harness.ledger()
    win_decided = int(sum(step_n))
    decided += win_decided
    steps += len(step_n)
    red = tracer.finish() if tracer else None
    peak = harness.memory_peak_bytes()

    rows = sample_rows(seed, G, int(cfg["oracle_sample_groups"]))
    host_states = read_rows(states, rows)
    del states, nxt
    harness.say("window", window_s=round(window_s, 4), steps=len(step_n),
                decided=win_decided,
                step_ms_median=round(1e3 * float(np.median(step_s)), 3),
                step_ms_min=round(1e3 * min(step_s), 3),
                step_ms_max=round(1e3 * max(step_s), 3),
                compiles_in_window={k: led2[k] - led1[k] for k in led2},
                memory_peak_bytes=peak)

    t0 = time.perf_counter()
    cks = checks(seed, G, W, B, R, steps, rows, host_states, decided)
    harness.say("reference", seconds=round(time.perf_counter() - t0, 3))
    return {
        "attempted": len(step_n) * B, "failed": 0,
        "end_to_end": {"storm_rate": win_decided / window_s / 1e6,
                       "setup_s": setup_s},
        "memory_peak_bytes": peak, "trace": red, "checks": cks,
        "config": cfg, "traffic": mix,
        "window": {"window_s": window_s, "steps": len(step_n),
                   "decided": win_decided, "step_t0": step_t0,
                   "step_s": step_s, "step_n": step_n, "replicas": R},
        "before": {"ledger": led1}, "after": {"ledger": led2},
        "for_control": {"seed": seed, "G": G, "W": W, "B": B, "R": R,
                        "steps": steps, "rows": rows},
    }
