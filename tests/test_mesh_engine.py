"""Device-mesh columnar engine (PC.ENGINE_MESH tentpole): the
shard_map kernel table (``ops/meshkernels.py``) at mesh=4 must be
bit-identical to the unsharded engine at the backend SPI (including
the fused dual-input and coordinator-self waves), produce identical
per-group decisions at the node level, and a blackbox capture recorded
under either mesh mode must replay bit-for-bit MATCH under the other —
the cross-mesh proof the knob's "off stays byte-for-byte" contract
rests on.  Modeled on ``test_wave_async.py``'s parity harness; the
test env's virtual 8-device mesh (conftest) provides the devices.
"""

import os

import numpy as np
import pytest

from gigapaxos_tpu.paxos.backend import ColumnarBackend
from gigapaxos_tpu.paxos.paxosconfig import PC
from gigapaxos_tpu.utils.config import Config
from tests.conftest import tscale

MESH = 4


def _mk(cap, W, mesh):
    Config.set(PC.ENGINE_MESH, mesh)
    bk = ColumnarBackend(cap, W)
    Config.unset(PC.ENGINE_MESH)
    want = "off" if mesh == "off" else mesh
    assert bk.engine_mesh == want, (bk.engine_mesh, want)
    rows = np.arange(cap, dtype=np.int32)
    bk.create(rows, np.full(cap, 3, np.int32), np.zeros(cap, np.int32),
              np.zeros(cap, np.int32), np.ones(cap, bool))
    return bk


def _assert_res_equal(a, b, msg):
    fields = getattr(a, "_fields", range(len(a)))
    for fa, fb, name in zip(a, b, fields):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb),
                                      err_msg=f"{msg}.{name}")


@pytest.mark.parametrize("seed", [0, 1])
def test_mesh_backend_parity_random_multitype(seed):
    """One unsharded backend and one mesh=4 backend driven through the
    same randomized multi-type op stream (duplicate-group batches,
    plain + fused dual-input waves, quorum replies) stay BIT-IDENTICAL
    in every output and in the final device state of every row."""
    W, cap, n = 8, 128, 64
    rng = np.random.default_rng(seed)
    plain = _mk(cap, W, mesh="off")
    mesh = _mk(cap, W, mesh=MESH)
    prev = None  # (rows, slots, reqs) decided in the prior round
    for round_ in range(4):
        rows = rng.integers(0, cap, n).astype(np.int32)
        reqs = ((np.uint64(round_ + 1) << np.uint64(40))
                | rng.integers(1, 1 << 31, n).astype(np.uint64))
        pr_p = plain.propose(rows, reqs)
        pr_m = mesh.propose(rows, reqs)
        _assert_res_equal(pr_p, pr_m, f"r{round_}.propose")
        if round_ % 2 and prev is not None:
            # fused accept+commit: the dual-input shard_map program
            ap, cp = plain.accept_commit(rows, pr_p.slot, pr_p.cbal,
                                         reqs, *prev)
            am, cm = mesh.accept_commit(rows, pr_m.slot, pr_m.cbal,
                                        reqs, *prev)
            _assert_res_equal(ap, am, f"r{round_}.f.accept")
            _assert_res_equal(cp, cm, f"r{round_}.f.commit")
        else:
            ap = plain.accept(rows, pr_p.slot, pr_p.cbal, reqs)
            am = mesh.accept(rows, pr_m.slot, pr_m.cbal, reqs)
            _assert_res_equal(ap, am, f"r{round_}.accept")
            if prev is not None:
                _assert_res_equal(plain.commit(*prev),
                                  mesh.commit(*prev),
                                  f"r{round_}.commit")
        newly = np.zeros(n, bool)
        for s in range(2):
            sid = np.full(n, s, np.int32)
            rr_p = plain.accept_reply(rows, pr_p.slot, pr_p.cbal, sid,
                                      ap.acked)
            rr_m = mesh.accept_reply(rows, pr_m.slot, pr_m.cbal, sid,
                                     am.acked)
            _assert_res_equal(rr_p, rr_m, f"r{round_}.reply{s}")
            newly |= np.asarray(rr_p.newly_decided)
        keep = np.flatnonzero(newly & np.asarray(pr_p.granted))
        prev = (rows[keep], np.asarray(pr_p.slot)[keep], reqs[keep])
    # prepare exercises the [B, W] window merge across mesh shards
    pr_rows = rng.permutation(cap)[:32].astype(np.int32)
    bals = np.full(32, 1 << 10, np.int32)
    _assert_res_equal(plain.prepare(pr_rows, bals),
                      mesh.prepare(pr_rows, bals), "prepare")
    # the decisive check: full per-row device state agrees
    snaps_p = plain.snapshot_rows(np.arange(cap))
    snaps_m = mesh.snapshot_rows(np.arange(cap))
    for r, (sp, sm) in enumerate(zip(snaps_p, snaps_m)):
        for f in sp:
            np.testing.assert_array_equal(
                sp[f], sm[f], err_msg=f"state row {r} field {f}")


def test_mesh_propose_self_parity():
    """The fused coordinator waves (propose + own accept + own vote,
    then reply + own commit) agree across mesh modes — these are the
    packed programs with the widest output stacks."""
    W, cap, n = 8, 64, 48
    plain = _mk(cap, W, mesh="off")
    mesh = _mk(cap, W, mesh=MESH)
    rng = np.random.default_rng(7)
    rows = rng.integers(0, cap, n).astype(np.int32)
    reqs = rng.integers(1, 1 << 62, n).astype(np.uint64)
    midx = np.zeros(n, np.int32)
    outs_p = plain.propose_self(rows, reqs, midx)
    outs_m = mesh.propose_self(rows, reqs, midx)
    _assert_res_equal(outs_p[0], outs_m[0], "propose_self.res")
    for i in range(1, 5):
        np.testing.assert_array_equal(np.asarray(outs_p[i]),
                                      np.asarray(outs_m[i]),
                                      err_msg=f"propose_self[{i}]")
    slots = np.asarray(outs_p[0].slot)
    granted = np.asarray(outs_p[0].granted)
    gi = np.flatnonzero(granted)
    rr_p = plain.accept_reply_commit_self(
        rows[gi], slots[gi], np.asarray(outs_p[0].cbal)[gi],
        np.ones(len(gi), np.int32), np.ones(len(gi), bool))
    rr_m = mesh.accept_reply_commit_self(
        rows[gi], slots[gi], np.asarray(outs_m[0].cbal)[gi],
        np.ones(len(gi), np.int32), np.ones(len(gi), bool))
    _assert_res_equal(rr_p[0], rr_m[0], "arcs.res")
    np.testing.assert_array_equal(rr_p[1], rr_m[1], err_msg="arcs.app")
    np.testing.assert_array_equal(rr_p[2], rr_m[2], err_msg="arcs.st")


@pytest.mark.smoke
@pytest.mark.parametrize("case", ["all", "rep+com", "handoff", "chunked"])
def test_mesh_node_wave_parity(case):
    """The whole worker batch in one shard_map program
    (``mesh.node_wave_p``: four sections, each masked to the lanes its
    shard owns, one psum) against the unsharded pair calls it replaces:
    results and every row's state bit-identical, the chunked wave too."""
    from tests.test_fused_wave import check_wave_submit
    Config.set(PC.ENGINE_MESH, MESH)
    try:
        check_wave_submit(case, mesh=None)
    finally:
        Config.unset(PC.ENGINE_MESH)


def test_engine_mesh_knob_resolution():
    """Knob authority (resolve_engine_mesh): an explicit N beyond this
    host's devices degrades to single-device (a big-mesh capture must
    replay on a small box), non-dividing capacity blocks auto, an
    explicit N that fits is taken, and ``mesh="off"`` beats the
    knob."""
    # more than the 8 virtual devices -> warned single-device fallback
    Config.set(PC.ENGINE_MESH, 64)
    bk = ColumnarBackend(128, 8)
    assert bk._mesh is None and bk.engine_mesh == "off"
    # capacity % devices != 0 blocks "auto" (no ragged shards)
    Config.set(PC.ENGINE_MESH, "auto")
    bk = ColumnarBackend(100, 8)
    assert bk._mesh is None and bk.engine_mesh == "off"
    # an explicit N that fits is taken; the argument beats the knob
    Config.set(PC.ENGINE_MESH, 2)
    assert ColumnarBackend(128, 8).engine_mesh == 2
    assert ColumnarBackend(128, 8, mesh="off").engine_mesh == "off"


# -- node level -----------------------------------------------------------


def _run_traffic(tmpdir, mesh, n_seq=40, n_burst=72, n_groups=8):
    """One 2-node cluster (quorum 2: accepts/replies/commits cross the
    wire).  Sequential phase -> order-sensitive digests prove identical
    decisions; concurrent burst -> counts prove exactly-once.  Same
    discipline as the mesh parity harness above, with the ramp that
    keeps a cold jit cache from eating client deadlines."""
    import shutil
    import time

    from gigapaxos_tpu.testing.harness import PaxosEmulation
    from gigapaxos_tpu.paxos.interfaces import CounterApp

    Config.set(PC.ENGINE_MESH, mesh)
    d = os.path.join(tmpdir, f"m{mesh}")
    emu = PaxosEmulation(d, n_nodes=2, n_groups=n_groups, group_size=2,
                         backend="columnar", app_cls=CounterApp,
                         capacity=256, window=16)
    try:
        want_mesh = "off" if mesh == "off" else mesh
        assert emu.nodes[0].backend.engine_mesh == want_mesh
        res = emu.run_load(n_seq, concurrency=1, timeout=tscale(30))
        assert res["errors"] == 0, res
        app = emu.nodes[0].app
        digests = {g: app.digest.get(g) for g in emu.groups}
        # ramp at the BURST's concurrency: it compiles the same batch
        # bucket the burst will hit, so a cold jit cache pays its
        # compile storm here instead of inside the measured burst
        # (where 16-deep closed-loop retransmits can exhaust client
        # deadlines — observed on a cold cache)
        emu.run_load(16, concurrency=16, timeout=tscale(90),
                     client_id=1 << 23)
        res = emu.run_load(n_burst, concurrency=16, timeout=tscale(90),
                           client_id=1 << 21)
        assert res["errors"] == 0, res
        total = n_seq + 16 + n_burst
        want = {g: total // n_groups + (1 if i < total % n_groups
                                        else 0)
                for i, g in enumerate(emu.groups)}
        deadline = time.time() + tscale(10)
        while time.time() < deadline and \
                any(app.count.get(g, 0) < want[g] for g in emu.groups):
            time.sleep(0.1)  # lagging commits drain
        counts = {g: app.count.get(g) for g in emu.groups}
        assert counts == want, (counts, want)
        return digests, counts
    finally:
        emu.stop()
        Config.unset(PC.ENGINE_MESH)
        shutil.rmtree(d, ignore_errors=True)


def test_mesh_node_decisions_match_off(tmp_path):
    """Acceptance: multi-type traffic on a mesh=4 node produces
    IDENTICAL per-group decisions (order-sensitive digests over the
    sequential phase, exactly-once counts over the burst) to the
    unsharded run of the same workload."""
    dig_off, cnt_off = _run_traffic(str(tmp_path), "off")
    dig_m, cnt_m = _run_traffic(str(tmp_path), MESH)
    assert dig_off == dig_m
    assert cnt_off == cnt_m


# -- blackbox cross-mesh replay proof -------------------------------------


def test_blackbox_cross_mesh_replay(tmp_path):
    """The replay proof both directions: a capture recorded unsharded
    replays bit-for-bit MATCH on a mesh-sharded engine, and a capture
    recorded mesh-sharded (manifest records engine_mesh=4) replays
    MATCH unsharded AND sharded-from-manifest.  The per-wave digests
    fold host mirrors, so any divergence in the shard_map kernels
    would surface as a wave digest mismatch here."""
    from gigapaxos_tpu.blackbox.capture import read_capture
    from gigapaxos_tpu.blackbox.__main__ import record_demo
    from gigapaxos_tpu.blackbox.replay import replay_capture

    cap_off = str(tmp_path / "off.gpbb")
    record_demo(cap_off, n_requests=32, n_groups=4, mesh="off")
    _, man = read_capture(cap_off)
    assert man["knobs"]["engine_mesh"] == "off"
    rep = replay_capture(cap_off, mesh=MESH)
    assert rep["verdict"] == "MATCH", rep
    assert rep["waves_diverged"] == 0

    cap_mesh = str(tmp_path / "mesh.gpbb")
    record_demo(cap_mesh, n_requests=32, n_groups=4, mesh=MESH)
    _, man = read_capture(cap_mesh)
    assert man["knobs"]["engine_mesh"] == MESH
    rep = replay_capture(cap_mesh, mesh="off")
    assert rep["verdict"] == "MATCH", rep
    # no override: the manifest's engine_mesh=4 pins the replay shape
    rep = replay_capture(cap_mesh)
    assert rep["verdict"] == "MATCH", rep
