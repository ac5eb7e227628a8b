"""The plain references and their controls: the comparison passes the
reference against itself and fails every control (a guarantee of the
configuration taken away)."""

import numpy as np
import pytest

from benchmarks import loadgen
from benchmarks.drivers import served as served_drv  # noqa: F401
from benchmarks.drivers import storm as storm_drv
from benchmarks.reference import counter_rsm, paxos_oracle


def fake_results(streams, answers):
    """Generator results that say what ``answers`` says."""
    import json
    out = []
    for st, ans in zip(streams, answers):
        n = len(st)
        out.append({"n_sent": n, "t_recv": np.ones(n), "status": np.zeros(n),
                    "reply": [json.dumps({"count": c, "digest": d}).encode()
                              for c, d in ans]})
    return out


def make_streams(seed, n_groups=16, warm=40, window=200):
    names = loadgen.plan_groups(seed, 100, n_groups)
    cid = (1 << 20) + 1
    return [[(names[k % n_groups], (cid + s) << 32 | k) for k in range(n)]
            for s, n in enumerate((warm, window))]


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_served_reference_against_itself_is_correct(seed):
    streams = make_streams(seed)
    ans, states = counter_rsm.replay(streams)
    cks = served_drv.compare(streams, fake_results(streams, ans), states,
                             0, 3)
    assert all(v <= lim for _n, v, lim in cks)
    # counts are positions in the group's order
    assert ans[1][0][0] == 40 // 16 + 1 + (0 < 40 % 16)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("broken, caught_by", [
    ("lost_write", "replica_groups_diverged"),
    ("reordered", "replica_groups_diverged"),
    ("doubled", "answers_wrong"),
    ("stale_answer", "answers_wrong"),
])
def test_served_controls_are_not_correct(seed, broken, caught_by):
    streams = make_streams(seed)
    ans, states = counter_rsm.replay(streams, broken=broken,
                                     victim=17 + seed)
    cks = {n: (v, lim) for n, v, lim in served_drv.compare(
        streams, fake_results(streams, ans), states, 0, 3)}
    assert cks[caught_by][0] > cks[caught_by][1]


def storm_case(seed, broken=None, G=64, W=8, B=256, R=3, steps=5):
    rows = storm_drv.sample_rows(seed, G, 48)
    oracles, _dec, _adm = storm_drv.replay(seed, G, W, B, R, steps, rows,
                                           broken=broken)
    host = storm_drv.rows_from_oracles(oracles, rows, R, W)
    sound, _d, _a = storm_drv.replay(seed, G, W, B, R, steps, rows)
    return storm_drv.compare_rows(rows, host, sound, W)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_storm_reference_against_itself_is_correct(seed):
    wrong, compared = storm_case(seed)
    assert wrong == 0 and compared > 48 * 5


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("broken", ["lost_commit", "no_quorum"])
def test_storm_controls_are_not_correct(seed, broken):
    wrong, _compared = storm_case(seed, broken=broken)
    assert wrong > 0


def test_oracle_copy_agrees_with_the_repos_oracle():
    """The benchmark's copy of the scalar oracle against the original, lane
    by lane (the original stays the kernels' property-test oracle)."""
    from gigapaxos_tpu.ops.oracle import make_oracle_group
    rng = np.random.default_rng(4)
    mine = paxos_oracle.make_fleet(3, 4)
    theirs = [make_oracle_group(3, 4, 0, r == 0) for r in range(3)]
    for _step in range(6):
        lanes = [int(x) for x in rng.integers(1, 1 << 62, 7)]
        n = paxos_oracle.storm_step(mine, lanes)
        coord, granted = theirs[0], []
        for req in lanes:
            status, slot, bal = coord.propose(req)
            if status == "granted":
                granted.append((slot, bal, req))
        acks = [[og.accept(s, b, q)[0] for s, b, q in granted]
                for og in theirs]
        newly = [False] * len(granted)
        for r in range(3):
            for i, (s, b, _q) in enumerate(granted):
                newly[i] |= coord.accept_reply(s, b, r, acks[r][i])[0]
        for og in theirs:
            for (s, _b, q), dec in zip(granted, newly):
                if dec:
                    og.commit(s, q)
        assert n == sum(newly) == 4  # the window admits W=4 of 7 lanes
    for a, b in zip(mine, theirs):
        assert (a.bal, a.exec_cursor, a.next_slot, a.decided) == \
            (b.bal, b.exec_cursor, b.next_slot, b.decided)
        assert {s: (bal, req) for s, (bal, req) in a.accepted.items()} == \
            {s: (pv.bal, pv.req_id) for s, pv in b.accepted.items()}
