"""The roofline's byte counts against values worked by hand for one lane of
each op, and the peaks table."""

import pytest

from benchmarks import roofline


def test_bytes_of_one_lane_by_hand():
    # propose: reads active, is_coord, coord_active (1 B each) and
    # next_slot, exec_cursor, cbal (4 B each) = 15; writes next_slot (4)
    # and a [W,4] proposal entry (16) = 20
    assert roofline.op_bytes("propose") == 15 + 20 == 35
    # accept: reads active (1), bal (4), exec_cursor (4) = 9; writes bal
    # (4) and a [W,4] accepted entry (16) = 20
    assert roofline.op_bytes("accept") == 9 + 20 == 29
    # accept reply: reads is_coord, coord_active (1 + 1), cbal, members
    # (4 + 4), the proposal entry (16) = 26; writes its vote word (4)
    assert roofline.op_bytes("accept_reply") == 26 + 4 == 30
    # commit: reads active (1), exec_cursor (4), two slot words (8) = 13;
    # writes a [W,3] decided entry (12) and exec_cursor (4) = 16
    assert roofline.op_bytes("commit") == 13 + 16 == 29


def test_bytes_of_one_decision_on_three_replicas():
    # lane in 12 + out 8, one propose, then accept, reply and commit thrice
    assert roofline.decision_bytes(3) == 20 + 35 + 3 * (29 + 30 + 29) == 319
    assert roofline.decision_bytes(1) == 20 + 35 + 88


def test_share_of_the_roofline():
    peaks = roofline.load_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9 and "source" in peaks
    # 262,144 decisions in 0.574 s of device time
    got = roofline.roofline_pct(262144, 3, 0.574, peaks)
    assert got == pytest.approx(100 * 262144 * 319 / 819e9 / 0.574)
    assert 0.017 < got < 0.018
    # nothing to read is nothing, never 0
    assert roofline.roofline_pct(0, 3, 1.0, peaks) is None
    assert roofline.roofline_pct(10, 3, 0.0, peaks) is None


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.load_peaks("cpu")


def test_both_roofline_readers_take_the_whole_device_path(monkeypatch):
    """Decisions inside the traced window over ALL device time in it: no
    program's name is looked for, so a kernel renamed, split or taken off
    the path cannot leave either metric silent."""
    import numpy as np

    from benchmarks import harness
    peaks = roofline.load_peaks("TPU v5 lite")
    monkeypatch.setattr(roofline, "load_peaks", lambda kind=None: peaks)
    here = harness.HERE
    storm = harness.load_module(
        here + "/layer_metrics/storm_step_roofline.py")
    served = harness.load_module(
        here + "/layer_metrics/paxos_kernels_roofline.py")
    red = {"busy_s": 1.5, "t_lo": 10.0, "t_hi": 12.0, "module_runs": {},
           "module_s": {}}
    # steps of 1 s from t = 8.5: the window [10, 12] holds half of the
    # second, the whole third and half of the fourth = 2 steps' decisions
    win = {"replicas": 3, "steps": 5, "step_t0": [8.5, 9.5, 10.5, 11.5, 12.5],
           "step_s": [1.0] * 5, "step_n": [100] * 5}
    assert storm.read({"trace": red, "window": win}) == pytest.approx(
        roofline.roofline_pct(200, 3, 1.5, peaks))
    # requests acknowledged inside the window, refused ones left out
    win = {"replicas": 3, "t_recv": np.array([9.0, 10.5, 11.0, 11.5, 13.0]),
           "status": np.array([0, 0, 5, 0, 0])}
    assert served.read({"trace": red, "window": win}) == pytest.approx(
        roofline.roofline_pct(2, 3, 1.5, peaks))
    # an untraced run, or a trace with no device time: nothing, never 0
    for reader in (storm, served):
        assert reader.read({"trace": None, "window": win}) is None
        assert reader.read({"trace": dict(red, busy_s=0.0),
                            "window": win}) is None
