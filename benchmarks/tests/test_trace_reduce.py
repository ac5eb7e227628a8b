"""The trace reduction on a trace recorded on a TPU v5e (three storm steps
at G=2^14, B=2^12 under the benchmark's annotations; recorded by
``record_trace.py`` in PR 25 and looked at by hand: the numbers below are
what the profile shows)."""

import os

import pytest

from benchmarks import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "storm_small.xplane.pb")


def test_interval_arithmetic():
    a = tr.merge([(0, 10), (5, 12), (20, 30), (30, 31), (40, 41)])
    assert a.tolist() == [[0, 12], [20, 31], [40, 41]]
    b = tr.merge([(8, 25), (40, 50)])
    assert tr.total(a) == 24 and tr.overlap(a, b) == tr.overlap(b, a) == 10
    assert tr.gaps(a, 0, 50).tolist() == [[12, 20], [31, 40], [41, 50]]
    assert tr.clip(a, 5, 25).tolist() == [[5, 12], [20, 25]]
    assert tr.merge([]).shape == (0, 2) and tr.overlap(a, tr.merge([])) == 0


def test_short_op_names():
    long = ("%fusion.80 = s32[4194304]{0:T(1024)S(1)} fusion(s32[262144,16]"
            "{0,1:T(8,128)S(1)} %fusion.45), kind=kCustom, calls=%fc.1")
    assert tr.short_op(long) == "fusion.80 fusion s32[4194304]"
    assert tr.short_op("odd name") == "odd name"


def test_recorded_trace():
    red = tr.reduce_trace(TRACE)
    assert red["n_devices"] == 1
    # the annotated window on the host's line: 45.967 ms
    assert red["window_s"] == pytest.approx(0.045967244, abs=1e-9)
    # three runs of the storm program, 29.527 ms of device time
    assert red["module_runs"] == {"jit_decide_storm_step": 3}
    assert red["module_s"]["jit_decide_storm_step"] == \
        pytest.approx(0.029527114, abs=1e-9)
    # busy is the union of the operations: inside the programs' runs
    assert red["busy_s"] == pytest.approx(0.029519214, abs=1e-9)
    assert sum(red["op_s"].values()) >= red["busy_s"] - 1e-9
    top = red["device_ops"]
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    assert all(name.startswith("jit_decide_storm_step/") and len(name) < 100
               for name, _s in top)
    # the idle gaps add up to the window less the busy time, and the
    # benchmark's annotations name most of them
    gaps = dict(red["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-6)
    assert set(gaps) == {"bench.storm.sync", "bench.storm.dispatch",
                         "bench.storm.inputs", tr.UNATTRIBUTED}
    assert gaps["bench.storm.sync"] == pytest.approx(0.007342794, abs=1e-9)


def test_a_trace_without_a_device_plane_reads_nothing(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.reduce_trace(str(tmp_path))
