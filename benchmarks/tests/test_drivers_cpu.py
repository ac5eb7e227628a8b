"""The drivers and the whole of a run after the look for a chip, on the CPU
at tiny sizes: ``correct`` comes out true on sound runs and false with the
timed path broken underneath."""

import time

import pytest


def test_storm_cell_is_correct_and_counts_the_whole_window(tiny_cell, measure,
                                                           capfd):
    line = measure(tiny_cell("tiny-storm-b256"), seed=3, seconds=0.5)
    assert line["correct"] is True and line["failed"] >= 0
    assert set(line["metrics"]) == {"storm_rate", "setup_s"}
    assert line["metrics"]["storm_rate"]["value"] > 0
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    err = capfd.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check ") and "limit" in err[-1]


def test_storm_seed_above_32_bits(tiny_cell, measure):
    line = measure(tiny_cell("tiny-storm-b256"), seed=2**31 + 12345,
                   seconds=0.2)
    assert line["correct"] is True


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_storm_faults_come_out_not_correct(tiny_cell, measure, monkeypatch,
                                           fault):
    from gigapaxos_tpu.ops import storm as storm_mod
    sound = storm_mod.storm

    def unchanged(states, g, rlo, rhi, valid):
        # the step reports its decisions and returns its state as it was
        import jax
        keep = jax.tree_util.tree_map(lambda a: a + 0, states)
        _new, n = sound(states, g, rlo, rhi, valid)
        return keep, n

    def half(states, g, rlo, rhi, valid):
        # half of the batch left out
        import jax.numpy as jnp
        B = valid.shape[0]
        return sound(states, g, rlo, rhi,
                     valid & (jnp.arange(B) < B // 2))

    monkeypatch.setattr(storm_mod, "storm",
                        unchanged if fault == "state_unchanged" else half)
    line = measure(tiny_cell("tiny-storm-b256"), seed=5, seconds=0.3)
    assert line["correct"] is False
    bad = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert bad & {"sample_fields_wrong", "decided_minus_admitted"}


def test_served_cell_is_correct(tiny_cell, measure):
    line = measure(tiny_cell("tiny-served-d16"), seed=11, seconds=1.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 16
    assert set(line["metrics"]) == {"commit_rate", "commit_p50_ms",
                                    "commit_p95_ms", "setup_s"}
    assert line["metrics"]["commit_p95_ms"]["value"] >= \
        line["metrics"]["commit_p50_ms"]["value"] > 0


@pytest.mark.parametrize("fault", ["answer_altered", "replica_skips_write"])
def test_served_faults_come_out_not_correct(tiny_cell, measure, monkeypatch,
                                            fault):
    from gigapaxos_tpu.paxos.interfaces import CounterApp
    sound = CounterApp.execute
    seen = {"n": 0}

    def altered(self, name, req_id, payload, is_stop=False):
        # an answer altered where it is produced: every 50th execution
        out = sound(self, name, req_id, payload, is_stop)
        seen["n"] += 1
        return out.replace(b'"count": ', b'"count": 1') \
            if seen["n"] % 50 == 0 else out

    def skips(self, name, req_id, payload, is_stop=False):
        # a step that leaves its state unchanged: every 50th execution
        seen["n"] += 1
        if seen["n"] % 50 == 0:
            return b'{"count": 0, "digest": 0}'
        return sound(self, name, req_id, payload, is_stop)

    monkeypatch.setattr(CounterApp, "execute",
                        altered if fault == "answer_altered" else skips)
    line = measure(tiny_cell("tiny-served-d16"), seed=13, seconds=1.0)
    assert line["correct"] is False
    bad = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert bad & {"answers_wrong", "replica_groups_diverged"}


def test_run_py_refuses_a_machine_without_a_tpu(measure, capsys):
    import jax
    assert jax.devices()[0].platform == "cpu"
    rc = measure.main(["--workload", "storm-1m-b256k", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc != 0 and '"correct"' not in out


@pytest.mark.parametrize("name", ["tiny-storm-b256", "tiny-served-d16"])
def test_controls_at_the_runs_own_size_are_not_correct(tiny_cell, name):
    """What ``chip_control.py`` does on the chip, at a size a test holds."""
    cell = tiny_cell(name)
    driver = cell.driver()
    run = driver.run(cell, seed=21, seconds=0.5, trace=False,
                     t_start=time.perf_counter())
    assert all(v <= lim for _n, v, lim in run["checks"])
    ctl = driver.controls(run, 21)
    assert set(ctl) == set(driver.CONTROLS)
    for broken, checks in ctl.items():
        assert any(v > lim for _n, v, lim in checks), broken
