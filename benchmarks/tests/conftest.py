"""The benchmark's own tests run on the CPU at tiny sizes:
``python -m pytest benchmarks/tests -q``.  Not part of ``tests/``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


@pytest.fixture(autouse=True)
def _clean_config():
    from gigapaxos_tpu.utils.config import Config
    yield
    Config.clear()


@pytest.fixture
def tiny_cell(monkeypatch):
    """A cell of ``tests/tiny/BENCHMARK.json``: the drivers and readers of
    the benchmark over configurations a CPU test can hold."""
    from benchmarks import harness
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config
    # the chip fuses whole waves (PC.FUSE_WAVES "auto" on an accelerator):
    # rehearse the handlers the chip run takes
    Config.set(PC.FUSE_WAVES, "on")

    # a test's window is a second or less: trace and warm up to match
    monkeypatch.setattr(harness, "TRACE_S", 0.3)

    def make(name):
        cell = harness.Cell(name, root=TINY)
        for const, value in (("RAMP_BURST_S", 0.1), ("WARMUP_BURST_S", 0.3),
                             ("QUIET_BURSTS", 1), ("MAX_BURSTS", 3)):
            if hasattr(cell.driver(), const):
                monkeypatch.setattr(cell.driver(), const, value)
        return cell
    return make


def load_run_py():
    """``benchmarks/run.py`` as a module (it is a script, not a package's
    member)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def measure():
    """``run.measure``: the whole of a run after the look for a chip."""
    import time
    run_py = load_run_py()

    def call(cell, seed=7, seconds=1.0, trace=False):
        return run_py.measure(
            cell, seed, seconds, trace, time.perf_counter(),
            {"platform": "cpu", "kind": "cpu", "count": 1})
    call.main = run_py.main
    return call
