"""Engine dispatches (``eng.submit`` calls, all three nodes) per
acknowledged request of the window."""
from benchmarks.harness import delta_total


def read(run: dict):
    calls = delta_total(run, "eng.submit", "calls")
    acked = run["window"].get("acked")
    return calls / acked if calls and acked else None
