"""How long a request waited for room in its group's window: seconds from
its first parking to the proposal that was granted (the total
``w.window_wait``), per request that was parked, all three nodes, after minus
before.  0 where the program parks on a full window and no request was; nothing
to read from a program that does not (no ``window_full`` counter)."""
from benchmarks.harness import delta_total


def read(run: dict):
    if "window_full" not in run["after"].get("counters", {}):
        return None
    waited = delta_total(run, "w.window_wait", "wall_s")
    items = delta_total(run, "w.window_wait", "items")
    return 1e3 * waited / items if items else 0.0
