"""Median host-clock time of one synced storm step of the window (a
per-piece statistic, which is why it is a layer's metric and no end-to-end
one: the rate is decisions of all steps over the whole window)."""
import statistics


def read(run: dict):
    steps = run["window"].get("step_s")
    return 1e3 * statistics.median(steps) if steps else None
