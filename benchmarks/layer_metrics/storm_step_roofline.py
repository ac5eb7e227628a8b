"""Share of the HBM roofline of the storm's device work.

Bytes: the decisions of the steps that ran inside the traced window (each
synced step's decisions times the share of its dispatch-to-sync interval
that lies in the window) times ``roofline.decision_bytes(R)``: defined by
the work, so a step renamed, split or rewritten reads the same count.
Time: the seconds in which any operation ran on the device in the traced
window, as the served cells' reader takes it.  Peak: ``peaks.json`` by
device kind.  No trace, no step in it or no device time: nothing to read."""
from benchmarks import roofline


def read(run: dict):
    red, win = run.get("trace"), run["window"]
    if not red or not red.get("busy_s") or red.get("t_lo") is None:
        return None
    decided = 0.0
    for t0, s, n in zip(win["step_t0"], win["step_s"], win["step_n"]):
        inside = min(t0 + s, red["t_hi"]) - max(t0, red["t_lo"])
        if inside > 0 and s > 0:
            decided += n * inside / s
    peaks = roofline.load_peaks()
    return roofline.roofline_pct(decided, win["replicas"], red["busy_s"],
                                 peaks)
