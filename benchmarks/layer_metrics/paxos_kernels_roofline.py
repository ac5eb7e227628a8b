"""Share of the HBM roofline of the served path's device work.

Bytes: the requests acknowledged inside the traced window, each a decision
on R replicas, times ``roofline.decision_bytes(R)`` (defined by the work: a
kernel fused, split or rewritten reads the same count).  Time: the seconds
in which any operation ran on the device in the traced window (all the
engine's jitted kernels of all three nodes; they share the chip).  Peak:
``peaks.json`` by device kind.  No trace, nothing acked in it or no device
time: nothing to read."""
import numpy as np

from benchmarks import roofline


def read(run: dict):
    red, win = run.get("trace"), run["window"]
    if not red or not red.get("busy_s") or red.get("t_lo") is None:
        return None
    t = win["t_recv"]
    inside = (t >= red["t_lo"]) & (t <= red["t_hi"]) & (win["status"] == 0)
    peaks = roofline.load_peaks()
    return roofline.roofline_pct(int(np.sum(inside)), win["replicas"],
                                 red["busy_s"], peaks)
