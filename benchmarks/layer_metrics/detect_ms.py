"""From the kill to the first survivor's suspicion of the victim: the
start of the first ``gp.fo.suspect`` that names it, on the span ring's
clock (the driver keeps the kill's instant on that clock too).  Nothing
where the suspicion fell outside the traced seconds."""
from benchmarks import span_ring


def read(run: dict):
    win = run["window"]
    t_kill, victim = win.get("t_kill_ring"), win.get("victim")
    seen = [s["t0"] for s in span_ring.of(span_ring.session() or [],
                                          "fo.suspect")
            if s.get("dead") == victim]
    if t_kill is None or not seen:
        return None
    return 1e3 * (min(seen) - t_kill)
