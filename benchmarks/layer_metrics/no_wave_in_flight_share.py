"""Share of the traced session in which NO node had an engine wave between
its submit's start and its collect's end: the program's own view of an idle
engine, to set beside the device's idle share (``busy_s`` / ``window_s``).

Every ``gp.eng.submit`` is followed by exactly one ``gp.eng.collect`` of its
thread, so a count that rises at a submit's start and falls at a collect's
end is the number of waves in flight; a wave already in flight when the
session began shows as a collect with no submit and is counted from the
session's start."""
from benchmarks import span_ring


def read(run: dict):
    spans = span_ring.session()
    if not spans:
        return None
    lo, hi = span_ring.bounds(spans)
    edges = sorted([(s["t0"], 1) for s in span_ring.of(spans, "eng.submit")]
                   + [(s["t1"], -1)
                      for s in span_ring.of(spans, "eng.collect")])
    if not edges or hi <= lo:
        return None
    depth = running = 0
    for _t, step in edges:  # waves in flight before the first span
        running += step
        depth = min(depth, running)
    in_flight, since, n = 0.0, lo, -depth
    for t, step in edges:
        if n > 0:
            in_flight += t - since
        n, since = n + step, t
    if n > 0:
        in_flight += hi - since
    return 100.0 * (1.0 - in_flight / (hi - lo))
