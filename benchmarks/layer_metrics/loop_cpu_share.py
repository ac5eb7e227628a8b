"""Percent of one core that the nodes' event loops took over the traced
window: for every thread that carries ``gp.net.read`` / ``gp.net.write``
records, its CPU clock's last ``c1`` less its first ``c0``, summed over
those threads and divided by the span ring's session seconds.  What the
wire's reads, scans, intake puts and writes take of the one interpreter.
None on a ring without ``gp.net.*`` records or thread clocks (a program
from before PR 38)."""
from benchmarks import span_ring


def read(run: dict):
    spans = span_ring.session()
    if not spans:
        return None
    net = span_ring.of(spans, "net.read", "net.write")
    if not net or any("c0" not in s for s in net):
        return None
    loops = {s["tid"] for s in net}
    mine = [s for s in spans if s["tid"] in loops and "c0" in s]
    lo, hi = span_ring.bounds(spans)
    cpu = sum(max(s["c1"] for s in mine if s["tid"] == tid)
              - min(s["c0"] for s in mine if s["tid"] == tid)
              for tid in loops)
    return 100.0 * cpu / (hi - lo) if hi > lo else None
