"""Share of a worker batch (``gp.w.process``) in which its thread was off
CPU other than on the disk: over every ``gp.w.process`` record of the traced
window, the wall seconds less the thread's CPU seconds (``c1 - c0``) less
the wall of the ``gp.wal.fsync`` records nested in it, over the wall
seconds.  What is left is waiting for the interpreter lock, or for the
device's copy back.  None on a ring whose records carry no thread clock (a
program from before PR 38)."""
from benchmarks import span_ring


def read(run: dict):
    spans = span_ring.session()
    procs = span_ring.of(spans or [], "w.process")
    if not procs or any("c0" not in s for s in procs):
        return None
    by_id = {s["id"]: s for s in spans}
    fsync = {}  # id of the enclosing w.process -> seconds in fsync
    for s in span_ring.of(spans, "wal.fsync"):
        p = by_id.get(s["parent"])
        while p is not None and p["kind"] != "w.process":
            p = by_id.get(p["parent"])
        if p is not None:
            fsync[p["id"]] = fsync.get(p["id"], 0.0) + s["t1"] - s["t0"]
    wall = span_ring.seconds(procs)
    off = sum(s["t1"] - s["t0"] - (s["c1"] - s["c0"]) - fsync.get(s["id"], 0.0)
              for s in procs)
    return 100.0 * off / wall if wall > 0 else None
