"""Milliseconds of the handlers' own Python in one worker batch: the self
time (wall less the wall of its direct children of any kind: ``eng.*``,
``wal*``, ``app.execute``, another handler) of every handler span of
``_process`` (``gp.w.node_wave``, its ``gp.w.pre`` / ``gp.w.post``, the
split and cold handlers, ``gp.w.upper.*``, ``gp.w.ar.*``, ``gp.w.rc.*``),
summed over the traced window and divided by its ``gp.w.process`` records.
None on a ring without handler spans or thread clocks (a program from
before PR 38)."""
from benchmarks import span_ring

HANDLERS = ("w.node_wave", "w.pre", "w.post", "w.requests", "w.acc_com",
            "w.accepts", "w.replies", "w.commits", "w.prepare_batch",
            "w.prepare_reply_batch")
PREFIXES = ("w.upper.", "w.ar.", "w.rc.")


def read(run: dict):
    spans = span_ring.session() or []
    procs = span_ring.of(spans, "w.process")
    mine = [s for s in spans
            if s["kind"] in HANDLERS or s["kind"].startswith(PREFIXES)]
    if not procs or not mine or any("c0" not in s for s in procs):
        return None
    children = {}  # parent id -> wall seconds of its direct children
    for s in spans:
        if s["parent"]:
            children[s["parent"]] = (children.get(s["parent"], 0.0)
                                     + s["t1"] - s["t0"])
    own = sum(s["t1"] - s["t0"] - children.get(s["id"], 0.0) for s in mine)
    return 1e3 * own / len(procs)
