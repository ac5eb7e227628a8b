"""Valid lanes a launch of the election programs: the ``lanes`` of every
``gp.eng.prepare`` and ``gp.eng.install`` of the traced seconds over their
``chunks`` (a chunk is one launch).  4,096 is a full bucket."""
from benchmarks import span_ring


def read(run: dict):
    spans = span_ring.of(span_ring.session() or [], "eng.prepare",
                         "eng.install")
    launches = sum(s["chunks"] for s in spans)
    return sum(s["lanes"] for s in spans) / launches if launches else None
