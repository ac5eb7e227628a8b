"""Share of the lanes launched that carried nothing: 1 - valid lanes over
padded lanes launched (``lanes`` and ``launched`` of ``gp.eng.submit``: a
chunk launches its bucket once per input, so a fused two-input wave
launches two buckets), over the traced window."""
from benchmarks import span_ring


def read(run: dict):
    subs = span_ring.of(span_ring.session() or [], "eng.submit")
    launched = sum(s["launched"] for s in subs)
    if not launched:
        return None
    return 100.0 * (1.0 - sum(s["lanes"] for s in subs) / launched)
