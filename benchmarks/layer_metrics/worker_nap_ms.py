"""Seconds the worker loops spent waiting for a frame (``gp.w.wait``: the
blocking get, up to ``BATCH_TIMEOUT_S``) or letting a batch fill
(``gp.w.coalesce``: the ``BATCH_COALESCE_S`` nap), per engine dispatch
(``gp.eng.submit``) of the traced window, all three nodes."""
from benchmarks import span_ring


def read(run: dict):
    spans = span_ring.session()
    if not spans:
        return None
    return span_ring.per_ms(span_ring.of(spans, "w.wait", "w.coalesce"),
                            len(span_ring.of(spans, "eng.submit")))
