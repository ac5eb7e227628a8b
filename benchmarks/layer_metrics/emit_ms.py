"""Seconds a worker batch spent handing its frames to the transport
(``gp.w.emit``: response encode and the hop to the event loop), per batch of
the traced window that had something to send."""
from benchmarks import span_ring


def read(run: dict):
    emits = span_ring.of(span_ring.session() or [], "w.emit")
    return span_ring.per_ms(emits, len(emits))
