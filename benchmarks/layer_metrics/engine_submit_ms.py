"""Host seconds of one engine dispatch before the device has it:
``gp.eng.submit`` (staging the batch, ``gp.eng.pack`` inside it, and the
launch) per dispatch of the traced window."""
from benchmarks import span_ring


def read(run: dict):
    subs = span_ring.of(span_ring.session() or [], "eng.submit")
    return span_ring.per_ms(subs, len(subs))
