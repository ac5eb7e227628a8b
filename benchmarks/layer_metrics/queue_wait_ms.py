"""How long frames sat in a node's intake queue: mean over worker batches of
the traced window of ``queue_wait_s`` on ``gp.w.decode`` (the batch's oldest
item, from its put to the worker's dequeue)."""
from benchmarks import span_ring


def read(run: dict):
    spans = span_ring.session()
    waits = [s["queue_wait_s"] for s in span_ring.of(spans or [], "w.decode")
             if "queue_wait_s" in s]
    return 1e3 * sum(waits) / len(waits) if waits else None
