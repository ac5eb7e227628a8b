"""Share of the reads that the read index answered (no slot, no accept, no
WAL append): the totals ``ri.reads`` over ``ri.reads`` + ``ri.fallbacks``
(reads that went as consensus rounds: the device or a peer refused, or no
answer came within a second), all three nodes, after minus before.  Nothing
to read from a program without the read index."""
from benchmarks.harness import delta_total


def read(run: dict):
    served = delta_total(run, "ri.reads", "items")
    fell = delta_total(run, "ri.fallbacks", "items") or 0
    if served is None or not served + fell:
        return None
    return 100.0 * served / (served + fell)
