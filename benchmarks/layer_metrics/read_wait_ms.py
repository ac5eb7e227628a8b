"""Milliseconds from a read's arrival at its coordinator to its answer,
per read the read index answered: the total ``ri.wait`` (its confirmation
round and the wait for its index to execute), wall seconds over items, all
three nodes, after minus before.  Nothing to read from a program without
the read index."""
from benchmarks.harness import delta_total


def read(run: dict):
    reads = delta_total(run, "ri.wait", "items")
    wall = delta_total(run, "ri.wait", "wall_s")
    return 1e3 * wall / reads if reads else None
