"""Milliseconds from a read's confirmation by a majority to its answer,
per read the read index answered: the total ``ri.exec_wait``, the part of
``read_wait_ms`` the read spent waiting for the slots below its index to
execute on its coordinator (0 for a read whose index had executed when it
was confirmed), all three nodes, after minus before."""
from benchmarks.harness import delta_total


def read(run: dict):
    reads = delta_total(run, "ri.exec_wait", "items")
    wall = delta_total(run, "ri.exec_wait", "wall_s")
    return 1e3 * wall / reads if reads else None
