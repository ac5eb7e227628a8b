"""Seconds a worker batch spent executing decided requests on the app
(``gp.app.execute``: the execute loop with the replies it routes), per batch
that executed anything: the total ``app.execute``, wall seconds over calls,
all three nodes, after minus before."""
from benchmarks.harness import delta_total


def read(run: dict):
    calls = delta_total(run, "app.execute", "calls")
    wall = delta_total(run, "app.execute", "wall_s")
    return 1e3 * wall / calls if calls else None
