"""Valid lanes per engine dispatch: items over calls of the ``eng.submit``
total across the window (all three nodes; padding to the bucket not
counted)."""
from benchmarks.harness import delta_total


def read(run: dict):
    calls = delta_total(run, "eng.submit", "calls")
    items = delta_total(run, "eng.submit", "items")
    return items / calls if calls else None
