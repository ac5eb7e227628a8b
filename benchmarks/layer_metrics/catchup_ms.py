"""From the first frontier frame a recovered node sent to its last row
level: the seconds of ``gp.rec.catchup``, from the exchange's sum (added
when it ends).  Nothing where no exchange ended between the snapshots."""
from benchmarks import harness


def read(run: dict):
    calls = harness.delta_total(run, "rec.catchup", "calls")
    if not calls:
        return None
    return 1e3 * harness.delta_total(run, "rec.catchup", "wall_s") / calls
