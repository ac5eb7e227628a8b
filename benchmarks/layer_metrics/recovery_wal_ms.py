"""The WAL's roll-forward inside a recovery: the seconds of ``gp.rec.wal``
(read, parse, one batched accept, one batched commit, re-execution) a
recovery, from the span's sum.  Nothing where no node recovered."""
from benchmarks import harness


def read(run: dict):
    calls = harness.delta_total(run, "rec.wal", "calls")
    if not calls:
        return None
    return 1e3 * harness.delta_total(run, "rec.wal", "wall_s") / calls
