"""The whole of a restarted node's recovery: the seconds of ``gp.rec.boot``
(``PaxosNode._recover``: table, device install, checkpoints, WAL) a boot
that recovered anything, from the span's sum (always on).  Nothing where no
node recovered in the window, or the program has no such span."""
from benchmarks import harness


def read(run: dict):
    calls = harness.delta_total(run, "rec.boot", "calls")
    if not calls:
        return None
    return 1e3 * harness.delta_total(run, "rec.boot", "wall_s") / calls
