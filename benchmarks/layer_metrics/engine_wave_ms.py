"""Host-clock time of one engine wave: seconds inside ``eng.submit`` (stage
and launch) plus seconds blocked in ``eng.collect`` (device and copy back),
over the window, per dispatch."""
from benchmarks.harness import delta_total


def read(run: dict):
    calls = delta_total(run, "eng.submit", "calls")
    sub = delta_total(run, "eng.submit", "wall_s")
    col = delta_total(run, "eng.collect", "wall_s")
    if not calls or sub is None or col is None:
        return None
    return 1e3 * (sub + col) / calls
