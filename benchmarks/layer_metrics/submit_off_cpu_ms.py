"""Milliseconds of one engine dispatch's host part (``gp.eng.submit``) in
which its thread was off CPU: the mean over the traced window's submits of
the wall time less the thread's CPU time (``c1 - c0``).  What of
``engine_submit_ms`` is waiting for the interpreter lock rather than
staging and launch.  None on a ring whose records carry no thread clock (a
program from before PR 38)."""
from benchmarks import span_ring


def read(run: dict):
    subs = span_ring.of(span_ring.session() or [], "eng.submit")
    if not subs or any("c0" not in s for s in subs):
        return None
    return 1e3 * sum(s["t1"] - s["t0"] - (s["c1"] - s["c0"])
                     for s in subs) / len(subs)
