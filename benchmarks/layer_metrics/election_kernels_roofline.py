"""Share of the HBM roofline of the election programs' device work.

Bytes: the promise lanes (``gp.eng.prepare``'s, a group on an acceptor
each) and the installs (``gp.eng.install``'s, with the slots
``gp.fo.install`` says were carried) of the traced seconds, times
``roofline_elections``' least bytes for each.  Time: the device seconds of
the programs those spans name (``program``: the jitted function, whose runs
the trace lists as ``jit_<program>``).  None when no election ran in the
traced seconds or the trace has no run of those programs; over 100% is a
wrong count."""
from benchmarks import roofline, roofline_elections, span_ring


def read(run: dict):
    red = run.get("trace")
    spans = span_ring.session() or []
    prep = span_ring.of(spans, "eng.prepare")
    inst = span_ring.of(spans, "eng.install")
    if not red or not (prep or inst):
        return None
    programs = {"jit_" + s["program"] for s in prep + inst if "program" in s}
    device_s = sum(v for k, v in red.get("module_s", {}).items()
                   if k in programs)
    if not device_s:
        return None
    carried = sum(s.get("carried", 0)
                  for s in span_ring.of(spans, "fo.install"))
    least = roofline_elections.election_bytes(
        sum(s["lanes"] for s in prep), sum(s["lanes"] for s in inst),
        carried, int(run["config"]["window"]))
    peak = roofline.load_peaks()["hbm_bytes_per_s"]
    return 100.0 * least / peak / device_s
