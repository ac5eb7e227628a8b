"""Share of the HBM roofline of the read index's device work.

Bytes: the coordinator lanes (``gp.eng.read_index``'s, a read's group each)
and the acceptor lanes (``gp.eng.confirm``'s, a group on a peer each) of the
traced seconds, times ``roofline_read_index``'s bytes for each.  Time: the
device seconds of the programs those spans name (``program``: the jitted
function, whose runs the trace lists as ``jit_<program>``).  None when no
read ran in the traced seconds or the trace has no run of those programs;
over 100% is a wrong count."""
from benchmarks import roofline, roofline_read_index, span_ring


def read(run: dict):
    red = run.get("trace")
    spans = span_ring.session() or []
    coord = span_ring.of(spans, "eng.read_index")
    acc = span_ring.of(spans, "eng.confirm")
    if not red or not (coord or acc):
        return None
    programs = {"jit_" + s["program"] for s in coord + acc if "program" in s}
    device_s = sum(v for k, v in red.get("module_s", {}).items()
                   if k in programs)
    if not device_s:
        return None
    least = roofline_read_index.read_index_bytes(
        sum(s["lanes"] for s in coord), sum(s["lanes"] for s in acc))
    peak = roofline.load_peaks()["hbm_bytes_per_s"]
    return 100.0 * least / peak / device_s
