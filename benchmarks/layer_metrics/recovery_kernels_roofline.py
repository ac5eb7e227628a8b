"""Share of the HBM roofline of a recovery's device work.

Bytes: the install, the cursors set from checkpoints and the WAL's
roll-forward of the recovery spans that lie WHOLLY inside the traced seconds
(``gp.rec.install`` ``rows``, ``gp.rec.checkpoints`` ``restored``,
``gp.rec.wal`` ``accepts`` / ``decisions``), times
``roofline_recovery``'s least bytes for each; a part that ends after the
traced seconds counts on neither side (the ``window`` line's
``recovery_spans`` says which did).  Time: the device seconds of the
programs those spans name (``programs``, as the trace lists them) over the
whole of the traced seconds: ``_recover`` loads its programs BEFORE the
tracer is started (``drivers/recovery.md``), so its warm-up launches are not
among them, but a catch-up that sets cursors or commits through the same
programs inside the traced seconds is, with no bytes counted for it: the
share is a floor.  None when no such span lies in the traced seconds or the
trace has no run of those programs; over 100% is a wrong count."""
from benchmarks import roofline, roofline_recovery, span_ring


def read(run: dict):
    red, lim = run.get("trace"), run["window"].get("trace_ring")
    if not red or not lim:
        return None
    spans = [s for s in span_ring.of(span_ring.session() or [],
                                     "rec.install", "rec.checkpoints",
                                     "rec.wal")
             if s["t0"] >= lim[0] and s["t1"] <= lim[1]]
    programs = {p for s in spans for p in s.get("programs", "").split("+")
                if p}
    device_s = sum(v for k, v in red.get("module_s", {}).items()
                   if k in programs)
    if not device_s:
        return None

    def total(kind, attr):
        return sum(s.get(attr, 0) for s in spans if s["kind"] == kind)
    least = roofline_recovery.recovery_bytes(
        total("rec.install", "rows"), total("rec.checkpoints", "restored"),
        total("rec.wal", "accepts"), total("rec.wal", "decisions"),
        int(run["config"]["window"]))
    peak = roofline.load_peaks()["hbm_bytes_per_s"]
    return 100.0 * least / peak / device_s
