"""Rows brought level per frontier frame sent (``rec.rows_level`` over
``rec.catchup_frames``): the batching of the catch-up itself; the per-row
gap path reads 1 at best.  Nothing where no frame was sent."""
from benchmarks import harness


def read(run: dict):
    frames = harness.delta_total(run, "rec.catchup_frames", "items")
    if not frames:
        return None
    return (harness.delta_total(run, "rec.rows_level", "items") or 0) / frames
