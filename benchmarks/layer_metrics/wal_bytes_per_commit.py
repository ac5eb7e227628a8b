"""Bytes appended to the write-ahead logs per acknowledged request: the
total ``wal.bytes`` (what ``log_raw_inline`` wrote: accepts with their
payloads on every replica), all three nodes, after minus before, over the
window's acked requests."""
from benchmarks.harness import delta_total


def read(run: dict):
    written = delta_total(run, "wal.bytes", "items")
    acked = run["window"].get("acked")
    return written / acked if written and acked else None
