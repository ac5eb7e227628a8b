"""Mean time of one write-ahead-log flush (``wal.fsync`` delay: sum over
count, after minus before), all three nodes."""


def read(run: dict):
    a, b = run["after"].get("wal_fsync"), run["before"].get("wal_fsync")
    if not a or not b or a["count"] == b["count"]:
        return None
    return 1e3 * (a["sum_s"] - b["sum_s"]) / (a["count"] - b["count"])
