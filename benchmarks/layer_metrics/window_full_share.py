"""Share of the lanes handed to ``propose`` that came back throttled, their
group's window full: the node counters ``window_full`` over ``proposed``, all
three nodes, after minus before.  A lane parked and proposed again counts
each time.  Nothing to read from a program without the counters."""


def read(run: dict):
    a, b = run["after"].get("counters", {}), run["before"].get("counters", {})
    if "window_full" not in a or "proposed" not in a:
        return None
    lanes = sum(a["proposed"]) - sum(b["proposed"])
    full = sum(a["window_full"]) - sum(b["window_full"])
    return 100.0 * full / lanes if lanes else None
