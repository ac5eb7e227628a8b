"""The longest stretch after the kill in which the client got no
acknowledged reply, on the generator's own clock: the time without service
as the client saw it (``loadgen_failover.outage``)."""


def read(run: dict):
    gap = run["window"].get("service_gap_s")
    return 1e3 * gap if gap is not None else None
