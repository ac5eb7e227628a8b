"""Bytes the three nodes sent and received per acknowledged request:
after minus before of ``transport.metrics()`` ``tx_bytes`` + ``rx_bytes``
summed over the nodes, over the window's acked requests (client traffic and
replica-to-replica traffic both; the same arithmetic as BENCH_WIRE.json)."""


def read(run: dict):
    a, b = run["after"].get("net"), run["before"].get("net")
    acked = run["window"].get("acked")
    if not a or not b or not acked:
        return None
    return (a["tx_bytes"] + a["rx_bytes"]
            - b["tx_bytes"] - b["rx_bytes"]) / acked
