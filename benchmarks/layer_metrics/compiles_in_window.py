"""Compiles and retraces the ``EngineLedger`` counted between the first and
the last request of the window.  Should read 0: every program the window
uses was loaded in set-up."""


def read(run: dict):
    a, b = run["after"].get("ledger"), run["before"].get("ledger")
    if not a or not b:
        return None
    return (a["compiles"] - b["compiles"]) + (a["retraces"] - b["retraces"])
