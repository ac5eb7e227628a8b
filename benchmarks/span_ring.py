"""The traced window as the program's own stage spans (``gp.*``): what the
readers ``layer_metrics/{queue_wait_ms,worker_nap_ms,engine_submit_ms,
padded_lane_share,no_wave_in_flight_share,emit_ms}.py`` share.

The program keeps its completed spans in a ring of its own
(``gigapaxos_tpu/utils/instrument.py``), and takes them only while spans are
on: with the operator's switch off, that is while the profiler's session
runs.  After a ``--trace 1`` run the ring therefore holds the traced seconds
and only those, and is still there when the nodes have stopped.  Seconds are
on the ring's clock (``time.monotonic``); the session is the ring's own
first start to last end.
"""

from __future__ import annotations

from typing import List, Optional, Tuple


def session() -> Optional[List[dict]]:
    """The completed spans, oldest first; None where there is nothing to
    read: a program without the ring's accessor (before PR 26), an empty
    ring, or a ring that was full and pushed spans out (``dropped``): a
    window that lost its beginning is no window."""
    try:
        from gigapaxos_tpu.utils.instrument import RequestInstrumenter
        spans = RequestInstrumenter.spans_snapshot()
        dropped = RequestInstrumenter.span_stats()["dropped"]
    except (ImportError, AttributeError, KeyError):
        return None
    return spans if spans and not dropped else None


def of(spans: List[dict], *kinds: str) -> List[dict]:
    return [s for s in spans if s["kind"] in kinds]


def seconds(spans: List[dict]) -> float:
    return sum(s["t1"] - s["t0"] for s in spans)


def bounds(spans: List[dict]) -> Tuple[float, float]:
    return min(s["t0"] for s in spans), max(s["t1"] for s in spans)


def per_ms(spans: List[dict], per: int) -> Optional[float]:
    """Milliseconds inside ``spans`` per one of ``per`` (None for none)."""
    return 1e3 * seconds(spans) / per if per else None
