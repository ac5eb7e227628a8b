"""What one stage span costs the HOST, off and on (PR 38): ``span`` with a
sum and ``traced`` without one, empty bodies, one thread, with the spans off,
then under a JAX profiler session (the event, the ring record and, on a tree
from PR 38 on, the two thread-clock reads), and ``time.thread_time`` alone.
``python3 tests/chip/microbench_span.py <tree root> <label>``; merges into
chiprun_out/microbench_pr38.json under "span.<label>"."""
import json
import os
import statistics
import sys
import tempfile
import time

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
import jax  # noqa: E402

from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI  # noqa
from gigapaxos_tpu.utils.instrument import span, traced  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "chiprun_out", "microbench_pr38.json")
CALLS = 20000


def per_call_us(body) -> float:
    """Median over five rounds of CALLS calls, in microseconds a call."""
    rounds = []
    for _ in range(5):
        RI.clear()
        t0 = time.perf_counter()
        for _ in range(CALLS):
            body()
        rounds.append(1e6 * (time.perf_counter() - t0) / CALLS)
    return round(statistics.median(rounds), 3)


def one_span():
    with span("mb.span", node=1, n=2, frames=3):
        pass


def one_traced():
    with traced("mb.traced", node=1, bytes=8):
        pass


def main():
    res = {"device": jax.devices()[0].device_kind, "calls": CALLS}
    res["off_span_us"] = per_call_us(one_span)
    res["off_traced_us"] = per_call_us(one_traced)
    res["thread_time_us"] = per_call_us(time.thread_time)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            res["on_span_us"] = per_call_us(one_span)
            res["on_traced_us"] = per_call_us(one_traced)
        finally:
            jax.profiler.stop_trace()
    res["records_have_c0"] = "c0" in (RI.spans_snapshot() or [{}])[-1]
    RI.clear()
    try:
        with open(OUT) as f:
            got = json.load(f)
    except (OSError, ValueError):
        got = {}
    got[f"span.{sys.argv[2]}"] = res
    with open(OUT, "w") as f:
        json.dump(got, f, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
