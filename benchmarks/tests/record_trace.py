#!/usr/bin/env python3
"""Record a small trace on the chip and show what is in it: run by hand
(``chiprun -- python3 benchmarks/tests/record_trace.py``).  A few storm steps at
a small size under the profiler, with the benchmark's annotations; the
``.xplane.pb`` goes to ``chiprun_out/`` and, once looked at, a copy is kept
as ``benchmarks/tests/data/storm_small.xplane.pb`` for ``test_trace_reduce.py``."""

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def show(path: str, top: int = 12) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + ev.duration_ns
            first = min((e.start_ns for e in evs), default=None)
            print(f"  line {line.name!r}: {len(evs)} events, first at "
                  f"{first}")
            for n, d in sorted(names.items(), key=lambda kv: -kv[1])[:top]:
                print(f"      {d / 1e6:10.3f} ms  {n[:100]}")


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness, trace_reduce
    from gigapaxos_tpu.ops.storm import make_fleet, storm
    from gigapaxos_tpu.utils.jaxcache import enable_persistent_cache
    enable_persistent_cache()
    print("device", harness.device_facts())
    G, W, B = 1 << 14, 16, 1 << 12
    states = make_fleet(G, W, R=3)
    rng = np.random.default_rng(1)
    valid = jnp.ones((B,), bool)

    def inputs():
        with harness.annotate("bench.storm.inputs"):
            return tuple(jnp.asarray(rng.integers(0, hi, B, dtype=np.int32))
                         for hi in (G, 1 << 31, 1 << 31))
    for _ in range(2):
        states, n = storm(states, *inputs(), valid)
        int(n)
    out = os.path.join(ROOT, "chiprun_out", "trace_small")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out, profiler_options=harness.trace_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        for _ in range(3):
            with harness.annotate("bench.storm.dispatch"):
                states, n = storm(states, *inputs(), valid)
            with harness.annotate("bench.storm.sync"):
                int(n)
            time.sleep(0.002)
        wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out)
    keep = os.path.join(ROOT, "chiprun_out", "storm_small.xplane.pb")
    shutil.copy(path, keep)
    shutil.rmtree(out, ignore_errors=True)
    print("trace", keep, os.path.getsize(keep), "bytes; host wall of the "
          f"annotated window {wall:.6f} s")
    show(keep)
    red = trace_reduce.reduce_trace(keep)
    print("reduced", {k: v for k, v in red.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
