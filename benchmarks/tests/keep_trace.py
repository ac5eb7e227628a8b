#!/usr/bin/env python3
"""One traced run of a cell, by hand on the chip, that keeps what a run
throws away: the ``.xplane.pb`` (``--keep``), the idle gaps put down to the
program's own ``gp.*`` spans as well as to ``bench.*``, and the span ring's
own account of the traced window (spans a second, ``dropped``, how far the
five top-level worker spans tile each worker thread).

    chiprun -- python3 benchmarks/tests/keep_trace.py --workload <name> \\
        --seed <n> [--seconds 20] [--keep]

Prints ``run.py``'s result line, then one JSON line ``{"spans": ...}``.  On
a program without the spans (before PR 26) the second line says so.
"""

import argparse
import collections
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
TOP = ("w.wait", "w.coalesce", "w.decode", "w.process", "w.tick")
PREFIXES = (("bench.", "gp.w."), ("gp.eng.", "gp.wal"))


def ring_account() -> dict:
    try:
        from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
        spans, st = RI.spans_snapshot(), RI.span_stats()
    except (ImportError, AttributeError):
        return {"spans": None, "why": "no span ring in this program"}
    if not spans:
        return {"spans": 0, "stats": {k: v for k, v in st.items()
                                      if k != "kinds"}}
    lo = min(s["t0"] for s in spans)
    hi = max(s["t1"] for s in spans)
    tiles = {}
    for tid in {s["tid"] for s in spans if s["kind"] == "w.decode"}:
        mine = [s for s in spans if s["tid"] == tid and s["kind"] in TOP]
        whole = max(s["t1"] for s in mine) - min(s["t0"] for s in mine)
        tiles[str(tid)] = round(
            sum(s["t1"] - s["t0"] for s in mine) / whole, 5)
    kernels = collections.Counter()
    unnamed = 0
    for s in spans:
        if s["kind"] == "eng.submit":
            kernels[f"{s.get('kernel')}@{s.get('bucket')}"] += 1
            unnamed += not (s.get("kernel") and s.get("bucket"))
    return {"spans": len(spans), "session_s": round(hi - lo, 4),
            "spans_per_s": round(len(spans) / (hi - lo), 1),
            "stats": {k: v for k, v in st.items() if k != "kinds"},
            "kinds": {k: {"count": v["count"],
                          "total_s": round(v["total_s"], 4)}
                      for k, v in st["kinds"].items()},
            "tiling_by_worker_thread": tiles,
            "submits_by_kernel_and_bucket": dict(kernels),
            "submits_without_kernel_or_bucket": unnamed}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--keep", action="store_true")
    args = p.parse_args()

    from benchmarks import harness, trace_reduce
    from gigapaxos_tpu.utils.jaxcache import enable_persistent_cache
    run_py = harness.load_module(os.path.join(ROOT, "benchmarks", "run.py"))
    enable_persistent_cache()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    gaps = {}
    finish = harness.Tracer.finish

    def finish_and_keep(self):
        self._thread.join()
        if self.error is None:
            path = trace_reduce.find_xplane(self.dir)
            for prefix in PREFIXES:  # str.startswith takes a tuple
                trace_reduce.NOTE_PREFIX = prefix
                gaps["+".join(prefix)] = trace_reduce.reduce_trace(
                    path, n_top=16)["idle_gaps"]
            trace_reduce.NOTE_PREFIX = "bench."
            if args.keep:
                shutil.copy(path, os.path.join(
                    out, f"{args.workload}.xplane.pb"))
        return finish(self)
    harness.Tracer.finish = finish_and_keep

    cell = harness.Cell(args.workload)
    device = harness.device_facts()
    line = run_py.measure(cell, args.seed, args.seconds, True,
                                 T_START, device)
    print(json.dumps(line), flush=True)
    print(json.dumps({"spans": ring_account(), "idle_gaps_by": gaps,
                      "workload": args.workload, "seed": args.seed}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
