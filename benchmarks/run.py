#!/usr/bin/env python3
"""The benchmark: one cell, one run, one line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s>
                              --trace <0|1>

A cell is data: its entry in ``BENCHMARK.json`` names a configuration
(``benchmarks/configs/<name>.json``) and a traffic mix
(``benchmarks/traffic/<name>.json``); the configuration names its driver
(``benchmarks/drivers/<driver>.py``); each per-layer metric the cell lists
is read by ``benchmarks/layer_metrics/<metric>.py``.  One process; on a
machine without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from benchmarks import harness
    cell = harness.Cell(args.workload)
    from gigapaxos_tpu import native
    from gigapaxos_tpu.utils.jaxcache import (cache_metrics,
                                              enable_persistent_cache)
    enable_persistent_cache()  # JAX_COMPILATION_CACHE_DIR wins when set
    device = harness.device_facts()
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(f"benchmarks/run.py: JAX gave this process {device}; the cell "
              f"{cell.name} runs on {cell.chips} TPU chip(s) or not at all",
              file=sys.stderr)
        return 2
    if not native.have_native():
        print("benchmarks/run.py: native/_hotpath.so did not build; the "
              "served path's host code must be the C++ one", file=sys.stderr)
        return 3
    harness.say("start", workload=cell.name, seed=args.seed,
                seconds=args.seconds, trace=args.trace, device=device,
                cache=cache_metrics(),
                imports_s=round(time.perf_counter() - T_START, 3))
    line = measure(cell, args.seed, args.seconds, bool(args.trace), T_START,
                   device)
    print(json.dumps(line), flush=True)
    return 0


def measure(cell, seed: int, seconds: float, trace: bool, t_start: float,
            device: dict) -> dict:
    """Drive the cell and build the result line (everything after the look
    for a chip; the tests call it on the CPU at tiny sizes)."""
    from benchmarks import harness
    run = cell.driver().run(cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=t_start)
    device = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    metrics = {}
    if not trace:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    else:
        red = run["trace"]
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        for m in cell.per_layer():
            value = cell.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = harness.print_checks(run["checks"])
    line = {"correct": bool(correct), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in run["checks"]}
    return line


if __name__ == "__main__":
    sys.exit(main())
