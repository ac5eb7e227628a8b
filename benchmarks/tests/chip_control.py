#!/usr/bin/env python3
"""The control on the chip at a cell's own size, run by hand:

    chiprun -- python3 benchmarks/tests/chip_control.py --workload <name> \\
        --seed <n> [--seconds 5]

One run of the cell as ``run.py`` makes it (a short window at the cell's own
load), its numbers compared as in every run; then, on the same run's own
requests or steps, each control of the cell's driver (the plain reference
with one stated guarantee taken away, put in the program's place) through
the same comparison.  Prints one line: the program's numbers, which have to
be within their limits, and each control's, of which one at least has to be
outside.  The benchmark's own runs never run this; ``test_reference.py``
keeps the same controls at a size a test run can hold."""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    args = p.parse_args(argv)
    from benchmarks import harness
    from gigapaxos_tpu.utils.jaxcache import enable_persistent_cache
    enable_persistent_cache()
    cell = harness.Cell(args.workload)
    device = harness.device_facts()
    if device["platform"] != "tpu":
        print(f"chip_control: needs the chip, found {device}",
              file=sys.stderr)
        return 2
    driver = cell.driver()
    run = driver.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=False, t_start=T_START)

    def verdict(checks):
        return {"correct": all(v <= lim for _n, v, lim in checks),
                **{n: v for n, v, _lim in checks}}
    out = {"workload": cell.name, "seed": args.seed, "device": device,
           "attempted": run["attempted"], "failed": run["failed"],
           "program": verdict(run["checks"]),
           "controls": {k: verdict(c)
                        for k, c in driver.controls(run, args.seed).items()}}
    print(json.dumps(out), flush=True)
    ok = out["program"]["correct"] and not any(
        c["correct"] for c in out["controls"].values())
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
