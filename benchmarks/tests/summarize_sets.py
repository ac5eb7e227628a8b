#!/usr/bin/env python3
"""Read what ``measure_cell.sh`` wrote (``chiprun_out/<workload>.jsonl``) and
print, per metric, each set's median and spread as the contract defines it:
(Q3 - Q1) / median by ``statistics.quantiles(n=4)``.

    python3 benchmarks/tests/summarize_sets.py chiprun_out/<workload>.jsonl
"""

import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def without_farthest(values):
    """The set less its run farthest from the median: what the driver's
    check of a bound's tightness reads."""
    med = statistics.median(values)
    rest = list(values)
    rest.remove(max(values, key=lambda v: abs(v - med)))
    return rest


def main(path: str) -> int:
    rows = [json.loads(ln) for ln in open(path) if ln.strip()]
    bad = [(r["kind"], r["seed"]) for r in rows
           if not r["line"] or not (r["line"].get("correct")
                                    or r["kind"] == "control")]
    print("runs:", len(rows), "not correct or no line:", bad)
    sets = {}
    for r in rows:
        if r["kind"] in ("set1", "set2", "traced") and r["line"]:
            for name, m in r["line"]["metrics"].items():
                sets.setdefault(name, {}).setdefault(r["kind"], []).append(
                    m["value"])
    for name, by_set in sets.items():
        parts = []
        for kind, vals in sorted(by_set.items()):
            sp = f"{100 * spread(vals):.3f}%" if len(vals) >= 2 and \
                statistics.median(vals) else "-"
            parts.append(f"{kind}: median {statistics.median(vals):.6g} "
                         f"spread {sp} [{min(vals):.6g}..{max(vals):.6g}]")
        print(f"{name:26s} " + " | ".join(parts))
        if {"set1", "set2"} <= set(by_set) and \
                min(len(by_set[k]) for k in ("set1", "set2")) >= 4:
            a, b = by_set["set1"], by_set["set2"]
            tight = 50 * (spread(without_farthest(a))
                          + spread(without_farthest(b)))
            print(f"{'':26s} as the check reads it: tightness "
                  f"{tight:.3f}% (has to stay under half the bound), "
                  f"looseness {100 * max(spread(a), spread(b)):.3f}% "
                  f"(eight times it has to reach the bound); second median "
                  f"{100 * (statistics.median(b) / statistics.median(a) - 1):+.3f}%")
    for r in rows:
        if r["kind"] == "traced" and r["line"]:
            d = r["line"]["device"]
            print(f"traced seed {r['seed']}: busy {d['busy_s']:.4f} s of "
                  f"{d['window_s']:.4f} s, idle "
                  f"{100 * (1 - d['busy_s'] / d['window_s']):.2f}%, peak "
                  f"{d['memory_peak_bytes']} B")
            print("   device_ops:", r["line"]["breakdown"]["device_ops"][:5])
            print("   idle_gaps:", r["line"]["breakdown"]["idle_gaps"])
        if r["kind"] == "control" and r["line"]:
            print(f"control seed {r['seed']}: program "
                  f"{r['line']['program']['correct']}; " + "; ".join(
                      f"{k} {v['correct']} "
                      f"{ {n: x for n, x in v.items() if x and n != 'correct'} }"
                      for k, v in r["line"]["controls"].items()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
