"""One kept storm step by class of operation (by hand, PR 37).

    python3 tests/chip/step_classes.py <xplane.pb> <out.json>

Takes the LAST whole run of jit_decide_storm_step on the first TPU plane,
lists its operations with their device time, and adds them up by class:
what the operation is, the shape it makes and the largest operand it reads
(an event's name is its whole HLO line)."""
import collections
import json
import re
import sys

from jax.profiler import ProfileData

SHAPE = re.compile(r"(pred|s32|u32|s8|u8|f32)\[([\d,]*)\]")


def words(dims):
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def classify(name):
    m = re.match(r"%?([\w.\-]+) = \(?(.*?)\)? ([\w\-]+)\(", name)
    if not m:
        return "?", name[:60]
    op = m.group(3)
    outs = SHAPE.findall(m.group(2))
    args = SHAPE.findall(name[m.end():])
    out = "+".join(f"{t}[{d}]" for t, d in outs[:3])
    big = max((words(d) for _, d in args), default=0)
    return op, f"{op} -> {out} <- max operand {big} words"


def main(path, out):
    pd = ProfileData.from_file(path)
    plane = next(p for p in pd.planes if p.name.startswith("/device:TPU:"))
    lines = {ln.name: ln for ln in plane.lines}
    runs = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in lines["XLA Modules"].events
            if "decide_storm_step" in e.name]
    lo, hi, mod = runs[-2] if len(runs) > 1 else runs[-1]
    ops = [(int(e.duration_ns), e.name) for e in lines["XLA Ops"].events
           if lo <= int(e.start_ns) < hi]
    by = collections.defaultdict(lambda: [0, 0])
    for d, n in ops:
        _, cls = classify(n)
        by[cls][0] += d
        by[cls][1] += 1
    res = {"module": mod, "step_ms": (hi - lo) / 1e6,
           "ops": len(ops), "ops_ms": sum(d for d, _ in ops) / 1e6,
           "all_steps_ms": [round((b - a) / 1e6, 3) for a, b, _ in runs],
           "classes": [[k, round(v[0] / 1e6, 3), v[1]] for k, v in sorted(
               by.items(), key=lambda kv: -kv[1][0])],
           "longest": [[round(d / 1e6, 3), n[:300]]
                       for d, n in sorted(ops, reverse=True)[:60]]}
    json.dump(res, open(out, "w"), indent=1)
    print(json.dumps({"step_ms": res["step_ms"], "ops": res["ops"],
                      "classes": res["classes"][:24]}, indent=0))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
