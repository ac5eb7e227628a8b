#!/usr/bin/env python3
"""One traced run of a cell, by hand on the chip, with who held the host's
CPU over the traced seconds (PERF.md §5, PR 38):

    chiprun -- python3 tests/chip/cpu_account.py --workload <name> \\
        --seed <n> [--seconds 20] [--root <checkout>] [--keep]

Prints ``run.py``'s result line, then one JSON line: ``keep_trace.py``'s
account of the span ring (spans a second, ``dropped``, tiling), the CPU
seconds of every thread of the process over the traced window from
``/proc/self/task`` (by role: the workers and the event loops as the ring
names them, the rest by thread name), and the device's idle seconds in the
window put down to each ``gp.*`` kind's SELF time (its span less its
children, on its own thread; summed over threads, so the kinds can add up
to more than the idle seconds while threads run side by side).
``--root`` runs another checkout (a parent unpacked beside this one); the
account of a program whose records carry no ``c0`` leaves the thread clocks
out.
"""

import argparse
import collections
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))


class TaskReader:
    """Reads ``/proc/<this pid>/task/*/stat`` in a helper started before
    the run: in this process a read a thread waits for the interpreter at
    every call (it stretched the traced 4 s to 6.5 s in PR 38's first
    round), and a child forked inside the window stalls every thread
    while a large address space is copied (1.5 s a snapshot, the second
    round).  One line down the pipe, one answer back."""

    def __init__(self):
        self.p = subprocess.Popen(
            ["sh", "-c", f"while read x; do for f in /proc/{os.getpid()}"
             "/task/*/stat; do cat $f; echo; done; echo END; done"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def read(self) -> str:
        self.p.stdin.write("\n")
        self.p.stdin.flush()
        return "".join(iter(self.p.stdout.readline, "END\n"))

    def close(self) -> None:
        self.p.stdin.close()
        self.p.wait(5)


def task_cpu(reader: TaskReader) -> dict:
    """native tid -> (comm, CPU seconds) of every thread of the process."""
    tick = os.sysconf("SC_CLK_TCK")
    out = {}
    for st in reader.read().splitlines():
        if ")" not in st:
            continue
        comm = st[st.index("(") + 1:st.rindex(")")]
        rest = st[st.rindex(")") + 2:].split()
        out[int(st.split()[0])] = (comm,
                                   (int(rest[11]) + int(rest[12])) / tick)
    return out


def cpu_by_role(lo: dict, hi: dict, names: dict, spans: list,
                window_s: float) -> dict:
    # window_s: the seconds between the two snapshots
    """Each thread's CPU over the window, and the shares of one core by
    role.  ``names``: native tid -> (python ident, python name)."""
    workers = {s["tid"] for s in spans if s["kind"].startswith("w.")}
    loops = {s["tid"] for s in spans if s["kind"].startswith("net.")}
    threads, roles = [], collections.Counter()
    for tid, (comm, c1) in hi.items():
        cpu = c1 - lo[tid][1] if tid in lo else c1  # born in the window
        ident, pyname = names.get(tid, (None, None))
        role = ("worker" if ident in workers else
                "loop" if ident in loops else
                "main" if tid == os.getpid() else
                f"py:{pyname}" if pyname else f"native:{comm}")
        roles[re.sub(r"[-_ ]?\d+$", "", role)] += cpu
        threads.append({"tid": tid, "comm": comm, "py": pyname,
                        "role": role, "cpu_s": round(cpu, 3)})
    threads.sort(key=lambda t: -t["cpu_s"])
    total = sum(roles.values())
    return {"window_s": round(window_s, 4), "cpu_s": round(total, 3),
            "cores": round(total / window_s, 4),
            "share_of_one_core": {k: round(100 * v / window_s, 2)
                                  for k, v in roles.most_common()},
            "threads": threads[:40]}


def thread_clocks(spans: list) -> dict:
    """Per ring thread: wall from first t0 to last t1, CPU from first c0 to
    last c1 (the top-level worker spans tile their thread)."""
    out = {}
    for tid in {s["tid"] for s in spans}:
        mine = [s for s in spans if s["tid"] == tid and "c0" in s]
        if not mine:
            continue
        kinds = collections.Counter(s["kind"].split(".")[0] for s in mine)
        wall = max(s["t1"] for s in mine) - min(s["t0"] for s in mine)
        cpu = max(s["c1"] for s in mine) - min(s["c0"] for s in mine)
        out[str(tid)] = {"kinds": dict(kinds), "wall_s": round(wall, 4),
                         "cpu_s": round(cpu, 4)}
    return out


def idle_by_self_time(xplane: str, spans: list, t_lo: float) -> dict:
    """Device-idle seconds of the window under each kind's self time."""
    import numpy as np
    from jax.profiler import ProfileData

    from benchmarks import trace_reduce as tr
    pd = ProfileData.from_file(xplane)
    ops, window = [], None
    for plane in pd.planes:
        if tr.DEVICE_PLANE.match(plane.name) and not ops:
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == tr.WINDOW:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
    if window is None or not ops:
        return {"why": "no window or no device operations"}
    lo, hi = window
    idle = tr.gaps(tr.clip(tr.merge(ops), lo, hi), lo, hi)
    shift = lo - int(t_lo * 1e9)  # ring clock (s) -> profile clock (ns)
    kids = collections.defaultdict(list)
    for s in spans:
        if s["parent"]:
            kids[s["parent"]].append(s)
    own = collections.defaultdict(list)
    for s in spans:
        a, b = int(s["t0"] * 1e9) + shift, int(s["t1"] * 1e9) + shift
        cut = sorted((int(c["t0"] * 1e9) + shift, int(c["t1"] * 1e9) + shift)
                     for c in kids[s["id"]])
        for c0, c1 in cut:
            if c0 > a:
                own[s["kind"]].append((a, min(c0, b)))
            a = max(a, c1)
        if b > a:
            own[s["kind"]].append((a, b))
    out = {k: tr.overlap(idle, tr.merge(v)) / 1e9 for k, v in own.items()}
    idle_s = tr.total(idle) / 1e9
    return {"idle_s": round(idle_s, 4), "window_s": (hi - lo) / 1e9,
            "by_kind_self_s": {k: round(v, 4) for k, v in sorted(
                out.items(), key=lambda kv: -kv[1]) if v > 1e-4},
            "any_span_s": round(tr.overlap(idle, tr.merge(
                np.concatenate([np.asarray(v) for v in own.values()])
                if own else np.zeros((0, 2), np.int64))) / 1e9, 4)}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--root", default=os.path.dirname(os.path.dirname(HERE)))
    p.add_argument("--keep", action="store_true")
    args = p.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)

    from benchmarks import harness, trace_reduce
    from gigapaxos_tpu.utils.jaxcache import enable_persistent_cache
    keep = harness.load_module(os.path.join(root, "benchmarks", "tests",
                                            "keep_trace.py"))
    run_py = harness.load_module(os.path.join(root, "benchmarks", "run.py"))
    enable_persistent_cache()
    out = os.path.join(os.path.dirname(os.path.dirname(HERE)), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    snap, extra = {}, {}
    tasks = TaskReader()

    def names():
        return {t.native_id: (t.ident, t.name)
                for t in threading.enumerate()}

    def run_traced(self):  # harness.Tracer._run, with /proc at both ends
        import jax
        try:
            time.sleep(self.start_after)
            jax.profiler.start_trace(
                self.dir, profiler_options=harness.trace_options())
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    self.t_lo = time.perf_counter()
                    snap["lo"] = task_cpu(tasks)
                    snap["t_lo"] = time.perf_counter()
                    time.sleep(self.trace_s)
                    snap["t_hi"], snap["names"] = time.perf_counter(), names()
                    snap["hi"] = task_cpu(tasks)
                    self.t_hi = time.perf_counter()
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:
            self.error = e
    harness.Tracer._run = run_traced
    finish = harness.Tracer.finish

    def finish_and_keep(self):
        self._thread.join()
        if self.error is None:
            path = trace_reduce.find_xplane(self.dir)
            extra["xplane"] = os.path.join(out, f"{args.workload}.xplane.pb")
            shutil.copy(path, extra["xplane"])
            extra["t_lo"] = self.t_lo
        return finish(self)
    harness.Tracer.finish = finish_and_keep

    cell = harness.Cell(args.workload)
    line = run_py.measure(cell, args.seed, args.seconds, True, T_START,
                          harness.device_facts())
    print(json.dumps(line), flush=True)
    tasks.close()
    from gigapaxos_tpu.utils.instrument import RequestInstrumenter as RI
    spans = RI.spans_snapshot()
    acc = {"workload": args.workload, "seed": args.seed, "root": root,
           "spans": keep.ring_account()}
    if "lo" in snap:
        acc["cpu"] = cpu_by_role(snap["lo"], snap["hi"], snap["names"],
                                 spans, snap["t_hi"] - snap["t_lo"])
    acc["thread_clocks"] = thread_clocks(spans)
    if "xplane" in extra and spans:
        try:
            acc["idle"] = idle_by_self_time(extra["xplane"], spans,
                                            extra["t_lo"])
        except Exception as e:  # a by-hand account: keep the rest
            acc["idle"] = {"error": repr(e)}
        if not args.keep:
            os.remove(extra["xplane"])
    name = f"cpu_account_{args.workload}_{os.path.basename(root)}.json"
    with open(os.path.join(out, name), "w") as f:
        json.dump({"line": line, "account": acc}, f, indent=1)
    acc.pop("thread_clocks")
    acc.get("cpu", {}).pop("threads", None)
    print(json.dumps(acc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
