"""What every driver shares: the cell's files, the tracer thread, the
device's facts, the counters' snapshots, the result line."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(phase: str, **facts) -> None:
    """An earlier line of standard output: one JSON object a line."""
    print(json.dumps({"phase": phase, **facts}), flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A driver or a reader by its file: found by name, never imported by
    a list kept in code.  Loaded once a process."""
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with its files."""

    def __init__(self, workload: str, root: str = ROOT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                             f"(has {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(root, conf["file"]))
        self.home = os.path.dirname(os.path.dirname(
            os.path.join(root, conf["file"])))
        self.traffic = load_json(self._find(
            "traffic", self.entry["traffic"] + ".json"))

    def _find(self, kind: str, file: str) -> str:
        """``<kind>/<file>`` beside the configuration's own directory, or
        in this benchmark's: a later PR that brings a directory of its own
        under ``paths`` keeps its mixes and readers there, and finds the
        drivers and readers of this one where they are."""
        for base in (self.home, HERE):
            path = os.path.join(base, kind, file)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"{kind}/{file} (named by BENCHMARK.json) is neither under "
            f"{self.home} nor under {HERE}")

    def end_to_end(self) -> List[dict]:
        """The end-to-end metrics this cell reports: those that list it,
        and those that list no cells."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        """The per-layer metrics this cell reports: those that list it;
        one that lists no cells goes with the metric it moves."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def driver(self):
        return load_module(self._find(
            "drivers", self.config["driver"] + ".py"))

    def reader(self, metric: str):
        return load_module(self._find("layer_metrics", metric + ".py"))


def device_facts() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest chip (None where the backend does
    not say, as on the CPU)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [int(p) for p in peaks if p is not None]
    return max(peaks) if peaks else None


def ledger() -> dict:
    from gigapaxos_tpu.utils.engineledger import EngineLedger
    from gigapaxos_tpu.utils.jaxcache import cache_metrics
    snap, cm = EngineLedger.snapshot(), cache_metrics()
    return {"compiles": snap["compiles"], "retraces": snap["retraces"],
            "cache_hits": cm["hits"], "cache_misses": cm["misses"]}


def filesystem_of(path: str) -> str:
    """The mount ``path`` lies on, as /proc/mounts has it."""
    best = ("", "?")
    try:
        real = os.path.realpath(path)
        with open("/proc/mounts") as f:
            for ln in f:
                _dev, mnt, fstype = ln.split()[:3]
                if (real == mnt or real.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best[0]):
                    best = (mnt, fstype)
    except OSError:
        pass
    return f"{best[1]} at {best[0]}"


TRACE_S = 4.0  # of the middle of the window, or half of a shorter one


def tracer_for(seconds: float) -> "Tracer":
    trace_s = min(TRACE_S, seconds / 2)
    return Tracer((seconds - trace_s) / 2, trace_s)


class Tracer:
    """Traces ``trace_s`` seconds from ``start_after`` seconds on, in a
    thread of its own so that neither the generator nor a step waits for
    the profiler.  The traced window is the annotation ``bench.window``;
    its ends on the host's clock are kept for counting what fell in it."""

    def __init__(self, start_after: float, trace_s: float):
        self.start_after, self.trace_s = start_after, trace_s
        self.dir = tempfile.mkdtemp(prefix="gp_bench_trace_")
        self.t_lo = self.t_hi = None
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax
        try:
            time.sleep(self.start_after)
            jax.profiler.start_trace(self.dir,
                                     profiler_options=trace_options())
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    self.t_lo = time.perf_counter()
                    time.sleep(self.trace_s)
                    self.t_hi = time.perf_counter()
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # reported by finish()
            self.error = e

    def finish(self) -> dict:
        """Wait for the profiler, reduce the trace, delete it."""
        from benchmarks import trace_reduce
        self._thread.join()
        try:
            if self.error is not None:
                raise self.error
            red = trace_reduce.reduce_trace(self.dir)
            red["t_lo"], red["t_hi"] = self.t_lo, self.t_hi
            return red
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def trace_options():
    """The profiler without the Python tracer (it would swamp the trace and
    slow the host), with the host's own spans and ``TraceAnnotation``s."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def print_checks(checks: List[Tuple[str, float, float]]) -> bool:
    """Each number compared beside its limit, as the last lines of standard
    error.  True when every number is within its limit."""
    ok = True
    for name, value, limit in checks:
        good = value is not None and value <= limit
        ok &= good
        print(f"check {name}: {value} (limit {limit})"
              f"{'' if good else '  <-- NOT WITHIN'}", file=sys.stderr)
    sys.stderr.flush()
    return ok


def delta_total(run: dict, tag: str, field: str):
    """After minus before of one field of a ``DelayProfiler`` total in a
    driver's counter snapshots; None where the program never recorded the
    tag.  For the readers under ``layer_metrics/``."""
    a = run["after"].get("totals", {}).get(tag)
    if a is None:
        return None
    b = run["before"].get("totals", {}).get(tag, {})
    return a[field] - b.get(field, 0)
