"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, time per jitted program and per device
operation, and the idle gaps by what the host was doing in them.

Only ``jax.profiler.ProfileData`` is used.  What a v5e trace looks like
(seen by hand in PR 25): one plane ``/device:TPU:<n>`` per chip with the
lines ``XLA Modules`` (one event per run of a jitted program, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per operation of
it), and one plane ``/host:CPU`` with a line per thread, on which a
``jax.profiler.TraceAnnotation`` shows as an event of its name.  All on
one clock, in nanoseconds.

The traced window is the benchmark's own annotation ``bench.window``; where
a trace has none, it is the span of the device's events.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW, NOTE_PREFIX = "bench.window", "bench."
UNATTRIBUTED = "host-unattributed"

Intervals = np.ndarray  # [n, 2] int64 ns, sorted, disjoint


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def merge(iv: Sequence[Tuple[int, int]]) -> Intervals:
    """Union of intervals as sorted disjoint rows."""
    a = np.asarray(iv, np.int64).reshape(-1, 2)
    if not len(a):
        return a
    a = a[np.argsort(a[:, 0], kind="stable")]
    hi = np.maximum.accumulate(a[:, 1])
    new = np.ones(len(a), bool)
    new[1:] = a[1:, 0] > hi[:-1]
    starts = a[new, 0]
    ends = hi[np.append(np.flatnonzero(new)[1:] - 1, len(a) - 1)]
    return np.stack([starts, ends], axis=1)


def clip(iv: Intervals, lo: int, hi: int) -> Intervals:
    if not len(iv):
        return iv
    out = np.stack([np.clip(iv[:, 0], lo, hi), np.clip(iv[:, 1], lo, hi)], 1)
    return out[out[:, 1] > out[:, 0]]


def total(iv: Intervals) -> int:
    return int((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0


def overlap(a: Intervals, b: Intervals) -> int:
    """ns covered by both of two merged interval sets."""
    if not len(a) or not len(b):
        return 0
    # coverage of b up to a point x, by prefix sums
    ends_cum = np.concatenate([[0], np.cumsum(b[:, 1] - b[:, 0])])

    def covered(x):
        i = np.searchsorted(b[:, 0], x, side="right")  # intervals begun
        full = ends_cum[i]
        # the last begun interval may reach past x
        last = np.maximum(i - 1, 0)
        over = np.where(i > 0, np.maximum(b[last, 1] - x, 0), 0)
        return full - over
    return int((covered(a[:, 1]) - covered(a[:, 0])).sum())


def gaps(busy: Intervals, lo: int, hi: int) -> Intervals:
    """What of [lo, hi) the merged set ``busy`` leaves uncovered."""
    b = clip(busy, lo, hi)
    edges = np.concatenate([[lo], b.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _strip(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def short_op(name: str) -> str:
    """An operation's event name is its whole HLO line; keep its id, what
    it is and the shape it makes: ``fusion.80 fusion s32[4194304]``."""
    m = re.match(r"%?([\w.\-]+) = \(?([\w\[\],]+)[^ ]* ([\w\-]+)\(", name)
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else name[:80]


def reduce_trace(path: str, n_top: int = 10) -> Dict:
    """Reduce one ``.xplane.pb`` (or a directory holding one).

    Returns ``window_s``; ``busy_s`` (union of device-operation intervals
    in the window, averaged over the device planes); ``n_devices``;
    ``module_s`` and ``module_runs`` (device seconds and runs per jitted
    program, summed over devices); ``op_s`` (per operation); ``device_ops``
    and ``idle_gaps`` as the result line's ``breakdown`` wants them.  A
    trace with no device plane gives ``busy_s`` None.
    """
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    dev_ops: List[List[Tuple[int, int]]] = []
    module_ns: Dict[str, int] = {}
    module_runs: Dict[str, int] = {}
    op_ns: Dict[str, int] = {}  # "<program>/<operation>" -> ns
    notes: Dict[str, List[Tuple[int, int]]] = {}
    window: Optional[Tuple[int, int]] = None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops: List[Tuple[int, int]] = []
            lines = {line.name: line for line in plane.lines}
            runs = []  # (start, end, program) of this device
            for ev in (lines[MODULES_LINE].events
                       if MODULES_LINE in lines else ()):
                n = _strip(ev.name)
                s, d = int(ev.start_ns), int(ev.duration_ns)
                runs.append((s, s + d, n))
                module_ns[n] = module_ns.get(n, 0) + d
                module_runs[n] = module_runs.get(n, 0) + 1
            runs.sort()
            starts = np.asarray([r[0] for r in runs], np.int64)
            for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                s, d = int(ev.start_ns), int(ev.duration_ns)
                ops.append((s, s + d))
                # the program whose run the operation lies in
                i = int(np.searchsorted(starts, s, side="right")) - 1
                prog = runs[i][2] if i >= 0 and s < runs[i][1] else "?"
                key = f"{prog}/{short_op(ev.name)}"
                op_ns[key] = op_ns.get(key, 0) + d
            dev_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(NOTE_PREFIX):
                        continue
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    if ev.name == WINDOW:
                        window = (s, s + d)
                    else:
                        notes.setdefault(ev.name, []).append((s, s + d))
    out: Dict = {"n_devices": len(dev_ops), "busy_s": None,
                 "window_s": None, "module_s": {}, "module_runs": {},
                 "op_s": {}, "device_ops": [], "idle_gaps": []}
    if not dev_ops or not any(dev_ops):
        return out
    merged = [merge(o) for o in dev_ops]
    if window is None:
        flat = np.concatenate([m for m in merged if len(m)])
        window = (int(flat[:, 0].min()), int(flat[:, 1].max()))
    lo, hi = window
    busy = [clip(m, lo, hi) for m in merged]
    out["window_s"] = (hi - lo) / 1e9
    out["busy_s"] = float(np.mean([total(b) for b in busy])) / 1e9
    out["module_s"] = {k: v / 1e9 for k, v in module_ns.items()}
    out["module_runs"] = module_runs
    out["op_s"] = {k: v / 1e9 for k, v in op_ns.items()}
    out["device_ops"] = [
        [k, v / 1e9] for k, v in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:n_top]]
    # idle gaps of the first device, by the benchmark's annotation that
    # covers them; the rest is the host's own, which only spans inside
    # the program could name
    idle = gaps(busy[0], lo, hi)
    by_name = {name: overlap(idle, merge(iv)) for name, iv in notes.items()}
    by_name[UNATTRIBUTED] = max(total(idle) - sum(by_name.values()), 0)
    out["idle_gaps"] = [
        [k, v / 1e9] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:n_top] if v > 0]
    out["longest_gap_s"] = float((idle[:, 1] - idle[:, 0]).max()) / 1e9 \
        if len(idle) else 0.0
    return out
