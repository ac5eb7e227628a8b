"""Compile/retrace ledger: the device axis of the flight deck (PR 18).

Every jit entry point in :mod:`gigapaxos_tpu.ops.kernels` and
:mod:`gigapaxos_tpu.ops.meshkernels` wraps its *traced* Python function
with :meth:`EngineLedger.traced`.  The wrapper body only runs while JAX
is tracing — i.e. exactly once per (kernel, signature) compile — so the
steady-state dispatch cost of the ledger is literally zero: after the
first compile the Python body is never re-entered and no counter, lock,
or clock is touched on the wave path.  That is a stronger guarantee
than the PR 7 "one attribute check when off" contract; there is no off
switch because there is nothing to switch off.

Two listener planes complement the trace counters where this JAX build
exposes :mod:`jax.monitoring` (guarded — older builds without it fall
back to trace counting alone):

- ``/jax/core/compile/backend_compile_duration`` events attribute XLA
  compile seconds to the kernel whose trace is in flight on that thread
  (compiles run synchronously inside the traced jit call, so a
  thread-local "current kernel" tag is exact).
- ``/jax/compilation_cache/cache_hits`` / ``cache_misses`` events count
  the persistent-cache outcome of each compile, surfacing whether
  ``utils/jaxcache.py``'s disk cache is actually absorbing compiles or
  merely configured.

The retrace alarm: :class:`ColumnarBackend` brackets its construction
warm-up in :meth:`warming` and calls :meth:`mark_warm` when the ladder
is hot.  After that, a *re*-trace of an already-compiled kernel — the
bucket ladder guarantees no legitimate shape ever re-traces — is an
incident: the ledger bumps the kernel's ``retraces`` counter and fires
every registered trigger callback (the node wires its flight
recorder's ``BlackboxRecorder.trigger``, gated by
``PC.ENGINE_RETRACE_TRIGGER``), so a mid-storm recompile dumps the
capture ring instead of silently eating the tail.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

from gigapaxos_tpu.utils.instrument import traced

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class EngineLedger:
    """Process-global compile/retrace ledger (class-attribute singleton,
    like :class:`DelayProfiler`)."""

    _lock = threading.Lock()
    # kernel name -> {"compiles", "retraces", "compile_s", "last_ts"}
    _kernels: Dict[str, dict] = {}
    _tl = threading.local()          # .current = kernel name mid-trace
    _warmed = False                  # first backend finished its warm-up
    _installed = False               # jax.monitoring listeners armed
    monitoring = False               # listener plane actually available
    cache_hits = 0
    cache_misses = 0
    compile_s = 0.0                  # aggregate XLA compile seconds
    # retrace trigger callbacks: reason -> ignored return (the node
    # registers its blackbox's trigger; deregistered on node stop)
    _trigger_fns: List[Callable[[str], object]] = []

    # -- wiring --------------------------------------------------------

    @classmethod
    def install(cls) -> None:
        """Arm the jax.monitoring listeners (idempotent; safe when the
        build has no monitoring module)."""
        with cls._lock:
            if cls._installed:
                return
            cls._installed = True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                cls._on_duration)
            monitoring.register_event_listener(cls._on_event)
            cls.monitoring = True
        except Exception:
            cls.monitoring = False

    @classmethod
    def _on_duration(cls, name: str, dur: float, **_kw) -> None:
        if name != _COMPILE_EVENT:
            return
        cur = getattr(cls._tl, "current", None)
        with cls._lock:
            cls.compile_s += dur
            if cur is not None and cur in cls._kernels:
                cls._kernels[cur]["compile_s"] += dur

    @classmethod
    def _on_event(cls, name: str, **_kw) -> None:
        if name == _CACHE_HIT_EVENT:
            with cls._lock:
                cls.cache_hits += 1
        elif name == _CACHE_MISS_EVENT:
            with cls._lock:
                cls.cache_misses += 1

    @classmethod
    def traced(cls, name: str, fn: Callable) -> Callable:
        """Wrap ``fn`` (the function handed to ``jax.jit``) so each
        trace of it is counted against ``name``.  The wrapper runs only
        under the tracer — never on a cached dispatch."""
        cls.install()

        def _traced(*args, **kwargs):
            cls.note_trace(name)
            cls._tl.current = name
            try:
                # under whatever dispatched: a trace inside a traced
                # window is named where it stalls (gp.eng.compile)
                with traced("eng.compile", kernel=name):
                    return fn(*args, **kwargs)
            finally:
                cls._tl.current = None

        _traced.__name__ = getattr(fn, "__name__", name)
        _traced.__qualname__ = _traced.__name__
        return _traced

    # -- trace accounting ----------------------------------------------

    @classmethod
    def note_trace(cls, name: str) -> None:
        """One tracer entry for kernel ``name`` (cold by construction:
        the tracer itself costs orders of magnitude more)."""
        fire = False
        with cls._lock:
            k = cls._kernels.get(name)
            if k is None:
                k = {"compiles": 0, "retraces": 0, "compile_s": 0.0,
                     "last_ts": 0.0, "hot": False}
                cls._kernels[name] = k
                known = False
            else:
                known = k["compiles"] > 0
            k["compiles"] += 1
            k["last_ts"] = time.time()
            warming = getattr(cls._tl, "warming", 0)
            if warming:
                # warm-up traces define the hot set: only kernels a
                # backend warms (the bucket-ladder entries) alarm on
                # re-trace — cold control ops legitimately trace new
                # capacities mid-life
                k["hot"] = True
            elif known and cls._warmed and k["hot"]:
                k["retraces"] += 1
                fire = True
            fns = list(cls._trigger_fns) if fire else ()
        if fns:
            cls._fire_retrace(name, fns)

    @classmethod
    def _fire_retrace(cls, name: str, fns) -> None:
        """Incident path (post-warmup retrace of a hot kernel): format
        the reason and fan out to the registered triggers.  Split out
        of :meth:`note_trace` so the lean trace path stays
        allocation-free on the common (non-incident) branch."""
        for fn in fns:
            try:
                fn(f"engine_retrace:{name}")
            except Exception:
                pass

    @classmethod
    def warming(cls) -> "_Warming":
        """Context manager bracketing a deliberate (re)compile burst —
        backend warm-up, cost-analysis lowering — so it never reads as
        a retrace incident."""
        return _Warming(cls)

    @classmethod
    def mark_warm(cls) -> None:
        """A backend finished `_warm_kernels`: from here on, a re-trace
        of a known kernel is an incident."""
        with cls._lock:
            cls._warmed = True

    # -- trigger plane -------------------------------------------------

    @classmethod
    def add_trigger(cls, fn: Callable[[str], object]) -> None:
        with cls._lock:
            if fn not in cls._trigger_fns:
                cls._trigger_fns.append(fn)

    @classmethod
    def remove_trigger(cls, fn: Callable[[str], object]) -> None:
        with cls._lock:
            try:
                cls._trigger_fns.remove(fn)
            except ValueError:
                pass

    # -- views ---------------------------------------------------------

    @classmethod
    def snapshot(cls) -> dict:
        """JSON-able ledger state for ``metrics()`` / ``GET /engine``."""
        with cls._lock:
            kernels = {n: dict(k) for n, k in cls._kernels.items()}
            return {
                "kernels": len(kernels),
                "compiles": sum(k["compiles"] for k in kernels.values()),
                "retraces": sum(k["retraces"] for k in kernels.values()),
                "compile_s": cls.compile_s,
                "cache_hits": cls.cache_hits,
                "cache_misses": cls.cache_misses,
                "monitoring": cls.monitoring,
                "warmed": cls._warmed,
            }

    @classmethod
    def kernels(cls) -> Dict[str, dict]:
        """Per-kernel ledger rows for ``GET /engine/kernels``."""
        with cls._lock:
            return {n: dict(k) for n, k in cls._kernels.items()}

    @classmethod
    def retraces(cls, name: Optional[str] = None) -> int:
        with cls._lock:
            if name is not None:
                k = cls._kernels.get(name)
                return int(k["retraces"]) if k else 0
            return sum(k["retraces"] for k in cls._kernels.values())

    # -- test hooks ----------------------------------------------------

    @classmethod
    def reset(cls) -> None:
        """Conftest family-reset for ``ENGINE_*``: drop trigger
        callbacks and the warm/retrace latches so one test's forced
        retrace can't alarm the next.  Keeps the compile tallies —
        jit caches persist across tests, so forgetting which kernels
        exist would miscount a later legitimate cache hit as fresh."""
        with cls._lock:
            cls._trigger_fns.clear()
            cls._warmed = False
            for k in cls._kernels.values():
                k["retraces"] = 0


class _Warming:
    """Re-entrant thread-local warming bracket."""

    __slots__ = ("_cls",)

    def __init__(self, cls):
        self._cls = cls

    def __enter__(self):
        tl = self._cls._tl
        tl.warming = getattr(tl, "warming", 0) + 1
        return self

    def __exit__(self, *exc):
        self._cls._tl.warming -= 1
        return False
