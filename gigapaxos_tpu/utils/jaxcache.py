"""Persistent XLA compilation cache, shared by every entry point.

The columnar kernels at serving capacity take seconds each to compile,
and the node runtime, the smoke, the bench, the dryrun children and the
test suite all compile the same dozen kernels in fresh processes.
JAX's persistent compilation cache keys on (HLO, platform, flags, cache
path), so one fixed directory makes every process after the first load
its compiles from disk.

Where the cache lives is decided from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it into
``jax_compilation_cache_dir`` and this module sets no other directory;
otherwise it is the fixed ``<checkout>/.jax_cache`` (git-ignored).
Never a temporary, pid- or time-derived path: a directory that moves
never hits.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

# the directory this process caches in, once enable_persistent_cache
# has run (the knobs are PROCESS-GLOBAL jax config; every
# ColumnarBackend construction calls it, and only the first call
# touches them)
_enabled: str | None = None


def enable_persistent_cache() -> None:
    """Turn JAX's persistent compilation cache on (idempotent; only the
    first call in a process touches jax config).  The directory is
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it — then
    no code sets another — and ``<checkout>/.jax_cache`` otherwise."""
    global _enabled
    if _enabled:
        return
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # cache everything: the hot kernels are small programs whose
    # compile time (not size) is what hurts
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled = jax.config.jax_compilation_cache_dir
    # arm the ledger's jax.monitoring listeners now so the very
    # first compile's cache_hits/cache_misses events are counted
    from gigapaxos_tpu.utils.engineledger import EngineLedger
    EngineLedger.install()


def cache_metrics() -> dict:
    """Live cache telemetry for ``metrics()`` / ``GET /engine``.  A
    cold-but-active cache now reads as ``active`` with ``misses > 0``,
    which is distinguishable from a disabled one (``active`` False,
    both counters frozen at whatever the in-memory plane saw)."""
    from gigapaxos_tpu.utils.engineledger import EngineLedger
    return {
        "active": bool(_enabled),
        "dir": _enabled,
        "hits": EngineLedger.cache_hits,
        "misses": EngineLedger.cache_misses,
    }
