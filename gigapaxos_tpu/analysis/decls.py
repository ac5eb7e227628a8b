"""Declared concurrency / hot-path / knob registry the rules read.

This file IS the project's concurrency contract, written down.  The
threading model: per node an asyncio event loop thread and ONE worker
thread, beside the lifecycle callers' threads (library / harness
create and delete) and the WAL writer.  Anything two of those touch
must be listed
here with the lock that guards it — the ``race`` rule then enforces
the contract mechanically, and NEW shared state that isn't declared
simply isn't checked, so declare it when you add it (MIGRATING has
the convention).

Deliberately NOT declared (single-writer by design, reads may tear
benignly): Transport's tx/rx/drop counters (event-loop-owned),
``PaxosNode._intake_tokens`` (decode-thread-owned),
``PaxosNode._stall_streak`` (lane-0 tick only), the singletons'
``enabled`` gates where only the boot path writes them, and
``RequestInstrumenter._last_evict``'s *readers* (the unlocked
throttle read is the point; the write still goes under the lock).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Optional, Tuple


@dataclass(frozen=True)
class ThreadedClass:
    """One class whose instances are touched by >1 thread.

    ``locks``: attribute names that hold ``threading.Lock``-likes.
    ``rlocks``: subset that are reentrant (nesting self is legal).
    ``guarded``: attr -> lock attr; every *mutation* of the attr must
    happen lexically inside ``with self.<lock>`` (``__init__`` and
    ``__new__`` excluded — no second thread exists yet).
    """

    locks: FrozenSet[str]
    rlocks: FrozenSet[str] = frozenset()
    guarded: Dict[str, str] = field(default_factory=dict)
    # methods exempt from the race rule (documented single-threaded
    # entry points, e.g. test-harness hooks) — use sparingly
    exempt_methods: FrozenSet[str] = frozenset()


@dataclass(frozen=True)
class HotPath:
    """One registered hot path (``"Class.method"`` key).

    mode "gate_first": the method must test one of ``gates`` before
    any allocation/formatting/logging work (the disabled cost is one
    attribute check).  mode "lean": the whole body must stay free of
    logging/formatting (allocation is its job, logging never is).
    """

    mode: str                      # "gate_first" | "lean"
    gates: Tuple[str, ...] = ()    # attr names or dotted Class.attr


@dataclass(frozen=True)
class WireDecl:
    """The wire-plane symmetry contract ``wiresym`` checks.

    All names refer to literals inside ``packets_rel``: the frame-type
    enum, the type->codec dispatch dict, the FRAG column packer/
    unpacker dicts, and the hello negotiation table (member name ->
    minimum peer wire version).  ``special_types`` are members with
    container/handshake semantics that deliberately have no entry in
    the codec dispatch; ``version_gated`` members may only be sent to
    a peer after its hello announced a sufficient version, so they
    must appear in the gate table.
    """

    packets_rel: str = "gigapaxos_tpu/paxos/packets.py"
    enum_name: str = "PacketType"
    decoders_name: str = "_DECODERS"
    packers_name: str = "_FRAG_PACKERS"
    unpackers_name: str = "_FRAG_UNPACKERS"
    gate_table: str = "WIRE_GATED"
    special_types: FrozenSet[str] = frozenset({"FRAG", "WIRE_HELLO"})
    version_gated: FrozenSet[str] = frozenset({"FRAG"})


@dataclass(frozen=True)
class Decls:
    threaded: Dict[str, ThreadedClass] = field(default_factory=dict)
    hot_paths: Dict[str, HotPath] = field(default_factory=dict)
    # canonical outer -> inner acquisition order; an observed edge
    # contradicting this order is a deadlock seed
    lock_order: Tuple[str, ...] = ()
    # lock ids that must be innermost (no other declared lock may be
    # acquired while holding one)
    leaf_locks: FrozenSet[str] = frozenset()
    # "Class.attr" of a *list* of locks -> helper methods that yield
    # them in canonical index order; accumulating acquisition (e.g.
    # ExitStack) must go through a helper or ``sorted(...)``
    indexed_locks: Dict[str, Tuple[str, ...]] = field(
        default_factory=dict)
    # alias lock attr -> canonical lock id (e.g. _engine_lock is
    # lane 0 of _engine_locks)
    lock_aliases: Dict[str, str] = field(default_factory=dict)
    # knob-family prefix -> call that must appear in tests/conftest.py
    # (None = plain Config.clear() coverage is enough)
    knob_families: Dict[str, Optional[str]] = field(default_factory=dict)
    # config class name holding the knob enum ("PC")
    knob_class: str = "PC"
    # -- interprocedural rules (analysis v2) ---------------------------
    # digest-affecting wave entry points: everything reachable from
    # these must read the engine clock, never the wall clock
    wave_roots: Tuple[str, ...] = ()
    # the one declared engine-clock accessor ("PaxosNode._now") —
    # itself allowed to read time.time() (it IS the pin fallback)
    engine_clock: str = ""
    # clockpurity exemptions: "Class.*" (whole class), "qualname"
    # (whole function) or "qualname::snippet-fragment" (one site) ->
    # non-empty why.  An empty why does NOT exempt — the rule treats
    # it as undeclared and fires.
    clock_exempt: Dict[str, str] = field(default_factory=dict)
    # loopblock exemptions, same key forms and same empty-why teeth
    loopblock_exempt: Dict[str, str] = field(default_factory=dict)
    # resetscope: rel-path suffixes of the scenario/harness files the
    # rule patrols, the mutator -> restorer call pairs it enforces,
    # and qualname exemptions (why required)
    reset_scope_files: Tuple[str, ...] = ()
    reset_pairs: Dict[str, Tuple[str, ...]] = field(default_factory=dict)
    reset_exempt: Dict[str, str] = field(default_factory=dict)
    # wire-plane symmetry contract (None disables wiresym)
    wire: Optional[WireDecl] = None


def project_decls() -> Decls:
    """The registry for THIS repo's tree."""
    threaded = {
        # the worker + event loop + lifecycle callers' threads; the
        # counters go through _stat_lock (a bare += loses updates)
        "PaxosNode": ThreadedClass(
            locks=frozenset({"_engine_lock", "_stat_lock"}),
            rlocks=frozenset({"_engine_lock"}),
            guarded={c: "_stat_lock" for c in (
                "n_executed", "n_decided", "n_paused", "n_unpaused",
                "n_redriven", "n_parked", "n_park_dropped",
                "n_redrive_capped", "n_wave_dups", "n_installs",
                "n_ballot_changes",
                "n_shed", "n_shed_disk", "n_wal_nacked",
                "_degraded_seen")},
        ),
        # name/row registry: the worker resolves while lifecycle
        # callers create/delete
        "GroupTable": ThreadedClass(
            locks=frozenset({"_mut"}),
            guarded={a: "_mut" for a in
                     ("_by_key", "_by_row", "_free", "_msets",
                      "_rows")},
        ),
        # note_rtt runs on worker threads, metrics() on the loop
        "Transport": ThreadedClass(
            locks=frozenset({"_rtt_lock"}),
            guarded={"_rtt": "_rtt_lock"},
        ),
        # the WAL file has one writer lock; the sqlite handle one db
        # lock.  _wal is guarded because compaction swaps the handle
        # in place — writers must re-read it under the lock (the
        # closed-handle race fixed alongside this suite).  _gen rides
        # the same contract: fsync-failure rotation bumps the
        # generation while holding the lock.  The health flags
        # (degraded / disk-full / rotation and quarantine tallies) are
        # written from writer threads and read by the stats listener,
        # so they get their own innermost _health_lock — nested inside
        # the wal/db sections that discover the faults
        "PaxosLogger": ThreadedClass(
            locks=frozenset({"_wal_lock", "_db_lock",
                             "_health_lock"}),
            guarded={**{a: "_wal_lock" for a in ("_wal", "_gen")},
                     **{a: "_health_lock" for a in
                        ("_degraded", "_disk_full", "_rotations",
                         "_quarantined", "_ckpt_bad")}},
        ),
        # class-attribute singletons: every update hook may be hit
        # from any stage thread
        "DelayProfiler": ThreadedClass(
            locks=frozenset({"_lock"}),
            guarded={a: "_lock" for a in
                     ("_delays", "_values", "_rates", "_totals",
                      "_hists")},
        ),
        "RequestInstrumenter": ThreadedClass(
            locks=frozenset({"_lock"}),
            guarded={a: "_lock" for a in
                     ("_ring", "_spans", "_open", "_slow",
                      "n_span_begun", "n_span_ended",
                      "n_span_orphaned", "n_span_dropped",
                      "_last_evict")},
        ),
        "ChaosPlane": ThreadedClass(
            locks=frozenset({"_lock"}),
            guarded={a: "_lock" for a in
                     ("_rules", "_blocked", "_rngs", "_per_pair",
                      "n_dropped", "n_blocked", "n_delayed",
                      "n_reordered", "enabled", "seed")},
        ),
        # storage fault plane: on_fsync/on_append run on WAL writer
        # threads (under the segment lock) while scenarios configure
        # rules from the harness thread
        "StorageChaos": ThreadedClass(
            locks=frozenset({"_lock"}),
            guarded={a: "_lock" for a in
                     ("_rules", "_rngs", "_poisoned", "_per_pair",
                      "n_fsync_eio", "n_enospc", "n_slow",
                      "n_torn", "enabled", "seed")},
        ),
        "Config": ThreadedClass(
            locks=frozenset({"_lock"}),
            rlocks=frozenset({"_lock"}),
            guarded={"_layers": "_lock"},
        ),
        # engine flight deck's compile/retrace ledger: note_trace runs
        # wherever JAX traces (lane workers, warm-up, the cost-sweep),
        # jax.monitoring listeners fire on compile threads, and
        # snapshot()/kernels() run on the stats listener.  `monitoring`
        # is deliberately undeclared: only the boot path (install)
        # writes it (the documented single-writer gate exemption).
        "EngineLedger": ThreadedClass(
            locks=frozenset({"_lock"}),
            guarded={a: "_lock" for a in
                     ("_kernels", "cache_hits", "cache_misses",
                      "compile_s", "_warmed", "_installed",
                      "_trigger_fns")},
        ),
        # flight-recorder capture ring: the note_* hooks run on the
        # intake/lane/logger threads while dump/snapshot run on
        # trigger threads and the stats listener; the class-level
        # _live registry is touched by node boot/stop and dump_all
        "BlackboxRecorder": ThreadedClass(
            locks=frozenset({"_lock", "_live_lock"}),
            guarded={**{a: "_lock" for a in
                        ("_ring", "_bytes", "n_records", "n_evicted",
                         "n_dumps", "_last_trigger", "_churn_mark",
                         "last_dump")},
                     "_live": "_live_lock"},
        ),
    }
    hot_paths = {
        # peer send entry: every frame crosses this
        "Transport._enqueue": HotPath(
            "gate_first", gates=("test_drop_rate",
                                 "ChaosPlane.enabled")),
        "Transport._enqueue_now": HotPath("lean"),
        "Transport._write": HotPath("lean"),
        # wire-plane aggregation (PR 13): the emit coalescer and the
        # FRAG codec sit on every storm-path frame; allocation is
        # their job, logging never is
        "Transport.send_many": HotPath("lean"),
        "Transport.send_frags": HotPath("lean"),
        "Transport._make_chunk": HotPath("lean"),
        "WireChunk.__init__": HotPath("lean"),
        "Frag.encode": HotPath("lean"),
        "Frag.split": HotPath("lean"),
        "ChaosPlane.on_send": HotPath("lean"),
        # storage fault hooks sit on every WAL fsync/append; one
        # class-attribute check when the plane is off
        "StorageChaos.on_fsync": HotPath("lean"),
        "StorageChaos.on_append": HotPath("lean"),
        "StorageChaos.is_poisoned": HotPath("lean"),
        # per-request tracing hooks: one attribute check when off
        "RequestInstrumenter.record": HotPath(
            "gate_first", gates=("enabled",)),
        "RequestInstrumenter.span_begin": HotPath(
            "gate_first", gates=("enabled",)),
        # the stage-span primitive at every worker/engine/WAL boundary:
        # off, the sums and this one gate (the operator's switch, then
        # the profiler's own is_enabled)
        "span.__enter__": HotPath(
            "gate_first", gates=("enabled",)),
        "span.traced": HotPath(
            "gate_first", gates=("enabled",)),
        "RequestInstrumenter.note_done": HotPath(
            "gate_first", gates=("enabled",)),
        "RequestInstrumenter.sampled_mask": HotPath(
            "gate_first", gates=("enabled",)),
        # per-stage delay hooks
        "DelayProfiler.update_delay": HotPath(
            "gate_first", gates=("enabled",)),
        "DelayProfiler.update_value": HotPath(
            "gate_first", gates=("enabled",)),
        "DelayProfiler.update_rate": HotPath(
            "gate_first", gates=("enabled",)),
        "DelayProfiler.update_total": HotPath(
            "gate_first", gates=("enabled",)),
        "DelayProfiler.add_total": HotPath(
            "gate_first", gates=("enabled",)),
        # columnar wave submits: allocation is their job, logging
        # and f-strings are not
        "ColumnarBackend.accept_submit": HotPath("lean"),
        "ColumnarBackend.accept_reply_submit": HotPath("lean"),
        "ColumnarBackend.commit_submit": HotPath("lean"),
        # the wave's submit half IS the constructor
        "EngineWave.__init__": HotPath("lean"),
        "EngineWave.collect": HotPath("lean"),
        # flight-recorder capture hooks: every call site gates on
        # `self.blackbox is not None` (one attribute check when off),
        # so the bodies just have to stay lean
        "BlackboxRecorder.note_frames": HotPath("lean"),
        "BlackboxRecorder.note_wave": HotPath("lean"),
        "BlackboxRecorder.note_wal": HotPath("lean"),
        "BlackboxRecorder.note_tick": HotPath("lean"),
        "BlackboxRecorder.note_ingress": HotPath("lean"),
        "BlackboxRecorder._append": HotPath("lean"),
        # compile-ledger trace hook: only runs while JAX traces a
        # kernel (never on steady-state dispatch), but it sits inside
        # every traced function — keep it free of logging/formatting
        "EngineLedger.note_trace": HotPath("lean"),
    }
    return Decls(
        threaded=threaded,
        hot_paths=hot_paths,
        # the engine lock is outermost (it serializes the worker
        # against control-plane ops), then the group table's mutation
        # lock, then the WAL/db sections (WITNESS_r01 showed the
        # worker nests them inside the engine lock on every durable
        # wave; the storage fault plane demoted them from leaves —
        # they now nest the health flag and StorageChaos leaves when
        # a write discovers a fault); stat/profiler/instrument/chaos
        # locks are leaves
        lock_order=("PaxosNode._engine_lock", "GroupTable._mut",
                    "PaxosLogger._wal_lock", "PaxosLogger._db_lock",
                    "PaxosLogger._health_lock",
                    "PaxosNode._stat_lock"),
        leaf_locks=frozenset({
            "PaxosNode._stat_lock", "Transport._rtt_lock",
            "DelayProfiler._lock", "RequestInstrumenter._lock",
            "ChaosPlane._lock", "Config._lock",
            "BlackboxRecorder._lock", "BlackboxRecorder._live_lock",
            # the WAL health flags and the storage fault plane are the
            # new innermost sections: a writer that trips EIO/ENOSPC
            # records it while still holding the segment/db lock, so
            # those two moved into lock_order above and these O(1)
            # regions became the leaves
            "PaxosLogger._health_lock", "StorageChaos._lock",
            # the compile-ledger lock protects dict/counter updates
            # only; trigger callbacks fire AFTER it is released
            "EngineLedger._lock",
        }),
        knob_families={
            "CHAOS_": "ChaosPlane.reset",
            "STORAGE_CHAOS_": "StorageChaos.reset",
            # read once at logger construction into per-node state,
            # torn down with the node; Config.clear() is enough
            "WAL_CRC": None,
            "BLACKBOX_": "BlackboxRecorder.reset",
            "TRACE_": "RequestInstrumenter.reset",
            "SLOW_TRACE_": "RequestInstrumenter.reset",
            "PROFILE_": "DelayProfiler.clear",
            # read at node boot into per-node state, torn down with
            # the node; Config.clear() coverage is enough
            "STATS_": None,
            # engine-shape knobs (ENGINE_MESH,
            # ENGINE_RETRACE_TRIGGER): read once at backend/node
            # construction, torn down with the node — but the compile/
            # retrace ledger the family now also covers is a process
            # singleton whose trigger registrations and warm/retrace
            # state must not leak across tests
            "ENGINE_": "EngineLedger.reset",
            # wire-plane knobs (PR 13): read once into the Transport at
            # node boot, torn down with the node — same contract
            "WIRE_": None,
            # lock-witness knobs mirror into the LockWitness singleton
            # (wrapped locks + the observed acquisition graph): a test
            # that arms it must not leak edges into the next test
            "LOCK_WITNESS": "LockWitness.reset",
            "WITNESS_": "LockWitness.reset",
        },
        # -- clockpurity ------------------------------------------------
        # wave entry points whose transitive closure feeds the blackbox
        # digests: _process (decode->handle->emit) and the tick path
        # (redrive/failover emissions ride the same digest stream)
        wave_roots=("PaxosNode._process", "PaxosNode._tick",
                    "PaxosNode._tick_inner"),
        engine_clock="PaxosNode._now",
        clock_exempt={
            # measurement-only stamps: they ride the artifact/metrics
            # plane, never a frame or a digest input
            "PaxosNode._process::_batch_t0":
                "wall anchor for the client-retry sleep budget; "
                "compared against client deadlines, not digested",
            "PaxosNode._process::monotonic":
                "emit-stage queue-delay profiler stamp (metrics only)",
            "PaxosNode._process_inner::time_ns":
                "RTT sample fed to Transport.note_rtt (metrics only)",
            "PaxosNode._process_inner::monotonic":
                "per-wave handler-latency profiler span (metrics only)",
            "PaxosNode._handle_hot_split::monotonic":
                "per-stage handler-latency profiler spans (metrics only)",
            "PaxosNode._execute_row::_batch_t0":
                "app-retry sleep budget: wall elapsed vs the batch's "
                "wall anchor gates a retry SLEEP, never a frame field",
            "PaxosNode._execute_row::waiter[1]":
                "client-waiter end-to-end latency sample "
                "(DelayProfiler plane)",
            "PaxosNode._elect_rows_led_by::monotonic":
                "election-scan profiler span (metrics only)",
            "PaxosNode._start_elections_batch::monotonic":
                "failover-batch profiler span (metrics only)",
            "PaxosNode._install_simple_rows::monotonic":
                "mass-install profiler span (metrics only)",
            "PaxosLogger.log_raw_inline::monotonic":
                "WAL-append latency profiler span (metrics only)",
            "PaxosNode._frontier_ask::monotonic":
                "start of the rec.catchup profiler span (metrics only; "
                "the exchange's timers read _now())",
            "PaxosNode._frontier_end::monotonic":
                "length of the rec.catchup profiler span (metrics only)",
            "PaxosNode._ri_arrive::monotonic":
                "a read's arrival for the ri.wait total (metrics only; "
                "its timeout reads _now())",
            "PaxosNode._ri_answer::monotonic":
                "the ri.wait / ri.exec_wait totals' end (metrics only)",
            "PendingReads.on_reply::monotonic":
                "a read's confirmation for the ri.exec_wait total "
                "(metrics only)",
            "_Rate.*":
                "DelayProfiler's internal rate window — the "
                "measurement plane's own clock",
            "Transport.*":
                "transport timing is pacing/metrics (RTT notes, paced "
                "sends, reconnect backoff); frames it moves are "
                "byte-identical regardless, so digests never see it",
            "DelayProfiler.*":
                "the profiler IS the measurement plane",
            "RequestInstrumenter.*":
                "per-request tracing stamps (observability plane)",
            "BlackboxRecorder.*":
                "capture-ring wall stamps annotate records for humans; "
                "replay digests come from note_frames' pinned ts",
            "ChaosPlane.*":
                "fault-injection delay arithmetic; chaos runs are "
                "seed-deterministic via their own rng, and the engine "
                "digests are taken on the frames it delivers",
            "StorageChaos.*":
                "slow-fsync delay arithmetic (sleep injection); the "
                "fault schedule itself is seed-deterministic via the "
                "per-(node,segment) rng streams",
            "EngineLedger.*":
                "compile-ledger wall stamps (last-trace times, compile "
                "seconds) are observability-plane only; traced kernels "
                "never read them and digests never see them",
        },
        # -- loopblock --------------------------------------------------
        loopblock_exempt={},
        # -- resetscope -------------------------------------------------
        reset_scope_files=("gigapaxos_tpu/chaos/scenarios.py",
                           "gigapaxos_tpu/testing/harness.py"),
        reset_pairs={
            # Config.set counts as its own restorer: a finally that
            # re-installs the prior value is the canonical pattern
            "Config.set": ("Config.clear", "Config.unset",
                           "Config.set"),
            "ChaosPlane.configure": ("ChaosPlane.reset",),
            "ChaosPlane.set_link": ("ChaosPlane.reset",
                                    "ChaosPlane.heal"),
            "ChaosPlane.partition": ("ChaosPlane.reset",
                                     "ChaosPlane.heal"),
            "StorageChaos.configure": ("StorageChaos.reset",),
            "StorageChaos.set_rule": ("StorageChaos.reset",
                                      "StorageChaos.clear",
                                      "StorageChaos.set_rule"),
        },
        reset_exempt={
            "PaxosEmulation.__init__":
                "every boot sets its knobs explicitly and tests "
                "restore via the autouse Config.clear fixture; the "
                "emulation object has no teardown scope of its own",
            "_sc_crash_storm":
                "chaos rules restored by run_scenario's finally "
                "(ChaosPlane.reset) across the dict dispatch",
            "_sc_partition_heal":
                "chaos rules restored by run_scenario's finally "
                "(ChaosPlane.reset) across the dict dispatch",
            "_sc_rolling_restart":
                "chaos rules restored by run_scenario's finally "
                "(ChaosPlane.reset) across the dict dispatch",
            "_sc_zipf_hot":
                "chaos rules restored by run_scenario's finally "
                "(ChaosPlane.reset) across the dict dispatch",
            "_sc_mini_partition_heal":
                "chaos rules restored by run_scenario's finally "
                "(ChaosPlane.reset) across the dict dispatch",
            "_sc_disk_storm":
                "storage rules restored by run_scenario's finally "
                "(StorageChaos.reset) across the dict dispatch",
            "_sc_mini_disk_fault":
                "storage rules restored by run_scenario's finally "
                "(StorageChaos.reset) across the dict dispatch",
        },
        wire=WireDecl(),
    )
