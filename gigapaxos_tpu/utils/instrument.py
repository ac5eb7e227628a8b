"""Per-request cross-stage tracing + pipeline-stage spans + the
cluster tracing plane.

Reference analog: ``gigapaxos/paxosutil/RequestInstrumenter.java`` — at
FINE log level the reference records per-request send/receive timestamps
across nodes so a single request's path can be reconstructed.  Here:
a process-global ring of (req_id, stage, node, t) events, enabled by
``PC.TRACE_REQUESTS`` (or ``RequestInstrumenter.enabled = True``), with
near-zero cost when disabled (one class-attribute check at each hook).

Stages recorded by the node runtime: ``recv`` (entry intake), ``fwd``
(entry forwards the proposal toward the coordinator), ``prop`` (slot
granted at the coordinator), ``acc.tx`` (accept fan-out leaves the
coordinator), ``acc`` (accept fsync-durable at an acceptor), ``dec``
(quorum crossed at the coordinator), ``com.tx`` (commit fan-out leaves
the coordinator), ``exec`` (app executed / response queued at a
replica).  The ``*.tx`` send stamps pair with the matching arrival
stamps on other nodes, so :meth:`cluster_breakdown` can attribute the
network hop between each pair of nodes.

Trace context (the cluster plane): a request's trace id IS its req_id
(req ids are globally unique — ``client_id << 32 | seqno`` — so the hot
batch packets already carry the trace id end to end with zero new wire
bytes).  The *sampled* decision is DETERMINISTIC in the trace id
(golden-ratio hash vs ``PC.TRACE_SAMPLE``), so every node in the
cluster reaches the same verdict without propagating a flag; a client
can additionally force a trace with the wire flag bit
``packets.Request.FLAG_SAMPLED``, which rides the flags byte through
Request/Proposal and the accept payload blobs (old nodes ignore the
unknown bit — the wire format is unchanged).  When sampling is off the
hot path pays one class-attribute check per hook, nothing else.

Stage spans (:class:`span`, the one primitive at every boundary of a
served wave): ``with span(kind, node=, n=, **attrs)`` always adds wall
seconds, a call and ``n`` items to the ``DelayProfiler`` total
``total or kind``.  The span itself comes on in two ways, with no knob
of its own: the operator's switch (``RequestInstrumenter.enabled``, from
``PC.TRACE_REQUESTS`` / ``PC.TRACE_SAMPLE``), or a JAX profiler session
(``jax.profiler.TraceAnnotation.is_enabled()``).  On, it is an event
``gp.<kind>`` on its thread's line of the profile's ``/host:CPU`` plane —
the same ``.xplane.pb`` and clock as the device's ``XLA Ops`` — with
``node``, ``wave``, ``n`` and the attributes as its stats, and a record
in the span ring: kind, node, thread, wave, ``parent`` (the id of the
enclosing span on the thread, 0 for none), t0, t1, n and attributes.
Off, it costs its sums and one gate check; the ring takes nothing, so
after a profiled window it holds that window and only that.

    gp.w.wait *      the worker's blocking get       timed_out
    gp.w.coalesce *  the coalescing nap              prev_items
    gp.w.decode      _decode_batch                   frames, queue_wait_s
    gp.w.process     _process under the engine lock  items, lock_wait_s
    gp.w.tick *      _tick under the engine lock     (shard)
    gp.w.emit        _emit_bundle                    frames
    gp.eng.submit    _submit1/_submit2   kernel, lanes, bucket, chunks,
                                         launched
    gp.eng.pack *    _packed (inside submit)         bytes
    gp.eng.collect   EngineWave.collect              lanes, overlap_s
    gp.wal *         log_raw_inline (lock, append, sync)  entries, bytes
    gp.wal.fsync *   the os.fsync alone (inside gp.wal)
    gp.app.execute   _execute_rows: a batch's execute loop   items,
                     (a call of its sum only where something  reply_bytes
                     executed)
    gp.eng.compile * a kernel's trace (EngineLedger.traced)  kernel
    gp.fo.suspect *  _on_node_dead (with its scan)   dead
    gp.fo.scan       rows led by a suspect, by member set    dead, elect
    gp.fo.elect_start  _start_elections_batch        items
    gp.fo.prepare    an acceptor's answer to Prepare(Batch)  lanes
    gp.fo.reply      a PrepareReplyBatch merged      lanes, slow_rows
    gp.fo.install    coordinator install (batch or one row)  items,
                                                     carried
    gp.eng.prepare   backend.prepare     kernel, program, lanes, bucket,
    gp.eng.install   backend.install_coordinator     chunks, bytes

(* :func:`traced`: the boundaries inside a summed stage are spans with
no sum of their own, and off they cost one gate check and no object.)
The first five are siblings and tile a worker thread's time.  ``wait``
and ``coalesce`` carry wave 0; the others the *wave id* of their worker
batch — one per batch, bound thread-locally by the worker, and at
submit for a collect — and trace events record the
wave they happened in, so :meth:`request_spans` /
:meth:`request_breakdown` decompose one request into decode, process,
WAL, and emit without rerunning the bench — and
:meth:`cluster_breakdown` generalizes that to the whole deployment by
merging per-node ring exports (``export_trace`` over ``/traces/<id>``).
``span_begin`` / ``span_end`` are the pair underneath.

Hygiene: ring eviction is age-based as well as size-based
(``max_age_s``): spans from long-dead waves no longer linger in the
aggregate view, and spans that were begun but never ended (a stage
crashed mid-span) age into an explicit ``orphaned`` counter instead of
silently skewing the begun/ended pairing forever; completed spans that a
full ring pushes out are counted as ``dropped``.  A bounded top-K
slow-request log (``slow_threshold_s`` / ``slow_k``) keeps the worst
sampled traces for the stats dumper.
"""

from __future__ import annotations

import heapq
import itertools
import sys
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Tuple

from gigapaxos_tpu.utils.profiler import DelayProfiler

# golden-ratio multiplicative hash: the deterministic sampling verdict
# every node computes identically from the trace id alone
_GOLD = 0x9E3779B97F4A7C15
_M64 = (1 << 64) - 1
_SBITS = 24  # sampling-threshold resolution (1/2^24 granularity)

_TA = None  # jax.profiler.TraceAnnotation, once jax is in the process


def _profiling() -> bool:
    """True while a JAX profiler session is active.  A process that
    never imported jax (a bare client) cannot have one, and is not
    made to import it here; once jax is there, this name is rebound to
    ``TraceAnnotation.is_enabled`` itself (one C call on the gate)."""
    global _TA, _profiling
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation
    _TA = TraceAnnotation
    _profiling = TraceAnnotation.is_enabled
    return _profiling()


class TraceContext(NamedTuple):
    """Compact trace context minted at the client/entry node.

    ``trace_id`` is the request id (globally unique already);
    ``parent_span`` is the wave id active at mint time (0 = none);
    ``sampled`` is the cluster-deterministic sampling verdict."""

    trace_id: int
    parent_span: int
    sampled: bool


class RequestInstrumenter:
    """Global trace + span rings; thread-safe, bounded (size AND age)."""

    enabled: bool = False
    # fraction of requests recorded while enabled (PC.TRACE_SAMPLE;
    # 1.0 = everything, the PC.TRACE_REQUESTS legacy behavior).  The
    # verdict is a pure function of the req_id, so all nodes agree.
    sample_rate: float = 1.0
    _sample_thresh: int = 1 << _SBITS
    # age-based eviction horizon for ring entries/spans (0 disables)
    max_age_s: float = 300.0
    # slow-request log: keep the top slow_k sampled traces whose total
    # exceeded slow_threshold_s (0 disables)
    slow_threshold_s: float = 0.0
    slow_k: int = 32

    _lock = threading.Lock()
    _ring: "deque" = deque(maxlen=200_000)   # (req, stage, node, t, wave)
    # completed span dicts: six times the benchmark's traced 4 s at the
    # served cells' 1.6-2.0K spans a second (PERF.md §6, PR 26)
    _spans: "deque" = deque(maxlen=50_000)
    _open: Dict[int, dict] = {}              # id(span) -> span, not ended
    _tls = threading.local()
    _wave_seq = itertools.count(1)
    n_span_begun: int = 0
    n_span_ended: int = 0
    n_span_orphaned: int = 0
    n_span_dropped: int = 0                  # pushed out of a full ring
    _slow: List[tuple] = []                  # min-heap (total, seq, id, ts)
    _slow_seq = itertools.count(1)
    _last_evict: float = 0.0

    # -- configuration -----------------------------------------------------

    @classmethod
    def configure(cls, sample_rate: Optional[float] = None,
                  max_age_s: Optional[float] = None,
                  slow_threshold_s: Optional[float] = None,
                  slow_k: Optional[int] = None) -> None:
        """Set the trace-plane knobs (node boot mirrors PC.* here)."""
        if sample_rate is not None:
            cls.sample_rate = max(0.0, min(1.0, float(sample_rate)))
            cls._sample_thresh = int(cls.sample_rate * (1 << _SBITS))
        if max_age_s is not None:
            cls.max_age_s = float(max_age_s)
        if slow_threshold_s is not None:
            cls.slow_threshold_s = float(slow_threshold_s)
        if slow_k is not None:
            cls.slow_k = max(1, int(slow_k))

    @classmethod
    def sampled(cls, req_id: int, force: bool = False) -> bool:
        """Cluster-deterministic sampling verdict for one trace id.
        ``force`` honors the wire FLAG_SAMPLED bit (client-forced)."""
        if not cls.enabled:
            return False
        if force or cls._sample_thresh >= (1 << _SBITS):
            return True
        h = ((int(req_id) * _GOLD) & _M64) >> (64 - _SBITS)
        return h < cls._sample_thresh

    @classmethod
    def sampled_mask(cls, req_ids) -> "object":
        """Vectorized sampling verdict over a u64 req-id array — the
        hot batch handlers prefilter with this so a 0.1% sample rate
        costs one numpy pass per batch, not a Python call per request
        (flag-forced traces ride the separate FLAG_SAMPLED checks)."""
        import numpy as np
        n = len(req_ids)
        if not cls.enabled:
            return np.zeros(n, bool)
        if cls._sample_thresh >= (1 << _SBITS):
            return np.ones(n, bool)
        with np.errstate(over="ignore"):
            h = (np.asarray(req_ids, np.uint64) * np.uint64(_GOLD)) \
                >> np.uint64(64 - _SBITS)
        return h < np.uint64(cls._sample_thresh)

    @classmethod
    def mint(cls, req_id: int, force: bool = False) -> TraceContext:
        """Mint the trace context at the client/entry node."""
        return TraceContext(int(req_id), cls.current_wave(),
                            cls.sampled(req_id, force))

    # -- wave plumbing -----------------------------------------------------

    @classmethod
    def next_wave(cls) -> int:
        """Fresh process-global wave id (one per worker batch)."""
        return next(cls._wave_seq)

    @classmethod
    def set_wave(cls, wave: int) -> None:
        """Bind the calling thread to ``wave``: trace events and spans
        recorded on this thread attach to it until rebound (the worker
        binds one per batch)."""
        cls._tls.wave = wave

    @classmethod
    def current_wave(cls) -> int:
        return getattr(cls._tls, "wave", 0)

    # -- per-request trace events ------------------------------------------

    @classmethod
    def record(cls, req_id: int, stage: str, node: int,
               force: bool = False) -> None:
        if not cls.enabled:
            return
        if not cls.sampled(req_id, force):
            return
        now = time.monotonic()
        with cls._lock:
            cls._ring.append((req_id, stage, node, now,
                              getattr(cls._tls, "wave", 0)))
        cls._maybe_evict(now)

    @classmethod
    def trace(cls, req_id: int) -> List[Tuple[str, int, float]]:
        """(stage, node, t) events of one request, time-ordered."""
        with cls._lock:
            evs = [(s, n, t) for r, s, n, t, _w in cls._ring if r == req_id]
        return sorted(evs, key=lambda e: e[2])

    @classmethod
    def spans(cls, req_id: int) -> Dict[str, float]:
        """Stage-to-stage latencies (seconds) for one request."""
        evs = cls.trace(req_id)
        out: Dict[str, float] = {}
        for (s1, _n1, t1), (s2, _n2, t2) in zip(evs, evs[1:]):
            out[f"{s1}->{s2}"] = t2 - t1
        if evs:
            out["total"] = evs[-1][2] - evs[0][2]
        return out

    @classmethod
    def format(cls, req_id: int) -> str:
        evs = cls.trace(req_id)
        if not evs:
            return f"req {req_id:#x}: no trace"
        t0 = evs[0][2]
        return f"req {req_id:#x}: " + " ".join(
            f"{s}@n{n}+{(t - t0) * 1e3:.2f}ms" for s, n, t in evs)

    # -- pipeline-stage spans ----------------------------------------------

    @classmethod
    def tracing(cls) -> bool:
        """Spans are on: the operator's switch, or a JAX profiler
        session (which then carries them as ``gp.<kind>`` events)."""
        return cls.enabled or _profiling()

    @classmethod
    def span_begin(cls, kind: str, node: int = -1,
                   wave: Optional[int] = None, **attrs) -> Optional[dict]:
        """Open a span of ``kind`` on the current (or given) wave.
        Returns the span handle to pass to :meth:`span_end`, or None
        when spans are off (span_end accepts None).  The pair under
        :class:`span`, which is what the hot path calls."""
        if not (cls.enabled or _profiling()):
            return None
        return cls._span_open(kind, node, wave, attrs)

    @classmethod
    def _span_open(cls, kind: str, node: int, wave: Optional[int],
                   attrs: dict, t0: Optional[float] = None) -> dict:
        sp = {"kind": kind, "node": node,
              "tid": threading.get_ident(),
              "wave": cls.current_wave() if wave is None else wave,
              "parent": 0, "t0": None, "t1": None}
        if attrs:
            sp.update(attrs)
        with cls._lock:
            cls.n_span_begun += 1
            sp["id"] = cls.n_span_begun
            cls._open[id(sp)] = sp
        sp["t0"] = time.monotonic() if t0 is None else t0
        return sp

    @classmethod
    def span_end(cls, sp: Optional[dict], **attrs) -> None:
        if sp is None:
            return
        now = time.monotonic()
        sp["t1"] = now
        if attrs:
            sp.update(attrs)
        with cls._lock:
            if cls._open.pop(id(sp), None) is not None:
                cls.n_span_ended += 1
                cls._keep(sp)
            elif sp.pop("_orphaned", False):
                # the end arrived after all, just later than the age
                # horizon (a long compile/recovery stall): move the
                # span back from orphaned to ended and keep the record
                # — a permanent false "lost end" would never clear,
                # and the slow request being diagnosed would lose its
                # span breakdown
                cls.n_span_orphaned -= 1
                cls.n_span_ended += 1
                cls._keep(sp)
            # else: the rings were clear()ed between begin and end —
            # count nothing (begun was reset too)
        cls._maybe_evict(now)

    @classmethod
    def _keep(cls, sp: dict) -> None:
        """Append a completed span (caller holds ``_lock``), counting
        what a full ring pushes out: a reader of a window must know
        that its beginning is gone."""
        if len(cls._spans) == cls._spans.maxlen:
            cls.n_span_dropped += 1
        cls._spans.append(sp)

    # -- age-based eviction (satellite: size-only eviction let spans
    # from long-dead waves linger and skewed the pairing counts) -------

    @classmethod
    def _maybe_evict(cls, now: float) -> None:
        if cls.max_age_s <= 0:
            return
        if now - cls._last_evict < max(1.0, cls.max_age_s / 4):
            return
        cls.evict(now)

    @classmethod
    def evict(cls, now: Optional[float] = None) -> int:
        """Drop ring entries and completed spans older than
        ``max_age_s``; spans still open past the horizon move to the
        ``orphaned`` counter (their ends were lost — a stage crashed or
        leaked its handle).  Returns how many items were evicted."""
        if now is None:
            now = time.monotonic()
        # under the lock: concurrent stage threads racing past the
        # _maybe_evict throttle would otherwise both stamp + sweep
        with cls._lock:
            cls._last_evict = now
        if cls.max_age_s <= 0:
            return 0
        cutoff = now - cls.max_age_s
        evicted = 0
        with cls._lock:
            # both rings are appended in monotonic time order
            while cls._ring and cls._ring[0][3] < cutoff:
                cls._ring.popleft()
                evicted += 1
            while cls._spans and cls._spans[0]["t1"] < cutoff:
                cls._spans.popleft()
                evicted += 1
            for k in [k for k, sp in cls._open.items()
                      if sp["t0"] < cutoff]:
                sp = cls._open.pop(k)
                # marked so a LATE span_end can undo the orphan verdict
                sp["_orphaned"] = True
                cls.n_span_orphaned += 1
                evicted += 1
        return evicted

    # -- span queries -------------------------------------------------------

    @classmethod
    def spans_snapshot(cls) -> List[dict]:
        """The completed spans in the ring, oldest first (one C-level
        copy under the lock; the dicts are the ring's own — read, do
        not write).  With :meth:`span_stats`'s ``dropped`` at 0 this is
        everything since spans came on."""
        with cls._lock:
            return list(cls._spans)

    @classmethod
    def wave_spans(cls, wave: int) -> List[dict]:
        """Completed spans of one wave, time-ordered."""
        with cls._lock:
            out = [dict(s) for s in cls._spans if s["wave"] == wave]
        return sorted(out, key=lambda s: s["t0"])

    @classmethod
    def request_spans(cls, req_id: int) -> List[dict]:
        """Pipeline-stage spans of every wave the request touched
        (request frame decode, its engine+WAL batch, commit waves,
        emit) — the per-request join of trace events and spans."""
        with cls._lock:
            waves = {w for r, _s, _n, _t, w in cls._ring
                     if r == req_id and w}
            out = [dict(s) for s in cls._spans if s["wave"] in waves]
        return sorted(out, key=lambda s: s["t0"])

    @classmethod
    def request_breakdown(cls, req_id: int) -> Dict[str, float]:
        """kind -> total seconds across the request's waves: decompose
        a slow request into decode / engine / wal / emit /
        eng.submit / eng.collect without rerunning the bench."""
        out: Dict[str, float] = {}
        for s in cls.request_spans(req_id):
            out[s["kind"]] = out.get(s["kind"], 0.0) + (s["t1"] - s["t0"])
        return out

    # -- cluster trace stitching -------------------------------------------

    @classmethod
    def export_trace(cls, trace_id: int) -> dict:
        """This process's share of one trace — the ``/traces/<id>``
        payload a peer (or the gateway) merges: the trace's ring events
        plus the completed spans of every wave it touched here.

        The rings are SNAPSHOT under the lock (one C-level deque copy)
        and scanned outside it: a trace scrape against a full 200k
        ring must not hold the hot-path lock for the whole linear
        scan — that would stall every lane's record()/span hooks while
        the observer observes."""
        with cls._lock:
            ring = list(cls._ring)
            span_snap = list(cls._spans)
        evs = [(s, n, t, w) for r, s, n, t, w in ring if r == trace_id]
        waves = {w for _s, _n, _t, w in evs if w}
        spans = [dict(s) for s in span_snap if s["wave"] in waves]
        return {"trace_id": int(trace_id),
                "events": [list(e) for e in sorted(evs,
                                                   key=lambda e: e[2])],
                "spans": spans}

    # (send stamp, arrival stamp): the cross-node pairs a network hop
    # is measured between.  The hop includes the receiver's queue wait
    # up to its stamp point — the per-node span breakdown separates it.
    _HOP_PAIRS = (("fwd", "prop"), ("acc.tx", "acc"), ("acc", "dec"),
                  ("com.tx", "exec"))

    @classmethod
    def cluster_breakdown(cls, trace_id: int,
                          exports: Optional[List[dict]] = None) -> dict:
        """Stitch one request's cluster-wide story from per-node ring
        exports (default: this process's rings — which, in an
        in-process multi-node emulation, already hold every node).

        Returns ``{trace_id, total_s, path, nodes, hops}``: ``path`` is
        the merged time-ordered event list (relative ms), ``nodes``
        maps node -> span-kind seconds (queue/decode/engine/wal/emit
        split per node), ``hops`` lists the network hops between the
        recorded send/arrival stamp pairs."""
        if exports is None:
            exports = [cls.export_trace(trace_id)]
        evs: set = set()
        spans: List[dict] = []
        seen_spans: set = set()
        for ex in exports or []:
            if not ex:
                continue
            for e in ex.get("events", []):
                evs.add((str(e[0]), int(e[1]), float(e[2]), int(e[3])))
            # resolve node-less spans (the WAL logger stamps node=-1)
            # through their wave WITHIN this export: wave ids are
            # per-process counters, so the wave->node join is only
            # valid inside one export — two separate node processes
            # both reach wave 42 (the in-process emulation shares one
            # counter, a real deployment does not)
            wave_node: Dict[int, int] = {}
            for e in ex.get("events", []):
                if e[3]:
                    wave_node.setdefault(int(e[3]), int(e[1]))
            for sp in ex.get("spans", []):
                if int(sp.get("node", -1)) >= 0 and sp.get("wave"):
                    wave_node.setdefault(int(sp["wave"]),
                                         int(sp["node"]))
            for sp in ex.get("spans", []):
                node = int(sp.get("node", -1))
                if node < 0:
                    node = wave_node.get(int(sp.get("wave") or 0), -1)
                key = (sp.get("kind"), node, sp.get("wave"),
                       sp.get("t0"))
                if key in seen_spans:
                    continue
                seen_spans.add(key)
                sp = dict(sp)
                sp["node"] = node
                spans.append(sp)
        ordered = sorted(evs, key=lambda e: (e[2], e[1], e[0]))
        if not ordered:
            return {"trace_id": int(trace_id), "total_s": None,
                    "path": [], "nodes": {}, "hops": []}
        t0 = ordered[0][2]
        path = [{"stage": s, "node": n, "t_ms": round((t - t0) * 1e3, 3)}
                for s, n, t, _w in ordered]
        # per-node pipeline-stage breakdown: each span belongs to ONE
        # node (a wave is a node-local worker batch; node resolution
        # for node-less spans already happened per export above)
        nodes: Dict[int, Dict[str, float]] = {}
        for sp in spans:
            if sp.get("t1") is None:
                continue
            d = nodes.setdefault(int(sp.get("node", -1)), {})
            k = sp["kind"]
            d[k] = d.get(k, 0.0) + (sp["t1"] - sp["t0"])
        # network hops: pair each arrival stamp with the latest earlier
        # send stamp from another node
        hops = []
        by_stage: Dict[str, list] = {}
        for s, n, t, _w in ordered:
            by_stage.setdefault(s, []).append((t, n))
        for src_stage, dst_stage in cls._HOP_PAIRS:
            srcs = by_stage.get(src_stage, [])
            if not srcs:
                continue
            for t_dst, n_dst in by_stage.get(dst_stage, []):
                best = None
                for t_src, n_src in srcs:
                    if n_src != n_dst and t_src <= t_dst and (
                            best is None or t_src > best[0]):
                        best = (t_src, n_src)
                if best is not None:
                    hops.append({
                        "stage": f"{src_stage}->{dst_stage}",
                        "from": best[1], "to": n_dst,
                        "s": t_dst - best[0]})
        return {"trace_id": int(trace_id),
                "total_s": ordered[-1][2] - t0,
                "path": path, "nodes": nodes, "hops": hops}

    # -- slow-request log ---------------------------------------------------

    @classmethod
    def note_done(cls, trace_id: int, total_s: float,
                  force: bool = False) -> None:
        """A sampled request finished end-to-end in ``total_s``; keep
        it in the top-K slow log when past the threshold."""
        if not cls.enabled or cls.slow_threshold_s <= 0:
            return
        if total_s < cls.slow_threshold_s:
            return
        if not cls.sampled(trace_id, force):
            return
        with cls._lock:
            heapq.heappush(cls._slow, (float(total_s),
                                       next(cls._slow_seq),
                                       int(trace_id), time.time()))
            while len(cls._slow) > cls.slow_k:
                heapq.heappop(cls._slow)

    @classmethod
    def slow_traces(cls) -> List[dict]:
        """Top-K slow sampled traces, slowest first (each with the
        monotone ``seq`` the stats dumper uses to emit only new ones)."""
        with cls._lock:
            items = sorted(cls._slow, reverse=True)
        return [{"trace_id": tid, "total_s": total, "seq": seq, "ts": ts}
                for total, seq, tid, ts in items]

    # -- aggregates ---------------------------------------------------------

    @classmethod
    def span_stats(cls) -> dict:
        """Aggregate span view for the metrics snapshot: per-kind count
        and total seconds, plus begin/end pairing counters.  ``open``
        counts spans currently in flight; ``orphaned`` counts spans
        whose end stamp never arrived within ``max_age_s`` (a lost end
        — without the split, pairing skew was indistinguishable from
        live load); ``dropped`` counts completed spans a full ring
        pushed out."""
        cls._maybe_evict(time.monotonic())
        with cls._lock:
            snap = list(cls._spans)  # scanned outside the hot-path lock
            out = {"begun": cls.n_span_begun,
                   "ended": cls.n_span_ended,
                   "orphaned": cls.n_span_orphaned,
                   "dropped": cls.n_span_dropped,
                   "open": len(cls._open)}
        agg: Dict[str, list] = {}
        for s in snap:
            a = agg.setdefault(s["kind"], [0, 0.0])
            a[0] += 1
            a[1] += s["t1"] - s["t0"]
        out["kinds"] = {k: {"count": c, "total_s": t}
                        for k, (c, t) in sorted(agg.items())}
        return out

    @classmethod
    def clear(cls) -> None:
        """Drop recorded data (keeps the configured knobs)."""
        with cls._lock:
            cls._ring.clear()
            cls._spans.clear()
            cls._open.clear()
            cls._slow.clear()
            cls.n_span_begun = 0
            cls.n_span_ended = 0
            cls.n_span_orphaned = 0
            cls.n_span_dropped = 0

    @classmethod
    def reset(cls) -> None:
        """clear() + restore default knobs (test harness hook)."""
        cls.clear()
        cls.enabled = False
        cls.configure(sample_rate=1.0, max_age_s=300.0,
                      slow_threshold_s=0.0, slow_k=32)


class _Off:
    """What :func:`traced` hands out while spans are off: one shared
    object that does nothing."""

    __slots__ = ()
    on = False

    def note(self, **attrs) -> None:
        pass

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()


class span:
    """One stage span: ``with span("w.decode", node=i, n=frames): ...``

    Always: adds the wall seconds, one call and ``n`` items (and, with
    ``cpu=`` a thread clock, CPU seconds) to the ``DelayProfiler``
    total ``total or kind``.  While spans are on
    (:meth:`RequestInstrumenter.tracing`) also: an event ``gp.<kind>``
    on this thread's line of the profiler's ``/host:CPU`` plane, with
    ``node``, ``wave``, ``n`` and the attributes given here as its
    stats, and the completed span in the ring, its ``parent`` the id of
    the enclosing ``span`` on this thread (0: none).  Off, it costs the
    sums and one gate check.  ``n`` may be set, and :meth:`note` called,
    inside the block; what comes that late reaches the ring only.
    :func:`traced` is the same span without a sum of its own."""

    __slots__ = ("kind", "total", "node", "n", "wave", "attrs", "t0",
                 "t1", "_cpu", "_c0", "_sp", "_ann")

    def __init__(self, kind: str, node: int = -1, n: int = 1,
                 total: Optional[str] = None, wave: Optional[int] = None,
                 cpu=None, **attrs):
        self.kind = kind
        self.total = kind if total is None else total
        self.node = node
        self.n = n
        self.wave = wave
        self.attrs = attrs
        self._cpu = cpu
        self._c0 = self._sp = None

    @property
    def on(self) -> bool:
        return self._sp is not None

    def note(self, **attrs) -> None:
        """Attributes known only inside the block (ring only)."""
        if self._sp is not None:
            self._sp.update(attrs)

    def __enter__(self) -> "span":
        if self._cpu is not None:
            self._c0 = self._cpu()
        t0 = self.t0 = time.monotonic()
        if RequestInstrumenter.enabled or _profiling():
            self._open(t0)
        return self

    def _open(self, t0: float) -> None:
        # inside [t0, t1]: what recording costs is the span's own, so
        # that sibling spans tile their thread's time
        ri = RequestInstrumenter
        stack = getattr(ri._tls, "stack", None)
        if stack is None:
            stack = ri._tls.stack = []
        wave = ri.current_wave() if self.wave is None else self.wave
        sp = self._sp = ri._span_open(self.kind, self.node, wave,
                                      self.attrs, t0)
        sp["parent"] = stack[-1] if stack else 0
        stack.append(sp["id"])
        self._ann = None
        if _profiling():
            self._ann = _TA("gp." + self.kind, node=self.node, wave=wave,
                            n=self.n, **self.attrs)
            self._ann.__enter__()

    def __exit__(self, *exc) -> bool:
        sp = self._sp
        if sp is None:
            t1 = self.t1 = time.monotonic()
        else:
            if self._ann is not None:
                self._ann.__exit__(*exc)
            RequestInstrumenter._tls.stack.pop()
            RequestInstrumenter.span_end(sp, n=self.n)
            t1 = self.t1 = sp["t1"]
        if self.total:
            c0 = self._c0  # None: no thread clock (PC.PROFILE_CPU off)
            DelayProfiler.add_total(
                self.total, t1 - self.t0, self.n,
                self._cpu() - c0 if c0 is not None else 0.0)
        return False

    @staticmethod
    def traced(kind: str, node: int = -1, n: int = 1,
               wave: Optional[int] = None, **attrs):
        """A span with no sum of its own, for the boundaries inside a
        summed stage (a wait, a tick, a pack, the fsync): the same
        event and ring record while spans are on; off, one gate check
        and no object."""
        if RequestInstrumenter.enabled or _profiling():
            return span(kind, node, n, "", wave, **attrs)
        return _OFF


traced = span.traced
