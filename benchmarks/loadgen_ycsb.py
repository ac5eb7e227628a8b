"""YCSB-style closed-loop clients over a record per group.

``threads`` independent clients, each with ONE operation outstanding, as
YCSB's client threads are: thread ``t`` draws its next record from the mix's
request distribution and its operation (read or update, the field, the
field's bytes) from ``default_rng([seed, t])``, sends it, and draws the next
when the reply is in.  There is NO per-group rule: two threads that draw one
record have two requests outstanding on one group, and under a skewed
distribution the hottest record has many.

The Zipfian draw is YCSB's ``ZipfianGenerator`` (Gray et al., "Quickly
generating billion-record synthetic databases") over the record count, and
``scrambled`` spreads its ranks over the key space as YCSB's
``ScrambledZipfianGenerator`` does: the rank, hashed (FNV-1a 64), modulo the
record count.  Written from memory of the YCSB core package.

Request ids are ``client_id << 32 | seq`` with ``seq`` counted from 0 in the
order of sending; what was sent over a second ago and not answered is sent
again under its id; every request is kept with its send time, receive time,
status and reply.  The wire format and the group key are those of
``loadgen.py``.  Imports nothing of the program.
"""

from __future__ import annotations

import asyncio
import contextlib
import struct
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.loadgen import (_HEAD, _LEN, _REQ, REQUEST, group_key,
                                scan_responses)

BLOCK = 256  # operations a thread draws at once
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)


def zeta(n: int, theta: float) -> float:
    return float(np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta))


def fnv64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of each value: FNV-1a over its eight
    bytes, low byte first, and the absolute value of the signed result."""
    val = np.asarray(values, np.uint64).copy()
    h = np.full(val.shape, _FNV_OFFSET, np.uint64)
    for _ in range(8):
        h = (h ^ (val & np.uint64(0xFF))) * _FNV_PRIME
        val >>= np.uint64(8)
    return np.abs(h.view(np.int64)).view(np.uint64)


class KeyChooser:
    """The mix's request distribution over ``n`` records: ``uniform``, or
    ``zipfian`` with ``zipf_constant`` (rank 0 the hottest), ``scrambled``
    over the key space or not."""

    def __init__(self, mix: dict, n: int):
        self.n, self.kind = n, mix["distribution"]
        if self.kind not in ("zipfian", "uniform"):
            raise ValueError(f"no request distribution {self.kind!r}")
        self.scrambled = bool(mix.get("scrambled", False))
        if self.kind == "zipfian":
            th = self.theta = float(mix["zipf_constant"])
            self.zetan = zeta(n, th)
            self.alpha = 1.0 / (1.0 - th)
            self.eta = (1.0 - (2.0 / n) ** (1.0 - th)) \
                / (1.0 - zeta(2, th) / self.zetan)

    def ranks(self, u: np.ndarray) -> np.ndarray:
        """Zipfian ranks of uniform draws ``u`` in [0, 1)."""
        uz = u * self.zetan
        tail = (self.n * (self.eta * u - self.eta + 1.0) ** self.alpha)
        r = np.where(uz < 1.0, 0, np.where(uz < 1.0 + 0.5 ** self.theta, 1,
                                            tail.astype(np.int64)))
        return np.minimum(r, self.n - 1).astype(np.int64)

    def keys(self, rng: np.random.Generator, k: int) -> np.ndarray:
        if self.kind == "uniform":
            return rng.integers(0, self.n, k)
        r = self.ranks(rng.random(k))
        if self.scrambled:
            r = (fnv64(r) % np.uint64(self.n)).astype(np.int64)
        return r


class ThreadPlan:
    """Thread ``t``'s operations, drawn a block at a time: the record, read
    or update, and for an update the field and its bytes.  The same seed,
    thread and mix give the same operations.  ``burst`` (1, 2, ..) gives a
    warm-up burst operations of its own; the window's are burst 0's."""

    def __init__(self, seed: int, t: int, mix: dict, chooser: KeyChooser,
                 fields: int, field_bytes: int, burst: int = 0):
        self.rng = np.random.default_rng(
            [int(seed), int(t)] + ([int(burst)] if burst else []))
        self.mix, self.chooser = mix, chooser
        self.fields, self.field_bytes = fields, field_bytes
        self.at, self.block = BLOCK, None

    def next(self) -> Tuple[int, bytes]:
        """(record, request payload): ``b"R"`` or ``b"U"``, the field,
        its bytes."""
        if self.at >= BLOCK:
            rng = self.rng
            self.block = (
                self.chooser.keys(rng, BLOCK),
                rng.random(BLOCK) < float(self.mix["read_share"]),
                rng.integers(0, self.fields, BLOCK),
                rng.integers(0, 256, (BLOCK, self.field_bytes), np.uint8))
            self.at = 0
        i = self.at
        self.at += 1
        keys, reads, flds, data = self.block
        if reads[i]:
            return int(keys[i]), b"R"
        return int(keys[i]), b"U" + bytes([int(flds[i])]) + data[i].tobytes()


def check_mix(mix: dict, fields: int) -> None:
    """The shapes the generator implements: every operation a read of the
    whole record or an update of one field."""
    if abs(mix["read_share"] + mix["update_share"] - 1.0) > 1e-9:
        raise ValueError("read_share + update_share has to be 1")
    if mix["update_fields"] != 1 or mix["read_fields"] != fields:
        raise ValueError("an update writes one field and a read returns "
                         f"all {fields}")


async def run_threads(
        servers: Sequence[Tuple[str, int]], n_records: int, seconds: float,
        mix: dict, *, seed: int, client_id: int, fields: int,
        field_bytes: int, threads: Optional[int] = None, burst: int = 0,
        drain_s: float = 60.0,
        annotate: Optional[Callable[[str], contextlib.AbstractContextManager]]
        = None) -> Dict:
    """``threads`` (default: the mix's) closed-loop clients on records
    ``g0 .. g<n_records-1>`` for ``seconds``, then up to ``drain_s`` for what
    is outstanding.  A record's requests go to server ``gkey % len(servers)``,
    its group's initial coordinator."""
    check_mix(mix, fields)
    note = annotate or (lambda _name: contextlib.nullcontext())
    n_threads = int(threads or mix["threads"])
    chooser = KeyChooser(mix, n_records)
    plans = [ThreadPlan(seed, t, mix, chooser, fields, field_bytes, burst)
             for t in range(n_threads)]
    gkeys: Dict[int, int] = {}  # record -> group key, as records are drawn

    cap = 1 << 15
    t_send = np.zeros(cap, np.float64)
    t_recv = np.full(cap, -1.0, np.float64)
    status = np.full(cap, -1, np.int16)
    record = np.zeros(cap, np.int64)
    thread = np.zeros(cap, np.int32)
    payloads: List[Optional[bytes]] = [None] * cap
    replies: List[Optional[bytes]] = [None] * cap
    req_base = client_id << 32
    loop = asyncio.get_running_loop()

    conns = []
    for host, port in servers:
        r, w = await asyncio.open_connection(host, port)
        w.write(_LEN.pack(4) + struct.pack("<i", client_id))
        conns.append((r, w))

    ready = list(range(n_threads))  # threads with nothing outstanding
    wake = asyncio.Event()
    wake.set()
    idle = asyncio.Event()
    n_sent = n_done = 0
    sending = True

    def grow():
        nonlocal t_send, t_recv, status, record, thread, cap
        t_send = np.concatenate([t_send, np.zeros(cap)])
        t_recv = np.concatenate([t_recv, np.full(cap, -1.0)])
        status = np.concatenate([status, np.full(cap, -1, np.int16)])
        record = np.concatenate([record, np.zeros(cap, np.int64)])
        thread = np.concatenate([thread, np.zeros(cap, np.int32)])
        payloads.extend([None] * cap)
        replies.extend([None] * cap)
        cap *= 2

    def frame(seq: int) -> Tuple[int, bytes]:
        pay = payloads[seq]
        gk = gkeys[int(record[seq])]
        return gk % len(conns), _REQ.pack(
            _HEAD - 4 + len(pay), REQUEST, client_id, 1, gk,
            req_base | seq, 0) + pay

    async def reader(idx: int):
        nonlocal n_done
        rd = conns[idx][0]
        buf = bytearray()
        while True:
            chunk = await rd.read(1 << 18)
            if not chunk:
                return
            with note("bench.recv"):
                buf += chunk
                got, consumed = scan_responses(buf)
                del buf[:consumed]
                now = time.perf_counter()
                for rid, st, pay in got:
                    seq = rid & 0xFFFFFFFF
                    if (rid >> 32) != client_id or seq >= n_sent \
                            or t_recv[seq] >= 0:
                        continue  # not ours, or a retransmit's second reply
                    t_recv[seq] = now
                    status[seq] = st
                    replies[seq] = pay
                    n_done += 1
                    ready.append(int(thread[seq]))
                wake.set()
                if not sending and n_done >= n_sent:
                    idle.set()

    readers = [loop.create_task(reader(i)) for i in range(len(conns))]
    t0 = time.perf_counter()
    t_stop = t0 + seconds

    async def writer():
        nonlocal n_sent, sending
        while time.perf_counter() < t_stop:
            if not ready:
                wake.clear()
                try:
                    await asyncio.wait_for(
                        wake.wait(), max(t_stop - time.perf_counter(), 0))
                except asyncio.TimeoutError:
                    break
                continue
            with note("bench.send"):
                go, ready[:] = list(ready), []
                if n_sent + len(go) > cap:
                    grow()
                out: Dict[int, List[bytes]] = {}
                for t in go:
                    seq = n_sent
                    rec, pay = plans[t].next()
                    if rec not in gkeys:
                        gkeys[rec] = group_key(f"g{rec}")
                    record[seq], thread[seq], payloads[seq] = rec, t, pay
                    n_sent += 1
                    dst, buf = frame(seq)
                    out.setdefault(dst, []).append(buf)
                    t_send[seq] = time.perf_counter()
                for dst, bufs in out.items():
                    conns[dst][1].write(b"".join(bufs))
            await asyncio.sleep(0)  # let the readers run
        sending = False
        for _, w in conns:
            await w.drain()
        if n_done >= n_sent:
            idle.set()

    wtask = loop.create_task(writer())
    # once a second, retransmit what was sent over a second ago (same ids;
    # the servers dedupe), until the window and the drain are over
    give_up = t_stop + drain_s
    n_resent = 0
    while not idle.is_set() and time.perf_counter() < give_up:
        try:
            await asyncio.wait_for(idle.wait(), timeout=1.0)
        except asyncio.TimeoutError:
            now = time.perf_counter()
            late = np.flatnonzero((t_recv[:n_sent] < 0)
                                  & (now - t_send[:n_sent] > 1.0))
            for seq in late[:2048].tolist():
                dst, buf = frame(seq)
                conns[dst][1].write(buf)
            n_resent += len(late[:2048])
    t_end = time.perf_counter()
    for t in readers + [wtask]:
        t.cancel()
    for _, w in conns:
        w.close()
    await asyncio.gather(*readers, wtask, return_exceptions=True)

    n = n_sent
    return {"n_sent": n, "t0": t0, "t_end": t_end, "n_resent": n_resent,
            "threads": n_threads, "client_id": client_id,
            "req_id": np.uint64(req_base) | np.arange(n, dtype=np.uint64),
            "record": record[:n].copy(), "thread": thread[:n].copy(),
            "payload": payloads[:n],
            "t_send": t_send[:n].copy(), "t_recv": t_recv[:n].copy(),
            "status": status[:n].copy(), "reply": replies[:n]}
