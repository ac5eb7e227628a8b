"""Closed-loop load generator with a deadline (the benchmark's own).

Started as a copy of ``gigapaxos_tpu/testing/loadgen.py`` ``run_fast_load``
(a global window of outstanding requests, array-indexed send and receive
times, once-a-second retransmits) and changed where a measurement needs it:

- it sends until ``seconds`` have passed, not until a count is reached, then
  waits (bounded by ``drain_s``) for what is outstanding;
- request ids are explicit: ``client_id << 32 | seq`` with ``seq`` counted
  from 0, so a reference replay carries the same ids;
- a group never has two requests outstanding, so each group's order is the
  send order whatever the interleaving of waves;
- every request is kept: its send time, receive time, status and the reply;
- requests never answered, or refused, are counted as failed, and the
  percentiles are over ALL requests of the window (a failed one is as slow
  as the whole wait).

It imports nothing of the program: the wire format (REQUEST and RESPONSE
frames of ``paxos/packets.py``) and the group key (blake2b-8 of the name)
are written out here.
"""

from __future__ import annotations

import asyncio
import contextlib
import hashlib
import struct
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

REQUEST, RESPONSE = 1, 2
_LEN = struct.Struct("<I")
# len | type | sender | n | gkey | req_id | flags   (then the payload)
_REQ = struct.Struct("<IBIIQQB")
_HEAD = _REQ.size  # 30 bytes: 4 of length + 26 of body head
BURST = 64  # most requests framed and written at once


def group_key(name: str) -> int:
    """Stable 64-bit key of a group name: blake2b-8, little endian."""
    return int.from_bytes(
        hashlib.blake2b(name.encode(), digest_size=8).digest(), "little")


def frames(sender: int, gkeys: np.ndarray, req_ids: np.ndarray,
           payload: bytes) -> bytes:
    """k equal-length REQUEST frames in one numpy pass."""
    k = len(gkeys)
    tmpl = np.frombuffer(
        _REQ.pack(_HEAD - 4 + len(payload), REQUEST, sender, 1, 0, 0, 0)
        + payload, np.uint8)
    arr = np.broadcast_to(tmpl, (k, len(tmpl))).copy()
    arr[:, 13:21] = np.ascontiguousarray(gkeys, "<u8").view(
        np.uint8).reshape(k, 8)
    arr[:, 21:29] = np.ascontiguousarray(req_ids, "<u8").view(
        np.uint8).reshape(k, 8)
    return arr.tobytes()


def scan_responses(buf: bytearray) -> Tuple[List[Tuple[int, int, bytes]],
                                            int]:
    """Every complete frame at the head of ``buf``: the RESPONSE frames as
    (req_id, status, payload), and the bytes consumed."""
    out, at, n = [], 0, len(buf)
    while at + 4 <= n:
        (blen,) = _LEN.unpack_from(buf, at)
        if at + 4 + blen > n:
            break
        if blen >= _HEAD - 4 and buf[at + 4] == RESPONSE:
            _l, _t, _s, _n, _gk, rid, st = _REQ.unpack_from(buf, at)
            out.append((rid, st, bytes(buf[at + _HEAD:at + 4 + blen])))
        at += 4 + blen
    return out, at


def plan_groups(seed: int, n_live: int, n_active: int) -> List[str]:
    """The seeded stream: a permutation of ``n_active`` of the ``n_live``
    group names ``g0..``; request k goes to entry ``k % n_active``."""
    rng = np.random.default_rng(seed)
    n_active = min(n_active, n_live)
    return [f"g{i}" for i in rng.choice(n_live, n_active, replace=False)]


async def run_closed_loop(
        servers: Sequence[Tuple[str, int]], group_names: Sequence[str],
        seconds: float, depth: int, *, client_id: int,
        payload: bytes = b"x", drain_s: float = 60.0,
        annotate: Optional[Callable[[str], contextlib.AbstractContextManager]]
        = None) -> Dict:
    """Send round-robin over ``group_names`` with ``depth`` outstanding for
    ``seconds``, then wait up to ``drain_s`` for the rest.

    Group k goes to server ``gkey % len(servers)``, its initial coordinator.
    ``annotate(name)`` wraps the send and receive paths (the profiler's
    ``TraceAnnotation`` in a traced run).
    """
    note = annotate or (lambda _name: contextlib.nullcontext())
    gkeys = np.asarray([group_key(g) for g in group_names], np.uint64)
    n_groups = len(gkeys)
    route_arr = (gkeys % np.uint64(len(servers))).astype(np.int64)
    cap = 1 << 16
    t_send = np.zeros(cap, np.float64)
    t_recv = np.full(cap, -1.0, np.float64)
    status = np.full(cap, -1, np.int16)
    replies: List[Optional[bytes]] = [None] * cap
    req_base = np.uint64(client_id << 32)
    loop = asyncio.get_running_loop()

    conns = []
    for host, port in servers:
        r, w = await asyncio.open_connection(host, port)
        w.write(_LEN.pack(4) + struct.pack("<i", client_id))
        conns.append((r, w))

    space = asyncio.Event()
    space.set()
    idle = asyncio.Event()
    n_sent = 0
    n_done = 0
    sending = True

    def grow():
        nonlocal t_send, t_recv, status, cap
        t_send = np.concatenate([t_send, np.zeros(cap)])
        t_recv = np.concatenate([t_recv, np.full(cap, -1.0)])
        status = np.concatenate([status, np.full(cap, -1, np.int16)])
        replies.extend([None] * cap)
        cap *= 2

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
                space.set()
                if not sending and n_done >= n_sent:
                    idle.set()

    readers = [loop.create_task(reader(i)) for i in range(len(conns))]
    t0 = time.perf_counter()
    t_stop = t0 + seconds

    async def writer():
        nonlocal n_sent, sending
        while time.perf_counter() < t_stop:
            take = min(depth - (n_sent - n_done), BURST)
            # one outstanding per group: request k waits for k - n_groups
            lo = n_sent - n_groups
            if take > 0 and lo + take > 0:
                a = max(lo, 0)
                unans = np.flatnonzero(t_recv[a:lo + take] < 0)
                if len(unans):
                    take = a + int(unans[0]) - lo
            if take <= 0:
                space.clear()
                try:
                    await asyncio.wait_for(
                        space.wait(), max(t_stop - time.perf_counter(), 0))
                except asyncio.TimeoutError:
                    break
                continue
            with note("bench.send"):
                k = n_sent
                if k + take > cap:
                    grow()
                ks = np.arange(k, k + take, dtype=np.int64)
                gs = ks % n_groups
                rts = route_arr[gs]
                t_send[k:k + take] = time.perf_counter()
                n_sent += take
                for dst in np.unique(rts):
                    m = rts == dst
                    conns[int(dst)][1].write(frames(
                        client_id, gkeys[gs[m]],
                        req_base | ks[m].astype(np.uint64), payload))
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
    while not idle.is_set() and time.perf_counter() < give_up:
        try:
            await asyncio.wait_for(idle.wait(), timeout=1.0)
        except asyncio.TimeoutError:
            now = time.perf_counter()
            late = np.flatnonzero((t_recv[:n_sent] < 0)
                                  & (now - t_send[:n_sent] > 1.0))
            for k in late[:2048]:
                g = int(k) % n_groups
                conns[int(route_arr[g])][1].write(frames(
                    client_id, gkeys[g:g + 1],
                    np.asarray([int(req_base) | int(k)], np.uint64),
                    payload))
    t_end = time.perf_counter()
    for t in readers + [wtask]:
        t.cancel()
    for _, w in conns:
        w.close()
    await asyncio.gather(*readers, wtask, return_exceptions=True)

    n = n_sent
    return {"n_sent": n, "t0": t0, "t_end": t_end,
            "seq_group": (np.arange(n) % n_groups).astype(np.int64),
            "req_id": req_base | np.arange(n, dtype=np.uint64),
            "t_send": t_send[:n].copy(), "t_recv": t_recv[:n].copy(),
            "status": status[:n].copy(), "reply": replies[:n]}


def summarize(res: Dict) -> Dict:
    """The end-to-end numbers of one closed-loop run: over ALL requests
    sent in the window.  A request never answered, or refused, is failed
    and counts as slow as the whole wait."""
    n = res["n_sent"]
    acked = (res["t_recv"] >= 0) & (res["status"] == 0)
    lat = np.where(res["t_recv"] >= 0, res["t_recv"], res["t_end"]) \
        - res["t_send"]
    lat = np.where(acked, lat, max(float(lat.max()) if n else 0.0,
                                   res["t_end"] - res["t0"]))
    last = float(res["t_recv"][acked].max()) if acked.any() else res["t_end"]
    span = last - float(res["t_send"][0]) if n else 0.0
    return {
        "attempted": int(n), "acked": int(acked.sum()),
        "failed": int(n - acked.sum()), "span_s": span,
        "commit_rate": float(acked.sum()) / span if span > 0 else None,
        "commit_p50_ms": 1e3 * float(np.percentile(lat, 50)) if n else None,
        "commit_p95_ms": 1e3 * float(np.percentile(lat, 95)) if n else None,
        "commit_p99_ms": 1e3 * float(np.percentile(lat, 99)) if n else None,
    }
