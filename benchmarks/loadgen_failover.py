"""Closed-loop generator for a deployment that loses a node inside the
window: ``loadgen.run_closed_loop``'s stream (round robin over the seeded
groups, ``depth`` outstanding, never two on one group, explicit ids, every
request kept) with the two rules a client of a replicated service follows
when a server dies, and a record of all it did:

- **the ring retransmit**: a request unanswered ``retransmit_after_s``
  after it was last sent is sent again, with the SAME id, to the next member
  in ring order after the one it last went to, and on round the ring for as
  long as it stays unanswered;
- **the dead-server rule**: a server whose connection closed is skipped, by
  retransmits and by new requests alike (a group it led goes first to the
  next in ring);
- **the fault**: ``kill()`` is called ``kill_at_s`` into the window, from a
  thread of its own, and when it was called and when it returned are kept.

Every transmission is kept (request, server, time), so the plain reference
(``reference/failover_rsm.py``) replays what was sent where, and which
requests were unanswered at the kill.  Imports nothing of the program; the
wire format and the group key are ``loadgen.py``'s.
"""

from __future__ import annotations

import asyncio
import contextlib
import struct
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.loadgen import BURST, _LEN, frames, group_key, scan_responses

TICK_S = 0.05  # how often unanswered requests are looked at


async def run_closed_loop_kill(
        servers: Sequence[Tuple[str, int]], group_names: Sequence[str],
        seconds: float, depth: int, *, client_id: int,
        payload: bytes = b"x", drain_s: float = 60.0,
        retransmit_after_s: float = 1.0,
        kill: Optional[Callable[[], None]] = None,
        kill_at_s: Optional[float] = None,
        on_start: Optional[Callable[[], None]] = None,
        annotate: Optional[Callable[[str], contextlib.AbstractContextManager]]
        = None) -> Dict:
    """``loadgen.run_closed_loop`` with the ring retransmit, the
    dead-server rule and one call of ``kill`` at ``kill_at_s``.  Group k's
    home is server ``gkey % len(servers)``, its initial coordinator.
    ``on_start()`` is called as the window opens, connections made: what
    has to keep the window's own time (a tracer) starts there."""
    note = annotate or (lambda _name: contextlib.nullcontext())
    gkeys = np.asarray([group_key(g) for g in group_names], np.uint64)
    n_groups, n_srv = len(gkeys), len(servers)
    home = (gkeys % np.uint64(n_srv)).astype(np.int64)
    cap = 1 << 16
    t_send = np.zeros(cap, np.float64)   # first transmission
    t_last = np.zeros(cap, np.float64)   # newest transmission
    last_to = np.zeros(cap, np.int64)    # the server it went to
    t_recv = np.full(cap, -1.0, np.float64)
    status = np.full(cap, -1, np.int16)
    replies: List[Optional[bytes]] = [None] * cap
    sends: List[Tuple[int, int, float]] = []  # every (request, server, t)
    req_base = np.uint64(client_id << 32)
    loop = asyncio.get_running_loop()

    conns = []
    closed_at: List[Optional[float]] = [None] * n_srv
    for i, (host, port) in enumerate(servers):
        try:
            r, w = await asyncio.open_connection(host, port)
        except OSError:  # dead before the window: closed from the start
            closed_at[i] = time.perf_counter()
            conns.append(None)
            continue
        w.write(_LEN.pack(4) + struct.pack("<i", client_id))
        conns.append((r, w))

    space = asyncio.Event()
    space.set()
    idle = asyncio.Event()
    n_sent = n_done = 0
    sending = True

    def grow():
        nonlocal t_send, t_last, last_to, t_recv, status, cap
        t_send = np.concatenate([t_send, np.zeros(cap)])
        t_last = np.concatenate([t_last, np.zeros(cap)])
        last_to = np.concatenate([last_to, np.zeros(cap, np.int64)])
        t_recv = np.concatenate([t_recv, np.full(cap, -1.0)])
        status = np.concatenate([status, np.full(cap, -1, np.int16)])
        replies.extend([None] * cap)
        cap *= 2

    def live_from(srv: int) -> int:
        """``srv``, or the next in ring whose connection is open."""
        for k in range(n_srv):
            cand = (srv + k) % n_srv
            if closed_at[cand] is None:
                return cand
        return srv

    def transmit(ks: np.ndarray, to: np.ndarray, now: float) -> None:
        for dst in np.unique(to):
            m = to == dst
            conns[int(dst)][1].write(frames(
                client_id, gkeys[ks[m] % n_groups],
                req_base | ks[m].astype(np.uint64), payload))
        t_last[ks], last_to[ks] = now, to
        sends.extend(zip(ks.tolist(), to.tolist(), [now] * len(ks)))

    async def reader(idx: int):
        nonlocal n_done
        rd = conns[idx][0]
        buf = bytearray()
        while True:
            try:
                chunk = await rd.read(1 << 18)
            except (ConnectionError, OSError):
                chunk = b""
            if not chunk:
                closed_at[idx] = time.perf_counter()
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

    readers = [loop.create_task(reader(i)) for i in range(n_srv)
               if conns[i] is not None]
    if on_start is not None:
        on_start()
    t0 = time.perf_counter()
    t_stop = t0 + seconds
    fault = {"t_call": None, "t_done": None}

    async def killer():
        await asyncio.sleep(max(t0 + kill_at_s - time.perf_counter(), 0))
        fault["t_call"] = time.perf_counter()
        await loop.run_in_executor(None, kill)
        fault["t_done"] = time.perf_counter()

    ktask = loop.create_task(killer()) \
        if kill is not None and kill_at_s is not None else None

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
                to = home[ks % n_groups]
                if any(c is not None for c in closed_at):
                    to = np.asarray([live_from(int(s)) for s in to],
                                    np.int64)
                now = time.perf_counter()
                t_send[k:k + take] = now
                n_sent += take
                transmit(ks, to, now)
            await asyncio.sleep(0)  # let the readers run
        sending = False
        if n_done >= n_sent:
            idle.set()

    wtask = loop.create_task(writer())
    give_up = t_stop + drain_s
    n_resent = 0
    while not idle.is_set() and time.perf_counter() < give_up:
        try:
            await asyncio.wait_for(idle.wait(), timeout=TICK_S)
        except asyncio.TimeoutError:
            now = time.perf_counter()
            late = np.flatnonzero(
                (t_recv[:n_sent] < 0)
                & (now - t_last[:n_sent] >= retransmit_after_s))
            if len(late):
                to = np.asarray([live_from((int(s) + 1) % n_srv)
                                 for s in last_to[late]], np.int64)
                transmit(late, to, now)
                n_resent += len(late)
    t_end = time.perf_counter()
    if ktask is not None:
        await ktask  # a kill still under way is waited for, not lost
    for t in readers + [wtask]:
        t.cancel()
    for c in conns:
        if c is not None:
            c[1].close()
    await asyncio.gather(*readers, wtask, return_exceptions=True)

    n = n_sent
    return {"n_sent": n, "t0": t0, "t_end": t_end,
            "seq_group": (np.arange(n) % n_groups).astype(np.int64),
            "req_id": req_base | np.arange(n, dtype=np.uint64),
            "t_send": t_send[:n].copy(), "t_recv": t_recv[:n].copy(),
            "status": status[:n].copy(), "reply": replies[:n],
            "home": home[np.arange(n) % n_groups],
            "sends": np.asarray(sends, np.float64).reshape(-1, 3),
            "n_resent": n_resent, "closed_at": closed_at,
            "t_kill": fault["t_call"], "t_kill_done": fault["t_done"]}


def longest_gap(t_kill: float, t_acked: np.ndarray):
    """The longest stretch from ``t_kill`` on with none of the
    acknowledgements ``t_acked`` in it (the kill itself opens the first):
    its length, when it began and when it ended; None where nothing was
    acknowledged after the kill."""
    after = np.concatenate([[t_kill], np.sort(t_acked[t_acked > t_kill])])
    gaps = np.diff(after)
    if not len(gaps):
        return None
    i = int(gaps.argmax())
    return float(gaps[i]), float(after[i]), float(after[i + 1])


def outage(res: Dict, victim: Optional[int] = None) -> Dict:
    """What the client saw of the fault: the longest stretch after the kill
    with no acknowledged reply at all (``service_gap_s``) and with none for
    a group ``victim`` led (``victim_groups_gap_s``: how long those groups
    had no service), the rate before the kill, and the rate from the
    first reply of a victim-led group after its gap."""
    t_kill = res["t_kill"]
    ok = (res["t_recv"] >= 0) & (res["status"] == 0)
    t = res["t_recv"][ok]
    out = {"service_gap_s": None, "gap_from_s": None,
           "victim_groups_gap_s": None, "rate_before_kill": None,
           "rate_after_takeover": None}
    if t_kill is None or not len(t):
        return out
    out["rate_before_kill"] = float((t <= t_kill).sum()) \
        / (t_kill - res["t0"])
    whole = longest_gap(t_kill, t)
    if whole is None:
        return out
    out["service_gap_s"], out["gap_from_s"] = whole[0], whole[1] - res["t0"]
    resumed = whole[2]
    if victim is not None:
        led = longest_gap(t_kill, res["t_recv"][ok & (res["home"] == victim)])
        if led is not None:
            out["victim_groups_gap_s"], resumed = led[0], led[2]
    last = float(t.max())
    if last > resumed:
        out["rate_after_takeover"] = float((t >= resumed).sum()) \
            / (last - resumed)
    return out
