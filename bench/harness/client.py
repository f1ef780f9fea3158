"""Clients that drive ``AsyncServer.submit`` and keep their own timestamps.

Every time here is the host's monotonic clock, read on the client side when a
frame reaches the client. A request is *due* when its open-loop arrival time
comes, or, in a closed loop, when its client sends it. Time to first token
counts from the due time, so a stall that delays later arrivals shows in their
TTFT; how late the generator itself ran is kept apart (``lateness``). A
session's client sends its next turn, built from the tokens it was served, when
the previous answer has come back whole.

The window opens when the clients start, except in a sessions loop: there it
opens once every client's opening turn has its first token (or has ended), so
that the opening turns' prefills, all sent at once, fall in set-up and the
window sees conversations under way.
"""
from __future__ import annotations

import asyncio
import dataclasses
import math
import time
from typing import List, Optional

import numpy as np

from bench.harness.traffic import Planned


@dataclasses.dataclass
class Record:
    idx: int
    prompt: np.ndarray
    max_new: int
    due: float
    submitted: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    error: Optional[str] = None
    prefix_reused: int = 0
    replica: int = -1


@dataclasses.dataclass
class Window:
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def holds(self, t: float) -> bool:
        return self.start <= t < self.end


async def _one(srv, request_cls, p: Planned, rec: Record, on_first=None) -> None:
    """Send one request and record its frames; ``on_first()`` is called at its
    first token, or at its end where none came."""
    rec.submitted = time.monotonic()
    try:
        async for ev in srv.submit(request_cls(prompt=p.prompt.tolist(),
                                               max_new=p.max_new)):
            now = time.monotonic()
            if ev.kind == "token":
                if on_first is not None and not rec.times:
                    on_first()
                rec.times.append(now)
                rec.tokens.append(int(ev.token))
            elif ev.kind == "finished":
                rec.finished = True
                rec.prefix_reused = ev.metrics.prefix_reused
                rec.replica = ev.metrics.replica
            else:
                rec.error = ev.error
    except asyncio.CancelledError:
        raise
    except Exception as e:           # AdmissionError and the like: a failed request
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        if on_first is not None and not rec.times:
            on_first()


async def drive(srv, request_cls, sched, seconds: float, on_open=None) -> tuple:
    """Send the schedule's traffic and measure a window of ``seconds``; returns
    (records, window, lateness list). ``on_open(window)`` is called as the
    window opens. When the window closes, requests still in flight are
    abandoned with the tokens they were served."""
    recs: List[Record] = []
    lateness: List[float] = []
    tasks = []
    window = Window(math.inf, math.inf)
    opened = asyncio.Event()
    waiting = [sched.clients if sched.loop == "sessions" else 0]

    def open_window():
        window.start = time.monotonic()
        window.end = window.start + seconds
        if on_open is not None:
            on_open(window)
        opened.set()

    def opening_answered():
        waiting[0] -= 1
        if waiting[0] == 0:
            open_window()

    async def closed_client(c: int):
        for i in sched.client_requests(c):
            now = time.monotonic()
            if now >= window.end:
                return
            p = sched.request(i)
            rec = Record(idx=i, prompt=p.prompt, max_new=p.max_new, due=now)
            recs.append(rec)
            await _one(srv, request_cls, p, rec)

    async def open_launcher():
        for i in range(sched.n):
            p = sched.request(i)
            due = window.start + p.offset_s
            if due >= window.end:
                return
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            lateness.append(time.monotonic() - due)
            rec = Record(idx=i, prompt=p.prompt, max_new=p.max_new, due=due)
            recs.append(rec)
            tasks.append(asyncio.ensure_future(_one(srv, request_cls, p, rec)))

    async def session_client(c: int):
        hook = opening_answered
        for s in sched.client_sessions(c):
            prompt = served = None
            for k in range(int(sched.opening[s]), int(sched.turns[s])):
                now = time.monotonic()
                if now >= window.end:
                    return
                p = sched.turn(s, k, prompt, served)
                rec = Record(idx=p.idx, prompt=p.prompt, max_new=p.max_new, due=now)
                recs.append(rec)
                await _one(srv, request_cls, p, rec, on_first=hook)
                hook = None
                if not rec.finished or rec.error is not None:
                    break                 # a session goes on from a whole answer only
                prompt, served = p.prompt, rec.tokens

    if sched.loop == "sessions":
        tasks.extend(asyncio.ensure_future(session_client(c)) for c in range(sched.clients))
    else:
        open_window()
        if sched.loop == "closed":
            tasks.extend(asyncio.ensure_future(closed_client(c))
                         for c in range(sched.clients))
        else:
            tasks.append(asyncio.ensure_future(open_launcher()))
    await opened.wait()
    await asyncio.sleep(max(0.0, window.end - time.monotonic()))
    for t in list(tasks):
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return recs, window, lateness


# ----------------------------------------------------------------- window stats

def output_tokens(recs: List[Record], w: Window) -> int:
    return sum(1 for r in recs for t in r.times if w.holds(t))


def ttfts(recs: List[Record], w: Window) -> List[float]:
    return [r.times[0] - r.due for r in recs if r.times and w.holds(r.times[0])]


def gaps(recs: List[Record], w: Window) -> List[float]:
    return [b - a for r in recs for a, b in zip(r.times, r.times[1:]) if w.holds(b)]


def p95(xs) -> Optional[float]:
    """95th percentile by linear interpolation between order statistics, over
    every sample given; None for no sample."""
    return float(np.percentile(np.asarray(xs, np.float64), 95)) if len(xs) else None
