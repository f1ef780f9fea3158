"""Clients that drive ``AsyncServer.submit`` and keep their own timestamps.

Every time here is the host's monotonic clock, read on the client side when a
frame reaches the client. A request is *due* when its open-loop arrival time
comes, or, in a closed loop, when its client sends it. Time to first token
counts from the due time, so a stall that delays later arrivals shows in their
TTFT; how late the generator itself ran is kept apart (``lateness``).
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import List, Optional

import numpy as np

from bench.harness.traffic import Planned, Schedule


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


@dataclasses.dataclass
class Window:
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def holds(self, t: float) -> bool:
        return self.start <= t < self.end


async def _one(srv, request_cls, p: Planned, rec: Record) -> None:
    rec.submitted = time.monotonic()
    try:
        async for ev in srv.submit(request_cls(prompt=p.prompt.tolist(),
                                               max_new=p.max_new)):
            now = time.monotonic()
            if ev.kind == "token":
                rec.times.append(now)
                rec.tokens.append(int(ev.token))
            elif ev.kind == "finished":
                rec.finished = True
                rec.prefix_reused = ev.metrics.prefix_reused
            else:
                rec.error = ev.error
    except asyncio.CancelledError:
        raise
    except Exception as e:           # AdmissionError and the like: a failed request
        rec.error = f"{type(e).__name__}: {e}"


async def drive(srv, request_cls, sched: Schedule, seconds: float,
                on_open=None) -> tuple:
    """Send the schedule's traffic for ``seconds``; returns (records, window,
    lateness list). When the window closes, requests still in flight are
    abandoned with the tokens they were served."""
    recs: List[Record] = []
    lateness: List[float] = []
    tasks = []
    start = time.monotonic()
    window = Window(start, start + seconds)
    if on_open is not None:
        on_open(window)

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

    if sched.loop == "closed":
        tasks.extend(asyncio.ensure_future(closed_client(c))
                     for c in range(sched.clients))
    else:
        tasks.append(asyncio.ensure_future(open_launcher()))
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
