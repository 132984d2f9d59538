"""The closed and the open request loop that drive ``Engine`` in a window.

Both wrap every call into the engine, and the open loop's waits, in
``jax.profiler.TraceAnnotation`` spans named ``bench.*``; the trace reduction
attributes device idle gaps to them.  The open loop keeps
``serving/loadgen.py``'s ``run_open_loop`` pacing: it submits what is due,
ticks the engine whenever it has work and sleeps only when idle, so queueing
delay accrues to requests, never to the device.  Each request is timed from
the moment it was due, on the engine's clock.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax

from .traffic import Arrival

IDLE_SLEEP_S = 0.002


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Record:
    """One request's timeline on the engine clock (seconds)."""
    index: int
    prompt_len: int
    max_new: int
    due: bool
    due_s: float
    submit_s: Optional[float]
    admit_s: Optional[float] = None
    first_s: Optional[float] = None
    finish_s: Optional[float] = None
    n_tokens: int = 0
    reason: Optional[str] = None
    prompt: object = None
    tokens: Optional[list] = None


def records(pairs, t0: float) -> List[Record]:
    out = []
    for a, h in pairs:
        out.append(Record(
            index=a.index, prompt_len=len(a.prompt), max_new=a.max_new,
            due=a.due, due_s=t0 + a.t, submit_s=h.submit_time,
            admit_s=h.admit_time, first_s=h.first_token_time,
            finish_s=h.finish_time, n_tokens=len(h.tokens),
            reason=h.finish_reason, prompt=a.prompt, tokens=list(h.tokens)))
    return out


class Window:
    """What the loops hand back: the window's bounds on the engine clock,
    the tokens delivered in it, and every submitted request."""

    def __init__(self):
        self.t0 = self.t1 = 0.0
        self.tokens = 0
        self.records: List[Record] = []
        self.pairs: list = []
        self.step_s: List[float] = []     # closed loop: each step's seconds


def delivered(pairs) -> int:
    return sum(len(h.tokens) for _, h in pairs)


def run_closed(eng, arrivals: List[Arrival], seconds: float, request,
               tracer=None) -> Window:
    """Set-up submits every session and steps until each has its first
    token; the window then steps for ``seconds``."""
    pairs = []
    with span("bench.submit"):
        for a in arrivals:
            pairs.append((a, eng.submit(request(a))))
    while True:
        with span("bench.step"):
            eng.step()
        with span("bench.drain"):
            eng.drain()
        if all(h.tokens for _, h in pairs):
            break
    w = Window()
    n0 = delivered(pairs)
    w.t0 = eng.now()
    while eng.now() - w.t0 < seconds:
        if tracer is not None:
            tracer.tick(eng.now() - w.t0, pairs)
        t = eng.now()
        with span("bench.step"):
            eng.step()
        w.step_s.append(eng.now() - t)
    with span("bench.drain"):
        eng.drain()
    w.t1 = eng.now()
    if tracer is not None:
        tracer.stop(pairs)
    w.tokens = delivered(pairs) - n0
    w.records = records(pairs, w.t0)
    w.pairs = pairs
    return w


def run_open(eng, arrivals: List[Arrival], seconds: float, request,
             drain_limit_s: float, tracer=None) -> Window:
    """Open loop: submit each arrival once its time has come; after the
    window keep the load on until every due request has finished (at most
    ``drain_limit_s`` more)."""
    arrivals = sorted(arrivals, key=lambda a: a.t)
    pairs, due = [], []
    w = Window()
    w.t0 = eng.now()
    idx = 0
    while True:
        now = eng.now() - w.t0
        if tracer is not None:
            tracer.tick(now, pairs)
        while idx < len(arrivals) and arrivals[idx].t <= now:
            a = arrivals[idx]
            with span("bench.submit"):
                h = eng.submit(request(a))
            pairs.append((a, h))
            if a.due:
                due.append(h)
            idx += 1
        if now >= seconds:
            if w.t1 == 0.0:
                w.t1 = eng.now()
                if tracer is not None:
                    tracer.stop(pairs)
            if all(h.finished for h in due) or now >= seconds + drain_limit_s:
                break
        with span("bench.step"):
            worked = eng.step()
        if not worked and idx < len(arrivals):
            wait = arrivals[idx].t - (eng.now() - w.t0)
            if wait > 0:
                with span("bench.wait"):
                    time.sleep(min(wait, IDLE_SLEEP_S))
    with span("bench.drain"):
        eng.drain()
    w.tokens = sum(len(h.tokens) for h in due)
    w.records = records(pairs, w.t0)
    w.pairs = pairs
    return w
