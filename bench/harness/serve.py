"""Drive the program's ``StreamEngine`` with a traffic mix; record what clients see.

The window drives ``submit`` and ``step`` only, as a server built on the
engine would: a request's tokens reach its client when the ``step`` that
produced them returns, and every such return is one *delivery* to each
request that received tokens.  All times are host-clock seconds
(``time.perf_counter``).

A run has three phases.  Warm-up runs each shape the window will use
(both prefill programs, and rounds admitting 1 to ``admit_per_round``
requests) through the same public calls, with requests of its own that
the metrics never see.  The mix's start then brings the system to a
running state: a closed loop admits every client's first request, an
open loop runs ``warm_s`` seconds of its schedule.  The window follows,
and ends at the first return of ``step`` at or after ``seconds``.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench.harness.traffic import Traffic


@dataclasses.dataclass
class Record:
    """One request as its client sees it."""
    index: int
    due: float
    sent: float
    prompt_len: int
    max_new: int
    handle: object = None
    deliveries: list = dataclasses.field(default_factory=list)  # (t, n) pairs
    done_t: float | None = None
    failed: bool = False

    @property
    def first_t(self) -> float | None:
        return self.deliveries[0][0] if self.deliveries else None

    @property
    def tokens(self) -> list[int]:
        return [] if self.handle is None else list(self.handle.out_tokens)


class Session:
    """The client side of one run: sends requests, steps, records deliveries."""

    def __init__(self, engine, clock=time.perf_counter):
        self.engine = engine
        self.clock = clock
        self.live: list[Record] = []
        self.records: list[Record] = []

    def send(self, index: int, prompt: np.ndarray, max_new: int, due: float) -> Record:
        rec = Record(index=index, due=due, sent=self.clock(),
                     prompt_len=len(prompt), max_new=max_new)
        with TraceAnnotation("bench.submit"):
            try:
                rec.handle = self.engine.submit(prompt, max_new)
            except (ValueError, RuntimeError):
                rec.failed = True
        self.records.append(rec)
        if not rec.failed:
            self.live.append(rec)
        return rec

    def idle(self) -> bool:
        return not self.live

    def step(self) -> list[Record]:
        """One ``engine.step``; returns the requests it completed."""
        with TraceAnnotation("bench.step"):
            self.engine.step()
        t = self.clock()
        done = []
        with TraceAnnotation("bench.deliver"):
            for rec in self.live:
                n = len(rec.handle.out_tokens) - sum(k for _, k in rec.deliveries)
                if n:
                    rec.deliveries.append((t, n))
                if rec.handle.done:
                    rec.done_t = t
                    rec.failed = rec.handle.status != "ok"
                    done.append(rec)
            if done:
                self.live = [r for r in self.live if r.done_t is None]
        return done


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    records: list          # every request the run sent (warm-up excluded)
    attempted: list        # requests the window served (open loop: those due in [t0, t1))
    lateness: list         # send time minus due time, window requests
    rounds: int

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def warm_up(session: Session, *, vocab: int, prefill_chunk: int, admit_per_round: int):
    """Compile every shape the window uses, through submit/step.

    Two one-token requests run the two prefill programs (a prompt of whole
    chunks, and one with a ragged tail).  Then rounds admitting 1, 2, ...,
    ``admit_per_round`` two-token requests each run the round with every
    admission count.
    """
    rng = np.random.default_rng(0)

    def prompt(n):
        return rng.integers(1, vocab, size=n, dtype=np.int32)

    for n in (prefill_chunk, prefill_chunk + 1):
        session.send(-1, prompt(n), 1, session.clock())
    session.step()
    for k in range(1, admit_per_round + 1):
        for _ in range(k):
            session.send(-1, prompt(prefill_chunk + 1), 2, session.clock())
        while not session.idle():
            session.step()
    session.records.clear()


def run_closed(session: Session, traffic: Traffic, seconds: float,
               on_open=None, on_close=None) -> Window:
    """Closed loop: ``traffic.clients`` callers, each sending its next
    request when the previous one completes."""
    first = []
    for i in range(traffic.clients):
        r = traffic.request(i)
        first.append(session.send(r.index, r.prompt, r.max_new, session.clock()))
    next_index = traffic.clients
    # Fill: step until every client's first request has been admitted.
    while any(not rec.deliveries and not rec.failed for rec in first):
        for rec in session.step():
            r = traffic.request(next_index)
            session.send(r.index, r.prompt, r.max_new, rec.done_t)
            next_index += 1
    in_flight = list(session.live)
    if on_open:
        on_open()
    t0 = session.clock()
    rounds = 0
    with TraceAnnotation("bench.window"):
        while True:
            done = session.step()
            rounds += 1
            t = session.clock()
            if t - t0 >= seconds:
                break
            for rec in done:
                r = traffic.request(next_index)
                session.send(r.index, r.prompt, r.max_new, rec.done_t)
                next_index += 1
    if on_close:
        on_close()
    t1 = t
    sent = [r for r in session.records if r.sent >= t0] + in_flight
    return Window(t0=t0, t1=t1, records=session.records, attempted=sent,
                  lateness=[r.sent - r.due for r in sent if r.sent >= t0],
                  rounds=rounds)


def run_open(session: Session, traffic: Traffic, seconds: float,
             on_open=None, on_close=None, drain_s: float = 60.0) -> Window:
    """Open loop: requests due on the mix's schedule, sent when due (or at
    the first return of ``step`` after that)."""
    start = session.clock()
    t0 = start + traffic.warm_s
    next_index = 0
    opened = False
    rounds = 0
    t = start
    span = None
    while True:
        t = session.clock()
        if not opened and t >= t0:
            opened = True
            if on_open:
                on_open()
            span = TraceAnnotation("bench.window")
            span.__enter__()
        if opened and t - t0 >= seconds:
            break
        while start + traffic.due(next_index) <= t:
            r = traffic.request(next_index)
            session.send(r.index, r.prompt, r.max_new, start + traffic.due(next_index))
            next_index += 1
        if session.idle():
            wake = min(start + traffic.due(next_index), t0 if not opened else t0 + seconds)
            with TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, wake - session.clock()))
            continue
        session.step()
        rounds += opened
    span.__exit__(None, None, None)
    if on_close:
        on_close()
    t1 = t
    due = [r for r in session.records if t0 <= r.due < t1]
    # Every request due in the window gets its first token, however late.
    deadline = session.clock() + drain_s
    while any(r.first_t is None and not r.failed for r in due) and session.clock() < deadline:
        session.step()
    for r in due:
        if r.first_t is None:
            r.failed = True
    return Window(t0=t0, t1=t1, records=session.records, attempted=due,
                  lateness=[r.sent - r.due for r in due], rounds=rounds)


def client_metrics(win: Window) -> dict:
    """The end-to-end metrics of a window, from the client's side."""
    delivered = 0
    gaps = []
    for rec in win.records:
        prev = None
        for t, n in rec.deliveries:
            if win.t0 < t <= win.t1:
                delivered += n
                if prev is not None:
                    gaps.append(t - prev)
            prev = t
    out = {
        "tokens_per_s": delivered / win.seconds,
        "token_gap_p95_ms": 1e3 * float(np.percentile(gaps, 95)) if gaps else None,
    }
    ttft = [r.first_t - r.due for r in win.attempted if r.first_t is not None]
    if ttft:
        out["ttft_p95_ms"] = 1e3 * float(np.percentile(ttft, 95))
    return out
