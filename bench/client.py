"""The open-loop client: one thread that dispatches and drains.

Requests are dispatched at their due times through
``decode.generate_stream`` stubs (one connection per stream in flight)
and every open stream is drained round-robin from the same thread with a
non-blocking ``next(timeout=0)``; when nothing moved, the thread naps
``POLL_S``. A request the server sheds with ``Overloaded`` goes back to
the head of the queue and the client holds dispatch for the
``retry_after_s`` the reply carries; after a shed only the queue's head
is dispatched until it gets in. Every time counts from the request's due
time, so a stall shows in the latencies of everything behind it.

Host spans (``jax.profiler.TraceAnnotation``) mark dispatch, drain and
the naps when a trace is taken; they cost nothing otherwise.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .traffic import Request

POLL_S = 0.0002
DRAIN_TIMEOUT_S = 120.0


@dataclass
class Record:
    req: Request
    due: float = 0.0              # host clock
    attempt_at: float = 0.0       # dispatch of the attempt in flight
    admitted_at: Optional[float] = None   # the attempt that got in
    late_s: Optional[float] = None        # due to first seen by the loop
    sheds: int = 0
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    error: Optional[str] = None


class OpenLoop:
    def __init__(self, stubs, reqs: List[Request], trace: bool = False):
        self.free = list(stubs)
        self.records = [Record(r) for r in reqs]
        self.open: Dict[int, tuple] = {}
        self.sheds = 0
        self.t0 = self.end = 0.0
        self.longest_pass_s = 0.0   # a stall of the client's thread
        if trace:
            from jax.profiler import TraceAnnotation
            self.span = TraceAnnotation
        else:
            self.span = lambda name: contextlib.nullcontext()

    # -- one stream ------------------------------------------------------
    def _dispatch(self, rec: Record, now: float) -> None:
        stub = self.free.pop()
        r = rec.req
        with self.span("bench.dispatch"):
            stream = stub.generate_stream.stream(
                r.prompt, r.max_new, timeout=DRAIN_TIMEOUT_S,
                window=r.max_new + 4)
        rec.attempt_at = now
        self.open[r.idx] = (rec, stub, stream)

    def _drain(self, WaitTimeout, Overloaded) -> tuple:
        """Pull every ready token; returns (moved, retry_after_s|None)."""
        moved, retry = False, None
        clock = time.perf_counter
        for idx in list(self.open):
            rec, stub, stream = self.open[idx]
            while True:
                try:
                    tok = stream.next(timeout=0.0)
                except WaitTimeout:
                    break
                except StopIteration:
                    rec.done = True
                    self._close(idx)
                    break
                except Overloaded as e:
                    rec.sheds += 1
                    self.sheds += 1
                    retry = max(retry or 0.0, e.retry_after_s or POLL_S)
                    if self.probe is rec:
                        self.probe = None
                    self._close(idx)
                    self.pending.appendleft(rec)
                    break
                except Exception as e:   # a failed request, counted
                    rec.error = f"{type(e).__name__}: {e}"
                    self._close(idx)
                    break
                t = clock()
                if not rec.times:
                    rec.admitted_at = rec.attempt_at
                    if self.probe is rec:
                        self.probe = None
                rec.times.append(t)
                rec.tokens.append(int(tok))
                moved = True
        return moved, retry

    def _close(self, idx: int) -> None:
        _, stub, _ = self.open.pop(idx)
        self.free.append(stub)

    # -- the window --------------------------------------------------------
    def run(self, seconds: float) -> None:
        with self.span("bench.window"):
            self._run(seconds)

    def _run(self, seconds: float) -> None:
        from repro.core.errors import Overloaded, WaitTimeout

        clock = time.perf_counter
        self.t0 = t0 = clock()
        self.end = end = t0 + seconds
        for rec in self.records:
            rec.due = t0 + rec.req.due
        self.pending = deque(self.records)
        self.probe: Optional[Record] = None
        hold_until = 0.0
        seen = 0    # the generator's lateness: due to first seen due
        last = t0
        while True:
            now = clock()
            self.longest_pass_s = max(self.longest_pass_s, now - last)
            last = now
            if now >= end:
                break
            while seen < len(self.records) and \
                    self.records[seen].due <= now:
                self.records[seen].late_s = now - self.records[seen].due
                seen += 1
            moved = False
            while (self.pending and self.pending[0].due <= now
                   and now >= hold_until and self.free
                   and self.probe is None):
                rec = self.pending.popleft()
                if rec.sheds:
                    self.probe = rec
                self._dispatch(rec, now)
                moved = True
                now = clock()
            with self.span("bench.drain"):
                got, retry = self._drain(WaitTimeout, Overloaded)
            if retry is not None:
                # head-of-line order: the shed requests wait by due time
                self.pending = deque(sorted(self.pending,
                                            key=lambda r: r.due))
                hold_until = clock() + retry
            if not (moved or got):
                name = "bench.retry_wait" if clock() < hold_until \
                    else "bench.idle"
                with self.span(name):
                    time.sleep(POLL_S)

    def finish(self) -> None:
        """After the window: dispatch nothing more, drain what is open."""
        from repro.core.errors import Overloaded, WaitTimeout

        self.pending = deque()
        deadline = time.perf_counter() + DRAIN_TIMEOUT_S
        while self.open and time.perf_counter() < deadline:
            moved, _ = self._drain(WaitTimeout, Overloaded)
            if not moved:
                time.sleep(POLL_S)
        for idx in list(self.open):
            rec, _, stream = self.open[idx]
            rec.error = "not finished within the drain timeout"
            stream.close()
            self._close(idx)
