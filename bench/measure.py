"""What a run measured, as the metric readers see it.

A reader (``bench/metrics/<name>.py``) gets one ``Run`` and returns a
number, or ``None`` where it finds nothing to read; the harness then
leaves the metric out of the line.

Times on the host clock are ``time.perf_counter`` seconds; the window is
``[t0, end)``. Trace times are the trace's own nanoseconds.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from .client import Record
from .trace import Span, Trace


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


@dataclass
class Run:
    m: Dict                      # the flat configuration
    records: List[Record]
    t0: float
    end: float
    setup_s: float
    trace: Optional[Trace] = None
    peaks: Optional[Dict[str, float]] = None
    notes: List[str] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.t0

    def due(self) -> List[Record]:
        """Requests due inside the window."""
        return [r for r in self.records if r.due < self.end]

    def ttfts_ms(self) -> List[float]:
        """Due to first token; a request with no token by the window's
        end counts with its wait so far."""
        out = []
        for r in self.due():
            first = r.times[0] if r.times and r.times[0] < self.end \
                else self.end
            out.append((first - r.due) * 1e3)
        return out

    def queue_waits_ms(self) -> List[float]:
        """Due to the dispatch that was not shed (or the window's end)."""
        out = []
        for r in self.due():
            t = r.admitted_at if r.admitted_at is not None \
                and r.admitted_at < self.end else self.end
            out.append((t - r.due) * 1e3)
        return out

    def gaps_ms(self) -> List[float]:
        """Gaps between consecutive tokens of a stream received in the
        window."""
        out = []
        for r in self.records:
            ts = [t for t in r.times if t < self.end]
            out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
        return out

    def tokens_received(self) -> int:
        return sum(1 for r in self.records for t in r.times
                   if self.t0 <= t < self.end)

    def peak_pages(self) -> int:
        """The most KV pages that admitted streams held at once: each
        holds its prompt plus answer, rounded up to pages, from its
        admission to its last token."""
        T = self.m["page_tokens"]
        ev = []
        for r in self.records:
            if r.admitted_at is None or not r.times:
                continue
            n = -(-(len(r.req.prompt) + r.req.max_new) // T)
            ev += [(r.admitted_at, n), (r.times[-1], -n)]
        held = peak = 0
        for _, n in sorted(ev, key=lambda e: (e[0], e[1])):
            held += n
            peak = max(peak, held)
        return peak

    def decode_lives(self) -> List[int]:
        """For every token after a stream's first received in the window,
        the live KV length of the decode step that made it."""
        return [len(r.req.prompt) + k for r in self.records
                for k, t in enumerate(r.times)
                if k >= 1 and self.t0 <= t < self.end]

    def prefills(self) -> List[int]:
        """Prompt lengths of the requests whose first token (made by
        their prefill) arrived in the window."""
        return [len(r.req.prompt) for r in self.records
                if r.times and self.t0 <= r.times[0] < self.end]

    # -- trace helpers ---------------------------------------------------
    def programs(self, names) -> List:
        """Executions of the device programs whose name starts with one
        of ``names``, inside the traced window."""
        if self.trace is None:
            return []
        return [e for e in self.trace.in_window(self.trace.modules)
                if e[0].startswith(tuple(names))]

    def ops(self, substrings) -> List:
        if self.trace is None:
            return []
        return [e for e in self.trace.in_window(self.trace.ops)
                if any(s in e[0] for s in substrings)]

    def program(self, prefix: str) -> List[Span]:
        """The program's spans whose name starts with ``prefix``, inside
        the traced window, with their stats (the program's counters)."""
        if self.trace is None:
            return []
        return [sp for sp in self.trace.in_window(self.trace.program)
                if sp.name.startswith(prefix)]


def seconds_of(events) -> float:
    return sum(e - s for _, s, e in events) / 1e9
