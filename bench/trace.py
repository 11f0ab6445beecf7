"""The profiler trace of a window, reduced to plain event lists.

``capture`` runs the window under ``jax.profiler`` with the Python
tracer off (it would slow every host call) and the program's own spans
on (``repro.tracing``); the client marks the window with a
``bench.window`` span. ``load`` reads the ``.xplane.pb`` it wrote with
nothing but JAX and keeps, on one clock:

    ops      device operations (the "XLA Ops" line of each device plane)
    modules  device programs (the "XLA Modules" line): one event per
             execution of a jitted program or eager op
    spans    the benchmark's host spans (``bench.*``)
    program  the program's host spans (``rpcool.*``, ``serve.*``), each
             a ``Span`` with its thread line and its stats (counters)
    window   (start, end) of the ``bench.window`` span

Times are nanoseconds.
"""

from __future__ import annotations

import contextlib
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Tuple

Event = Tuple[str, float, float]   # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the program's span names (``repro.tracing``); the host planes also hold
# the runtime's and the compiler's events, which are no layer's
PROGRAM = ("rpcool.", "serve.")


class Span(NamedTuple):
    """A program span: an ``Event`` with its thread and its stats."""
    name: str
    start: float          # ns, on the trace's clock
    end: float
    line: str             # host thread: "<plane>#<line index>"
    stats: Dict[str, object]


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the block, with the program's spans on for its length
    where the program has them."""
    import jax

    try:
        from repro import tracing
    except ImportError:
        tracing = None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        if tracing is not None:
            tracing.enable(True)
        yield
    finally:
        if tracing is not None:
            tracing.enable(False)
        jax.profiler.stop_trace()


@dataclass
class Trace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    spans: List[Event] = field(default_factory=list)
    program: List[Span] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)
    devices: int = 0

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def in_window(self, events: List[Event]) -> List[Event]:
        lo, hi = self.window
        return [e for e in events if e[1] >= lo and e[2] <= hi]

    def busy_s(self) -> float:
        """Union of the device-op intervals inside the window, seconds,
        averaged over the devices traced."""
        return union_ns(clip(self.ops, self.window)) / 1e9 / max(
            1, self.devices)


def clip(events: List[Event], window: Tuple[float, float]) -> List[Event]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union_ns(events: List[Event]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: List[Event], window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """Intervals of the window in which no event runs."""
    out, t = [], window[0]
    for _, s, e in sorted(clip(events, window), key=lambda ev: ev[1]):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    tr = Trace()
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        if device and any(line.name == OPS_LINE for line in plane.lines):
            tr.devices += 1
        for i, line in enumerate(plane.lines):
            if device and line.name in (OPS_LINE, MODULES_LINE):
                dst = tr.ops if line.name == OPS_LINE else tr.modules
                dst.extend((e.name, e.start_ns, e.end_ns)
                           for e in line.events)
            elif not device:
                for e in line.events:
                    if e.name == "bench.window":
                        tr.window = (e.start_ns, e.end_ns)
                    elif e.name.startswith("bench."):
                        tr.spans.append((e.name, e.start_ns, e.end_ns))
                    elif e.name.startswith(PROGRAM):
                        tr.program.append(Span(
                            e.name, e.start_ns, e.end_ns,
                            f"{plane.name}#{i}", dict(e.stats)))
    return tr
