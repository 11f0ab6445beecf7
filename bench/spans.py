"""The program's own spans in a cell's traced window, and the device-idle
time they account for.

The serving thread marks its layer boundaries with host spans
(``repro.tracing``) on the profiler's clock, which ``bench.trace.capture``
turns on and ``bench.trace.load`` keeps in ``Trace.program``:

    rpcool.request, rpcool.pump           RPC ring, seal, sandbox, marshal
    serve.step, serve.step.*              host half of a decode step
    serve.admit, serve.prefill, serve.pool_write, serve.seal,
    serve.first_token, serve.release      admission and release

Device-idle time (the window less the union of device ops) is put down
to the innermost program span open over it; a layer's idle time is then
what falls inside one of its spans and outside any span of another
layer nested in it. Three per-layer metrics read it
(``bench/metrics/<name>.py`` call ``read``):

    rpc_host_ms    RPC layer's idle time per decode step (``serve.step``)
    step_host_ms   decode-step layer's idle time per decode step
    admit_host_ms  admission layer's idle time per admission
                   (``serve.admit``)

A traced run (``bench.run --trace 1``) labels its longest idle gaps with
``label_gaps`` and prints ``idle_line``.

    python3 -m bench.spans --workload <cell> --seed <n> --seconds <s>

runs the cell exactly as ``python3 -m bench.run ... --trace 1`` does, and
after the harness's own result line prints one more JSON line,
``program_spans``: the three numbers, device-idle seconds by innermost
span name (``none`` where no program span is open; they add up to the
window's idle time), the longest idle gaps labelled by the program span
that covers most of each (the client's label where none does), the span
counts, and what a span costs on this host.
"""

from __future__ import annotations

import bisect
import heapq
import json
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from .trace import Span, Trace, gaps

NONE = "none"

# metric -> (the names of its layer's spans, the span counted per)
LAYERS = {
    "rpc_host_ms": (("rpcool.request", "rpcool.pump"), "serve.step"),
    "step_host_ms": (("serve.step", "serve.step.inputs",
                      "serve.step.dispatch", "serve.step.wait",
                      "serve.step.fanout"), "serve.step"),
    "admit_host_ms": (("serve.admit", "serve.prefill", "serve.pool_write",
                       "serve.seal", "serve.first_token", "serve.release"),
                      "serve.admit"),
}


def innermost(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """The stretches of time in which some span is open, each with the
    name of the innermost one: the latest started of those still open
    (spans of one thread nest, so that is the top of its stack)."""
    bounds = sorted({t for sp in spans for t in (sp.start, sp.end)})
    order = sorted(spans, key=lambda sp: sp.start)
    open_, k, out = [], 0, []
    for a, b in zip(bounds, bounds[1:]):
        while k < len(order) and order[k].start <= a:
            sp = order[k]
            heapq.heappush(open_, (-sp.start, sp.end, k))
            k += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        if open_:
            out.append((a, b, order[open_[0][2]].name))
    return out


def _cover(segs, ends, lo: float, hi: float) -> Dict[str, float]:
    """Nanoseconds of [lo, hi) under each label of ``segs`` (sorted and
    disjoint, as ``innermost`` gives them; ``ends`` their end times)."""
    cover: Dict[str, float] = {}
    j = bisect.bisect_right(ends, lo)
    while j < len(segs) and segs[j][0] < hi:
        a, b, name = segs[j]
        cover[name] = cover.get(name, 0.0) + min(b, hi) - max(a, lo)
        j += 1
    return cover


def idle_by_span(trace: Trace, spans: List[Span]) -> Dict[str, float]:
    """Device-idle seconds of the window by the innermost program span
    open over them; ``none`` holds what no program span covers. The
    values add up to the window's idle time."""
    segs = innermost(spans)
    ends = [b for _, b, _ in segs]
    out: Dict[str, float] = {}
    total = 0.0
    for lo, hi in gaps(trace.ops, trace.window):
        total += hi - lo
        for name, ns in _cover(segs, ends, lo, hi).items():
            out[name] = out.get(name, 0.0) + ns
    out = {n: v / 1e9 for n, v in out.items()}
    out[NONE] = total / 1e9 - sum(out.values())
    return out


def in_window(trace: Trace, spans: List[Span],
              name: Optional[str] = None) -> List[Span]:
    """The spans (named ``name``, if given) that lie in the window."""
    return [sp for sp in trace.in_window(spans)
            if name is None or sp.name == name]


def host_ms(trace: Trace, spans: List[Span],
            idle: Optional[Dict[str, float]] = None
            ) -> Dict[str, Optional[float]]:
    """``rpc_host_ms``, ``step_host_ms`` and ``admit_host_ms``: each
    layer's device-idle time over the spans it is counted per that lie in
    the window, milliseconds; None where there are none."""
    idle = idle_by_span(trace, spans) if idle is None else idle
    out = {}
    for metric, (names, per) in LAYERS.items():
        n = len(in_window(trace, spans, per))
        out[metric] = 1e3 * sum(idle.get(x, 0.0) for x in names) / n \
            if n else None
    return out


def read(run, metric: str) -> Optional[float]:
    """One of ``LAYERS``' metrics over a run's traced program spans; None
    without a trace, or where the window holds none of the spans it is
    counted per."""
    if run.trace is None:
        return None
    return host_ms(run.trace, run.trace.program)[metric]


def idle_line(idle: Dict[str, float], window_s: float) -> str:
    """``idle_by_span``'s seconds, largest first, as one printed line."""
    by = sorted(idle.items(), key=lambda kv: -kv[1])
    return ("device idle by program span: "
            + ", ".join(f"{n} {v:.6f}s" for n, v in by)
            + f"; total {sum(idle.values()):.6f}s of {window_s:.6f}s")


def label_gaps(trace: Trace, spans: List[Span],
               client: List[List]) -> List[List]:
    """The window's longest idle gaps, as ``bench.run.breakdown`` lists
    them (``client``: [label, seconds], longest first), each relabelled
    with the innermost program span covering most of it; a gap no
    program span covers keeps the client's label."""
    segs = innermost(spans)
    ends = [b for _, b, _ in segs]
    longest = sorted(gaps(trace.ops, trace.window),
                     key=lambda g: g[0] - g[1])[:len(client)]
    out = []
    for (lo, hi), (label, secs) in zip(longest, client):
        cover = _cover(segs, ends, lo, hi)
        out.append([max(cover, key=cover.get) if cover else label, secs])
    return out


def span_cost_us(n: int = 20000) -> Dict[str, float]:
    """Microseconds per entered and exited span on this host: spans off,
    on with no profiler running, and on under a running profiler."""
    import jax

    from repro import tracing

    def timed():
        t = time.perf_counter()
        for i in range(n):
            with tracing.span("serve.step", batch=i):
                pass
        return 1e6 * (time.perf_counter() - t) / n

    was = tracing.on
    out = {}
    try:
        tracing.enable(False)
        out["off"] = timed()
        tracing.enable(True)
        out["on_unprofiled"] = timed()
        log_dir = tempfile.mkdtemp(prefix="bench-span-cost-")
        jax.profiler.start_trace(log_dir)
        try:
            out["on_profiled"] = timed()
        finally:
            jax.profiler.stop_trace()
            shutil.rmtree(log_dir, ignore_errors=True)
    finally:
        tracing.enable(was)
    return out


def report(trace: Trace, spans: List[Span], client_gaps: List[List]
           ) -> Dict:
    idle = idle_by_span(trace, spans)
    return {
        **host_ms(trace, spans, idle),
        "idle_s": sum(idle.values()),
        "window_s": trace.window_s,
        "idle_by_span": idle,
        "idle_gaps": label_gaps(trace, spans, client_gaps),
        "span_events": len(in_window(trace, spans)),
        "decode_steps": len(in_window(trace, spans, "serve.step")),
        "admissions": len(in_window(trace, spans, "serve.admit")),
    }


def main(argv=None) -> int:
    """``bench.run`` with ``--trace 1``; then the ``program_spans`` line."""
    from . import run

    rc, held = run.execute(list(sys.argv[1:] if argv is None else argv)
                           + ["--trace", "1"])
    if rc or held is None or held.trace is None:
        return rc
    t = held.trace
    rep = report(t, t.program, run.client_gaps(t))
    rep["span_cost_us"] = span_cost_us()
    print(json.dumps({"program_spans": rep}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
