"""The program's spans (``repro.tracing``) as the harness reads them: a
smoke-size engine served under ``bench.trace.capture`` leaves them on its
server thread, nested as the serving layers nest, and ``bench.trace.load``
keeps them with their stats; the device-idle time they account for, and
the three metrics that read it, worked out by hand on a trace built
here."""

from dataclasses import replace
from types import SimpleNamespace

import pytest

from bench import run as bench_run
from bench import spans as sp
from bench import spec
from bench.measure import Run
from bench.trace import Span, Trace, capture, load

MS = 1e6


def _marker(log_dir, name):
    """(line, start, end) of the host event ``name``, with lines named as
    ``bench.trace.load`` names them."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name == name:
                    return f"{plane.name}#{i}", e.start_ns, e.end_ns
    raise LookupError(name)


def test_served_streams_leave_nested_spans_on_the_server_thread(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    from repro import tracing
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serving import PoolConfig, ServeEngine
    from repro.serving.engine import DecodeService

    cfg = replace(get_smoke_config("yi_9b"), num_layers=2)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params,
                      PoolConfig(num_pages=64, page_tokens=8,
                                 max_pages_per_seq=8),
                      max_active=4, backend="ref", serve_threaded=True)
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
    log_dir = str(tmp_path)
    try:
        # solo runs on this thread, untraced: the reference, and the
        # compiles done before the trace
        refs = [list(eng.generate_tokens(p, 6)) for p in prompts]
        stubs = [eng.router.stub(eng.endpoint_name, DecodeService,
                                 pid=30 + i, pod="pod0")
                 for i in range(len(prompts))]
        # the capture alone turns the spans on, for its length only
        with capture(log_dir):
            assert tracing.on
            with TraceAnnotation("test.clients"):
                streams = [s.generate_stream.stream(p, 6, timeout=60.0)
                           for s, p in zip(stubs, prompts)]
                outs = [list(st) for st in streams]
        assert not tracing.on
    finally:
        eng.shutdown()
    assert outs == refs

    client_line, lo, hi = _marker(log_dir, "test.clients")
    program = load(log_dir).program
    assert program and all(isinstance(s, Span) for s in program)
    assert not any(s.name.startswith("bench.") for s in program)
    spans = [s for s in program if s.start >= lo and s.end <= hi]
    serve = [s for s in spans if s.name.startswith("serve.")]
    lines = {s.line for s in serve}
    # every serve.* span lies on one thread, the one that pumps streams,
    # which is not the clients' thread
    assert len(lines) == 1 and client_line not in lines
    (server_line,) = lines
    assert any(s.name == "rpcool.pump" and s.line == server_line
               for s in spans)
    assert any(s.name == "rpcool.request" and s.stats.get("fn")
               for s in spans)

    steps = [s for s in serve if s.name == "serve.step"]
    assert steps and all(1 <= s.stats["batch"] <= 3 for s in steps)
    # counters ride on the spans as stats: every live row holds a page
    assert all(s.stats["live_pages"] >= s.stats["batch"] for s in steps)
    for step in steps:
        waits = [s for s in serve if s.name == "serve.step.wait"
                 and step.start <= s.start and s.end <= step.end]
        assert len(waits) == 1, step
    admits = sorted(s.stats["rid"] for s in serve if s.name == "serve.admit")
    releases = sorted(s.stats["rid"] for s in serve
                      if s.name == "serve.release")
    assert len(admits) == len(prompts) == len(set(admits))
    assert admits == releases
    assert sorted(s.stats["prompt"] for s in serve
                  if s.name == "serve.admit") == [2, 3, 4]
    for admit in (s for s in serve if s.name == "serve.admit"):
        inner = {s.name for s in serve
                 if admit.start <= s.start and s.end <= admit.end}
        assert {"serve.prefill", "serve.pool_write", "serve.seal",
                "serve.first_token"} <= inner


# -- the reduction, by hand ----------------------------------------------
def _span(name, a, b, **stats):
    return Span(name, a * MS, b * MS, "/host:CPU#1", stats)


def _trace(program=()):
    # busy 0-10, 30-40, 90-95, 97-100 ms: idle 10-30, 40-90, 95-97
    t = Trace(window=(0.0, 100 * MS), devices=1)
    t.ops = [("fusion", 0, 10 * MS), ("paged_attention", 30 * MS, 40 * MS),
             ("fusion", 90 * MS, 95 * MS), ("copy", 97 * MS, 100 * MS)]
    t.spans = [("bench.drain", 0, 100 * MS),
               ("bench.dispatch", 96 * MS, 97 * MS)]
    t.program = list(program)
    return t


SPANS = [
    _span("rpcool.pump", 5, 45, streams=2),
    _span("serve.step", 8, 42, batch=2),
    _span("serve.step.dispatch", 8, 9),
    _span("serve.step.wait", 9, 32),
    _span("serve.step.fanout", 33, 35),
    _span("rpcool.request", 50, 80, fn=3),
    _span("serve.admit", 52, 78, rid=4, prompt=16, max_new=8),
    _span("serve.prefill", 52, 60),
    _span("serve.first_token", 60, 70),
    _span("serve.step", 120, 130, batch=1),    # after the window
]


def test_idle_time_by_innermost_span():
    idle = sp.idle_by_span(_trace(), SPANS)
    want = {"serve.step.wait": 20, "serve.step": 2, "rpcool.pump": 3,
            "rpcool.request": 4, "serve.prefill": 8,
            "serve.first_token": 10, "serve.admit": 8, "none": 17}
    assert idle == pytest.approx({k: v / 1e3 for k, v in want.items()})
    assert sum(idle.values()) == pytest.approx(0.072)


def test_host_ms_per_step_and_per_admission():
    ms = sp.host_ms(_trace(), SPANS)
    # one step and one admission in the window
    assert ms == pytest.approx({"step_host_ms": 22.0, "rpc_host_ms": 7.0,
                                "admit_host_ms": 26.0})


def _run(trace):
    return Run(m={}, records=[], t0=0.0, end=0.1, setup_s=1.0, trace=trace)


def test_readers_take_the_host_metrics_from_the_traced_program():
    run = _run(_trace(SPANS))
    for name, want in (("step_host_ms", 22.0), ("rpc_host_ms", 7.0),
                       ("admit_host_ms", 26.0)):
        assert spec.reader(name).read(run) == pytest.approx(want), name


def test_run_gives_the_windows_program_spans_with_their_stats():
    run = _run(_trace(SPANS))
    steps = run.program("serve.step")
    # the step after the window is left out; its children count
    assert [s.name for s in steps] == ["serve.step", "serve.step.dispatch",
                                       "serve.step.wait", "serve.step.fanout"]
    assert steps[0].stats == {"batch": 2}
    assert [s.stats for s in run.program("serve.admit")] == \
        [{"rid": 4, "prompt": 16, "max_new": 8}]
    assert _run(None).program("serve.") == []


def _labels(gaps):
    return [label for label, _ in gaps], [secs for _, secs in gaps]


def test_gaps_take_the_program_span_over_most_of_them():
    t = _trace()
    client = bench_run.breakdown(SimpleNamespace(trace=t))["idle_gaps"]
    labels, secs = _labels(client)
    assert labels == ["bench.drain", "bench.drain", "bench.dispatch"]
    assert secs == pytest.approx([0.05, 0.02, 0.002])
    labels, secs2 = _labels(sp.label_gaps(t, SPANS, client))
    assert labels == ["serve.first_token", "serve.step.wait",
                      "bench.dispatch"]
    assert secs2 == secs
    # the harness's breakdown labels them so from the trace's own spans
    traced = bench_run.breakdown(SimpleNamespace(trace=_trace(SPANS)))
    assert _labels(traced["idle_gaps"]) == (labels, secs)


def test_idle_line_adds_up_to_the_windows_idle_time():
    line = sp.idle_line(sp.idle_by_span(_trace(), SPANS), 0.1)
    assert line.startswith("device idle by program span: "
                           "serve.step.wait 0.020000s, none 0.017000s")
    assert line.endswith("; total 0.072000s of 0.100000s")


def test_a_program_without_spans_reads_nothing():
    t = _trace()
    assert sp.host_ms(t, []) == {"rpc_host_ms": None, "step_host_ms": None,
                                 "admit_host_ms": None}
    for name in sp.LAYERS:
        assert spec.reader(name).read(_run(t)) is None
        assert spec.reader(name).read(_run(None)) is None
    assert sp.idle_by_span(t, []) == pytest.approx({"none": 0.072})
    client = bench_run.breakdown(SimpleNamespace(trace=t))["idle_gaps"]
    assert sp.label_gaps(t, [], client) == client
