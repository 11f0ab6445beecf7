"""Program spans (``repro.tracing``): off, a span is the one shared null
context and no annotation is built; on, each serve-loop boundary builds
one, named by its layer and carrying that boundary's counts."""

import pytest

from repro import tracing
from repro.core import RPC, Orchestrator


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    construction."""

    def __init__(self):
        self.built = []

    def __call__(self, name, **stats):
        self.built.append((name, stats))
        return tracing._NULL


@pytest.fixture
def recorder(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(tracing, "_annotation", rec)
    monkeypatch.setattr(tracing, "on", False)
    return rec


def _channel():
    orch = Orchestrator()
    ch = RPC(orch, pid=1).open("traced")
    ch.add(7, lambda ctx, a: a + 1)
    return ch, RPC(orch, pid=2).connect("traced")


def test_off_returns_the_shared_null_context_and_builds_nothing(recorder):
    spans = [tracing.span("serve.step", batch=b) for b in range(100)]
    assert all(s is tracing._NULL for s in spans)
    ch, conn = _channel()
    toks = [conn.call_async(7, k) for k in range(4)]
    assert ch.serve_once() == 4
    assert [conn.wait(t) for t in toks] == [1, 2, 3, 4]
    assert recorder.built == []


def test_on_builds_one_annotation_per_served_request(recorder, monkeypatch):
    monkeypatch.setattr(tracing, "on", True)
    ch, conn = _channel()
    toks = [conn.call_async(7, k) for k in range(3)]
    assert ch.serve_once() == 3
    assert [conn.wait(t) for t in toks] == [1, 2, 3]
    assert recorder.built == [("rpcool.request", {"fn": 7})] * 3
    # an idle sweep has no stream to pump and marks nothing
    assert ch.serve_once() == 0
    assert len(recorder.built) == 3


def test_enable_binds_the_profiler_annotation():
    from jax.profiler import TraceAnnotation

    try:
        tracing.enable(True)
        assert tracing.on
        assert isinstance(tracing.span("serve.step", batch=2),
                          TraceAnnotation)
    finally:
        tracing.enable(False)
    assert not tracing.on and tracing.span("serve.step") is tracing._NULL
