"""Program spans on the profiler's clock.

The serving thread marks its layer boundaries (RPC request and stream
pump, admission, decode step, release) with named host spans. While a
``jax.profiler`` trace runs and spans are enabled, each span lands in
the trace's host plane beside the device ops, with its keyword
arguments as event stats, so a device-idle gap can be put down to the
host work that covers it::

    from repro import tracing
    tracing.enable(True)
    with tracing.span("serve.step", batch=8):
        ...

Spans are off by default, and then ``span`` costs one test of a module
flag and returns one shared null context: no annotation is built, no
environment is read. Like ShmCheck's disabled hooks (one ``is not None``
check), the flag is process-wide, because the spans it governs sit on
the serving thread of whatever engine runs. The RPC paths that serve
one request in microseconds test ``on`` themselves before calling
``span``, so that their off path is that one test alone.
"""

from __future__ import annotations

import contextlib

on = False
_NULL = contextlib.nullcontext()
_annotation = None


def enable(flag: bool) -> None:
    """Turn program spans on or off for the whole process."""
    global on, _annotation
    if flag and _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    on = bool(flag)


def span(name: str, **stats):
    """A context manager marking ``name`` on the profiler's host trace
    (with ``stats`` as its event stats) when spans are on, else the
    shared null context."""
    if on:
        return _annotation(name, **stats)
    return _NULL
