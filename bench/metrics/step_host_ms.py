"""step_host_ms (layer: scheduler batching): device-idle time under the
program's ``serve.step`` span and its children (inputs, dispatch, wait,
fanout), per decode step in the traced window, ms (bench/spans.py): the
host half of a decode step. Moves itl_p95_ms: every stream's next token
waits on it."""

from bench import spans


def read(run):
    return spans.read(run, "step_host_ms")
