"""admit_host_ms (layer: scheduler admission): device-idle time under the
program's ``serve.admit`` and ``serve.release`` spans and their children
(prefill, pool write, seal, first token), per admission (``serve.admit``)
in the traced window, ms (bench/spans.py). Moves itl_p95_ms: an
admission runs on the server thread between decode steps, so every other
stream waits on it."""

from bench import spans


def read(run):
    return spans.read(run, "admit_host_ms")
