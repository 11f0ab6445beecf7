"""queue_wait_p90_ms (layer: scheduler admission): from a request's due
time to the dispatch the scheduler did not shed, 90th percentile over
the requests due in the window; one not admitted by the window's end
counts with its wait so far. Host clock of the benchmark's client.
Moves itl_p95_ms: an admission runs its prefill under the decode lock,
between the steps every other stream waits on."""

from bench.measure import percentile


def read(run):
    return percentile(run.queue_waits_ms(), 90)
