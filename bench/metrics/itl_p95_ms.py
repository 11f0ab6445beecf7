"""itl_p95_ms: gap between consecutive tokens of one stream as the client
receives them, 95th percentile over every gap received in the window."""

from bench.measure import percentile


def read(run):
    return percentile(run.gaps_ms(), 95)
