"""first_token_p90_ms (layer: client): time to first token, from each
request's due time to the client's receipt of its first token, 90th
percentile over every request due in the window; a request with no token
by the window's end counts with its wait so far. Host clock. A stall of
the host shows here first, so it is read per layer, not bounded end to
end. Moves itl_p95_ms: a first token is made by a prefill on the server
thread, between the decode steps that every other stream waits on."""

from bench.measure import percentile


def read(run):
    return percentile(run.ttfts_ms(), 90)
