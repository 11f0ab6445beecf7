"""rpc_host_ms (layer: RPC ring/marshal/seal/sandbox): device-idle time
under the program's ``rpcool.request`` and ``rpcool.pump`` spans, less
what a ``serve.*`` span nested in them covers, per decode step
(``serve.step``) in the traced window, ms (bench/spans.py). Moves
itl_p95_ms: the RPC pump and every request it serves run on the server
thread between the decode steps every stream waits on."""

from bench import spans


def read(run):
    return spans.read(run, "rpc_host_ms")
