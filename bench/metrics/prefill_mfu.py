"""prefill_mfu (layer: model step, prefill): FLOPs the window's prompts
need (the configuration's family counts them, ``prefill_flops`` in
bench/families/<family>.py: for dense, matmuls over every position,
causal attention, the last position's unembedding) over the chip's bf16
peak times the device time of the prefill program's executions in the
trace, percent. Moves itl_p95_ms: a prefill runs on the server thread
between decode steps, so every other stream waits on it."""

from bench import model
from bench.measure import seconds_of

# the jit that ``serving/paged_model.prefill_kv`` wraps around
# ``Model.prefill`` is named after its inner function ``_run``
PROGRAMS = ("jit__run",)


def read(run):
    events = run.programs(PROGRAMS)
    prompts = run.prefills()
    if not events or not prompts or run.peaks is None:
        return None
    fam = model.family(run.m)
    flops = sum(fam.prefill_flops(run.m, p) for p in prompts)
    return 100.0 * flops / run.peaks["bf16_flop_per_s"] / seconds_of(events)
