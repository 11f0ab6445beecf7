"""decode_step_ms (layer: model step, decode): device time of the decode
step program, summed over its executions in the trace, per execution.
Moves itl_p95_ms."""

from bench.measure import seconds_of

PROGRAMS = ("jit_paged_decode_step",)


def read(run):
    events = run.programs(PROGRAMS)
    if not events:
        return None
    return 1e3 * seconds_of(events) / len(events)
