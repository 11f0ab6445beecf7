"""batch_occupancy (layer: scheduler batching): tokens after each
stream's first (the ones decode steps made) that clients received in
the traced window, over the executions of the decode step in the trace.
Streams per step. Moves out_tok_per_s."""

PROGRAMS = ("jit_paged_decode_step",)


def read(run):
    steps = len(run.programs(PROGRAMS))
    if not steps:
        return None
    return len(run.decode_lives()) / steps
