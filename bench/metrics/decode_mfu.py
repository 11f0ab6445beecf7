"""decode_mfu (layer: model step, decode): the whole decode step's share
of the chip's roofline. The least time the window's steps could take is
the larger of the FLOPs they need over the bf16 peak and the bytes they
need over HBM bandwidth (the configuration's family counts them,
``decode_work`` in bench/families/<family>.py: for dense, weights read
once a step, each token's live K/V and its new K/V; neither the pool copy
nor dead block table slots count); it is divided by the steps' device
time, percent. Which bound applies is noted on an earlier line. Moves
itl_p95_ms."""

from bench import model, work
from bench.measure import seconds_of

PROGRAMS = ("jit_paged_decode_step",)


def read(run):
    events = run.programs(PROGRAMS)
    if not events or run.peaks is None:
        return None
    need = model.family(run.m).decode_work(run, len(events))
    if need is None:
        return None
    flops, nbytes = need
    least, bound = work.least_seconds(flops, nbytes, run.peaks)
    run.notes.append(f"decode_mfu: {bound}-bound ({flops:.6g} FLOP, "
                     f"{nbytes:.6g} B over {len(events)} steps)")
    return 100.0 * least / seconds_of(events)
