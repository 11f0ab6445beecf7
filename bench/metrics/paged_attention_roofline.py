"""paged_attention_roofline (layer: kernel, kernels/paged_attention): the
Pallas paged-attention kernel's share of its roofline. Needed work is
over live tokens only (q.K and p.V FLOPs; live K/V, q and output bytes;
bench/work.py); its least time over the summed device time of the
kernel's calls in the trace, percent. Moves itl_p95_ms."""

from bench import work
from bench.measure import seconds_of

# the kernel's ``pallas_call(name="paged_attention")``
OPS = ("paged_attention",)


def read(run):
    events = run.ops(OPS)
    lives = run.decode_lives()
    if not events or not lives or run.peaks is None:
        return None
    flops, nbytes = work.paged_attention(run.m, lives)
    least, bound = work.least_seconds(flops, nbytes, run.peaks)
    run.notes.append(f"paged_attention_roofline: {bound}-bound")
    return 100.0 * least / seconds_of(events)
