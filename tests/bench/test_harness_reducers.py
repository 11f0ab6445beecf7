"""Each metric reader against numbers worked out by hand, on a run and a
trace built here."""

import pytest

from bench import spec, work
from bench.client import Record
from bench.measure import Run
from bench.trace import Trace, gaps, union_ns
from bench.traffic import Request

M = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
     "head_dim": 2, "intermediate_size": 16, "vocab_size": 10,
     "num_hidden_layers": 3, "norm_kind": "rmsnorm"}
PEAKS = {"bf16_flop_per_s": 1e6, "hbm_bytes_per_s": 1e5}


def _rec(idx, due, prompt, times, admitted=None):
    r = Record(Request(idx, due, [1] * prompt, len(times)))
    r.due = due
    r.admitted_at = admitted if admitted is not None else \
        (times[0] if times else None)
    r.times = list(times)
    r.tokens = [1] * len(times)
    r.done = True
    return r


def _run(trace=None):
    recs = [
        _rec(0, 0.0, 4, [0.1, 0.2, 0.4], admitted=0.05),
        _rec(1, 0.5, 6, [0.9, 1.0, 1.1, 1.2]),
        _rec(2, 1.5, 3, []),                 # due, never admitted
        _rec(3, 2.5, 3, [2.6]),              # due after the window
    ]
    return Run(m=M, records=recs, t0=0.0, end=2.0, setup_s=12.5,
               trace=trace, peaks=PEAKS)


def _trace():
    ms = 1e6
    t = Trace(window=(0.0, 2000 * ms), devices=1)
    t.modules = [("jit_paged_decode_step(7)", 100 * ms, 110 * ms),
                 ("jit_paged_decode_step(7)", 300 * ms, 330 * ms),
                 ("jit__run(3)", 50 * ms, 90 * ms),
                 ("jit_paged_decode_step(7)", 1990 * ms, 2010 * ms)]
    t.ops = [("fusion.1", 100 * ms, 104 * ms),
             ("paged_attention", 104 * ms, 110 * ms),
             ("paged_attention", 300 * ms, 320 * ms),
             ("fusion.1", 315 * ms, 330 * ms),
             ("jit__run", 50 * ms, 90 * ms)]
    return t


def read(name, run):
    return spec.reader(name).read(run)


def test_host_clock_metrics():
    run = _run()
    assert read("setup_s", run) == 12.5
    # tokens received in [0, 2): 3 + 4
    assert read("out_tok_per_s", run) == 7 / 2.0
    # ttft: 100, 400, and 500 (still waiting at the end)
    assert read("first_token_p90_ms", run) == pytest.approx(480.0)
    # queue waits: 50, 400, 500
    assert read("queue_wait_p90_ms", run) == pytest.approx(480.0)
    # gaps: 100, 200, 100, 100, 100 ms
    assert read("itl_p95_ms", run) == pytest.approx(180.0)


def test_readers_find_nothing_without_a_trace():
    run = _run()
    for name in ("decode_step_ms", "decode_mfu", "paged_attention_roofline",
                 "prefill_mfu", "batch_occupancy", "device_idle_share"):
        assert read(name, run) is None, name


def test_trace_metrics_by_hand():
    run = _run(_trace())
    # the step that runs past the window is left out: 10 + 30 ms / 2
    assert read("decode_step_ms", run) == pytest.approx(20.0)
    # decode tokens in the window: 2 + 3 over 2 steps
    assert read("batch_occupancy", run) == pytest.approx(2.5)
    lives = [5, 6, 7, 8, 9]
    assert sorted(run.decode_lives()) == lives
    flops, nbytes = spec.family("dense").decode_window(M, 2, lives)
    least = max(flops / 1e6, nbytes / 1e5)
    assert read("decode_mfu", run) == pytest.approx(100 * least / 0.040)
    f, b = work.paged_attention(M, lives)
    assert read("paged_attention_roofline", run) == pytest.approx(
        100 * max(f / 1e6, b / 1e5) / 0.026)
    dense = spec.family("dense")
    pf = dense.prefill_flops(M, 4) + dense.prefill_flops(M, 6)
    assert read("prefill_mfu", run) == pytest.approx(100 * pf / 1e6 / 0.040)
    # busy: 50-90, 100-110, 300-330 ms = 80 ms of 2 s
    assert read("device_idle_share", run) == pytest.approx(96.0)


def test_union_and_gaps():
    ev = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40)]
    assert union_ns(ev) == 30
    assert gaps(ev, (0, 50)) == [(20, 30), (40, 50)]
