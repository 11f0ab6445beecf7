"""The harness end to end on the CPU at smoke sizes (``--rehearse``):
the last line is well formed, and a broken timed path reads incorrect.

These drive ``bench.run.main`` in this process: they skip its look for a
chip and use interpret-mode kernels, so no chip and no subprocess."""

import json

import pytest

from bench import run as bench_run
from bench import spec

V_SMOKE = 256


def _line(capsys, workload, seed=5, seconds=3.0):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 0, err
    res = json.loads(out.strip().splitlines()[-1])
    return res, out, err


@pytest.mark.parametrize("workload", ["yi9b-chat", "olmo1b-chat"])
def test_rehearsal_prints_a_well_formed_correct_line(capsys, workload):
    res, out, err = _line(capsys, workload)
    assert list(res)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    cell = spec.cell(workload)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    for v in res["metrics"].values():
        assert v["value"] > 0 and v["unit"]
    assert res["device"]["platform"] == "cpu"
    assert "compiles in window: 0 lowerings" in out
    # every compared number and its limit close stderr
    last = err.strip().splitlines()[-len(res["compared"]):]
    assert all(line.startswith("compared ") for line in last)


def test_no_chip_means_no_result(capsys):
    rc = bench_run.main(["--workload", "yi9b-chat", "--seed", "1",
                         "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert "no TPU" in err


def _broken_token(monkeypatch):
    """A token altered where it is produced: each stream's second decoded
    token is bumped before it is streamed."""
    from repro.serving.engine import StreamScheduler

    orig = StreamScheduler.step_batch

    def step_batch(self):
        b = orig(self)
        for s in self.slots:
            if s.produced == 3 and s.buf:
                s.cur = (s.cur + 1) % V_SMOKE
                s.buf[-1] = s.cur
        return b

    monkeypatch.setattr(StreamScheduler, "step_batch", step_batch)


def _stale_state(monkeypatch):
    """A step that returns its state unchanged: the decode step hands
    back the pools it was given, so no decoded token's K/V is kept."""
    import repro.serving.engine as engine

    orig = engine.paged_decode_step

    def step(cfg, params, tokens, pos, bt, lens, k, v, *rest, **kw):
        logits, _, _, oob = orig(cfg, params, tokens, pos, bt, lens, k, v,
                                 *rest, **kw)
        return logits, k, v, oob

    monkeypatch.setattr(engine, "paged_decode_step", step)


@pytest.mark.parametrize("fault", [_broken_token, _stale_state],
                         ids=["token_altered", "state_unchanged"])
@pytest.mark.parametrize("workload", ["yi9b-chat", "olmo1b-chat"])
def test_a_broken_timed_path_reads_incorrect(capsys, monkeypatch, fault,
                                             workload):
    fault(monkeypatch)
    res, _, _ = _line(capsys, workload)
    assert res["correct"] is False
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
