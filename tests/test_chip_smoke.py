"""``chip_smoke.py``: its CPU rehearsal, run in this process, and its
refusal to run anywhere but a TPU without the rehearsal flag."""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from repro import kernels

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_streams_match_solo_without_oob(chip_smoke, capsys):
    assert chip_smoke.main(["--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert "check streams_match_solo: PASS" in "\n".join(lines)
    assert "check oob_events_zero: PASS" in "\n".join(lines)
    assert "check kernel_vs_ref_logits: PASS" in "\n".join(lines)
    assert "backend=interpret" in "\n".join(lines)
    last = json.loads(lines[-1])
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}


def test_refuses_cpu_without_rehearsal_flag(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


@pytest.mark.parametrize("platform,want", [
    ("tpu", "tpu"), ("cpu", "ref"), ("gpu", RuntimeError)])
def test_default_backend_never_falls_back(monkeypatch, platform, want):
    monkeypatch.setattr(kernels.jax, "devices",
                        lambda: [SimpleNamespace(platform=platform)])
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="no kernel backend"):
            kernels.default_backend()
    else:
        assert kernels.default_backend() == want


def test_default_backend_raises_failed_device_query(monkeypatch):
    def broken():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(kernels.jax, "devices", broken)
    with pytest.raises(RuntimeError, match="backend init failed"):
        kernels.default_backend()
