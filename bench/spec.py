"""Name resolution: every cell, configuration, traffic mix, per-layer
metric and limit is a file found by its name in ``BENCHMARK.json``.

    bench/configs/<config>.json     sizes, serving pool, reference kind
    bench/reference/<kind>.py       the plain float32 reference forward
    bench/traffic/<traffic>.json    parameters for bench/traffic.py
    bench/metrics/<metric>.py       one reader per per-layer metric
    bench/limits/<cell>.json        the numbers `correct` compares

A later cell or metric adds files; no existing file changes.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _json(path: Path) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _json(ROOT / "BENCHMARK.json")


def load_module(path: Path, name: str):
    """Import a Python file by path (file names may hold ``-``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name: str) -> Dict[str, Any]:
    return _json(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return _json(BENCH_DIR / "traffic" / f"{name}.json")


def limits(cell: str) -> Dict[str, Any]:
    return _json(BENCH_DIR / "limits" / f"{cell}.json")


def reference(kind: str):
    return importlib.import_module(f"bench.reference.{kind}")


def reader(metric: str):
    """The per-layer metric's reader module: ``read(run) -> float|None``."""
    return load_module(BENCH_DIR / "metrics" / f"{metric}.py",
                       f"bench_metric_{metric.replace('.', '_')}")


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, Any]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: Dict[str, Any] | None = None) -> Cell:
    bench = bench or benchmark()
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    w = rows[0]
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], traffic_name=w["traffic"],
        config=config(w["config"]), traffic=traffic(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        limits=limits(name))
