"""Every cell of BENCHMARK.json resolves its files by name, and the file
keeps to the benchmark's contract."""

import json
import re

import pytest

from bench import spec, traffic

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_config_traffic_limits_and_readers(name):
    cell = spec.cell(name, BENCH)
    assert cell.chips == 1
    assert cell.config["name"] == cell.config_name
    assert "rate_per_s" in cell.traffic
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.reader(m["name"]).read), m["name"]
    assert {"setup_s"} <= {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    chip = cell.limits["chip"]
    for key in ("max_logit_gap", "lost_tokens", "failed_requests",
                "oob_events"):
        assert key in chip and key in cell.limits["rehearsal"]


def test_names_units_and_keys_keep_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + CELLS + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # every cell that lists the metric reports the metric it moves
        target = next(e for e in BENCH["end_to_end"]
                      if e["name"] == m["moves"])
        for w in m.get("workloads", CELLS):
            assert w in target.get("workloads", CELLS), (m["name"], w)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_source_and_cuts(cfg):
    data = spec.config(cfg["name"])
    assert data["source"] == cfg["source"]
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    assert data["hidden_size"] == data["program"]["head_dim"] * \
        data["num_attention_heads"]
    s = data["serving"]
    # the pool holds max_active sequences of the longest length
    assert s["pool_pages"] >= s["max_active"] * s["max_pages_per_seq"]


@pytest.mark.parametrize("name", CELLS)
def test_every_length_pair_fits_the_sequence_cap(name):
    cell = spec.cell(name, BENCH)
    cap = cell.traffic["max_total_tokens"]
    s = cell.config["serving"]
    assert cap <= s["max_pages_per_seq"] * s["page_tokens"]
    for p, o in traffic.pairs(cell.traffic):
        assert p + o <= cap
    # warm-up steps every batch size with one stream of the longest
    # output, so that output must outlast max_active steps
    assert max(o for _, o in traffic.pairs(cell.traffic)) > s["max_active"]
    r = cell.config["rehearsal"]
    small = traffic.pairs(cell.traffic, r["prompt_divisor"],
                          r["output_divisor"])
    for p, o in small:
        assert p + o <= r["max_pages_per_seq"] * r["page_tokens"]
    assert max(o for _, o in small) > r["max_active"]
