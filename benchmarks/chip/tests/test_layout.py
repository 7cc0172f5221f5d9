"""BENCHMARK.json and the files it names hold together (CPU, no chip).

    python -m pytest benchmarks/chip/tests
"""

import json
import math
import os
import re

import pytest

from benchmarks.chip import layout

BENCH = layout.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    size = os.path.getsize(os.path.join(layout.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    for n in names:
        assert NAME.match(n), n
    assert len(set(CELLS)) == len(CELLS)
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        for c in m.get("workloads", []):
            assert c in CELLS, (m["name"], c)


def test_bounds():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_workload_file_names_known_pieces(cell):
    entry = layout.cell(cell)
    work = layout.workload(cell)
    assert work["config"] == entry["config"]
    assert work["traffic_name"] == entry["traffic"]
    layout.driver(work["kind"])
    with pytest.raises(KeyError):
        layout.driver("no-such-kind")
    cfg = layout.config(entry["config"])
    assert cfg["name"] == entry["config"]
    assert entry["chips"] in (1, 4)
    assert 1 <= len(entry["why"]) <= 200
    for k, v in work["limits"].items():
        assert isinstance(v, (int, float)) and math.isfinite(v), (k, v)
    assert float(work["trace_seconds"]) > 0


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_enough(cell):
    e2e = {m["name"] for m in layout.metrics_for(cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per = layout.metrics_for(cell, "per_layer")
    assert per
    for m in per:
        assert m["moves"] in e2e, (cell, m["name"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader(metric):
    mod = layout.metric_reader(metric)
    assert callable(mod.read)
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert m["layer"] and "\n" not in m["layer"]


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_files(config):
    c = next(x for x in BENCH["configs"] if x["name"] == config)
    assert c["file"] == f"benchmarks/chip/configs/{config}.json"
    with open(os.path.join(layout.ROOT, c["file"])) as f:
        data = json.load(f)
    assert data["name"] == config
    assert sorted(data["reduced"]) == sorted(c["reduced"])
    assert callable(layout.reference(config).__dict__.get("evaluate")
                    or layout.reference(config).__dict__.get("logits"))
    assert any(w["config"] == config for w in BENCH["workloads"])


def test_peaks_table():
    p = layout.peaks("TPU v5 lite")
    assert p["ops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        layout.peaks("cpu")
