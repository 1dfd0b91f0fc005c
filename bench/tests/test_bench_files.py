"""BENCHMARK.json and the files it names: every configuration, traffic mix,
per-layer reader, reference and cell kind loads by name, and the entries
agree with each other."""
from __future__ import annotations

import json

import pytest

import harness
import peaks
from conftest import bench_with_serve

BENCH = harness.load_benchmark()
WITH_SERVE = bench_with_serve()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_loads(cfg):
    c = harness.load_config(BENCH, cfg["name"])
    assert c["name"] == cfg["name"]
    for key in cfg["reduced"]:
        assert key in c and c[f"published_{key}"] != c[key]
    harness.load_ref(c["arch"])
    assert "train" in c["limits"]


@pytest.mark.parametrize("cell", WITH_SERVE["workloads"],
                         ids=lambda w: w["name"])
def test_cell_files_load(cell):
    mix = harness.load_traffic(cell["traffic"])
    harness.load_kind(mix["kind"])
    cfg = harness.load_config(WITH_SERVE, cell["config"])
    assert mix["kind"] in cfg["limits"]
    e2e = [m["name"]
           for m in harness.cell_metrics(WITH_SERVE, cell["name"], False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(WITH_SERVE, cell["name"], True)


@pytest.mark.parametrize("m", WITH_SERVE["per_layer"], ids=lambda m: m["name"])
def test_metric_reader(m):
    reader = harness.load_metric(m["name"])
    assert reader.LAYER == m["layer"]
    assert reader.read({}) is None
    moves = harness.find(WITH_SERVE["end_to_end"], m["moves"], "metric")
    for cell in m["workloads"]:
        assert cell in moves.get("workloads", [cell])


def test_names_unique():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_result_line_puts_checks_last(capsys):
    harness.print_result({"correct": True, "attempted": 1, "failed": 0,
                          "checks": {"gap": harness.check(0.5, 1.0)},
                          "metrics": {}, "device": {}})
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1] == "check gap: 0.5 limit 1.0"
