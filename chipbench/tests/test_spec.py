"""Cells, configurations, mixes and metrics are found from BENCHMARK.json
by name, and the file keeps to the benchmark's contract."""
import json
import re

import pytest

from chipbench import spec
from chipbench.spec import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_keys():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/configs/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = spec.find_cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
    assert cell.config["name"] == cell.config_name
    assert cell.mix["kind"] == "serve"
    assert spec.load_runner(cell.config["runner"]).run
    assert spec.load_reference(cell.config["reference"]).param_layout


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_found_by_name(metric):
    assert callable(spec.load_metric_reader(metric["name"]).read)
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")


def test_every_reader_file_is_a_metric():
    names = {m["name"] for m in BENCH["per_layer"]}
    files = {p.stem for p in (ROOT / "chipbench" / "layer_metrics").glob("*.py")}
    assert files == names


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.find_cell("no-such-cell")


def test_configs_state_source_cuts_and_departures():
    for c in BENCH["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
        assert f["departures"] and f["assumed"] and f["deployment"]
        assert all(k in f["model"] for k in f["reduced"])
        assert f["published_widths"]


def test_layout_parameter_counts_match_the_configs():
    from chipbench import weights

    for c in BENCH["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        ref = spec.load_reference(f["reference"])
        n = weights.n_params(ref.param_layout(f["model"]))
        assert n == f["parameters"], (c["name"], n)
