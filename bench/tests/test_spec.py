"""Every cell, configuration, traffic mix and metric of BENCHMARK.json is
found by its name, and the file keeps to the benchmark's contract."""

import json
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.benchmark()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_parts_found_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    for n in (cell["name"], cell["config"], cell["traffic"]):
        assert NAME.match(n)
    conf = spec.configuration(BENCH, cell["config"])
    mix = spec.traffic(cell["traffic"])
    assert spec.reference(conf["reference"]).logits
    for key in ("rate_rps", "prompt", "output", "max_batch", "prefill_budget",
                "max_len", "check"):
        assert key in mix
    assert mix["prompt"]["min"] >= conf["serving"]["prefill_chunk"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= mix["max_len"]
    e2e = spec.metrics_for(BENCH, cell["name"], "end_to_end")
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert spec.metrics_for(BENCH, cell["name"], "per_layer")


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_work_module_found_by_the_reference_name(conf):
    body = spec.configuration(BENCH, conf["name"])
    assert callable(spec.work(body["reference"]).of)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    body = spec.configuration(BENCH, conf["name"])
    assert body["name"] == conf["name"]
    assert sorted(body["reduced"]) == sorted(conf["reduced"])
    for key in conf["reduced"]:
        assert NAME.match(key) and key in body
        assert not key.endswith(("_dim", "_rank", "_size"))
    assert any(c["config"] == conf["name"] for c in BENCH["workloads"])


METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_reader_found_by_name(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert callable(spec.reader(metric["name"]))
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert len(metric["layer"]) <= 200


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.cell(BENCH, "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.traffic("../configs/x")
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.work("no_such_block")
