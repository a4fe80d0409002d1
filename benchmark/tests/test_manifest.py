"""BENCHMARK.json against the contract's mechanical limits, and against
the data files the harness finds by name."""

import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFESTS = [os.path.join(REPO, "BENCHMARK.json"),
             os.path.join(BENCH, "rehearsal", "BENCHMARK.json")]


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(params=MANIFESTS, ids=["benchmark", "rehearsal"])
def manifest(request):
    return request.param, _load(request.param)


def test_keys_and_limits(manifest):
    path, m = manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(path) <= 64 * 1024
    assert m["paths"] == ["benchmark"]
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    # the full check has to fit with 24 cells
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(m["workloads"]) <= 24 and 1 <= len(m["configs"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128


def test_names_units_and_entries(manifest):
    _, m = manifest
    metrics = m["end_to_end"] + m["per_layer"]
    for group in (m["configs"], m["workloads"], metrics):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= e["bound"] <= 0.25
    for e in m["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert e["source"] in SOURCES
    for e in metrics:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    for text in ([c["why"] for c in m["configs"]]
                 + [c["source"] for c in m["configs"]]
                 + [w["why"] for w in m["workloads"]]
                 + [e["layer"] for e in m["per_layer"]] + m["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_cells_configs_and_metrics_fit_together(manifest):
    path, m = manifest
    root = os.path.dirname(path)
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    assert {w["config"] for w in cells.values()} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in cells.values()) <= \
        max(1, len(cells) // 2)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        data = _load(os.path.join(root, c["file"]))
        assert set(c["reduced"]) == set(data["reduced"])
        assert {"source", "sidecar", "chips", "route", "guarantees",
                "assumed", "reduced"} <= set(data)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def cells_of(metric):
        got = metric.get("workloads", list(cells))
        assert got and set(got) <= set(cells)
        return set(got)

    for name, cell in cells.items():
        assert sum(name in cells_of(e) for e in m["end_to_end"]) >= 2
        assert any(name in cells_of(e) for e in m["per_layer"])
        assert os.path.isfile(os.path.join(
            BENCH, "drivers",
            _mix(root, configs[cell["config"]], cell)["driver"] + ".py"))
    for e in m["per_layer"]:
        assert e["moves"] in e2e
        assert cells_of(e) <= cells_of(e2e[e["moves"]])


def _mix(root, config, cell):
    beside = os.path.dirname(os.path.dirname(
        os.path.join(root, config["file"])))
    for d in (beside, BENCH):
        p = os.path.join(d, "traffic", cell["traffic"] + ".json")
        if os.path.isfile(p):
            return _load(p)
    raise AssertionError(f"no traffic file for {cell['name']}")


def test_every_layer_metric_has_its_file_and_they_agree(manifest):
    _, m = manifest
    layers = set()
    for e in m["per_layer"]:
        layer = _load(os.path.join(BENCH, "layers", e["name"] + ".json"))
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert layer[key] == e[key], (e["name"], key)
        assert os.path.isfile(os.path.join(
            BENCH, "readers", layer["reader"]["kind"] + ".py"))
        layers.add(e["layer"])
    perf = open(os.path.join(REPO, "PERF.md"), encoding="utf-8").read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in (".work", "__pycache__")]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(base, f), REPO))
