"""Every entry of BENCHMARK.json is found by name in files of its own, and
the file keeps to the rules of its format."""

import json
import os
import re

import pytest

from bench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells(bench):
    return [c["name"] for c in bench["workloads"]]


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_and_units(bench):
    groups = [bench["configs"], bench["workloads"],
              bench["end_to_end"] + bench["per_layer"]]
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        for n in names:
            assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_entry_keys(bench, kind):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[kind]
    for e in bench[kind]:
        assert set(e) <= allowed, e
        for key in ("why", "layer") + (("source",) if kind == "configs"
                                       else ()):
            if key in e:
                assert 1 <= len(e[key]) <= 200, e
                assert "\n" not in e[key] and "\t" not in e[key]


def test_every_config_is_found_and_used(bench):
    used = {c["config"] for c in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert os.path.exists(os.path.join(ROOT, cfg["reference"]))


def test_every_cell_finds_its_files(bench):
    pairs = set()
    for c in bench["workloads"]:
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200
        assert (c["config"], c["traffic"]) not in pairs
        pairs.add((c["config"], c["traffic"]))
        cell, _, cfg, traffic = bench_run.find_cell(bench, c["name"])
        assert cell is c and cfg["chips"] == c["chips"]
        assert os.path.exists(os.path.join(
            ROOT, "bench", "drivers", traffic["driver"] + ".py"))
    four = sum(c["chips"] == 4 for c in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_per_layer_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        path = os.path.join(ROOT, "bench", "metrics", m["name"] + ".py")
        reader = bench_run.load_module(path, "m_" + m["name"].replace(".", "_"))
        assert callable(reader.read)


def test_each_metric_moves_one_end_to_end_metric_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells(bench)):
            assert cell in cells(bench)
            assert cell in moved.get("workloads", cells(bench)), (m, cell)


def test_every_cell_reports_enough(bench):
    for cell in cells(bench):
        e2e = [m["name"] for m in bench_run.metrics_for(bench["end_to_end"],
                                                        cell)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert bench_run.metrics_for(bench["per_layer"], cell)


def test_layers_are_named_alike(bench):
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"`{layer}`" in perf, layer
