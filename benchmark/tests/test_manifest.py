"""BENCHMARK.json against the files it names and the contract's own
limits on names, units and lengths."""

import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank|_size)$|^n_embd$|^n_inner$|^n_head$")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _json(ROOT, "BENCHMARK.json")


def test_keys_and_limits(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, cells // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_names_units_and_lines(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names)), group
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                if key in entry:
                    text = entry[key]
                    assert 1 <= len(text) <= 200 and "\n" not in text \
                        and "\t" not in text, (entry["name"], key)
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_cell_and_configuration_has_its_file(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    for cell in manifest["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        data = _json(BENCH, "workloads", cell["name"] + ".json")
        assert data["config"] == cell["config"] in configs
        assert data["chips"] == cell["chips"] in (1, 4)
        assert data["why"] == cell["why"]
        assert not data.get("rehearsal")
        assert NAME.match(cell["traffic"])
        pairs.add((cell["config"], cell["traffic"]))
        used.add(cell["config"])
    assert len(pairs) == len(manifest["workloads"])
    assert used == set(configs)
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for name, config in configs.items():
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert config["file"] == f"benchmark/configs/{name}.json"
        data = _json(ROOT, config["file"])
        assert data["source"] == config["source"]
        assert data["reduced"] == config["reduced"]
        assert len(config["reduced"]) <= 16
        for key in config["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert key in data["published"]


def _cells_of(metric, manifest):
    return set(metric.get("workloads")
               or [w["name"] for w in manifest["workloads"]])


def test_metrics_cover_the_cells(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    listed = {m["name"] for m in manifest["per_layer"]}
    # every metric file is a metric of the manifest
    assert {e[:-len(".json")]
            for e in os.listdir(os.path.join(BENCH, "layer_metrics"))} == listed
    for m in manifest["per_layer"]:
        data = _json(BENCH, "layer_metrics", m["name"] + ".json")
        # a metric file names no cell: the cells' own files list it
        assert set(data) == {"layer", "unit", "better", "source", "moves",
                             "what", "reader"}
        for key in ("unit", "better", "source", "layer", "moves"):
            assert data[key] == m[key], (m["name"], key)
        # the metric it moves is reported in every cell where this is
        assert m["moves"] in e2e
        assert _cells_of(m, manifest) <= _cells_of(e2e[m["moves"]], manifest)
    for cell in manifest["workloads"]:
        name = cell["name"]
        data = _json(BENCH, "workloads", name + ".json")
        reports = {n for n, m in e2e.items() if name in _cells_of(m, manifest)}
        assert reports == set(data["reports"]) | {"setup_s"}
        assert len(reports) >= 2
        assert set(data["per_layer"]) == {
            m["name"] for m in manifest["per_layer"]
            if name in _cells_of(m, manifest)
        } != set()


def test_every_workload_file_is_a_cell(manifest):
    assert {e[:-len(".json")]
            for e in os.listdir(os.path.join(BENCH, "workloads"))} == {
        w["name"] for w in manifest["workloads"]
    }


def test_no_cell_is_named_in_code(manifest):
    names = [e["name"] for g in ("configs", "workloads", "per_layer",
                                 "end_to_end")
             for e in manifest[g] if e["name"] != "setup_s"]
    for entry in os.listdir(BENCH):
        if entry.endswith(".py"):
            with open(os.path.join(BENCH, entry)) as f:
                text = f.read()
            for name in names:
                assert not re.search(
                    r"[\"']" + re.escape(name) + r"[\"']", text
                ), (entry, name)
