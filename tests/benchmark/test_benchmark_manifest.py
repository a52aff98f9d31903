"""BENCHMARK.json against the contract's limits that can be read off the
file, and against the data files its names point at."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def man():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_size(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    assert man["paths"] == ["benchmark", "tests/benchmark"]
    assert all(PATH.match(p) and ".." not in p for p in man["paths"])
    assert len(man["command"]) <= 32
    for word in man["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert word.split("/")[0] in man["paths"]


def test_names_units_and_one_line_texts(man):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in man[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry and group != "end_to_end" and \
                        not (group == "per_layer" and key == "source"):
                    text = entry[key]
                    assert 1 <= len(text) <= 200 and "\n" not in text \
                        and "\t" not in text, (entry["name"], key)
    assert len(names) == len(set(names))
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in man["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in man["configs"]:
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])


def test_entries_have_just_the_contracts_keys(man):
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")


def test_cells_configs_and_chips(man):
    configs = {c["name"]: c for c in man["configs"]}
    assert len({c["source"] for c in configs.values()}) == len(configs)
    assert len({c["file"] for c in configs.values()}) == len(configs)
    used = set()
    pairs = set()
    for w in man["workloads"]:
        assert w["chips"] in (1, 4)
        assert w["config"] in configs
        used.add(w["config"])
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "traffic", w["traffic"] + ".json"))
    assert used == set(configs) and len(pairs) == len(man["workloads"])
    four = [w for w in man["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(man["workloads"]) // 2)
    for c in configs.values():
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(REPO, c["file"])) as f:
            data = json.load(f)
        assert data["reduced"] == c["reduced"]
        assert data["name"] == c["name"]


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(man):
    cells = {w["name"] for w in man["workloads"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] == 0.25
    for cell in cells:
        mine = {n for n, m in e2e.items() if cell in m.get("workloads", cells)}
        assert len(mine - {"setup_s"}) >= 1, cell
        layers = [m for m in man["per_layer"] if cell in m.get("workloads", ())]
        assert layers, cell
        for m in layers:
            assert m["moves"] in mine, (cell, m["name"])
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", ())) <= cells


def test_each_per_layer_metric_has_a_file_naming_a_reader_that_exists(man):
    layers = {}
    for m in man["per_layer"]:
        with open(os.path.join(REPO, "benchmark", "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "readers", spec["reader"] + ".py")), m["name"]
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"
    assert not any("mfu" in m["name"] for m in man["per_layer"])  # no model
    # PERF.md's list of layers uses the same names, letter for letter
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"| {layer} |" in perf, layer


def test_files_under_paths_are_named_from_the_allowed_characters(man):
    for path in man["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, name), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
