"""BENCHMARK.json and the data files it names: the manifest keeps to the
contract's limits, every cell's files are found by name, and what the
harness would report agrees with what the manifest declares."""

import glob
import json
import os
import re

import pytest

from rehearsal import REPO  # puts benchmark/ on sys.path

import run as bench  # noqa: E402

MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
LAYER_FILES = sorted(glob.glob(
    os.path.join(REPO, "benchmark", "layer_metrics", "*.json")))


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))


def test_check_fits_the_chip_time_with_every_cell_the_contract_admits():
    runs = 2 + 14 * 24
    total = runs * (MANIFEST["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in MANIFEST["end_to_end"]:
        allowed |= {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        allowed |= {"layer", "moves"}
        assert LINE.match(metric["layer"])
        assert metric["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        moved = [m for m in MANIFEST["end_to_end"]
                 if m["name"] == metric["moves"]]
        # the end-to-end metric it moves is reported wherever it is
        assert moved and set(cells_of(metric)) <= set(cells_of(moved[0]))
    assert set(metric) <= allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(cells_of(metric)) <= set(CELLS)
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_names_are_unique_and_setup_s_is_there():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[key]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    setup = [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert LINE.match(config["source"]) and LINE.match(config["why"])
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    data = json.load(open(os.path.join(REPO, config["file"])))
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert all(key in data and NAME.match(key) for key in data["reduced"])
    # the shapes of the source are never cut: only the scale is
    assert data["edge_factor"] == 16
    assert data["generator"] == {
        "a": 0.57, "b": 0.19, "c": 0.19, "d": 0.05, "permuted_ids": True}
    assert data["vertices"] == 1 << data["scale"]
    assert data["edges"] == data["vertices"] * data["edge_factor"]
    assert data["options_set_by_the_benchmark"] == {}
    assert data["guarantees"] and data["stands_for"] and data["assumed"]
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_files_are_found_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert LINE.match(cell["why"]) and cell["chips"] in (1, 4)
    catalog = bench.Catalog([REPO])
    loaded = catalog.cell(cell["name"])
    assert loaded["config"]["name"] == cell["config"]
    assert loaded["traffic"]["name"] == cell["traffic"]
    assert loaded["config"]["chips"] == cell["chips"]
    driver = catalog.driver(loaded["traffic"]["driver"])
    for hook in ("setup", "measure", "check", "teardown"):
        assert callable(getattr(driver, hook))
    references = catalog.plugins("references", "REFERENCES")
    wanted = [loaded["traffic"].get("reference")] + [
        t["reference"] for t in loaded["traffic"].get("templates", [])]
    assert all(r in references for r in wanted if r)
    # what the harness reports in this cell is what the manifest declares
    readers = catalog.plugins("readers", "READERS")
    reported = {m["name"] for m in loaded["layer_metrics"]}
    declared = {m["name"] for m in MANIFEST["per_layer"]
                if cell["name"] in cells_of(m)}
    assert declared <= reported and reported
    assert all(m["reader"] in readers for m in loaded["layer_metrics"])
    assert any(cell["name"] in cells_of(m) and m["name"] != "setup_s"
               for m in MANIFEST["end_to_end"])


@pytest.mark.parametrize("path", LAYER_FILES,
                         ids=lambda p: os.path.basename(p)[:-5])
def test_layer_metric_file_agrees_with_the_manifest(path):
    metric = json.load(open(path))
    assert os.path.basename(path) == metric["name"] + ".json"
    entry = [m for m in MANIFEST["per_layer"] if m["name"] == metric["name"]]
    assert entry, "a layer metric file the manifest does not declare"
    for key in ("unit", "better", "source", "layer", "moves"):
        assert metric[key] == entry[0][key], key
    kinds = {c["name"]: json.load(open(os.path.join(REPO, c["file"])))["kind"]
             for c in MANIFEST["configs"]}
    by_kind = {w["name"] for w in MANIFEST["workloads"]
               if kinds[w["config"]] in metric["kinds"]}
    assert set(cells_of(entry[0])) <= by_kind


def test_files_under_paths_are_named_from_the_admitted_characters():
    for base in MANIFEST["paths"]:
        for folder, dirs, files in os.walk(os.path.join(REPO, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_a_cell_added_as_data_alone_is_found(tmp_path):
    """The fixture cell lives with the tests: one entry, one traffic file,
    no edit to a file of the benchmark."""
    extra = os.path.join(os.path.dirname(__file__), "point_cell")
    cell = bench.Catalog([extra, REPO]).cell("g500-served.point")
    assert cell["traffic"]["clients"] == 8
    assert cell["config"]["name"] == "g500-served"
    assert {m["name"] for m in cell["layer_metrics"]} >= {
        "server_request_ms", "spilled_share", "device_idle.served"}
    with pytest.raises(bench.BenchmarkError):
        bench.Catalog([REPO]).cell("g500-served.point")
