"""BENCHMARK.json and the data files it names: the manifest keeps to the
contract's limits, every cell's files are found by name, and what the
harness would report agrees with what the manifest declares. Every test of
entries runs over each view of `rehearsal.VIEWS`: the checkout's manifest,
and the checkout's merged with a fixture configuration's."""

import json
import os
import re

import pytest

from rehearsal import MANIFEST, POINT_CELL, REPO, VIEWS, over

import run as bench  # noqa: E402  (rehearsal puts benchmark/ on the path)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
#: Graph500's Kronecker initiator, as a configuration's file states it
RMAT = {"a": 0.57, "b": 0.19, "c": 0.19, "d": 0.05, "permuted_ids": True}


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


@pytest.mark.parametrize("view,metric", over(lambda view: view.metrics))
def test_metric_entry(view, metric):
    manifest, cells_of = view.manifest, view.cells_of
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in manifest["end_to_end"]:
        allowed |= {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        allowed |= {"layer", "moves"}
        assert LINE.match(metric["layer"])
        assert metric["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        moved = [m for m in manifest["end_to_end"]
                 if m["name"] == metric["moves"]]
        # the end-to-end metric it moves is reported wherever it is
        assert moved and set(cells_of(metric)) <= set(cells_of(moved[0]))
    assert set(metric) <= allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(cells_of(metric)) <= set(view.cells)
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("view", VIEWS, ids=lambda v: v.name)
def test_names_are_unique_and_setup_s_is_there(view):
    manifest = view.manifest
    for key in ("configs", "workloads"):
        names = [e["name"] for e in manifest[key]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in view.metrics]
    assert len(names) == len(set(names))
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    setup = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(view.cells) // 2)
    # every cell reports set-up, one more end-to-end metric and a layer metric
    for cell in view.cells:
        assert len(view.declared("end_to_end", cell)) >= 2, cell
        assert view.declared("per_layer", cell), cell


@pytest.mark.parametrize(
    "view,config", over(lambda view: view.manifest["configs"]))
def test_config_entry_and_file(view, config):
    manifest = view.manifest
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert LINE.match(config["source"]) and LINE.match(config["why"])
    assert any(config["file"].startswith(p + "/") for p in manifest["paths"])
    data = view.config_file(config["name"])
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"] and len(data["reduced"]) <= 16
    assert all(key in data and NAME.match(key) for key in data["reduced"])
    # every cut of scale is explained, and nothing else is
    assert set(data["reduced_why"]) == set(data["reduced"])
    assert all(data["reduced_why"].values())
    assert data["kind"] in bench.REHEARSAL_SCALE and data["chips"] in (1, 4)
    assert data["options_set_by_the_benchmark"] == {}
    assert data["guarantees"] and data["stands_for"] and data["assumed"]
    assert any(w["config"] == config["name"] for w in manifest["workloads"])
    # the shapes of the source are never cut, only the scale is: asserted
    # source by source, on the files that state that source's shapes (a PR
    # that brings another source adds its asserts as a test file of its own)
    if "graph500" in config["source"].lower():
        assert "generator" in data  # no slipping out by leaving it out
    if "generator" in data:  # Graph500's Kronecker / R-MAT generator
        assert data["generator"] == RMAT
        assert data["edge_factor"] == 16
        assert data["vertices"] == 1 << data["scale"]
        assert data["edges"] == data["vertices"] * data["edge_factor"]


@pytest.mark.parametrize("view,cell", over(lambda v: v.manifest["workloads"]))
def test_cell_files_are_found_by_name(view, cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert LINE.match(cell["why"]) and cell["chips"] in (1, 4)
    catalog = view.catalog
    loaded = view.cell(cell["name"])
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
    # for it, no less and no more
    readers = catalog.plugins("readers", "READERS")
    reported = {m["name"] for m in loaded["layer_metrics"]}
    assert reported == view.declared("per_layer", cell["name"]) and reported
    assert all(m["reader"] in readers for m in loaded["layer_metrics"])
    assert set(loaded["end_to_end"]) == view.declared(
        "end_to_end", cell["name"])
    assert set(loaded["end_to_end"]) > {"setup_s"}


@pytest.mark.parametrize(
    "view,path", over(lambda view: view.layer_files(),
                      ids=lambda p: os.path.basename(p)[:-5]))
def test_layer_metric_file_agrees_with_the_manifest(view, path):
    metric = json.load(open(path))
    assert os.path.basename(path) == metric["name"] + ".json"
    entry = [m for m in view.manifest["per_layer"]
             if m["name"] == metric["name"]]
    assert entry, "a layer metric file the manifest does not declare"
    for key in ("unit", "better", "source", "layer", "moves"):
        assert metric[key] == entry[0][key], key
    assert metric["reader"] in view.catalog.plugins("readers", "READERS")
    # every cell the entry lists is of a kind the file's reader reads: so
    # the cells that report the metric are the cells the manifest lists
    by_kind = {cell for cell in view.cells
               if view.kind_of(cell) in metric["kinds"]}
    assert set(view.cells_of(entry[0])) <= by_kind
    for cell in view.cells:
        loaded = {m["name"] for m in view.cell(cell)["layer_metrics"]}
        assert (metric["name"] in loaded) == bench.reports(entry[0], cell)


def test_files_under_paths_are_named_from_the_admitted_characters():
    for base in MANIFEST["paths"]:
        for folder, dirs, files in os.walk(os.path.join(REPO, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_a_cell_added_as_data_alone_is_found(tmp_path):
    """The fixture cell lives with the tests: its entry, one traffic file
    and the re-declared entries of the metrics whose lists it joins (as a
    PR's manifest would hold them), no edit to a file of the benchmark."""
    catalog = bench.Catalog([POINT_CELL, REPO])
    cell = catalog.cell("g500-served.point")
    assert cell["traffic"]["clients"] == 8
    assert cell["config"]["name"] == "g500-served"
    listless = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    assert {m["name"] for m in cell["layer_metrics"]} == listless | {
        "server_request_ms", "spilled_share", "device_idle.served"}
    assert set(cell["end_to_end"]) == {"setup_s", "request_p50_ms"}
    # the checkout's cells report what they did: a re-declared entry
    # keeps their names in its list
    for name in (w["name"] for w in MANIFEST["workloads"]):
        assert ({m["name"] for m in catalog.cell(name)["layer_metrics"]}
                == {m["name"] for m in
                    bench.Catalog([REPO]).cell(name)["layer_metrics"]})
    with pytest.raises(bench.BenchmarkError):
        bench.Catalog([REPO]).cell("g500-served.point")


def test_a_cell_no_list_names_reports_the_listless_metrics_alone(tmp_path):
    """The one rule (`Catalog.cell`): a cell of a known kind that no
    `workloads` list names reports the metrics without a list, nothing
    else; the kind alone never makes a cell report a metric."""
    root = tmp_path / "root"
    root.mkdir()
    cell = dict(MANIFEST["workloads"][0], name="unlisted.cell")
    (root / "BENCHMARK.json").write_text(json.dumps({"workloads": [cell]}))
    loaded = bench.Catalog([str(root), REPO]).cell("unlisted.cell")
    listless = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    assert listless and len(listless) < len(MANIFEST["per_layer"])
    assert {m["name"] for m in loaded["layer_metrics"]} == listless
    assert set(loaded["end_to_end"]) == {"setup_s"}
