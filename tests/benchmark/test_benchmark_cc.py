"""The benchmark's plain CC reference (benchmark/references/gap_cc.py)
against a hand graph and against the executor, what its `agrees` refuses,
the bytes function, the driver `gap-repeat`, the entries of the cell
`gap-kron-cc.cc`, and the cell's rehearsals.

The rehearsal runs at scale 7, under `FRONTIER_CC_MIN_EDGES`, so its
submits run the dense path; the harness gives a rehearsal no graph option
but its own (`run.REHEARSAL_OPTIONS`), so it cannot force Afforest there:
the Afforest records are carried by `tests/test_afforest.py` and, for the
reader, by the record test below."""

import json
import os
import re

import numpy as np
import pytest

from rehearsal import CHECKOUT, REPO, rehearse  # puts benchmark/ on sys.path

import run as bench  # noqa: E402
from data import EdgeList, rmat_edges  # noqa: E402

CATALOG = bench.Catalog([REPO])
CELL = "gap-kron-cc.cc"
NEW_METRICS = {
    "afforest_sample_device_ms.olap": "device_trace",
    "afforest_finish_device_ms.olap": "device_trace",
    "afforest_roofline": "device_trace",
    "afforest_finish_slot_share": "program_counter",
}


@pytest.fixture(scope="module")
def reference():
    return CATALOG.plugins("references", "REFERENCES")["gap-cc"]


def test_reference_on_a_hand_graph(reference):
    # 0-1-2 by a loop and a parallel pair, 4-3 reversed, 5 alone with a
    # self loop, 7-6, 8 alone
    data = EdgeList(9, np.array([2, 1, 0, 1, 4, 5, 7], np.int32),
                    np.array([1, 2, 1, 0, 3, 5, 6], np.int32))
    want = reference.expect(data)
    np.testing.assert_array_equal(want, [0, 0, 0, 3, 3, 5, 6, 6, 8])
    assert want.dtype == np.int64
    assert reference.agrees(want.astype(np.float32), want)


def test_agrees_refuses(reference):
    data = EdgeList(*rmat_edges(8, 16, 500, 2**31 + 7))
    want = reference.expect(data)
    good = want.astype(np.float32)
    assert reference.agrees(good, want)
    moved = good.copy()
    moved[int(np.argmax(want != np.arange(data.n)))] += 1
    for bad in (moved, good + 0.5, good[:-1], np.full_like(good, np.nan),
                np.arange(data.n, dtype=np.float32)):
        assert not reference.agrees(bad, want)


def test_reference_agrees_with_afforest_on_the_generated_graph(reference):
    from janusgraph_tpu.olap.csr import csr_from_edges
    from janusgraph_tpu.olap.programs import ConnectedComponentsProgram
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor

    data = EdgeList(*rmat_edges(10, 16, 500, 500))
    ex = TPUExecutor(csr_from_edges(data.n, data.src, data.dst),
                     frontier="always")
    got = ex.run(ConnectedComponentsProgram())["component"]
    assert ex.last_run_info["path"] == "frontier"
    assert ex.last_run_info["link_passes"]["sample"] >= 1
    assert reference.agrees(got, reference.expect(data))


def test_reference_imports_nothing_from_the_package():
    text = open(os.path.join(
        REPO, "benchmark", "references", "gap_cc.py")).read()
    assert "janusgraph_tpu" not in text and "import jax" not in text


# ------------------------------------------------- the cell's own entries
def test_bytes_function_of_a_pass():
    count = CATALOG.plugins("readers", "BYTES")["cc-pass"]
    shapes = {"vertices": 1 << 20, "edges": 1 << 24}
    assert count(shapes) == 8 * (1 << 24) + 8 * (1 << 20) == 142606336


def test_finish_slot_share_reads_the_afforest_records_and_nothing_else():
    from janusgraph_tpu.observability import registry

    read = CATALOG.plugins("readers", "READERS")["record-ratio"]
    metric = json.load(open(os.path.join(
        REPO, "benchmark", "layer_metrics", "afforest_finish_slot_share.json")))

    class Run:
        shapes = {"vertices": 100, "edges": 1000}

    registry.reset()
    registry.record_run("olap", {"path": "fused", "supersteps": 20})
    assert read(Run, **metric["args"]) is None
    registry.record_run("olap", {"path": "frontier", "finish_slots": 30,
                                 "closure_slots": 1000})
    registry.record_run("olap", {"path": "frontier", "finish_slots": 10,
                                 "closure_slots": 1000})
    assert read(Run, **metric["args"]) == pytest.approx(0.02)


def test_the_cell_is_one_chip_one_traffic_and_names_its_files():
    cell = CHECKOUT.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and config["kind"] == "olap-adopted"
    assert config["structure_seed"] == 500 and config["scale"] == 20
    assert config["reduced"] == ["scale"]
    for reading in ("SCALE 27", "39.5-40.9 s", "31,403,768"):
        assert reading in config["reduced_why"]["scale"], reading
    assert {"algorithm", "labels", "verifier", "seed_rule"} <= set(
        config["assumed"])
    # the graph is gap-kron-bc's
    bc = CHECKOUT.config_file("gap-kron-bc")
    for key in ("generator", "structure_seed", "scale", "edge_factor",
                "vertices", "edges", "simple_edges", "closure_slots",
                "vertices_with_an_edge"):
        assert config[key] == bc[key], key
    assert traffic == {**traffic, "driver": "gap-repeat",
                       "program": "ConnectedComponentsProgram", "args": {},
                       "result_state": "component", "reference": "gap-cc",
                       "roots": None, "warmup_submits": 2,
                       "traced_seconds": 4.0}
    assert "gap-repeat" in config["assumed"]["seed_rule"]
    cells_of_config = [w["name"] for w in CHECKOUT.manifest["workloads"]
                       if w["config"] == "gap-kron-cc"]
    assert cells_of_config == [CELL]
    assert set(cell["end_to_end"]) == {"setup_s", "submit_p50_s"}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_layer_metric_is_this_cells_alone(name):
    entry = CHECKOUT.entry("per_layer", name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "submit_p50_s"
    assert entry["source"] == NEW_METRICS[name]
    assert entry["layer"] == "superstep kernels"
    metric = json.load(open(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".json")))
    assert name in {m["name"] for m in CHECKOUT.cell(CELL)["layer_metrics"]}
    if metric["reader"] == "roofline":
        assert metric["args"]["bytes_function"] == "cc-pass"
        assert metric["args"]["modules"] == [
            "jit_afforest_sample", "jit_afforest_finish"]
    if metric["reader"] == "trace-scope":
        assert metric["args"]["scopes"] in (
            ["afforest.sample"], ["afforest.finish"])
    if metric["source"] == "device_trace":
        assert metric["args"]["per"] == {
            "executions_of": ["jit_afforest_sample"]}


def test_the_cell_joins_the_shared_lists():
    joined = {m["name"] for m in CHECKOUT.manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined == set(NEW_METRICS) | {
        "compiles_in_window.olap", "device_idle.olap",
        "executor_host_ms.olap", "idle_unnamed_share.olap"}
    assert CELL in CHECKOUT.entry("end_to_end", "submit_p50_s")["workloads"]


def test_the_scopes_and_modules_are_the_programs():
    from janusgraph_tpu.olap import frontier

    text = open(frontier.__file__).read()
    for name in ("def afforest_sample(", "def afforest_finish(",
                 'afforest_scope("sample")', 'afforest_scope("finish")'):
        assert name in text, name


# ---------------------------------------------------------- the rehearsals
def test_rehearsal_is_correct_and_reports_the_record_metrics(tmp_path):
    line, notes, lines = rehearse(CELL, tmp_path, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == notes["counts"]["requests"] >= 1
    warm = [ln for ln in lines if "warm-up submit" in ln]
    # scale 7 lies under FRONTIER_CC_MIN_EDGES: the dense path
    assert len(warm) == 2 and all("path=fused" in ln for ln in warm)
    metrics = line["metrics"]
    assert metrics["compiles_in_window.olap"]["value"] == 0
    assert metrics["executor_host_ms.olap"]["value"] > 0
    # the device's scopes and executables are read from the device's
    # trace, and a dense run records no Afforest fields: nothing to read
    assert not set(NEW_METRICS) & set(metrics)
    assert notes["notes"]["run_info"]["path"] == "fused"


def test_every_seed_runs_the_same_labelling(tmp_path):
    digests = []
    for seed in (1, 2**31 + 11):
        (tmp_path / str(seed)).mkdir()
        _, _, lines = rehearse(CELL, tmp_path / str(seed), seed=seed)
        (said,) = [ln for ln in lines if "structure's own labelling" in ln]
        digests.append(re.search(r"digest (\w+)", said).group(1))
    assert digests[0] == digests[1]


def test_rehearsal_reports_its_end_to_end_metrics(tmp_path):
    line, _, _ = rehearse(CELL, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "submit_p50_s"}
    assert line["metrics"]["submit_p50_s"]["value"] > 0
