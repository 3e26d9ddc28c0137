"""The benchmark's plain LCC reference (benchmark/references/lcc.py) against
a triple loop, what its `agrees` refuses, the bytes function and the
entries of the cell `graphalytics-lcc.lcc`, and the cell's rehearsal."""

import itertools
import json
import os

import numpy as np
import pytest

from rehearsal import CHECKOUT, REPO, rehearse  # puts benchmark/ on sys.path

import run as bench  # noqa: E402
from data import EdgeList, rmat_edges  # noqa: E402

CATALOG = bench.Catalog([REPO])
CELL = "graphalytics-lcc.lcc"
NEW_METRICS = {
    "intersect_device_ms.olap": "device_trace",
    "credit_device_ms.olap": "device_trace",
    "candidates_per_edge": "program_counter",
    "probe_slots_per_candidate": "program_counter",
    "lcc_pass_roofline": "device_trace",
}


@pytest.fixture(scope="module")
def reference():
    return CATALOG.plugins("references", "REFERENCES")["graphalytics-lcc"]


def triple_loop(n, src, dst):
    """T(v) and d(v) by the definition: every unordered pair of distinct
    neighbours of v, tested for an edge in either direction."""
    joined = np.zeros((n, n), bool)
    for s, d in zip(src, dst):
        if s != d:
            joined[s, d] = joined[d, s] = True
    T = np.zeros(n, np.int64)
    for v in range(n):
        for u, w in itertools.combinations(np.flatnonzero(joined[v]), 2):
            T[v] += joined[u, w]
    return T, joined.sum(axis=1)


def small_graph(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 13))
    m = int(rng.integers(0, 4 * n + 1))
    # duplicates, both directions and self loops come by themselves
    return EdgeList(n, rng.integers(0, n, m).astype(np.int32),
                    rng.integers(0, n, m).astype(np.int32))


@pytest.mark.parametrize("seed", range(24))
def test_reference_against_a_triple_loop(reference, seed):
    data = small_graph(seed)
    want = reference.expect(data)
    T, d = triple_loop(data.n, data.src, data.dst)
    np.testing.assert_array_equal(want["triangles"], T)
    np.testing.assert_array_equal(want["degree"], d)
    assert want["triangles"].dtype == np.int64
    assert want["lcc64"].dtype == np.float64
    pairs = d * (d - 1) / 2
    np.testing.assert_array_equal(
        want["lcc64"], np.where(d >= 2, T / np.maximum(pairs, 1), 0.0))
    assert reference.agrees(want["lcc64"].astype(np.float32), want)


@pytest.fixture(scope="module")
def generated(reference):
    """The generated graph at scale 8 under a large seed, its expectation
    and the float32 answer the program owes."""
    data = EdgeList(*rmat_edges(8, 16, 500, 2**31 + 7))
    want = reference.expect(data)
    d, T = want["degree"], want["triangles"]
    pairs = (d * (d - 1) // 2)
    good = np.where(pairs > 0, T.astype(np.float32)
                    / np.maximum(pairs, 1).astype(np.float32),
                    np.float32(0.0)).astype(np.float32)
    assert (d == 0).any() and (d == 1).any() and (T > 0).any()
    return data, want, good


def _one_triangle_missed(good, want):
    v = int(np.argmax(want["triangles"] > 0))
    d = int(want["degree"][v])
    bad = good.copy()
    bad[v] = np.float32(want["triangles"][v] - 1) / np.float32(
        d * (d - 1) // 2)
    return bad


def _bfloat16_rounded(good, want):
    import ml_dtypes

    return good.astype(ml_dtypes.bfloat16).astype(np.float32)


def _with(index_of, value):
    def spoil(good, want):
        bad = good.copy()
        bad[index_of(want)] = value
        return bad
    return spoil


SPOILED = {
    "one-triangle-missed-at-one-vertex": _one_triangle_missed,
    "bfloat16-rounded": _bfloat16_rounded,
    "a-nan": _with(lambda want: 0, np.nan),
    "an-infinity": _with(lambda want: 0, np.inf),
    "nonzero-below-two-neighbours": _with(
        lambda want: int(np.argmax(want["degree"] == 1)), np.float32(1e-30)),
    "nonzero-at-an-isolated-vertex": _with(
        lambda want: int(np.argmax(want["degree"] == 0)), np.float32(0.5)),
    "wrong-shape": lambda good, want: good[:-1],
    "float64-not-float32": lambda good, want: good.astype(np.float64),
    "every-answer-zero": lambda good, want: np.zeros_like(good),
}


@pytest.mark.parametrize("name", sorted(SPOILED))
def test_agrees_refuses(reference, generated, name):
    _, want, good = generated
    assert reference.agrees(good, want)
    assert not reference.agrees(SPOILED[name](good, want), want)


def test_reference_agrees_with_the_executor(reference, generated):
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta
    from janusgraph_tpu.olap.csr import csr_from_edges
    from janusgraph_tpu.olap.programs import LCCProgram

    data, want, good = generated
    g = open_graph({"storage.backend": "inmemory"})
    try:
        csr = csr_from_edges(data.n, data.src, data.dst)
        delta.get_snapshot(g).adopt(csr, g.backend.mutation_epoch())
        result = g.compute().program(LCCProgram()).submit()
    finally:
        g.close()
    np.testing.assert_array_equal(result.states["lcc"], good)
    np.testing.assert_array_equal(
        result.states["triangles"], want["triangles"])


def test_reference_imports_nothing_from_the_package():
    text = open(os.path.join(
        REPO, "benchmark", "references", "lcc.py")).read()
    assert "janusgraph_tpu" not in text and "import jax" not in text


# ------------------------------------------------- the cell's own entries
def test_bytes_function_of_the_pass():
    count = CATALOG.plugins("readers", "BYTES")["lcc-pass"]
    assert count({"vertices": 1 << 20, "edges": 16 << 20}) == (
        8 * (16 << 20) + 8 * (1 << 20))


def test_the_cell_is_one_chip_one_traffic_and_names_its_files():
    cell = CHECKOUT.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and config["kind"] == "olap-adopted"
    assert config["structure_seed"] == 500 and config["scale"] in range(16, 21)
    assert config["reduced"] == ["scale"]
    for reading in ("3.2 s", "scale 20", "candidate", "peak"):
        assert reading in config["reduced_why"]["scale"], reading
    assert traffic == {**traffic, "driver": "submit-loop",
                       "program": "LCCProgram", "args": {},
                       "result_state": "lcc", "roots": None,
                       "reference": "graphalytics-lcc",
                       "warmup_submits": 1, "traced_seconds": 4.0}
    cells_of_config = [w["name"] for w in CHECKOUT.manifest["workloads"]
                       if w["config"] == "graphalytics-lcc"]
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
        assert metric["args"]["paths"] == ["intersect"]
        assert metric["args"]["modules"] == ["jit_lcc_pass"]
        assert metric["args"]["per"] == {
            "executions_of": ["jit_lcc_pass"]}
    if metric["reader"] == "trace-scope":
        assert metric["args"]["scopes"] in (
            ["lcc.intersect"], ["lcc.credit"])


def test_the_cell_joins_the_shared_lists_and_not_the_superstep_one():
    joined = {m["name"] for m in CHECKOUT.manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined == set(NEW_METRICS) | {
        "compiles_in_window.olap", "device_idle.olap",
        "executor_host_ms.olap", "idle_unnamed_share.olap"}
    assert "superstep_device_ms.olap" not in joined


# ---------------------------------------------------------- the rehearsals
def test_rehearsal_is_correct_and_reports_the_record_metrics(tmp_path):
    line, notes, lines = rehearse(CELL, tmp_path, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == notes["counts"]["requests"] >= 1
    warm = [ln for ln in lines if "warm-up submit" in ln]
    assert len(warm) == 1 and "path=intersect" in warm[0]
    metrics = line["metrics"]
    assert metrics["candidates_per_edge"]["value"] > 0
    assert metrics["probe_slots_per_candidate"]["value"] > 0
    assert metrics["compiles_in_window.olap"]["value"] == 0
    assert metrics["executor_host_ms.olap"]["value"] > 0
    # the device's scopes and executables are read from the device's
    # trace: none on the CPU
    assert not {n for n, source in NEW_METRICS.items()
                if source == "device_trace"} & set(metrics)
    assert notes["counts"]["supersteps_traced"] >= 1
    info = notes["notes"]["run_info"]
    assert info["path"] == "intersect" and info["supersteps"] == 1


def test_rehearsal_reports_its_end_to_end_metrics(tmp_path):
    line, _, _ = rehearse(CELL, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "submit_p50_s"}
    assert line["metrics"]["submit_p50_s"]["value"] > 0
