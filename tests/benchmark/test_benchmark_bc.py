"""The benchmark's plain Brandes reference (benchmark/references/bc.py)
against a count of every shortest path, what its `agrees` refuses, the
bytes function and the entries of the cell `gap-kron-bc.bc`, and the
cell's rehearsal."""

import itertools
import json
import os

import numpy as np
import pytest

from rehearsal import CHECKOUT, REPO, rehearse  # puts benchmark/ on sys.path

import run as bench  # noqa: E402
from data import EdgeList, rmat_edges  # noqa: E402

CATALOG = bench.Catalog([REPO])
CELL = "gap-kron-bc.bc"
NEW_METRICS = {
    "brandes_forward_device_ms.olap": "device_trace",
    "brandes_backward_device_ms.olap": "device_trace",
    "brandes_trial_roofline": "device_trace",
}


@pytest.fixture(scope="module")
def reference():
    return CATALOG.plugins("references", "REFERENCES")["gap-bc"]


def every_path(n, src, dst, sources):
    """Betweenness by the definition: for each source s and target t, the
    share of the shortest s-t paths through v, from distances and path
    counts of every pair (a Floyd-Warshall closure and its counts)."""
    joined = np.zeros((n, n), bool)
    for a, b in zip(src, dst):
        if a != b:
            joined[a, b] = joined[b, a] = True
    dist = np.where(joined, 1.0, np.inf)
    np.fill_diagonal(dist, 0.0)
    for k in range(n):
        dist = np.minimum(dist, dist[:, k:k + 1] + dist[k:k + 1, :])
    # sigma[s, t]: walks of length dist[s, t] from s to t
    sigma = np.eye(n)
    power = np.eye(n)
    for length in range(1, n):
        power = power @ joined
        sigma += np.where(dist == length, power, 0.0)
    scores = np.zeros(n)
    for s in sources:
        for t, v in itertools.product(range(n), range(n)):
            if len({s, t, v}) < 3 or not np.isfinite(dist[s, t]):
                continue
            if dist[s, v] + dist[v, t] == dist[s, t]:
                scores[v] += sigma[s, v] * sigma[v, t] / sigma[s, t]
    return scores


def small_graph(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 13))
    m = int(rng.integers(0, 3 * n + 1))
    # duplicates, both directions and self loops come by themselves
    return EdgeList(n, rng.integers(0, n, m).astype(np.int32),
                    rng.integers(0, n, m).astype(np.int32))


@pytest.mark.parametrize("seed", range(24))
def test_reference_against_every_shortest_path(reference, seed):
    data = small_graph(seed)
    sources = sorted(set(np.random.default_rng(seed).integers(
        0, data.n, 3).tolist()))
    want = reference.expect(data, sources=sources)
    np.testing.assert_allclose(
        want["betweenness"], every_path(data.n, data.src, data.dst, sources),
        rtol=1e-12, atol=1e-12)
    assert want["betweenness"].dtype == np.float64
    assert want["depth"].shape == (len(sources), data.n)
    assert reference.agrees(want["betweenness"].astype(np.float32), want)


@pytest.fixture(scope="module")
def generated(reference):
    """The generated graph at scale 12 under a large seed (path counts
    pass 2^8 there: PERF.md section 6, PR 38), the trials'
    sources as the driver draws them, the expectation and the float32
    answer the program owes (the float64 sums rounded once)."""
    driver = CATALOG.driver("gap-trials")
    config = CHECKOUT.config_file("gap-kron-bc")
    data = EdgeList(*rmat_edges(12, 16, 500, 2**31 + 7))
    spec = CHECKOUT.traffic_of(CELL)["sources"]
    trials = driver.trial_sources(config, data, spec)
    want = reference.expect(data, sources=trials[0])
    good = want["betweenness"].astype(np.float32)
    assert (want["betweenness"] == 0).any() and (want["betweenness"] > 0).any()
    return data, trials, want, good


def test_trials_are_the_structures_vertices_under_every_seed():
    driver = CATALOG.driver("gap-trials")
    config = CHECKOUT.config_file("gap-kron-bc")
    spec = CHECKOUT.traffic_of(CELL)["sources"]
    drawn = []
    for seed in (1, 2**31 + 7):
        n, src, dst, perm = rmat_edges(8, 16, 500, seed)
        trials = driver.trial_sources(config, EdgeList(n, src, dst, perm),
                                      spec)
        assert [len(t) for t in trials] == [4, 4]
        inverse = np.argsort(perm)
        drawn.append([int(inverse[v]) for t in trials for v in t])
    assert drawn[0] == drawn[1] and len(set(drawn[0])) == 8


def _one_vertex_off(good, want):
    v = int(np.argmax(want["betweenness"]))
    bad = good.copy()
    bad[v] = np.float32(want["betweenness"][v] * (1 + 2e-4))
    return bad


def _bfloat16_path_counts(good, want, data, trials, reference):
    import ml_dtypes

    return reference.expect(
        data, sources=trials[0], sigma_dtype=ml_dtypes.bfloat16,
    )["betweenness"].astype(np.float32)


def _with(index_of, value):
    def spoil(good, want):
        bad = good.copy()
        bad[index_of(want)] = value
        return bad
    return spoil


SPOILED = {
    "one-vertex-off-by-2e-4": _one_vertex_off,
    "a-nan": _with(lambda want: 0, np.nan),
    "an-infinity": _with(lambda want: 0, np.inf),
    "nonzero-where-the-reference-is-zero": _with(
        lambda want: int(np.argmax(want["betweenness"] == 0)),
        np.float32(1e-30)),
    "zero-where-the-reference-is-not": _with(
        lambda want: int(np.argmax(want["betweenness"] > 0)), 0.0),
    "wrong-shape": lambda good, want: good[:-1],
    "float64-not-float32": lambda good, want: good.astype(np.float64),
    "every-answer-zero": lambda good, want: np.zeros_like(good),
}


@pytest.mark.parametrize("name", sorted(SPOILED))
def test_agrees_refuses(reference, generated, name):
    _, _, want, good = generated
    assert reference.agrees(good, want)
    assert not reference.agrees(SPOILED[name](good, want), want)


def test_bfloat16_path_counts_are_refused(reference, generated):
    """The precision below the configuration's: path counts kept in
    bfloat16 miss 1e-4 relative on the generated graph (below scale 12
    its path counts stay under 2^8 and bfloat16 holds them exactly)."""
    data, trials, want, good = generated
    coarse = _bfloat16_path_counts(good, want, data, trials, reference)
    assert not reference.agrees(coarse, want)
    assert reference.errors(coarse, want)[0] > 10 * 1e-4


def test_reference_agrees_with_the_executor(reference, generated):
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta
    from janusgraph_tpu.olap.csr import csr_from_edges
    from janusgraph_tpu.olap.programs import BetweennessCentralityProgram

    data, trials, want, _ = generated
    g = open_graph({"storage.backend": "inmemory"})
    try:
        csr = csr_from_edges(data.n, data.src, data.dst)
        delta.get_snapshot(g).adopt(csr, g.backend.mutation_epoch())
        result = g.compute().program(
            BetweennessCentralityProgram(trials[0])).submit()
    finally:
        g.close()
    assert reference.agrees(result.states["betweenness"], want)
    error, zeros = reference.errors(result.states["betweenness"], want)
    assert error < 1e-5 and zeros == int((want["betweenness"] == 0).sum())


def test_reference_imports_nothing_from_the_package():
    text = open(os.path.join(
        REPO, "benchmark", "references", "bc.py")).read()
    assert "janusgraph_tpu" not in text and "import jax" not in text


# ------------------------------------------------- the cell's own entries
def test_bytes_function_of_a_trial():
    count = CATALOG.plugins("readers", "BYTES")["brandes-trial"]
    shapes = {"vertices": 1 << 20, "closure_slots": 31403768, "sources": 4}
    assert count(shapes) == 8 * 31403768 + 12 * (1 << 20) * 4


def test_the_cell_is_one_chip_one_traffic_and_names_its_files():
    cell = CHECKOUT.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and config["kind"] == "olap-adopted"
    assert config["structure_seed"] == 500 and config["scale"] in (18, 19, 20)
    assert config["reduced"] == ["scale", "trials"]
    assert config["trials"] == 2 and config["sources_per_trial"] == 4
    for reading in ("3.2 s", "scale 20", "peak"):
        assert reading in config["reduced_why"]["scale"], reading
    assert {"source_picker", "own_dependency", "verifier"} <= set(
        config["assumed"])
    assert traffic == {**traffic, "driver": "gap-trials",
                       "program": "BetweennessCentralityProgram",
                       "result_state": "betweenness",
                       "reference": "gap-bc",
                       "sources": {"draw": 4, "count": 8, "per_trial": 4},
                       "warmup_submits": 2}
    assert traffic["traced_seconds"] >= 2.0
    cells_of_config = [w["name"] for w in CHECKOUT.manifest["workloads"]
                       if w["config"] == "gap-kron-bc"]
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
    assert metric["args"]["per"] == {"count": "trials_traced"}
    if metric["reader"] == "roofline":
        assert metric["args"]["paths"] == ["brandes"]
        assert metric["args"]["modules"] == [
            "jit_brandes_forward", "jit_brandes_backward", "jit_plan_body"]
    if metric["reader"] == "trace-scope":
        assert metric["args"]["scopes"] in (
            ["brandes.forward"], ["brandes.backward"])


def test_the_cell_joins_the_shared_lists():
    joined = {m["name"] for m in CHECKOUT.manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined == set(NEW_METRICS) | {
        "compiles_in_window.olap", "device_idle.olap",
        "executor_host_ms.olap", "idle_unnamed_share.olap",
        "search_rounds", "wide_rounds"}
    assert "superstep_device_ms.olap" not in joined


# ---------------------------------------------------------- the rehearsals
def test_rehearsal_is_correct_and_reports_the_record_metrics(tmp_path):
    line, notes, lines = rehearse(CELL, tmp_path, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == notes["counts"]["requests"] >= 1
    warm = [ln for ln in lines if "warm-up trial" in ln]
    assert len(warm) == 2 and all("path=brandes" in ln for ln in warm)
    metrics = line["metrics"]
    assert metrics["search_rounds"]["value"] > 0
    assert metrics["wide_rounds"]["value"] > 0
    assert metrics["compiles_in_window.olap"]["value"] == 0
    assert metrics["executor_host_ms.olap"]["value"] > 0
    # the device's scopes and executables are read from the device's
    # trace: none on the CPU
    assert not set(NEW_METRICS) & set(metrics)
    assert notes["counts"]["trials_traced"] >= 1
    info = notes["notes"]["run_info"]
    assert info["path"] == "brandes"
    assert info["rounds"] == info["forward_rounds"] + info["backward_rounds"]
    assert notes["notes"]["max_rel_error"] < 1e-5
    assert notes["notes"]["exact_zeros"] > 0


def test_rehearsal_reports_its_end_to_end_metrics(tmp_path):
    line, _, _ = rehearse(CELL, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "submit_p50_s"}
    assert line["metrics"]["submit_p50_s"]["value"] > 0
