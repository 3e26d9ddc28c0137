"""The cell of PR 32, `graph500-sssp.kernel3`, and the cell it measured and
could not add, `g500-olap.cc` (kept as files under `cc_cell/`, found through
`--root`, as `point_cell/` is: its work moves with the seed's labels, so its
median spreads ten times what the contract admits; PERF.md section 7): their
plain references (benchmark/references/sssp.py, cc_cell's cc.py) against
independent methods and against the executor at scale 8, Graph500's
validation refusing what it must, the weights as a property of the
structure, the new readers, and both cells' CPU rehearsals."""

import numpy as np
import pytest

import os

from rehearsal import HERE, REPO, rehearse  # puts benchmark/ on sys.path

import run as bench  # noqa: E402
from data import EdgeList, rmat_edges  # noqa: E402

BIG_SEED = 2**31 + 13
CC_CELL = os.path.join(HERE, "cc_cell")
CATALOG = bench.Catalog([CC_CELL, REPO])


@pytest.fixture(scope="module")
def driver():
    return CATALOG.driver("graph500-search")


@pytest.fixture(scope="module")
def config():
    return CATALOG.cell("graph500-sssp.kernel3")["config"]


@pytest.fixture(scope="module")
def data(driver, config):
    data = EdgeList(*rmat_edges(8, 16, config["structure_seed"], BIG_SEED))
    data.weight = driver.edge_weights(config, data.m)
    return data


@pytest.fixture(scope="module")
def kernel3():
    return CATALOG.plugins("references", "REFERENCES")["graph500-kernel3"]


@pytest.fixture(scope="module")
def sssp():
    import importlib

    return importlib.import_module(
        CATALOG.plugins("references", "REFERENCES")[
            "graph500-kernel3"].__module__)


@pytest.fixture(scope="module")
def answer(data, driver, config):
    """One search of the cell's program at scale 8, through submit()."""
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta
    from janusgraph_tpu.olap.csr import csr_from_edges
    from janusgraph_tpu.olap.programs import ShortestPathProgram

    root = driver.search_keys(config, data, 4)[0]
    g = open_graph({"storage.backend": "inmemory"})
    try:
        csr = csr_from_edges(data.n, data.src, data.dst, weights=data.weight)
        delta.get_snapshot(g).adopt(csr, g.backend.mutation_epoch())
        traffic = CATALOG.cell("graph500-sssp.kernel3")["traffic"]
        result = g.compute().program(ShortestPathProgram(
            seed_index=root, **traffic["args"])).submit()
    finally:
        g.close()
    return root, {k: np.asarray(result.states[k])
                  for k in traffic["result_states"]}


# ------------------------------------------------------------- the data
def test_weights_belong_to_the_structure_not_to_the_seed(driver, config):
    """Two seeds: other ids, and under the permutations the same weighted
    edges in the same order, so the same searches and the same work."""
    n, src, dst, perm = rmat_edges(10, 16, config["structure_seed"], BIG_SEED)
    _, src2, dst2, perm2 = rmat_edges(10, 16, config["structure_seed"], 12345)
    w, w2 = (driver.edge_weights(config, len(src)),
             driver.edge_weights(config, len(src2)))
    assert w.dtype == np.float32 and np.array_equal(w, w2)
    assert 0.0 <= w.min() and w.max() < 1.0 and 0.45 < w.mean() < 0.55
    back, back2 = np.argsort(perm), np.argsort(perm2)
    assert not np.array_equal(src, src2)
    assert np.array_equal(back[src], back2[src2])
    assert np.array_equal(back[dst], back2[dst2])
    # another structure seed, other weights
    assert not np.array_equal(
        w, driver.edge_weights({"structure_seed": 501}, len(src)))


def test_search_keys_are_the_structures_and_have_a_proper_edge(driver,
                                                               config):
    first = EdgeList(*rmat_edges(10, 16, config["structure_seed"], BIG_SEED))
    other = EdgeList(*rmat_edges(10, 16, config["structure_seed"], 12345))
    keys, keys2 = (driver.search_keys(config, first, 4),
                   driver.search_keys(config, other, 4))
    assert len(set(keys)) == 4 and keys != keys2
    assert [int(np.argsort(first.perm)[k]) for k in keys] == [
        int(np.argsort(other.perm)[k]) for k in keys2]
    for data, key in ((first, keys[0]), (other, keys2[3])):
        proper = data.src != data.dst
        assert np.any((data.src[proper] == key) | (data.dst[proper] == key))
    # Graph500's rule bites: a vertex whose only edge is a self loop is no key
    lonely = EdgeList(4, np.array([0, 1, 2], np.int32),
                      np.array([0, 2, 1], np.int32),
                      np.arange(4, dtype=np.int32))
    assert sorted(driver.search_keys(config, lonely, 4)) == [1, 2]


# ----------------------------------------------------- kernel 3's reference
def test_reference_distances_float32_against_float64(data, kernel3, sssp,
                                                     answer):
    """scipy's Dijkstra (what `expect` holds float32 against) and the
    float64 fixpoint of the reference's own rounds are the same numbers,
    and float32 lies within the stated bound of them."""
    root, _ = answer
    want = kernel3.expect(data, root)
    assert want["f32"].dtype == np.float32 and want["f64"].dtype == np.float64
    exact = sssp.fixpoint(data.n, *want["closure"], root, np.float64)
    np.testing.assert_allclose(want["f64"], exact, rtol=1e-12)
    reached = np.isfinite(exact)
    assert 0 < reached.sum() and not reached.all()  # isolated vertices
    assert np.all(np.abs(want["f32"][reached] - exact[reached])
                  <= sssp.F64_RTOL * exact[reached])


def test_reference_agrees_with_the_executor(data, kernel3, answer):
    root, got = answer
    want = kernel3.expect(data, root)
    assert got["distance"].dtype == np.float32
    assert kernel3.disagreements(got, want) == []
    assert kernel3.agrees(got, want)
    # parents as exact integers of another type pass too
    assert kernel3.agrees(
        {**got, "predecessor": got["predecessor"].astype(np.int32)}, want)
    # another key's answer is not this key's
    assert not kernel3.agrees(got, kernel3.expect(data, int(data.src[0]) ^ 1))


def _tampered(name, got, want, data):
    distance = got["distance"].copy()
    parent = got["predecessor"].astype(np.int64)
    root = want["root"]
    reached = np.flatnonzero((distance < 1e18) & (np.arange(data.n) != root))
    if name == "one-ulp":
        v = reached[np.argmax(distance[reached])]
        distance[v] = np.nextafter(distance[v], np.float32(np.inf))
    elif name == "cycle":
        # two vertices that take each other as parent: every pointer still
        # follows a real edge (a zero-length two-cycle is what a careless
        # tie-break over an absorbed weight would return)
        v = next(v for v in reached if parent[parent[v]] != v
                 and parent[v] != root)
        parent[parent[v]] = v
    elif name == "parent-without-an-edge":
        linked = set(zip(data.src.tolist(), data.dst.tolist()))
        v, p = next((v, p) for v in reached for p in reached
                    if v != p and (v, p) not in linked
                    and (p, v) not in linked
                    and distance[p] < distance[v])
        parent[v] = p
    elif name == "parent-of-an-unreached-vertex":
        parent[np.flatnonzero(distance >= 1e18)[0]] = root
    elif name == "root-not-its-own-parent":
        parent[root] = reached[0]
    elif name == "half-reached-edge":
        v = reached[np.argmax(distance[reached])]
        distance[v], parent[v] = np.float32(1e18), -1
    elif name == "fractional-parent":
        return {"distance": distance,
                "predecessor": got["predecessor"] + np.float32(0.5)}
    elif name == "float64-distances":
        return {"distance": distance.astype(np.float64),
                "predecessor": parent}
    elif name == "a-vertex-short":
        return {"distance": distance[:-1], "predecessor": parent[:-1]}
    return {"distance": distance, "predecessor": parent}


@pytest.mark.parametrize("name,reasons", [
    ("one-ulp", {"distance-bits"}),
    ("cycle", {"cycle"}),
    ("parent-without-an-edge", {"parent-edge"}),
    ("parent-of-an-unreached-vertex", {"unreached-or-range"}),
    ("root-not-its-own-parent", {"root"}),
    ("half-reached-edge", {"edge-half-reached"}),
    ("fractional-parent", {"parent-not-integral"}),
    ("float64-distances", {"distance-not-float32"}),
    ("a-vertex-short", {"shape"}),
])
def test_validation_refuses(name, reasons, data, kernel3, answer):
    root, got = answer
    want = kernel3.expect(data, root)
    wrong = kernel3.disagreements(_tampered(name, got, want, data), want)
    assert reasons <= set(wrong), wrong
    assert not kernel3.agrees(_tampered(name, got, want, data), want)


def test_validation_reads_the_edge_list_not_the_distances(data, sssp,
                                                          kernel3, answer):
    """`validate` alone, on distances that ARE a fixpoint of another
    weighting: every rule holds there and the parents pass, so what refuses
    such an answer in `agrees` is the comparison of the bits."""
    root, got = answer
    dist = np.where(got["distance"] >= 1e18, np.inf,
                    got["distance"]).astype(np.float32)
    parent = got["predecessor"].astype(np.int64)
    sender, receiver, w = sssp.closure(data.src, data.dst, data.weight)
    assert sssp.validate(data.n, sender, receiver, w, root, parent,
                         dist) == []
    broken = sssp.validate(data.n, sender, receiver, w * np.float32(2), root,
                           parent, dist)
    assert "parent-edge" in broken


# ----------------------------------------------------------- cc's reference
def _union_find_min_labels(n, src, dst):
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in zip(src.tolist(), dst.tolist()):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    return np.array([find(v) for v in range(n)], np.int64)


@pytest.mark.parametrize("seed", range(5))
def test_cc_reference_against_a_union_find(seed):
    reference = CATALOG.plugins("references", "REFERENCES")["cc-min-label"]
    rng = np.random.default_rng(seed)
    n = 400
    m = [0, 150, 300, 600, 2000][seed]
    data = EdgeList(n, rng.integers(0, n, m).astype(np.int32),
                    rng.integers(0, n, m).astype(np.int32))
    want = reference.expect(data)
    assert want.dtype == np.int64
    np.testing.assert_array_equal(
        want, _union_find_min_labels(n, data.src, data.dst))
    assert reference.agrees(want.astype(np.float32), want)
    wrong = want.copy()
    wrong[np.argmax(want != np.arange(n)) if m else 0] += 1
    assert not reference.agrees(wrong, want)
    assert not reference.agrees(want + 0.5, want)
    assert not reference.agrees(want[:-1], want)


def test_cc_reference_agrees_with_the_executor_on_the_generated_graph():
    from janusgraph_tpu.olap.csr import csr_from_edges
    from janusgraph_tpu.olap.programs import ConnectedComponentsProgram
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor

    reference = CATALOG.plugins("references", "REFERENCES")["cc-min-label"]
    data = EdgeList(*rmat_edges(8, 16, 500, BIG_SEED))
    want = reference.expect(data)
    np.testing.assert_array_equal(
        want, _union_find_min_labels(data.n, data.src, data.dst))
    ex = TPUExecutor(csr_from_edges(data.n, data.src, data.dst))
    for mode in ("always", "off"):  # the cell's path at scale 20, and dense
        got = ex.run(ConnectedComponentsProgram(), frontier=mode)
        assert reference.agrees(np.asarray(got["component"]), want), mode
        assert ("rounds" in ex.last_run_info) == (mode == "always")


# ------------------------------------------------------------- the readers
def test_record_ratio_reads_the_frontier_records_and_nothing_else():
    from janusgraph_tpu.observability import registry

    read = CATALOG.plugins("readers", "READERS")["record-ratio"]

    class Run:
        shapes = {"vertices": 100, "edges": 1000}

    registry.reset()
    assert read(Run, "olap", "relaxed_slots", shape="edges",
                shape_factor=2.0) is None
    registry.record_run("olap", {"path": "fused", "supersteps": 20})
    assert read(Run, "olap", "tier_slots", "relaxed_slots") is None
    registry.record_run("olap", {"rounds": 5, "relaxed_slots": 3000,
                                 "tier_slots": 8192})
    registry.record_run("olap", {"rounds": 7, "relaxed_slots": 5000,
                                 "tier_slots": 16384})
    assert read(Run, "olap", "relaxed_slots", shape="edges",
                shape_factor=2.0) == pytest.approx(8000 / 4000)
    assert read(Run, "olap", "tier_slots", "relaxed_slots") == pytest.approx(
        24576 / 8000)
    registry.reset()


def test_bytes_function_of_the_step():
    count = CATALOG.plugins("readers", "BYTES")["sssp-relax"]
    shapes = {"vertices": 1 << 20, "edges": 16 << 20}
    assert count(shapes) == 0.0  # an untraced run has no rounds to read
    shapes.update(rounds_traced=10, relaxed_slots_traced=50 << 20)
    assert count(shapes) == 16 * (50 << 20) / 10 + 8 * (1 << 20)


# ---------------------------------------------------------- the rehearsals
def test_kernel3_rehearsal_is_correct_and_reports_the_record_metrics(
        tmp_path):
    line, notes, lines = rehearse("graph500-sssp.kernel3", tmp_path, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == notes["counts"]["requests"] > 4
    # every key once in the warm-up, each search on the frontier engine
    warm = [ln for ln in lines if "warm-up search" in ln]
    assert len(warm) == 4 and all("path=frontier" in ln for ln in warm)
    assert len({ln.split("key=")[1].split(":")[0] for ln in warm}) == 4
    metrics = line["metrics"]
    assert metrics["search_rounds"]["value"] >= 2
    assert metrics["relaxed_slots_per_edge"]["value"] > 1  # label-correcting
    assert metrics["tier_slots_per_relaxed_slot"]["value"] >= 1
    assert {"compiles_in_window.olap", "executor_host_ms.olap"} <= set(metrics)
    # the device's scopes are read from the device's trace: none on the CPU
    assert not {"relax_device_ms.olap", "scatter_device_ms.olap",
                "parent_device_ms.olap", "sssp_step_roofline"} & set(metrics)
    assert notes["counts"]["supersteps_traced"] >= 2
    assert notes["counts"]["relaxed_slots_traced"] > 0
    assert notes["notes"]["run_info"]["path"] == "frontier"
    assert len(notes["notes"]["per_root_median_s"]) == 4


def test_kernel3_rehearsal_reports_its_end_to_end_metrics(tmp_path):
    line, notes, _ = rehearse("graph500-sssp.kernel3", tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "submit_p50_s"}
    assert line["metrics"]["submit_p50_s"]["value"] > 0


def test_cc_cell_is_found_as_files_and_is_no_cell_of_the_checkout():
    cell = CATALOG.cell("g500-olap.cc")
    assert cell["config"]["name"] == "g500-olap"
    assert cell["traffic"]["program"] == "ConnectedComponentsProgram"
    assert set(cell["end_to_end"]) == {"setup_s", "submit_p50_s"}
    assert {"search_rounds", "relaxed_slots_per_edge",
            "tier_slots_per_relaxed_slot", "superstep_device_ms.olap"} <= {
        m["name"] for m in cell["layer_metrics"]}
    with pytest.raises(bench.BenchmarkError):
        bench.Catalog([REPO]).cell("g500-olap.cc")


def test_cc_rehearsal_is_correct(tmp_path):
    line, notes, _ = rehearse("g500-olap.cc", tmp_path, trace=1,
                              extra=("--root", CC_CELL))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 2
    assert {"compiles_in_window.olap", "executor_host_ms.olap"} <= set(
        line["metrics"])
    # at the rehearsal's scale the executor runs CC dense (under 2**20
    # edges), whose record has no frontier totals: nothing to read
    if notes["notes"]["run_info"]["path"] != "frontier":
        assert "search_rounds" not in line["metrics"]
