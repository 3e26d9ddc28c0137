"""The cell `graph500-bfs.kernel2`, Graph500 kernel 2: its plain reference
(benchmark/references/bfs.py) on a hand graph, against the executor through
submit() and against what it must refuse, the bytes function of
benchmark/readers/bfs.py and the new metrics' readers, the cell's entries
and the lists it joins, and its CPU rehearsals, in which the bottom-up hop
runs."""

import json
import os
import types

import numpy as np
import pytest

from rehearsal import CHECKOUT, REPO, rehearse  # puts benchmark/ on sys.path

import run as bench  # noqa: E402
from data import EdgeList, rmat_edges  # noqa: E402

CATALOG = bench.Catalog([REPO])
CELL = "graph500-bfs.kernel2"
BIG_SEED = 2**31 + 17
NEW_METRICS = {
    "bottom_up_device_ms.olap": "device_trace",
    "bottom_up_rounds": "program_counter",
    "examined_slots_per_edge": "program_counter",
    "bfs_search_roofline": "device_trace",
}
#: the other cell whose hops the direction rule picks: it reads the new
#: metrics of the bottom-up hop, but not the roofline of a whole search
ALSO_READ_BY = {
    "bottom_up_device_ms.olap": ["g500-olap.bfs"],
    "bottom_up_rounds": ["g500-olap.bfs"],
    "examined_slots_per_edge": ["g500-olap.bfs"],
    "bfs_search_roofline": [],
}
#: the accepted metrics whose lists the cell joins
JOINED = {
    "search_rounds", "wide_rounds", "relaxed_slots_per_edge",
    "tier_slots_per_relaxed_slot", "superstep_device_ms.olap",
    "compiles_in_window.olap", "device_idle.olap", "executor_host_ms.olap",
    "idle_unnamed_share.olap",
    # the narrow top-down hops before and after the bottom-up ones
    "expand_device_ms.olap", "relax_device_ms.olap",
    "scatter_device_ms.olap",
}
#: one search, one execution of the module that makes its start vectors
PER_SEARCH = {"executions_of": ["jit_start"]}


@pytest.fixture(scope="module")
def kernel2():
    return CATALOG.plugins("references", "REFERENCES")["graph500-kernel2"]


@pytest.fixture(scope="module")
def readers():
    return CATALOG.plugins("readers", "READERS")


# ------------------------------------------------------------ the reference
def test_reference_on_a_hand_graph(kernel2):
    # 0-1 twice, 1-2, 2-3 reversed, a loop on 3, 4 alone, 5-6 apart
    data = EdgeList(7, np.array([0, 1, 1, 3, 3, 5], np.int32),
                    np.array([1, 0, 2, 2, 3, 6], np.int32))
    want = kernel2.expect(data, 0)
    inf = np.inf
    np.testing.assert_array_equal(want["levels"],
                                  [0, 1, 2, 3, inf, inf, inf])
    assert want["levels"].dtype == np.float32
    got = {"distance": np.array([0, 1, 2, 3, 1e18, 1e18, 1e18], np.float32),
           "predecessor": np.array([0, 0, 1, 2, -1, -1, -1], np.float32)}
    assert kernel2.disagreements(got, want) == []
    # directed: 3 -> 2 does not lead from 2 to 3
    directed = kernel2.expect(data, 0, undirected=False)
    np.testing.assert_array_equal(directed["levels"],
                                  [0, 1, 2, inf, inf, inf, inf])


@pytest.fixture(scope="module")
def answer():
    """One search of the cell's program at scale 9, through submit() on one
    device, as on the cell's chip (the suite's 8 virtual devices would
    route it to the mesh)."""
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta
    from janusgraph_tpu.olap.csr import csr_from_edges
    from janusgraph_tpu.olap.programs import ShortestPathProgram

    cell = CATALOG.cell(CELL)
    driver = CATALOG.driver(cell["traffic"]["driver"])
    data = EdgeList(*rmat_edges(9, 16, cell["config"]["structure_seed"],
                                BIG_SEED))
    data.weight = driver.edge_weights(cell["config"], data.m)
    root = driver.search_keys(cell["config"], data, 8)[0]
    g = open_graph({"storage.backend": "inmemory",
                    "computer.sharded-auto": False})
    try:
        csr = csr_from_edges(data.n, data.src, data.dst, weights=data.weight)
        delta.get_snapshot(g).adopt(csr, g.backend.mutation_epoch())
        result = g.compute().program(ShortestPathProgram(
            seed_index=root, **cell["traffic"]["args"])).submit()
    finally:
        g.close()
    return data, root, result.run_info, {
        k: np.asarray(result.states[k])
        for k in cell["traffic"]["result_states"]}


def test_reference_agrees_with_the_executor(kernel2, answer):
    data, root, info, got = answer
    assert info["path"] == "frontier" and info["bottom_up_rounds"] >= 1
    want = kernel2.expect(data, root)
    assert kernel2.disagreements(got, want) == []
    assert kernel2.agrees(
        {**got, "predecessor": got["predecessor"].astype(np.int64)}, want)
    # another key's answer is not this key's
    other = int(np.flatnonzero(np.isinf(want["levels"]))[0])
    assert not kernel2.agrees(got, kernel2.expect(data, other))


@pytest.mark.parametrize("name,reason", [
    ("float64-levels", "distance-not-float32"),
    ("a-vertex-short", "shape"),
    ("fractional-parent", "parent-not-integral"),
    ("unreached-with-a-parent", "unreached-or-range"),
    ("half-reached-edge", "edge-half-reached"),
    ("root-not-its-own-parent", "root"),
    ("parents-rounded-to-bfloat16", "parent-edge"),
])
def test_agrees_refuses(kernel2, answer, name, reason):
    data, root, _, got = answer
    want = kernel2.expect(data, root)
    level, parent = got["distance"].copy(), got["predecessor"].copy()
    reached = np.flatnonzero((level < 1e18) & (level > 0))
    deep = reached[np.argmax(level[reached])]
    if name == "float64-levels":
        level = level.astype(np.float64)
    elif name == "a-vertex-short":
        level, parent = level[:-1], parent[:-1]
    elif name == "fractional-parent":
        parent = parent + np.float32(0.5)
    elif name == "unreached-with-a-parent":
        parent[np.flatnonzero(level >= 1e18)[0]] = root
    elif name == "half-reached-edge":
        level[deep], parent[deep] = np.float32(1e18), -1
    elif name == "root-not-its-own-parent":
        parent[root] = deep
    elif name == "parents-rounded-to-bfloat16":
        # parents in a precision below float32: bfloat16 keeps 8 bits, so
        # vertex ids from 256 on round to other vertices
        bits = parent.view(np.uint32)
        parent = ((bits + 0x8000) & 0xFFFF0000).view(np.float32)
    wrong = kernel2.disagreements({"distance": level, "predecessor": parent},
                                  want)
    assert reason in wrong, wrong


def test_reference_imports_nothing_from_the_package():
    text = open(os.path.join(
        REPO, "benchmark", "references", "bfs.py")).read()
    assert "janusgraph_tpu" not in text and "import jax" not in text


# --------------------------------------------------------------- the readers
def _traced(searches, modules=None, scopes=None):
    summary = {"modules": {"jit_plan_body": (7 * searches, 0.004),
                           **(modules or {})},
               "scopes": scopes or {}}
    if searches:
        summary["modules"]["jit_start"] = (searches, 0.003)
    return types.SimpleNamespace(
        trace_summary=summary, counts={"supersteps_traced": 6 * searches},
        notes={}, shapes={"vertices": 1 << 20, "edges": 1 << 24},
        catalog=CATALOG,
        devices=[types.SimpleNamespace(platform="tpu",
                                       device_kind="TPU v5 lite")])


def _metric(name):
    return json.load(open(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".json")))


def test_bytes_function_of_a_search():
    count = CATALOG.plugins("readers", "BYTES")["graph500-bfs"]
    assert count({"vertices": 1 << 20, "edges": 1 << 24}) == 142606336


def test_bottom_up_device_ms_is_per_search(readers):
    metric = _metric("bottom_up_device_ms.olap")
    assert metric["reader"] == "trace-scope"
    assert metric["args"]["per"] == PER_SEARCH
    read = readers[metric["reader"]]
    run = _traced(3, scopes={"frontier.bottom_up": 0.3})
    assert read(run, **metric["args"]) == pytest.approx(100.0)
    # a parent without the scope, or without jit_start, reads nothing
    assert read(_traced(3), **metric["args"]) is None
    assert read(_traced(0, scopes={"frontier.bottom_up": 0.3}),
                **metric["args"]) is None
    assert read(types.SimpleNamespace(trace_summary=None, counts={}),
                **metric["args"]) is None


def test_bfs_search_roofline_is_per_search(readers):
    import device

    metric = _metric("bfs_search_roofline")
    assert metric["reader"] == "roofline"
    assert metric["args"]["per"] == PER_SEARCH
    assert metric["args"]["modules"] == [
        "jit_start", "jit_step", "jit_plan_body", "jit_bottom_up"]
    read = readers[metric["reader"]]
    run = _traced(3, modules={"jit_step": (10, 0.2),
                              "jit_bottom_up": (6, 0.096)})
    seconds = (0.003 + 0.2 + 0.004 + 0.096) / 3
    peak = device.peaks("TPU v5 lite")["bytes_per_s"]
    assert read(run, **metric["args"]) == pytest.approx(
        100.0 * 142606336 / peak / seconds)
    # the parent makes no jit_start: nothing to read
    assert read(_traced(0, modules={"jit_step": (10, 0.2)}),
                **metric["args"]) is None


# ------------------------------------------------- the cell's own entries
def test_the_cell_is_one_chip_one_traffic_and_names_its_files():
    cell = CHECKOUT.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and config["kind"] == "olap-adopted"
    assert config["structure_seed"] == 500 and config["scale"] == 20
    assert config["reduced"] == ["scale", "search_keys"]
    assert config["search_keys"] == traffic["roots"]["count"] == 8
    assert config["closure_slots"] == 2 * config["edges"]
    # the graph is g500-olap's
    olap = CHECKOUT.config_file("g500-olap")
    for key in ("generator", "structure_seed", "scale", "edge_factor",
                "vertices", "edges"):
        assert config[key] == olap[key], key
    assert traffic == {**traffic, "driver": "graph500-search",
                       "program": "ShortestPathProgram",
                       "args": {"undirected": True, "track_paths": True,
                                "max_iterations": 1000},
                       "result_states": ["distance", "predecessor"],
                       "reference": "graph500-kernel2",
                       "roots": {"param": "seed_index", "count": 8},
                       "warmup_submits": 8, "traced_seconds": 4.0}
    assert set(cell["end_to_end"]) == {"setup_s", "submit_p50_s"}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_layer_metric_lists_the_cells_that_read_it(name):
    entry = CHECKOUT.entry("per_layer", name)
    assert entry["workloads"] == [CELL] + ALSO_READ_BY[name]
    assert entry["moves"] == "submit_p50_s"
    assert entry["source"] == NEW_METRICS[name]
    assert entry["layer"] == "superstep kernels"
    for cell in entry["workloads"]:
        assert name in {m["name"]
                        for m in CHECKOUT.cell(cell)["layer_metrics"]}


def test_the_cell_joins_the_shared_lists():
    joined = {m["name"] for m in CHECKOUT.manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined == set(NEW_METRICS) | JOINED
    assert CELL in CHECKOUT.entry("end_to_end", "submit_p50_s")["workloads"]


def test_the_scope_and_module_are_the_programs():
    from janusgraph_tpu.olap import frontier

    text = open(frontier.__file__).read()
    for name in ("def bottom_up(", 'frontier_scope("bottom_up")',
                 'bottom_up_rounds=', 'record["examined_slots"]'):
        assert name in text, name


# ---------------------------------------------------------- the rehearsals
def test_rehearsal_runs_bottom_up_and_reports_the_record_metrics(tmp_path):
    line, notes, lines = rehearse(CELL, tmp_path, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == notes["counts"]["requests"] > 8
    warm = [ln for ln in lines if "warm-up search" in ln]
    assert len(warm) == 8 and all("path=frontier" in ln for ln in warm)
    metrics = line["metrics"]
    # no size gate: the rule goes bottom-up at the rehearsal's scale 7
    assert metrics["bottom_up_rounds"]["value"] >= 1
    assert 0 < metrics["examined_slots_per_edge"]["value"] < 1
    assert {"search_rounds", "relaxed_slots_per_edge",
            "compiles_in_window.olap", "executor_host_ms.olap"} <= set(
        metrics)
    # the device's scopes and executables are read from the device's
    # trace: none on the CPU
    assert not {"bottom_up_device_ms.olap", "bfs_search_roofline"} & set(
        metrics)


def test_rehearsal_reports_its_end_to_end_metrics(tmp_path):
    line, _, _ = rehearse(CELL, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "submit_p50_s"}
    assert line["metrics"]["submit_p50_s"]["value"] > 0


def test_the_other_cell_of_the_rule_reads_the_bottom_up_counts(tmp_path):
    """`g500-olap.bfs` runs the same hop loop, so the rule picks its
    directions too, and its record carries the new totals."""
    line, _, _ = rehearse("g500-olap.bfs", tmp_path, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert {"bottom_up_rounds", "examined_slots_per_edge"} <= set(metrics)
    # Beamer's edges examined: never more than the relaxed slots of the
    # top-down hops alone would have been, a whole closure's at most
    assert 0 < metrics["examined_slots_per_edge"]["value"] <= 1
    assert "bfs_search_roofline" not in metrics
