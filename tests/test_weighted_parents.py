"""Weighted single-source shortest paths with a parent array (Graph500 v3
kernel 3's result): `ShortestPathProgram(weighted=True, track_paths=True)`
on the frontier engine against the benchmark's plain reference
(`benchmark/references/sssp.py`: distances bit for bit, parents by
Graph500's validation), on seeded random weighted multigraphs with the
cases the parent rule has to survive: parallel edges of different weights,
self loops, weights of 0, weights small enough to be absorbed by the
addition, an unreachable part. Executors that cannot return a weighted
parent refuse the combination by name; weighted distances WITHOUT parents
stay bit-equal to the same fixpoint on every path that folds in float32."""

import importlib.util
import os

import numpy as np
import pytest

from janusgraph_tpu.olap import csr_from_edges
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.programs import ShortestPathProgram
from janusgraph_tpu.olap.programs.shortest_path import (
    INF,
    reconstruct_path,
)
from janusgraph_tpu.olap.tpu_executor import TPUExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, *path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


sssp = _load("plain_sssp", "benchmark", "references", "sssp.py")


class Edges:
    """What the reference reads: n, src, dst, weight."""

    def __init__(self, n, src, dst, weight):
        self.n, self.src, self.dst, self.weight = n, src, dst, weight


def multigraph(seed, n=300, m=1500, island=40):
    """A weighted multigraph with every awkward case: the last `island`
    vertices joined among themselves only, ten self loops, sixty parallel
    edges under other weights, forty weights of 0 and forty of 2**-26
    (absorbed by any distance of 2**-2 or more)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n - island, m).astype(np.int32)
    dst = rng.integers(0, n - island, m).astype(np.int32)
    src[:20] = rng.integers(n - island, n, 20)
    dst[:20] = rng.integers(n - island, n, 20)
    weight = rng.random(m, dtype=np.float32)
    weight[rng.integers(20, m, 40)] = 0.0
    weight[rng.integers(20, m, 40)] = np.float32(2.0 ** -26)
    src[100:110] = dst[100:110]
    src[200:260], dst[200:260] = src[300:360], dst[300:360]
    return Edges(n, src, dst, weight)


def _csr(data):
    return csr_from_edges(data.n, data.src, data.dst, weights=data.weight)


def _program(root, undirected, **kw):
    return ShortestPathProgram(
        seed_index=root, weighted=True, undirected=undirected,
        max_iterations=1000, **kw)


def _as_infinite(distance):
    """The program's "unreached" (1e18, in whatever float it was kept) as
    the reference's infinity."""
    distance = np.asarray(distance)
    return np.where(distance >= np.float32(INF), np.inf, distance)


# ------------------------------------------------ the frontier engine
@pytest.mark.parametrize("undirected", [True, False],
                         ids=["undirected", "directed"])
@pytest.mark.parametrize("seed", range(6))
def test_frontier_engine_returns_reference_distances_and_valid_parents(
        seed, undirected):
    data = multigraph(seed)
    # rungs under the top: all but the widest hops run the narrow step
    ex = TPUExecutor(_csr(data), frontier_e_min=64, frontier_f_min=16)
    for root in (0, 7):
        got = ex.run(_program(root, undirected, track_paths=True))
        info = ex.last_run_info
        assert info["path"] == "frontier"
        assert info["wide_rounds"] < info["rounds"] / 2
        # ended by its fixpoint, and the record carries the totals
        assert info["rounds"] == info["supersteps"] < 1000
        assert info["relaxed_slots"] <= info["tier_slots"]
        want = sssp.KernelThree.expect(data, root, undirected=undirected)
        assert sssp.KernelThree.disagreements(got, want) == []
        # the island is unreached: INF and -1, as the BFS tracker says it
        assert np.all(np.asarray(got["distance"])[-40:] >= INF)
        assert np.all(np.asarray(got["predecessor"])[-40:] == -1)
        assert got["predecessor"][root] == root
        # and a path can be walked, its weights summing to the distance
        reached = np.flatnonzero(np.asarray(got["distance"]) < INF)
        path = reconstruct_path(got, int(reached[-1]))
        assert path[0] == root and path[-1] == reached[-1]


#: ladder -> (executor options, which hops must be wide), on 1100 edges:
#: (1024, m) holds every hop of these searches under the top rung, (128, m)
#: makes the hops in the middle wide rounds on the closure pack, and the
#: default floor (8192) lies above m, so the ladder is (m,): all wide.
LADDERS = {
    "narrow": (dict(frontier_e_min=1024, frontier_f_min=16), "none"),
    "mixed": (dict(frontier_e_min=64, frontier_f_min=16,
                   autotune_max_tiers=2), "some"),
    "default": ({}, "all"),
}
_narrow_runs = {}


def _search(seed, ladder, undirected):
    data = multigraph(seed, n=260, m=1100, island=30)
    ex = TPUExecutor(_csr(data), **LADDERS[ladder][0])
    got = ex.run(_program(3, undirected, track_paths=True))
    return data, got, ex.last_run_info


@pytest.mark.parametrize("undirected", [True, False],
                         ids=["undirected", "directed"])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("ladder", list(LADDERS))
def test_wide_rounds_are_the_same_search_with_the_same_parents(
        ladder, seed, undirected):
    """Kernel 3's search with the top rung's hops run as wide rounds on
    the weighted closure pack: the reference's distances bit for bit, a
    valid parent array, and hop for hop the search the narrow step runs
    (its rounds, its frontiers, its parents: the parent pass reads the
    round of each vertex's last improvement)."""
    data, got, info = _search(seed, ladder, undirected)
    wide = [t["wide"] for t in info["tiers"]]
    assert wide == [t["E_cap"] == len(data.src) for t in info["tiers"]]
    assert info["wide_rounds"] == sum(wide)
    expect = LADDERS[ladder][1]
    assert {"none": not any(wide), "all": all(wide),
            "some": any(wide) and not all(wide)}[expect]
    want = sssp.KernelThree.expect(data, 3, undirected=undirected)
    assert sssp.KernelThree.disagreements(got, want) == []
    if (seed, undirected) not in _narrow_runs:
        _narrow_runs[seed, undirected] = _search(
            seed, "narrow", undirected)[1:]
    narrow, narrow_info = _narrow_runs[seed, undirected]
    np.testing.assert_array_equal(
        np.asarray(got["distance"]).view(np.uint32),
        np.asarray(narrow["distance"]).view(np.uint32))
    np.testing.assert_array_equal(got["predecessor"], narrow["predecessor"])
    assert [(t["frontier"], t["relaxed_slots"]) for t in info["tiers"]] == [
        (t["frontier"], t["relaxed_slots"]) for t in narrow_info["tiers"]]


@pytest.mark.parametrize("weights", [
    pytest.param([0.0, 0.0, 0.0, 0.0], id="all-zero"),
    pytest.param([1.0, 1.0, 0.0, 0.0], id="zero-cycle-at-equal-distance"),
    pytest.param([0.5, 0.5, 2.0 ** -30, 2.0 ** -30], id="absorbed-cycle"),
])
def test_zero_and_absorbed_weights_give_a_tree(weights):
    """0 -> 1, 0 -> 2, 1 -> 2, 2 -> 1: where the last two cost nothing (or
    nothing float32 can see), 1 and 2 explain each other's distance; the
    parents must still lead to the root."""
    data = Edges(3, np.array([0, 0, 1, 2], np.int32),
                 np.array([1, 2, 2, 1], np.int32),
                 np.array(weights, np.float32))
    for undirected in (False, True):
        got = TPUExecutor(_csr(data)).run(
            _program(0, undirected, track_paths=True), frontier="always")
        want = sssp.KernelThree.expect(data, 0, undirected=undirected)
        assert sssp.KernelThree.disagreements(got, want) == []
        assert reconstruct_path(got, 1)[0] == 0
        assert reconstruct_path(got, 2)[0] == 0


def test_submit_runs_the_combination_on_the_frontier_engine():
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta

    data = multigraph(11)
    graph = open_graph({"storage.backend": "inmemory"})
    try:
        delta.get_snapshot(graph).adopt(
            _csr(data), graph.backend.mutation_epoch())
        result = graph.compute().program(
            _program(3, True, track_paths=True)).submit()
    finally:
        graph.close()
    assert result.run_info["path"] == "frontier"
    got = {k: np.asarray(result.states[k])
           for k in ("distance", "predecessor")}
    want = sssp.KernelThree.expect(data, 3)
    assert sssp.KernelThree.agrees(got, want)


@pytest.mark.parametrize("program,orientations", [
    (lambda: _program(0, True, track_paths=True), 2),
    (lambda: _program(0, False), 1),
    (lambda: ShortestPathProgram(seed_index=0, max_iterations=4), 1),
], ids=["weighted-parents", "weighted", "bfs"])
def test_frontier_run_record_totals_and_registry_counters(program,
                                                          orientations):
    """`rounds`, `relaxed_slots`, `tier_slots`, `wide_rounds`: sums over
    the hops of the record's own `tiers`, and the registry's counters move
    by them. A hop holds `E_cap` slots an orientation, a wide one (the top
    rung) the slots of the pack it gathers."""
    from janusgraph_tpu.observability import registry

    def counters():
        snap = registry.snapshot()
        return {k: snap.get("olap.frontier." + k, {}).get("count", 0)
                for k in ("rounds", "relaxed_slots", "tier_slots",
                          "wide_rounds")}

    # the ladder (128, m): every search here has wide and narrow hops
    ex = TPUExecutor(_csr(multigraph(3)), frontier_e_min=64,
                     frontier_f_min=16, autotune_max_tiers=2)
    before = counters()
    ex.run(program())
    info = registry.last_run("olap")
    assert info["path"] == "frontier" and info["rounds"] == len(info["tiers"])
    assert info["relaxed_slots"] == sum(
        t["relaxed_slots"] for t in info["tiers"])
    pack_slots = ex._hybrid_pack(orientations == 2).slots
    for t in info["tiers"]:
        assert t["wide"] == (t["E_cap"] == ex.csr.num_edges)
        assert t["tier_slots"] == (
            pack_slots if t["wide"] else orientations * t["E_cap"])
    assert info["tier_slots"] == sum(t["tier_slots"] for t in info["tiers"])
    assert 0 < info["wide_rounds"] < info["rounds"]
    assert info["wide_rounds"] == sum(t["wide"] for t in info["tiers"])
    assert all(t["relaxed_slots"] <= t["tier_slots"] for t in info["tiers"])
    moved = {k: v - before[k] for k, v in counters().items()}
    assert moved == {k: info[k] for k in moved}


# ------------------------------------ who refuses the combination, by name
def _sharded(csr):
    import jax
    from jax.sharding import Mesh

    from janusgraph_tpu.parallel import ShardedExecutor

    return ShardedExecutor(
        csr, mesh=Mesh(np.array(jax.devices()[:8]), ("p",)))


@pytest.mark.parametrize("runner,named", [
    (lambda csr, p: TPUExecutor(csr).run(p, frontier="off"),
     "dense superstep path of the single-device executor"),
    (lambda csr, p: TPUExecutor(csr, frontier="off").run(p),
     "dense superstep path of the single-device executor"),
    (lambda csr, p: TPUExecutor(csr).run(
        p, checkpoint_path="/nonexistent/ck", checkpoint_every=2),
     "dense superstep path of the single-device executor"),
    (lambda csr, p: CPUExecutor(csr).run(p), "the CPU executor"),
    (lambda csr, p: CPUExecutor(csr, strategy="hybrid").run(p),
     "the CPU executor"),
    (lambda csr, p: _sharded(csr).run(p), "the sharded executor"),
    (lambda csr, p: _sharded(csr).run(p, frontier="always"),
     "the sharded executor"),
], ids=["tpu-frontier-off-run", "tpu-frontier-off-executor",
        "tpu-checkpointed", "cpu-scalar", "cpu-hybrid", "mesh",
        "mesh-frontier-always"])
def test_other_paths_refuse_weighted_parents_by_name(runner, named):
    data = multigraph(2, n=120, m=400, island=10)
    with pytest.raises(ValueError) as refused:
        runner(_csr(data), _program(0, True, track_paths=True))
    assert named in str(refused.value)
    assert "track_paths" in str(refused.value)


# ----------------------------- weighted distances without parents: as before
@pytest.mark.parametrize("undirected", [True, False],
                         ids=["undirected", "directed"])
@pytest.mark.parametrize("runner", [
    lambda csr, p: TPUExecutor(csr).run(p),
    lambda csr, p: TPUExecutor(csr).run(p, frontier="off"),
    lambda csr, p: CPUExecutor(csr, strategy="hybrid").run(p),
    lambda csr, p: CPUExecutor(csr, strategy="ell").run(p),
    lambda csr, p: _sharded(csr).run(p),
    lambda csr, p: _sharded(csr).run(p, frontier="off"),
], ids=["tpu-frontier", "tpu-dense", "cpu-hybrid", "cpu-ell",
        "mesh-frontier", "mesh-dense"])
def test_weighted_distances_alone_are_the_float32_fixpoint(runner,
                                                           undirected):
    data = multigraph(4, n=200, m=900, island=20)
    got = runner(_csr(data), _program(5, undirected))
    assert set(got) == {"distance"}
    sender, receiver, w = sssp.closure(
        data.src, data.dst, data.weight, undirected)
    want = sssp.fixpoint(data.n, sender, receiver, w, 5)
    np.testing.assert_array_equal(
        _as_infinite(got["distance"]).astype(np.float32).view(np.uint32),
        want.view(np.uint32))


@pytest.mark.parametrize("undirected", [True, False],
                         ids=["undirected", "directed"])
def test_scalar_oracle_is_within_the_float64_bound(undirected):
    """The per-edge oracle loop keeps its own float width: held to the
    float64 fixpoint by the bound the reference states for float32."""
    data = multigraph(4, n=100, m=500, island=10)
    got = _as_infinite(CPUExecutor(_csr(data)).run(
        _program(5, undirected))["distance"])
    sender, receiver, w = sssp.closure(
        data.src, data.dst, data.weight, undirected)
    want = sssp.fixpoint(data.n, sender, receiver, w, 5, np.float64)
    reached = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), reached)
    assert np.all(np.abs(got[reached] - want[reached])
                  <= sssp.F64_RTOL * want[reached])


# ------------------------------------------------ the reference's own checks
def _dijkstra64(data, root, undirected):
    import heapq

    adjacency = [[] for _ in range(data.n)]
    for s, d, w in zip(data.src.tolist(), data.dst.tolist(),
                       data.weight.astype(np.float64).tolist()):
        adjacency[s].append((d, w))
        if undirected:
            adjacency[d].append((s, w))
    dist = np.full(data.n, np.inf)
    dist[root] = 0.0
    heap = [(0.0, root)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for v, w in adjacency[u]:
            if d + w < dist[v]:
                dist[v] = d + w
                heapq.heappush(heap, (d + w, v))
    return dist


@pytest.mark.parametrize("undirected", [True, False],
                         ids=["undirected", "directed"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float32_fixpoint_is_within_1e5_of_a_float64_dijkstra(seed,
                                                              undirected):
    data = multigraph(seed)
    want = sssp.KernelThree.expect(data, 0, undirected=undirected)
    exact = _dijkstra64(data, 0, undirected)
    np.testing.assert_array_equal(np.isfinite(exact),
                                  np.isfinite(want["f32"]))
    np.testing.assert_allclose(want["f64"], exact, rtol=1e-12)
    # scipy's Dijkstra and the float64 fixpoint are the same numbers
    sender, receiver, w = want["closure"]
    np.testing.assert_allclose(
        sssp.fixpoint(data.n, sender, receiver, w, 0, np.float64),
        want["f64"], rtol=1e-12)
    reached = np.isfinite(exact)
    assert np.all(np.abs(want["f32"][reached] - exact[reached])
                  <= sssp.F64_RTOL * exact[reached])


def _bfloat16(x):
    """float32 values rounded to bfloat16 (round to nearest even), as
    float32."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_distances_from_bfloat16_messages_fail_agrees(seed):
    """The same rounds with every message rounded to bfloat16: what a
    kernel that carries its messages in the narrower type would return.
    The reference must call it wrong, by the bits AND by the float64 bound,
    with a parent array that is valid for those distances."""
    data = multigraph(seed)
    sender, receiver, w = sssp.closure(data.src, data.dst, data.weight)
    want = sssp.KernelThree.expect(data, 0)
    dist = np.full(data.n, np.inf, np.float32)
    dist[0] = 0
    for _ in range(data.n):
        with np.errstate(invalid="ignore"):
            message = _bfloat16(dist[sender] + w)
        new = dist.copy()
        np.minimum.at(new, receiver, message)
        if np.array_equal(new, dist):
            break
        dist = new
    got = {"distance": np.where(np.isfinite(dist), dist,
                                np.float32(INF)).astype(np.float32),
           "predecessor": np.full(data.n, -1, np.int64)}
    wrong = sssp.KernelThree.disagreements(got, want)
    assert "distance-bits" in wrong and "distance-f64" in wrong
    assert not sssp.KernelThree.agrees(got, want)
