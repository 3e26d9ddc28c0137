"""ShortestPath path tracking (VERDICT r2 #9): predecessor-array state on
device + host chain reconstruction, parity vs networkx on random graphs,
across CPU oracle / TPU executor / 8-device mesh.
"""

import numpy as np
import pytest

from janusgraph_tpu.olap import csr_from_edges
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.programs import ShortestPathProgram
from janusgraph_tpu.olap.programs.shortest_path import reconstruct_path
from janusgraph_tpu.olap.tpu_executor import TPUExecutor
from janusgraph_tpu.parallel import ShardedExecutor


def random_graph(n=150, m=600, seed=5):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    return csr_from_edges(n, src, dst), src, dst


@pytest.fixture(scope="module")
def mesh8():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("p",))


def nx_graph(n, src, dst):
    import networkx as nx

    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(src.tolist(), dst.tolist()))
    return g


@pytest.mark.parametrize("runner", ["cpu", "tpu", "mesh"])
def test_paths_match_networkx(runner, mesh8):
    import networkx as nx

    g, src, dst = random_graph()
    prog = ShortestPathProgram(seed_index=0, track_paths=True)
    if runner == "cpu":
        res = CPUExecutor(g).run(prog)
    elif runner == "tpu":
        res = TPUExecutor(g).run(prog)
    else:
        res = ShardedExecutor(g, mesh=mesh8).run(prog)

    G = nx_graph(g.num_vertices, src, dst)
    nx_dist = nx.single_source_shortest_path_length(G, 0)
    nx_paths = nx.single_source_shortest_path(G, 0)

    dist = np.asarray(res["distance"])
    for v in range(g.num_vertices):
        if v in nx_dist:
            assert dist[v] == nx_dist[v], f"distance mismatch at {v}"
            path = reconstruct_path(res, v)
            assert path is not None
            # same length as an optimal path, valid edges, right endpoints
            assert len(path) == len(nx_paths[v])
            assert path[0] == 0 and path[-1] == v
            edges = set(zip(src.tolist(), dst.tolist()))
            for a, b in zip(path, path[1:]):
                assert (a, b) in edges, f"path uses nonexistent edge {a}->{b}"
        else:
            assert dist[v] >= 1e18
            assert reconstruct_path(res, v) is None


def test_undirected_paths(mesh8):
    g, src, dst = random_graph(n=60, m=150, seed=9)
    prog = ShortestPathProgram(seed_index=3, track_paths=True, undirected=True)
    res = CPUExecutor(g).run(prog)

    import networkx as nx

    G = nx.Graph()
    G.add_nodes_from(range(g.num_vertices))
    G.add_edges_from(zip(src.tolist(), dst.tolist()))
    nx_dist = nx.single_source_shortest_path_length(G, 3)
    dist = np.asarray(res["distance"])
    edges = set(zip(src.tolist(), dst.tolist())) | set(
        zip(dst.tolist(), src.tolist())
    )
    for v, d in nx_dist.items():
        assert dist[v] == d
        path = reconstruct_path(res, v)
        assert len(path) == d + 1
        for a, b in zip(path, path[1:]):
            assert (a, b) in edges


def test_track_paths_weighted_walks_cheapest_paths():
    """Weighted + track_paths is a mode now (it raised ValueError): the
    frontier engine returns parents along cheapest paths, and the paths
    they reconstruct weigh what networkx's dijkstra says."""
    import networkx as nx

    rng = np.random.default_rng(21)
    n, m = 90, 360
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    wts = rng.uniform(0.25, 2.0, m).astype(np.float32)
    csr = csr_from_edges(n, src, dst, weights=wts)
    res = TPUExecutor(csr).run(ShortestPathProgram(
        seed_index=0, weighted=True, track_paths=True, max_iterations=200))
    G = nx.DiGraph()
    G.add_nodes_from(range(n))
    cheapest = {}
    for s, d, w in zip(src.tolist(), dst.tolist(), wts.tolist()):
        cheapest[(s, d)] = min(cheapest.get((s, d), np.inf), w)
    G.add_weighted_edges_from((s, d, w) for (s, d), w in cheapest.items())
    nx_dist = nx.single_source_dijkstra_path_length(G, 0)
    assert len(nx_dist) > 50
    for v in range(n):
        path = reconstruct_path(res, v)
        if v not in nx_dist:
            assert path is None and res["predecessor"][v] == -1
            continue
        assert path[0] == 0 and path[-1] == v
        total = sum(cheapest[(a, b)] for a, b in zip(path, path[1:]))
        assert abs(total - nx_dist[v]) < 1e-4
        assert abs(float(res["distance"][v]) - nx_dist[v]) < 1e-4


def test_plain_distance_mode_unchanged(mesh8):
    g, _, _ = random_graph(n=80, m=300, seed=2)
    plain = CPUExecutor(g).run(ShortestPathProgram(seed_index=0))
    tracked = CPUExecutor(g).run(
        ShortestPathProgram(seed_index=0, track_paths=True)
    )
    np.testing.assert_allclose(plain["distance"], tracked["distance"])


# --------------------------------------------- weighted paths (round 5)
def test_weighted_paths_parity_networkx():
    """Weighted SSSP paths: the device program carries only distances;
    weighted_predecessors derives the predecessor array host-side from
    the fixpoint relaxation equation. Distance-parity vs networkx
    dijkstra, and every reconstructed path's weight sum equals the
    reported distance."""
    import networkx as nx

    from janusgraph_tpu.olap.programs.shortest_path import (
        INF,
        reconstruct_path,
        weighted_predecessors,
    )

    rng = np.random.default_rng(11)
    n, m = 120, 500
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    wts = rng.uniform(0.5, 3.0, m).astype(np.float32)
    csr = csr_from_edges(n, src, dst, weights=wts)
    seed = int(src[0])
    prog = ShortestPathProgram(
        seed_index=seed, weighted=True, max_iterations=200
    )
    res = TPUExecutor(csr).run(prog)
    dist = np.asarray(res["distance"])

    G = nx.DiGraph()
    G.add_nodes_from(range(n))
    for s, d, w in zip(src, dst, wts):
        # parallel edges: networkx DiGraph keeps ONE — keep the minimum
        if G.has_edge(int(s), int(d)):
            G[int(s)][int(d)]["weight"] = min(
                G[int(s)][int(d)]["weight"], float(w)
            )
        else:
            G.add_edge(int(s), int(d), weight=float(w))
    nx_dist = nx.single_source_dijkstra_path_length(G, seed)
    for v in range(n):
        if v in nx_dist:
            assert abs(dist[v] - nx_dist[v]) < 1e-3, (v, dist[v], nx_dist[v])
        else:
            assert dist[v] >= INF

    pred = weighted_predecessors(csr, res, seed)
    res2 = {"distance": dist, "predecessor": pred}
    # weight lookup for path verification
    wmap = {}
    for s, d, w in zip(src, dst, wts):
        key = (int(s), int(d))
        wmap[key] = min(wmap.get(key, float("inf")), float(w))
    checked = 0
    for v in range(n):
        if v == seed or dist[v] >= INF:
            continue
        path = reconstruct_path(res2, v)
        assert path is not None and path[0] == seed and path[-1] == v
        total = sum(wmap[(a, b)] for a, b in zip(path, path[1:]))
        assert abs(total - dist[v]) < 1e-3, (v, total, dist[v])
        checked += 1
    assert checked > 50  # the graph is well connected from the seed


def test_weighted_paths_adversarial_cases():
    """Review repros: zero-weight self-loops, zero-weight cycles among
    equal-distance vertices, and long cheap chains vs short expensive
    edges must all yield correct paths."""
    from janusgraph_tpu.olap.programs.shortest_path import (
        reconstruct_path,
        weighted_predecessors,
    )

    # zero-weight self-loop must not become its own predecessor
    csr = csr_from_edges(
        2,
        np.array([1, 0], dtype=np.int32),
        np.array([1, 1], dtype=np.int32),
        weights=np.array([0.0, 1.0], dtype=np.float32),
    )
    prog = ShortestPathProgram(seed_index=0, weighted=True,
                               max_iterations=10)
    res = dict(TPUExecutor(csr).run(prog))
    res["predecessor"] = weighted_predecessors(csr, res, 0)
    assert reconstruct_path(res, 1) == [0, 1]

    # zero-weight cycle between equal-distance vertices
    csr = csr_from_edges(
        3,
        np.array([0, 0, 1, 2], dtype=np.int32),
        np.array([1, 2, 2, 1], dtype=np.int32),
        weights=np.array([1.0, 1.0, 0.0, 0.0], dtype=np.float32),
    )
    prog = ShortestPathProgram(seed_index=0, weighted=True,
                               max_iterations=10)
    res = dict(TPUExecutor(csr).run(prog))
    res["predecessor"] = weighted_predecessors(csr, res, 0)
    assert reconstruct_path(res, 1) == [0, 1]
    assert reconstruct_path(res, 2) == [0, 2]


def test_weighted_shortest_path_step_reaches_fixpoint():
    """The traversal step must converge weighted relaxation past the
    unweighted max_hops default: a 12-edge cheap chain beats a direct
    expensive edge."""
    from janusgraph_tpu.core.graph import open_graph

    g = open_graph({"ids.authority-wait-ms": 0.0})
    mgmt = g.management()
    mgmt.make_property_key("w", float)
    mgmt.make_edge_label("road")
    t = g.traversal()
    tx = t.tx
    vs = [tx.add_vertex("place") for _ in range(13)]
    for a, b in zip(vs, vs[1:]):
        tx.add_edge(a, "road", b, w=0.1)
    tx.add_edge(vs[0], "road", vs[12], w=100.0)
    t.commit()
    try:
        paths = g.traversal().V(vs[0].id).shortest_path(
            weight_key="w"
        ).to_list()
        dest = {p[-1].id: p for p in paths}
        assert len(dest[vs[12].id]) == 13  # the cheap chain, not the hop
    finally:
        g.close()
