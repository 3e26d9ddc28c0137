"""Connected components by Afforest on the frontier engine
(`FrontierEngine.run_cc`): the labels equal `CPUExecutor`'s min-label
propagation and the benchmark's plain reference (`gap-cc`) bit for bit on
graphs built to drive each part of it, and the run record says what the
sample and the finish did."""

import importlib.util
import os

import numpy as np
import pytest

from janusgraph_tpu.olap import csr_from_edges
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.programs import ConnectedComponentsProgram
from janusgraph_tpu.olap.tpu_executor import TPUExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(REPO, "benchmark", "references", "gap_cc.py")
    spec = importlib.util.spec_from_file_location("gap_cc_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.REFERENCES["gap-cc"]


REFERENCE = _reference()

#: narrow rungs under the ladder's top: the default E floor (8192) lies
#: above these graphs' closures, which makes every finish top-rung
RUNGS = dict(frontier_e_min=16, frontier_f_min=8)


class Edges:
    def __init__(self, n, src, dst):
        self.n = n
        self.src = np.asarray(src, np.int32)
        self.dst = np.asarray(dst, np.int32)


def multigraph(seed, n=300, m=700):
    """Random edges with self loops and parallel edges in both
    directions."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    src[:15] = dst[:15]                      # self loops
    src[15:45], dst[15:45] = dst[45:75], src[45:75]  # reversed parallels
    src[75:90], dst[75:90] = src[90:105], dst[90:105]  # parallels
    return Edges(n, src, dst)


def isolated():
    """A few small trees among 60 vertices, most of them isolated."""
    return Edges(60, [3, 4, 10, 11, 11, 40], [4, 5, 11, 12, 13, 41])


def small_components(k=40, size=3):
    """Only triangles: the giant is one of them and the finish reads every
    other one."""
    src, dst = [], []
    for c in range(k):
        a, b, d = c * size, c * size + 1, c * size + 2
        src += [a, b, d]
        dst += [b, d, a]
    return Edges(k * size, src, dst)


def giant_unseen(clusters=40):
    """The true giant is 40 cliques of 4 chained by bridge vertices, so
    laid out that the two neighbours the sample reads of each vertex lie
    in one clique: a closure row holds its vertex's higher neighbours,
    then its lower ones. A bridge (the lowest ids) joins the two lowest
    vertices of its clique and the lowest of the next, each of which has
    two higher neighbours in its own clique. The sample leaves the giant
    in pieces of at most 5; a separate path of 30 vertices, linked whole
    by the sample, is the sampled mode."""
    bridges = clusters - 1
    src, dst = [], []
    for c in range(clusters):
        v = [bridges + 4 * c + k for k in range(4)]
        for i in range(4):
            for j in range(i + 1, 4):
                src.append(v[i])
                dst.append(v[j])
        if c < bridges:
            src += [c, c, c]
            dst += [v[0], v[1], v[0] + 4]
    path0 = bridges + 4 * clusters
    src += list(range(path0, path0 + 29))
    dst += list(range(path0 + 1, path0 + 30))
    return Edges(path0 + 30, src, dst)


def low_degrees():
    """Vertices of degree 0, 1 and 2 only: pairs, paths of three, loops of
    four, so the sample's two neighbours are every vertex's whole row."""
    src, dst = [], []
    v = 0
    for _ in range(20):
        src.append(v)
        dst.append(v + 1)
        v += 3  # v + 2 stays isolated
    for _ in range(20):
        src += [v, v + 1]
        dst += [v + 1, v + 2]
        v += 3
    for _ in range(10):
        src += [v, v + 1, v + 2, v + 3]
        dst += [v + 1, v + 2, v + 3, v]
        v += 4
    return Edges(v + 5, src, dst)


def long_chain(n=400):
    """A path over the first n / 2 vertices, each hooked under the one
    before: one pass, then a forest n / 2 deep for the jumps."""
    return Edges(n, np.arange(n // 2 - 1), np.arange(1, n // 2))


def shuffled_chain(n=400, seed=5):
    """The same path under shuffled ids: several passes."""
    perm = np.random.default_rng(seed).permutation(n)
    return Edges(n, perm[np.arange(n // 2 - 1)], perm[np.arange(1, n // 2)])


GRAPHS = {
    "multigraph-0": lambda: multigraph(0),
    "multigraph-1": lambda: multigraph(1, n=500, m=600),
    "multigraph-2": lambda: multigraph(2, n=200, m=1600),
    "isolated": isolated,
    "small-components": small_components,
    "giant-unseen": giant_unseen,
    "low-degrees": low_degrees,
    "long-chain": long_chain,
    "shuffled-chain": shuffled_chain,
}

#: ladder -> executor options: narrow finishes, and the default ladder,
#: whose one rung (the closure's slots) makes every finish top-rung
LADDERS = {"rungs": RUNGS, "top": {}}


def afforest(edges, **options):
    csr = csr_from_edges(edges.n, edges.src, edges.dst)
    ex = TPUExecutor(csr, frontier="always", **options)
    got = ex.run(ConnectedComponentsProgram())
    return csr, got["component"], ex.last_run_info


@pytest.mark.parametrize("ladder", list(LADDERS))
@pytest.mark.parametrize("graph", list(GRAPHS))
def test_labels_are_propagations_and_the_references(graph, ladder):
    edges = GRAPHS[graph]()
    csr, got, info = afforest(edges, **LADDERS[ladder])
    want = CPUExecutor(csr).run(ConnectedComponentsProgram())["component"]
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, np.asarray(want).astype(np.float32))
    assert REFERENCE.agrees(got, REFERENCE.expect(edges))
    assert info["path"] == "frontier" and info["algorithm"] == "afforest"
    assert set(info["link_passes"]) == set(info["compress_jumps"]) == {
        "sample", "finish"}
    simple = {(min(a, b), max(a, b))
              for a, b in zip(edges.src.tolist(), edges.dst.tolist()) if a != b}
    assert info["closure_slots"] == 2 * len(simple)
    assert info["link_passes"]["sample"] >= 1
    assert info["compress_jumps"]["sample"] >= info["link_passes"]["sample"]
    assert 0 < info["giant_share"] <= 1
    assert 0 <= info["finish_slots"] <= info["closure_slots"]
    assert info["wide_rounds"] == 0
    if info["tiers"]:
        (tier,) = info["tiers"]
        assert tier["frontier"] == info["finish_vertices"] > 0
        assert tier["edges"] == info["finish_slots"] == info["relaxed_slots"]
        assert tier["top_rung"] == (tier["E_cap"] == info["closure_slots"])
        if ladder == "top":
            assert tier["top_rung"]
        assert tier["E_cap"] >= tier["edges"]
    else:
        assert info["finish_vertices"] == info["finish_slots"] == 0
        assert info["link_passes"]["finish"] == 0


def test_every_triangle_but_the_giant_is_read_by_the_finish():
    edges = small_components()
    _, _, info = afforest(edges, **RUNGS)
    assert info["giant_share"] == 3 / edges.n
    assert info["finish_vertices"] == edges.n - 3
    assert info["finish_slots"] == info["closure_slots"] - 6
    # the sample linked every triangle whole: nothing is left to hook
    assert info["link_passes"]["finish"] == 0


def test_a_sampled_mode_that_is_not_the_giant_still_answers():
    edges = giant_unseen()
    _, got, info = afforest(edges, **RUNGS)
    # the path of 30 is the mode; the bridges and cliques (199 vertices)
    # are one component, which the finish has to join
    assert info["giant_share"] == 30 / edges.n
    assert np.all(got[:199] == 0) and np.all(got[199:] == 199)
    assert info["link_passes"]["finish"] >= 1


def test_a_long_chain_jumps_deep():
    _, _, info = afforest(long_chain())
    # one pass hangs each vertex under the one before: 199 deep
    assert info["link_passes"]["sample"] == 1
    assert info["compress_jumps"]["sample"] >= 8


def test_whole_rows_leave_the_finish_nothing_to_hook():
    _, _, info = afforest(low_degrees(), **RUNGS)
    assert info["link_passes"]["finish"] == 0


def test_no_edge_but_loops_is_every_vertex_alone():
    edges = Edges(7, [0, 3, 3], [0, 3, 3])
    _, got, info = afforest(edges)
    np.testing.assert_array_equal(got, np.arange(7, dtype=np.float32))
    assert info["closure_slots"] == 0 and info["tiers"] == []
    assert info["link_passes"] == {"sample": 0, "finish": 0}


def test_the_registry_holds_the_record_and_the_counters_move():
    from janusgraph_tpu.observability import registry

    registry.reset()
    _, _, info = afforest(giant_unseen(), **RUNGS)
    last = registry.last_run("olap")
    for key in ("link_passes", "compress_jumps", "finish_vertices",
                "finish_slots", "closure_slots", "giant_share"):
        assert last[key] == info[key]
    snap = registry.snapshot()
    assert snap["olap.frontier.rounds"]["count"] == 1
    assert snap["olap.frontier.relaxed_slots"]["count"] == info["finish_slots"]
    assert info["supersteps"] == sum(info["link_passes"].values())


def test_the_finish_tier_is_reused_and_the_sample_compiles_once():
    edges = multigraph(3)
    csr = csr_from_edges(edges.n, edges.src, edges.dst)
    ex = TPUExecutor(csr, frontier="always", **RUNGS)
    first = ex.run(ConnectedComponentsProgram())["component"]
    compiled = len(ex._compiled)
    again = ex.run(ConnectedComponentsProgram())["component"]
    assert len(ex._compiled) == compiled
    np.testing.assert_array_equal(first, again)
    assert ex.last_run_info["retraces"] == 0
    keys = [k[0] for k in ex._compiled if str(k[0]).startswith("afforest")]
    assert keys.count("afforest-sample") == 1
    assert keys.count("afforest-finish") == len(ex.last_run_info["tiers"])


def test_a_narrow_finish_compacts_the_rows_outside_the_giant():
    _, _, info = afforest(multigraph(1, n=500, m=600), **RUNGS)
    (tier,) = info["tiers"]
    assert not tier["top_rung"] and tier["E_cap"] < info["closure_slots"]
    assert tier["F_cap"] >= info["finish_vertices"]
    assert info["link_passes"]["finish"] >= 1
    assert info["compress_jumps"]["finish"] >= info["link_passes"]["finish"]


# ------------------------------------------------- the renumbered CC view
#: the fields whose values the renumbering must leave as they were
SAME_WORK = ("link_passes", "compress_jumps", "finish_vertices",
             "finish_slots", "tiers")


def with_isolated(edges, where):
    """`edges` with 8 vertices of no edge interleaved (`where`): at index
    0, at the end, on vertices the sample picks (i x n / 1024 of the new
    numbering), or none at all. Returns the graph and the new index of
    each old vertex (the order kept)."""
    k = 0 if where == "none" else 8
    n = edges.n + k
    if where == "first":
        new = np.arange(k)
    elif where == "last":
        new = np.arange(n - k, n)
    elif where == "picks":
        picks = np.unique(np.arange(1024) * n // 1024)
        new = picks[np.linspace(0, len(picks) - 1, k).astype(int)]
    else:
        new = np.arange(0)
    kept = np.setdiff1d(np.arange(n), new)
    return Edges(n, kept[edges.src], kept[edges.dst]), kept


def full_view(ex):
    """The executor's engine set to run Afforest over all n vertices, the
    view as it was before the renumbering: the simple closure's own arrays
    and the picks at i x n / 1024."""
    engine = ex._frontier()
    picks = np.arange(1024) * engine.n // 1024
    engine._fargs_cache["cc"] = dict(
        engine._simple_args(), picks=ex.jnp.asarray(picks.astype(np.int32)))
    return ex


def counts(info):
    out = {key: info[key] for key in SAME_WORK}
    out["giant"] = round(info["giant_share"] * info["vertices"])
    return out


@pytest.mark.parametrize("where", ["first", "last", "picks", "none"])
@pytest.mark.parametrize("graph", list(GRAPHS))
def test_interleaved_isolated_vertices_change_no_count(graph, where):
    base = GRAPHS[graph]()
    edges, kept = with_isolated(base, where)
    csr = csr_from_edges(edges.n, edges.src, edges.dst)
    ex = TPUExecutor(csr, frontier="always", **RUNGS)
    got = ex.run(ConnectedComponentsProgram())["component"]
    info = ex.last_run_info
    want = CPUExecutor(csr).run(ConnectedComponentsProgram())["component"]
    np.testing.assert_array_equal(got, np.asarray(want).astype(np.float32))
    assert REFERENCE.agrees(got, REFERENCE.expect(edges))
    link = edges.src != edges.dst
    with_edge = np.unique(np.concatenate([edges.src[link], edges.dst[link]]))
    assert info["vertices"] == edges.n
    assert info["sample_vertices"] == len(with_edge)
    assert where == "none" or len(with_edge) < edges.n
    # the same work as over all n rows, on the same graph
    full = full_view(TPUExecutor(csr, frontier="always", **RUNGS))
    again = full.run(ConnectedComponentsProgram())["component"]
    np.testing.assert_array_equal(again, got)
    assert full.last_run_info["sample_vertices"] == edges.n
    assert counts(info) == counts(full.last_run_info)
    # and as on the graph without the 8 vertices, but for the tier, whose
    # ladder is clamped at n
    _, alone, info0 = afforest(base, **RUNGS)
    assert ({**counts(info), "tiers": None}
            == {**counts(info0), "tiers": None})
    np.testing.assert_array_equal(got[kept], kept[alone.astype(np.int64)])


def test_a_graph_whose_every_pick_is_isolated_still_answers():
    # 4,096 vertices, picks at every 4th: edges among the others alone,
    # triangles of three consecutive non-picks and a path through them
    n = 4096
    others = np.flatnonzero(np.arange(n) % 4)
    src = np.concatenate([others[:-1:3], others[1::3][:len(others) // 3]])
    dst = np.concatenate([others[1::3], others[2::3]])[:len(src)]
    src, dst = src[:len(dst)], dst
    src = np.concatenate([src, others[:200:3]])
    dst = np.concatenate([dst, others[3:203:3]])
    edges = Edges(n, src, dst)
    picks = np.arange(1024) * n // 1024
    assert not np.isin(picks, np.concatenate([src, dst])).any()
    csr, got, info = afforest(edges, **RUNGS)
    assert REFERENCE.agrees(got, REFERENCE.expect(edges))
    want = CPUExecutor(csr).run(ConnectedComponentsProgram())["component"]
    np.testing.assert_array_equal(got, np.asarray(want).astype(np.float32))
    # the giant is the first pick, its own vertex: the finish reads every
    # vertex with an edge
    assert info["giant_share"] == 1 / n
    assert info["finish_vertices"] == info["sample_vertices"] < n
    assert info["finish_slots"] == info["closure_slots"]
    full = full_view(TPUExecutor(csr, frontier="always", **RUNGS))
    full.run(ConnectedComponentsProgram())
    assert counts(info) == counts(full.last_run_info)


@pytest.mark.parametrize("graph", ["small-components", "multigraph-2"])
def test_a_graph_without_isolated_vertices_is_not_renumbered(graph):
    edges = GRAPHS[graph]()
    csr = csr_from_edges(edges.n, edges.src, edges.dst)
    assert (np.diff(csr.out_indptr) + np.diff(csr.in_indptr) > 0).all()
    ex = TPUExecutor(csr, frontier="always", **RUNGS)
    got = ex.run(ConnectedComponentsProgram())["component"]
    info = ex.last_run_info
    assert info["sample_vertices"] == info["vertices"] == edges.n
    view = ex._frontier_engine._fargs_cache["cc"]
    assert "rank" not in view and "ids" not in view
    # the closure's own arrays, not a copy
    assert view["out_dst"] is ex._frontier_engine._simple_args()["out_dst"]
    assert REFERENCE.agrees(got, REFERENCE.expect(edges))


def test_the_brandes_view_is_unchanged_by_a_cc_run():
    from janusgraph_tpu.olap.programs import BetweennessCentralityProgram

    edges = multigraph(1, n=500, m=600)
    csr = csr_from_edges(edges.n, edges.src, edges.dst)
    ex = TPUExecutor(csr, frontier="always", **RUNGS)
    sources = [int(edges.src[20]), int(edges.dst[30])]
    before = ex.run(BetweennessCentralityProgram(sources))["betweenness"]
    engine = ex._frontier_engine
    simple = engine._simple_args()
    kept = {key: np.asarray(a).copy() for key, a in simple.items()}
    ex.run(ConnectedComponentsProgram())
    assert ex.last_run_info["sample_vertices"] < edges.n
    assert engine._simple_args() is simple
    for key, a in simple.items():
        np.testing.assert_array_equal(np.asarray(a), kept[key])
    # the closure's view over all n vertices, as Brandes reads it
    assert simple["out_deg"].shape == (edges.n,)
    after = ex.run(BetweennessCentralityProgram(sources))["betweenness"]
    np.testing.assert_array_equal(after, before)


def test_a_cc_run_alone_builds_only_its_own_view():
    edges = multigraph(1, n=500, m=600)
    csr = csr_from_edges(edges.n, edges.src, edges.dst)
    ex = TPUExecutor(csr, frontier="always")
    ex.run(ConnectedComponentsProgram())
    cache = ex._frontier_engine._fargs_cache
    assert "cc" in cache and "simple" not in cache
    n1 = ex.last_run_info["sample_vertices"]
    assert cache["cc"]["out_deg"].shape == (n1,)
    assert cache["cc"]["rank"].shape == (edges.n,)


def test_a_vertex_picked_twice_votes_twice_without_an_edge():
    # n = 600 < 1024: a vertex is picked once or twice. Vertex 0 (picked
    # twice, no edge) ties with every pair of once-picked vertices and
    # comes first, so it is the giant, as over all n rows
    n = 600
    picked = np.bincount(np.arange(1024) * n // 1024, minlength=n)
    once = np.flatnonzero(picked == 1)
    assert picked[0] == 2
    edges = Edges(n, once[0:-1:2], once[1::2])
    csr, got, info = afforest(edges, **RUNGS)
    assert REFERENCE.agrees(got, REFERENCE.expect(edges))
    assert info["giant_share"] == 1 / n
    assert info["finish_vertices"] == info["sample_vertices"] < n
    full = full_view(TPUExecutor(csr, frontier="always", **RUNGS))
    full.run(ConnectedComponentsProgram())
    assert counts(info) == counts(full.last_run_info)
