"""The frontier engine's bottom-up hop (`FrontierEngine._bottom_up_fn`) and
its direction rule, against the plain reference of Graph500 kernel 2
(benchmark/references/bfs.py): on R-MAT graphs read undirected and directed
and on hand graphs (a long path, a star, two components, loops and parallel
edges, a root of degree 1), each searched with every hop after hop 0
forced top-down, forced bottom-up, and by the rule. Levels must equal the
reference's in every mode, parents must pass Graph500's validation in
every mode and, forced top-down, equal the dense path's."""

import importlib.util
import os

import numpy as np
import pytest

from janusgraph_tpu.olap.csr import csr_from_edges
from janusgraph_tpu.olap.programs import ShortestPathProgram
from janusgraph_tpu.olap.tpu_executor import TPUExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, *path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


KERNEL2 = _load(
    "bottom_up_reference", ("benchmark", "references", "bfs.py")).KernelTwo
RMAT = _load("bottom_up_data", ("benchmark", "data.py")).rmat_edges
MODES = ("top_down", "bottom_up", None)


class Edges:
    """What the reference reads of a generated graph."""

    def __init__(self, n, src, dst):
        self.n = n
        self.src = np.asarray(src, np.int32)
        self.dst = np.asarray(dst, np.int32)

    @property
    def m(self):
        return len(self.src)


def _rmat(scale):
    n, src, dst, _ = RMAT(scale, 16, 500, 2**31 + 45)
    return Edges(n, src, dst)


def _path(n=300):
    return Edges(n, np.arange(n - 1), np.arange(1, n))


def _star(n=64):
    return Edges(n, np.zeros(n - 1, np.int64), np.arange(1, n))


def _two_components():
    # a ring of 40 and a path of 20, joined by nothing
    ring = np.arange(40)
    return Edges(60, np.r_[ring, np.arange(40, 59)],
                 np.r_[(ring + 1) % 40, np.arange(41, 60)])


def _loops_and_parallel_edges():
    rng = np.random.default_rng(45)
    src = rng.integers(0, 50, 150)
    dst = rng.integers(0, 50, 150)
    # every edge twice, one reversed, and a loop on every tenth vertex
    loops = np.arange(0, 50, 10)
    return Edges(50, np.r_[src, dst, loops], np.r_[dst, src, loops])


def _root_of_degree_one():
    # vertex 0's one edge leads into a dense random graph
    rng = np.random.default_rng(7)
    src = rng.integers(1, 80, 600)
    dst = rng.integers(1, 80, 600)
    return Edges(80, np.r_[0, src], np.r_[1, dst])


#: name -> (graph, root, undirected)
GRAPHS = {
    "rmat8-undirected": (lambda: _rmat(8), None, True),
    "rmat8-directed": (lambda: _rmat(8), None, False),
    "rmat10-undirected": (lambda: _rmat(10), None, True),
    "rmat10-directed": (lambda: _rmat(10), None, False),
    "path300": (_path, 0, True),
    "path300-directed": (_path, 0, False),
    "star-from-a-leaf": (_star, 5, True),
    "two-components": (_two_components, 3, True),
    "loops-and-parallel-edges": (_loops_and_parallel_edges, 10, True),
    "root-of-degree-one": (_root_of_degree_one, 0, True),
}


@pytest.fixture(scope="module")
def searches():
    """name -> (graph, root, undirected, {mode: (states, run record)},
    the dense path's states): one executor a graph, its compiled hops
    shared by the three modes."""
    done = {}

    def search(name):
        if name not in done:
            make, root, undirected = GRAPHS[name]
            graph = make()
            if root is None:  # the R-MAT graphs: a vertex of high degree
                root = int(np.argmax(np.bincount(graph.src, minlength=graph.n)))
            ex = TPUExecutor(
                csr_from_edges(graph.n, graph.src, graph.dst),
                frontier_e_min=16, frontier_f_min=8,
            )
            program = ShortestPathProgram(
                root, undirected=undirected, track_paths=True,
                max_iterations=1000)
            runs = {}
            for mode in MODES:
                ex._frontier().direction = mode
                runs[mode] = (ex.run(program), ex.last_run_info)
            ex._frontier().direction = None
            dense = ex.run(program, frontier="off")
            done[name] = (graph, root, undirected, runs, dense)
        return done[name]

    return search


@pytest.mark.parametrize("mode", MODES, ids=lambda m: m or "rule")
@pytest.mark.parametrize("name", list(GRAPHS))
def test_levels_and_parents_against_the_reference(searches, name, mode):
    graph, root, undirected, runs, dense = searches(name)
    got, info = runs[mode]
    want = KERNEL2.expect(graph, root, undirected=undirected)
    assert KERNEL2.disagreements(got, want) == []
    # levels are the top-down engine's bit for bit, whatever the direction
    top_down = runs["top_down"][0]
    assert np.array_equal(got["distance"].view(np.uint32),
                          top_down["distance"].view(np.uint32))
    directions = [hop["direction"] for hop in info["tiers"]]
    assert directions[0] != "bottom_up"  # hop 0 reads the root's row
    if mode == "top_down":
        assert "bottom_up" not in directions
        # today's parents: the smallest-index sender, as the dense path
        np.testing.assert_array_equal(got["predecessor"],
                                      dense["predecessor"])
        np.testing.assert_array_equal(got["distance"], dense["distance"])
    elif mode == "bottom_up":
        assert set(directions[1:]) <= {"bottom_up"}
    bottom_up = [hop for hop in info["tiers"]
                 if hop["direction"] == "bottom_up"]
    assert info["bottom_up_rounds"] == len(bottom_up)
    assert info["bottom_up_slots"] == sum(
        hop["bottom_up_slots"] for hop in bottom_up)
    assert info["bottom_up_tier_slots"] >= info["bottom_up_slots"]
    assert info["examined_slots"] == (
        info["relaxed_slots"] + info["bottom_up_slots"])


def test_the_rule_goes_bottom_up_on_rmat_and_reads_less(searches):
    _, _, _, runs, _ = searches("rmat10-undirected")
    rule, top_down = runs[None][1], runs["top_down"][1]
    assert rule["bottom_up_rounds"] >= 1
    assert rule["examined_slots"] < top_down["examined_slots"]
    assert top_down["examined_slots"] == top_down["relaxed_slots"]


def test_a_long_path_goes_bottom_up_only_at_its_end(searches):
    """300 levels of one vertex each (the last hop reaches nothing): a
    frontier of 2 slots goes bottom-up once the unreached rows hold fewer
    than ALPHA x 2 slots, and stays there, since it never shrinks."""
    from janusgraph_tpu.olap.frontier import ALPHA

    _, _, _, runs, _ = searches("path300")
    info = runs[None][1]
    directions = [hop["direction"] for hop in info["tiers"]]
    assert info["rounds"] == 300
    # hop t leaves vertices t + 1 .. 299 unreached, 2 slots each but the
    # last, which has 1
    first = next(t for t in range(1, 300) if 2 * ALPHA > 2 * (299 - t) - 1)
    assert directions[1:first] == ["top_down"] * (first - 1)
    assert directions[first:] == ["bottom_up"] * (300 - first)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
def test_start_vectors_are_made_on_the_device_when_unweighted(weighted):
    """An unweighted search's start vectors come from `jit_start` (the
    unit the kernel-2 metrics count searches by); a weighted search's are
    copied from the host, and both start from the same values."""
    graph = _rmat(7)
    ex = TPUExecutor(csr_from_edges(
        graph.n, graph.src, graph.dst,
        weights=np.ones(graph.m, np.float32)))
    engine = ex._frontier()
    root = int(graph.src[0])
    program = ShortestPathProgram(root, weighted=weighted, undirected=True,
                                  track_paths=True, max_iterations=1000)
    got = ex.run(program)
    started = {k for k in ex._compiled if k[0] == "frontier-start"}
    assert started == (set() if weighted else {("frontier-start", True)})
    want = KERNEL2.expect(graph, root)
    assert KERNEL2.disagreements(got, want) == []
    dist, pred, mask = engine._host_start(root, True)
    device = engine._start_fn(True)(np.int32(root))
    np.testing.assert_array_equal(np.asarray(device[0]), np.asarray(dist))
    np.testing.assert_array_equal(np.asarray(device[2]), np.asarray(mask))
    # the root's own index unweighted, its round (0) weighted
    assert np.asarray(device[1])[root] == root
    assert np.asarray(pred)[root] == 0.0
    np.testing.assert_array_equal(np.delete(np.asarray(device[1]), root),
                                  np.delete(np.asarray(pred), root))


def test_stages_shrink_to_the_first_rung():
    ex = TPUExecutor(csr_from_edges(4096, np.arange(4095), np.arange(1, 4096)))
    engine = ex._frontier()
    assert engine._bottom_up_stages(4096) == [
        (4096, 1), (1024, 2)]
    engine.F_MIN = 16
    assert engine._bottom_up_stages(100) == [
        (100, 1), (25, 2), (16, 4)]
    assert engine._bottom_up_stages(16) == [(16, 1)]


def _tampered(kind, got, want):
    level = got["distance"].copy()
    parent = got["predecessor"].astype(np.int64)
    reached = np.flatnonzero((level < 1e18) & (level > 0))
    deep = reached[np.argmax(level[reached])]
    if kind == "wrong-parent":
        # a vertex one level up that is no neighbour of `deep`
        sender, receiver, _ = want["closure"]
        linked = set(sender[receiver == deep].tolist())
        parent[deep] = next(v for v in reached if v not in linked
                            and level[v] == level[deep] - 1)
    elif kind == "cycle":
        v = next(v for v in reached
                 if level[v] >= 2 and parent[parent[v]] != want["root"])
        parent[parent[v]] = v
    elif kind == "level-off-by-one":
        level[deep] += 1
    return {"distance": level, "predecessor": parent}


@pytest.mark.parametrize("kind,reason", [
    ("wrong-parent", "parent-edge"),
    ("cycle", "cycle"),
    ("level-off-by-one", "level"),
])
def test_the_validator_refuses(searches, kind, reason):
    graph, root, undirected, runs, _ = searches("rmat10-undirected")
    got = runs[None][0]
    want = KERNEL2.expect(graph, root, undirected=undirected)
    wrong = KERNEL2.disagreements(_tampered(kind, got, want), want)
    assert reason in wrong, wrong
    assert KERNEL2.disagreements(got, want) == []


@pytest.mark.parametrize("size", [1, 7, 64, 300, 1000])
def test_first_true_is_nonzero_at_every_size(size):
    """Both of `_first_true`'s forms (a binary search over the running
    count below len / 32, nonzero above) give nonzero's indices, then the
    fill."""
    import jax.numpy as jnp

    from janusgraph_tpu.olap.frontier import _first_true

    flags = np.random.default_rng(size).random(4096) < 0.05
    want = np.flatnonzero(flags)[:size]
    want = np.r_[want, np.full(size - len(want), 4096)]
    got = np.asarray(_first_true(jnp, jnp.asarray(flags), size, 4096))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
