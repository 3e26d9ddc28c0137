"""The benchmark's copy of the generator is deterministic in the seed and
keeps Graph500's shapes; its plain references agree with the executors at
scale 8 and refuse an answer that is wrong."""


import numpy as np
import pytest

from rehearsal import REPO  # puts benchmark/ on sys.path

import run as bench  # noqa: E402
from data import EdgeList, rmat_edges  # noqa: E402

BIG_SEED = 2**31 + 5  # the driver's seeds do not fit 32 signed bits


@pytest.fixture(scope="module")
def data():
    return EdgeList(*rmat_edges(8, 16, 500, BIG_SEED))


@pytest.fixture(scope="module")
def references():
    return bench.Catalog([REPO]).plugins("references", "REFERENCES")


@pytest.fixture(scope="module")
def graph(data):
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta
    from janusgraph_tpu.olap.csr import csr_from_edges

    g = open_graph({"storage.backend": "inmemory"})
    csr = csr_from_edges(data.n, data.src, data.dst)
    delta.get_snapshot(g).adopt(csr, g.backend.mutation_epoch())
    yield g
    g.close()


def test_generator_is_deterministic_in_the_seed():
    n, src, dst, perm = rmat_edges(10, 16, 500, BIG_SEED)
    n2, src2, dst2, perm2 = rmat_edges(10, 16, 500, BIG_SEED)
    assert n == n2 == 1024 and len(src) == len(dst) == 16 * 1024
    assert src.dtype == dst.dtype == np.int32
    assert np.array_equal(src, src2) and np.array_equal(dst, dst2)
    assert np.array_equal(perm, perm2)
    assert 0 <= src.min() and src.max() < n and dst.max() < n
    assert EdgeList(n, src, dst).digest() == EdgeList(n2, src2, dst2).digest()


def test_another_seed_relabels_the_same_structure():
    """Other ids and other bytes, the same degrees in the same edge order:
    so the same pack shapes, compiled programs and work for every seed."""
    n, src, dst, perm = rmat_edges(10, 16, 500, BIG_SEED)
    _, src2, dst2, perm2 = rmat_edges(10, 16, 500, BIG_SEED + 1)
    assert not np.array_equal(src, src2)
    assert sorted(perm) == sorted(perm2) == list(range(n))
    back, back2 = np.argsort(perm), np.argsort(perm2)
    assert np.array_equal(back[src], back2[src2])
    assert np.array_equal(back[dst], back2[dst2])
    assert np.array_equal(np.sort(np.bincount(src, minlength=n)),
                          np.sort(np.bincount(src2, minlength=n)))
    # another structure seed is another graph
    _, src3, _, _ = rmat_edges(10, 16, 501, BIG_SEED)
    assert not np.array_equal(np.sort(np.bincount(src, minlength=n)),
                              np.sort(np.bincount(src3, minlength=n)))


def test_generator_spans_chunks_and_keeps_the_rmat_skew():
    """More edges than one chunk of draws; the initiator's .57/.19/.19/.05
    shows as a heavy tail: a few vertices hold most edges."""
    n, src, dst, _ = rmat_edges(15, 16, 7, 7)
    assert len(src) == 16 << 15 > 1 << 18
    deg = np.sort(np.bincount(src, minlength=n))[::-1]
    assert deg[: n // 100].sum() > 0.2 * len(src)
    assert (deg == 0).mean() > 0.2
    # ids are permuted: the hub is not vertex 0
    assert np.bincount(src, minlength=n).argmax() != 0


def test_out_lists_keep_duplicates(data):
    indptr, nbr = data.out_lists
    assert indptr[-1] == data.m == len(nbr)
    assert np.array_equal(data.out_degree, np.bincount(data.src,
                                                       minlength=data.n))
    v = int(np.argmax(data.out_degree))
    assert sorted(nbr[indptr[v]:indptr[v + 1]]) == sorted(
        data.dst[data.src == v])


def test_pagerank_reference_agrees_with_the_executor(data, graph, references):
    from janusgraph_tpu.olap.programs import PageRankProgram

    args = {"max_iterations": 20, "tol": 0.0}
    result = graph.compute().program(PageRankProgram(**args)).submit()
    got = np.asarray(result.states["rank"])
    ref = references["pagerank"]
    want = ref.expect(data, **args)
    assert want.sum() == pytest.approx(1.0)
    assert ref.agrees(got, want)
    # a float32 answer off by a part in a thousand on one vertex fails
    wrong = got.copy()
    wrong[3] *= 1.001
    assert not ref.agrees(wrong, want)
    assert not ref.agrees(got[:-1], want)


@pytest.mark.parametrize("pick", [0, 1, 2])
def test_bfs_reference_agrees_with_the_executor(data, graph, references,
                                                pick):
    from janusgraph_tpu.olap.programs import ShortestPathProgram

    able = np.flatnonzero(data.out_degree >= 1)
    root = int(able[[0, len(able) // 2, -1][pick]])
    args = {"seed_index": root, "max_iterations": 4}
    result = graph.compute().program(ShortestPathProgram(**args)).submit()
    got = np.asarray(result.states["distance"])
    ref = references["bfs-distances"]
    want = ref.expect(data, **args)
    assert want[root] == 0 and np.isfinite(want).sum() > 1
    assert np.nanmax(want[np.isfinite(want)]) <= 4
    assert ref.agrees(got, want)
    wrong = got.copy()
    reached = np.flatnonzero(np.isfinite(want) & (want > 0))
    wrong[reached[0]] += 1
    assert not ref.agrees(wrong, want)


def test_bfs_reference_against_scipy(data, references):
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra

    adj = sp.csr_matrix((np.ones(data.m), (data.src, data.dst)),
                        shape=(data.n, data.n))
    root = int(np.argmax(data.out_degree))
    want = dijkstra(adj, directed=True, indices=root, unweighted=True,
                    limit=3)
    got = references["bfs-distances"].expect(
        data, seed_index=root, max_iterations=3)
    assert np.array_equal(got, want)


def test_count_references(data, references):
    indptr, nbr = data.out_lists
    v = int(np.argmax(data.out_degree))
    two = references["two-hop-distinct-count"]
    brute = set()
    for u in data.dst[data.src == v]:
        brute.update(data.dst[data.src == u].tolist())
    assert two.expect(data, index=v) == len(brute)
    assert two.agrees(len(brute), len(brute))
    assert not two.agrees(len(brute) + 1, len(brute))
    assert not two.agrees(None, len(brute))
    one = references["out-degree-count"]
    assert one.expect(data, index=v) == int((data.src == v).sum())
    lonely = int(np.argmin(data.out_degree))
    assert data.out_degree[lonely] == 0
    assert two.expect(data, index=lonely) == 0
