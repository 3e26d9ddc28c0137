"""The benchmark's plain CDLP reference (benchmark/references/cdlp.py): it
agrees with the repo's plain reference (tests/test_cdlp.py: a Counter per
vertex) and with the executor at scale 8, under Graphalytics' exact match,
and refuses every answer that is not that."""

import importlib.util
import os

import numpy as np
import pytest

from rehearsal import REPO  # puts benchmark/ on sys.path

import run as bench  # noqa: E402
from data import EdgeList, rmat_edges  # noqa: E402

BIG_SEED = 2**31 + 7


@pytest.fixture(scope="module")
def data():
    return EdgeList(*rmat_edges(8, 16, 500, BIG_SEED))


@pytest.fixture(scope="module")
def reference():
    return bench.Catalog([REPO]).plugins("references", "REFERENCES")[
        "cdlp-labels"]


@pytest.fixture(scope="module")
def plain():
    spec = importlib.util.spec_from_file_location(
        "plain_cdlp", os.path.join(REPO, "tests", "test_cdlp.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.reference_cdlp


@pytest.mark.parametrize("rounds", [0, 1, 3, 10])
def test_reference_agrees_with_the_plain_reference(data, reference, plain,
                                                   rounds):
    want = reference.expect(data, max_iterations=rounds)
    assert want.dtype == np.int64 and want.shape == (data.n,)
    np.testing.assert_array_equal(
        want, plain(data.n, data.src, data.dst, rounds))
    # isolated vertices keep their own label, whatever the rounds
    deg = np.bincount(data.src, minlength=data.n) + np.bincount(
        data.dst, minlength=data.n)
    lonely = np.flatnonzero(deg == 0)
    assert len(lonely) and np.array_equal(want[lonely], lonely)


def test_reference_agrees_with_the_executor_and_refuses_the_rest(
        data, reference):
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta
    from janusgraph_tpu.olap.csr import csr_from_edges
    from janusgraph_tpu.olap.programs import CDLPProgram

    g = open_graph({"storage.backend": "inmemory"})
    try:
        csr = csr_from_edges(data.n, data.src, data.dst)
        delta.get_snapshot(g).adopt(csr, g.backend.mutation_epoch())
        result = g.compute().program(CDLPProgram(max_iterations=10)).submit()
    finally:
        g.close()
    got = np.asarray(result.states["label"])
    want = reference.expect(data, max_iterations=10)
    assert got.dtype == np.int32 and reference.agrees(got, want)
    assert reference.agrees(got.astype(np.float64), want)  # integral floats
    # exact match: one label off by one, a fraction, a shape, a dtype
    wrong = got.copy()
    wrong[5] += 1
    assert not reference.agrees(wrong, want)
    assert not reference.agrees(got + 0.25, want)
    assert not reference.agrees(got[:-1], want)
    assert not reference.agrees(got.astype(str), want)
    nan = got.astype(np.float64)
    nan[0] = np.nan
    assert not reference.agrees(nan, want)
    # nine rounds are not ten on this graph: the rounds are really run
    assert not reference.agrees(
        got, reference.expect(data, max_iterations=9)) or np.array_equal(
        reference.expect(data, max_iterations=9), want)


def test_bytes_functions_of_the_cell(reference):
    count = bench.Catalog([REPO]).plugins("readers", "BYTES")
    shapes = {"vertices": 1 << 20, "edges": 16 << 20}
    assert count["mode-fold"](shapes) == 8 * (16 << 20) + 4 * (1 << 20)
    assert count["cdlp-round"](shapes) == 16 * (16 << 20) + 8 * (1 << 20)
    # a whole round moves more than its fold, which moves more than nothing
    assert count["cdlp-round"](shapes) > count["mode-fold"](shapes) > 0
