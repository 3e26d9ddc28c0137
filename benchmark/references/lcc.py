"""Plain reference for LDBC Graphalytics LCC (specification v1.0,
arXiv:2011.15028) over the generated edge list: numpy and scipy only,
int64 counts, float64 quotient, nothing imported from the package.

The graph is read as Graphalytics' file reads it: undirected, parallel
edges once, self loops dropped; isolated vertices kept, at 0. With A the
simple symmetric 0/1 matrix, d its row sums and T(v) the number of edges
among v's neighbours (the triangles through v):

    lcc(v) = 2 T(v) / (d (d - 1)),   0 where d < 2

T comes from sparse products over an ORIENTED half of A, so that memory
and about a minute of check time hold at scale 20 (the sum of d^2, what
`(A @ A).multiply(A)` costs, is 3.5e9 at scale 17 already). With the
vertices renumbered by (degree, index), L keeps each edge once, from its
lower end to its higher; a triangle u < v < w is then one entry of each of

    P = (L @ L).multiply(L)      at (u, w): the paths u -> v -> w closed
    Q = (L.T @ L).multiply(L)    at (v, w): the pairs u -> v, u -> w closed

and T = rowsum(P) [v lowest] + rowsum(Q) [v in the middle] + colsum(Q)
[v highest]. Both products run in row blocks cut by their multiply-adds.
`tests/benchmark/test_benchmark_lcc.py` holds this against a triple loop.

`agrees` is stricter than Graphalytics' own validation (an epsilon match,
1e-4 as remembered): see its docstring.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

#: multiply-adds of one block of a sparse product (2^22: the block that
#: read fastest here, 8.9 s at scale 18 against 11.5 at 2^26)
_BLOCK_FLOPS = 1 << 22

#: float32's result against float64's: T and d (d - 1) / 2 are integers
#: below 2^31, each rounded to float32 within 2^-24 relative, and the
#: division is within one ulp (2^-23 of the next binade at worst, 2^-24
#: relative of its own): three roundings, under 2^-22 together. A bfloat16
#: step anywhere (2^-8) misses it by four orders of magnitude.
RTOL = 2.0 ** -22

#: below this count float32's error moves `lcc x d (d - 1) / 2` by less
#: than a half, so the count is recovered exactly
EXACT_BELOW = 1 << 21


def simple_closure(n, src, dst):
    """(lo, hi) of the simple undirected graph: each unordered pair of
    distinct vertices joined by some edge, once, lo < hi."""
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    key = np.unique((lo * n + hi)[lo != hi])
    return key // n, key % n


def _masked_product_sums(left, right, mask, n):
    """Row and column sums of `(left @ right).multiply(mask)`, the product
    made in row blocks of about `_BLOCK_FLOPS` multiply-adds."""
    row = np.repeat(np.arange(n), np.diff(left.indptr))
    flops = np.bincount(
        row, weights=np.diff(right.indptr)[left.indices], minlength=n)
    block_of = (np.cumsum(flops) // _BLOCK_FLOPS).astype(np.int64)
    stops = np.r_[np.flatnonzero(np.diff(block_of)) + 1, n]
    rows, cols = np.zeros(n, np.int64), np.zeros(n, np.int64)
    start = 0
    for stop in stops:
        block = (left[start:stop] @ right).multiply(mask[start:stop])
        rows[start:stop] = np.asarray(block.sum(axis=1)).ravel()
        cols += np.asarray(block.sum(axis=0)).ravel()
        start = stop
    return rows, cols


def triangles(n, lo, hi):
    """(T, d): per vertex the triangles through it and its degree in the
    simple graph, int64."""
    d = np.bincount(lo, minlength=n) + np.bincount(hi, minlength=n)
    # renumber by (degree, index) and keep each edge from its lower end:
    # L is upper triangular, the forward lists of hubs stay short, and
    # rows that are read together lie together
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), d))] = np.arange(n)
    low, high = np.minimum(rank[lo], rank[hi]), np.maximum(rank[lo], rank[hi])
    L = sp.csr_matrix(
        (np.ones(len(low), np.int64), (low, high)), shape=(n, n))
    lowest, _ = _masked_product_sums(L, L, L, n)
    middle, highest = _masked_product_sums(L.T.tocsr(), L, L, n)
    return (lowest + middle + highest)[rank], d


class GraphalyticsLcc:
    @staticmethod
    def expect(data, **_):
        lo, hi = simple_closure(data.n, data.src, data.dst)
        T, d = triangles(data.n, lo, hi)
        pairs = d * (d - 1) // 2
        lcc64 = np.where(pairs > 0, T / np.maximum(pairs, 1), 0.0)
        return {"triangles": T, "degree": d, "lcc64": lcc64}

    @staticmethod
    def agrees(got, want) -> bool:
        """`got` is the float32 `lcc` of every vertex. It must be finite;
        exactly 0.0 where d < 2; everywhere within `RTOL` relative of the
        float64 quotient; and where T < 2^21 the count recovered from it,
        `rint(got x d (d - 1) / 2)` in float64, must EQUAL T: one triangle
        missed or doubled at any such vertex fails. Graphalytics itself
        asks only for an epsilon match."""
        T, d, lcc64 = want["triangles"], want["degree"], want["lcc64"]
        got = np.asarray(got)
        if got.shape != lcc64.shape or got.dtype != np.float32:
            return False
        got = got.astype(np.float64)
        if not np.all(np.isfinite(got)):
            return False
        if np.any(got[d < 2] != 0.0):
            return False
        if np.any(np.abs(got - lcc64) > RTOL * lcc64):
            return False
        small = T < EXACT_BELOW
        recovered = np.rint(got * (d * (d - 1) // 2))
        return bool(np.array_equal(recovered[small], T[small]))


REFERENCES = {"graphalytics-lcc": GraphalyticsLcc}
