"""Plain reference for the GAP Benchmark Suite's kernel BC (Beamer,
Asanovic, Patterson; arXiv:1508.03619): Brandes (2001) from a few sources
over the generated edge list, numpy alone, float64, nothing imported from
the package.

The graph is read as GAP's builder reads it: undirected, parallel edges
once, self loops dropped. From each source s, level by level: the
vertices at depth t + 1 are the unreached neighbours of depth t; sigma_s
(the number of shortest paths) of a vertex at depth t + 1 is the sum of
sigma_s over its neighbours at depth t. Then, from the deepest level back,
each vertex v at depth t takes

    delta_s(v) = sum over its neighbours w at depth t + 1 of
                 sigma_s(v) / sigma_s(w) * (1 + delta_s(w))

and the score is the sum over the sources of delta_s(v), a source's own
delta_s(s) left out (Brandes' definition). Scores are not normalised.
Every sum is `np.add.at` over the edges of one level.
`tests/benchmark/test_benchmark_bc.py` holds this against a count of
every shortest path on tiny graphs.

`agrees` asks for 1e-4 relative of float64 at every vertex and an exact
0.0 wherever the reference is 0: see its docstring.
"""

from __future__ import annotations

import numpy as np

#: float32's scores against float64's, per vertex: every term is positive,
#: so no cancellation; a path count is rounded once a level at most
#: (2^-24), a dependency once a term and once a sum, over a dozen levels
#: and tree-ordered sums: about 1e-6 expected (measured on the chip:
#: PERF.md section 6, PR 38). Path counts kept in bfloat16 (2^-9 a
#: rounding) miss by well over 10x.
RTOL = 1e-4


def simple_closure(n, src, dst):
    """(lo, hi) of the simple undirected graph: each unordered pair of
    distinct vertices joined by some edge, once, lo < hi."""
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    key = np.unique((lo * n + hi)[lo != hi])
    return key // n, key % n


def adjacency(n, src, dst):
    """(indptr, neighbours) of the simple closure, both directions."""
    lo, hi = simple_closure(n, src, dst)
    ends, other = np.r_[lo, hi], np.r_[hi, lo]
    order = np.argsort(ends, kind="stable")
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(ends, minlength=n), out=indptr[1:])
    return indptr, other[order]


def _rows(indptr, nbr, frontier):
    """(u, v) of every edge out of the vertices `frontier`."""
    starts, lengths = indptr[frontier], indptr[frontier + 1] - indptr[frontier]
    total = int(lengths.sum())
    first = np.cumsum(lengths) - lengths
    slot = np.arange(total) - np.repeat(first - starts, lengths)
    return np.repeat(frontier, lengths), nbr[slot]


def brandes(indptr, nbr, s, sigma_dtype=np.float64):
    """(delta_s, depth) from one source: delta float64 (depth -1 where
    unreached). `sigma_dtype` keeps the path counts in that precision,
    rounded after every sum (the precision test: bfloat16)."""
    n = len(indptr) - 1
    depth = np.full(n, -1, np.int64)
    sigma = np.zeros(n, np.float64)
    depth[s], sigma[s] = 0, 1.0
    frontier, levels = np.array([s]), []
    while len(frontier):
        u, v = _rows(indptr, nbr, frontier)
        t = depth[frontier[0]]
        v_new = np.unique(v[depth[v] == -1])
        depth[v_new] = t + 1
        down = depth[v] == t + 1  # the edges of the DAG out of level t
        u, v = u[down], v[down]
        np.add.at(sigma, v, sigma[u])
        sigma[v_new] = sigma[v_new].astype(sigma_dtype).astype(np.float64)
        levels.append((u, v))
        frontier = v_new
    delta = np.zeros(n, np.float64)
    for u, v in reversed(levels):
        np.add.at(delta, u, sigma[u] / sigma[v] * (1.0 + delta[v]))
    delta[s] = 0.0
    return delta, depth


class GapBc:
    @staticmethod
    def expect(data, sources, sigma_dtype=np.float64, **_):
        """`betweenness` (float64, the sum over `sources`) and `depth`
        (int64, one row a source, -1 unreached)."""
        indptr, nbr = adjacency(data.n, data.src, data.dst)
        scores = np.zeros(data.n, np.float64)
        depths = []
        for s in sources:
            delta, depth = brandes(indptr, nbr, int(s), sigma_dtype)
            scores += delta
            depths.append(depth)
        return {"betweenness": scores, "depth": np.array(depths)}

    @staticmethod
    def errors(got, want):
        """(largest relative error, vertices where both read exactly 0) of
        float32 scores against the reference's."""
        got = np.asarray(got, np.float64)
        exact = want["betweenness"]
        rel = np.abs(got - exact) / np.where(exact > 0, exact, 1.0)
        return float(rel.max(initial=0.0)), int(
            np.count_nonzero((got == 0.0) & (exact == 0.0)))

    @staticmethod
    def agrees(got, want) -> bool:
        """`got` is the float32 `betweenness` of every vertex. It must be
        finite; exactly 0.0 wherever the reference is 0 (leaves, unreached
        vertices, vertices on no shortest path: an empty sum); and within
        `RTOL` relative of the float64 sum everywhere else, which also
        refuses a 0 where the reference is positive."""
        exact = want["betweenness"]
        got = np.asarray(got)
        if got.shape != exact.shape or got.dtype != np.float32:
            return False
        got = got.astype(np.float64)
        if not np.all(np.isfinite(got)):
            return False
        if np.any(got[exact == 0.0] != 0.0):
            return False
        return bool(np.all(np.abs(got - exact) <= RTOL * exact))


REFERENCES = {"gap-bc": GapBc}
