"""Plain references over the generated edge list: numpy / scipy, float64,
independent of the package's executors. A reference is a pair: `expect`
computes the answer from the data, `agrees` holds the system's answer to
the guarantee the configuration states."""

from __future__ import annotations

import numpy as np

#: ranks: the executor's float32 against float64. Every rank is at least
#: (1 - damping) / n, so a purely relative bound checks them all.
RANK_RTOL = 1e-4


class PageRank:
    """Power iteration with dangling mass spread evenly (the program's
    stated semantics), a fixed number of iterations."""

    @staticmethod
    def expect(data, max_iterations=20, damping=0.85, **_):
        import scipy.sparse as sp

        n = data.n
        outdeg = np.bincount(data.src, minlength=n).astype(np.float64)
        dangling = outdeg == 0
        inv = 1.0 / np.maximum(outdeg, 1.0)
        # transposed adjacency, duplicate edges summed: row v gathers from
        # the sources of v's in-edges
        pull = sp.csr_matrix(
            (np.ones(data.m, np.float64), (data.dst, data.src)), shape=(n, n)
        )
        rank = np.full(n, 1.0 / n)
        for _ in range(max_iterations):
            rank = (1.0 - damping) / n + damping * (
                pull @ (rank * inv) + rank[dangling].sum() / n
            )
        return rank

    @staticmethod
    def agrees(got, want) -> bool:
        got = np.asarray(got, np.float64)
        return (
            got.shape == want.shape
            and bool(np.isfinite(got).all())
            and float(np.max(np.abs(got - want) / want)) <= RANK_RTOL
        )


class BfsDistances:
    """Hop distance from the root along out-edges, level by level, to at
    most `max_iterations` hops; unreached is infinity."""

    @staticmethod
    def expect(data, seed_index, max_iterations=4, **_):
        indptr, nbr = data.out_lists
        dist = np.full(data.n, np.inf)
        dist[seed_index] = 0.0
        frontier = np.array([seed_index], np.int64)
        for hop in range(1, max_iterations + 1):
            starts, ends = indptr[frontier], indptr[frontier + 1]
            total = int((ends - starts).sum())
            if total == 0:
                break
            # every out-neighbour of the frontier, by slices of `nbr`
            offsets = np.repeat(starts - np.cumsum(ends - starts)
                                + (ends - starts), ends - starts)
            reached = np.unique(nbr[offsets + np.arange(total)])
            frontier = reached[np.isinf(dist[reached])]
            dist[frontier] = float(hop)
        return dist

    @staticmethod
    def agrees(got, want) -> bool:
        got = np.asarray(got, np.float64)
        # the program marks "unreached" with a large finite number
        got = np.where(got <= np.nanmax(want[np.isfinite(want)]), got, np.inf)
        return got.shape == want.shape and bool(np.array_equal(got, want))


class TwoHopDistinctCount:
    """g.V(v).out().out().dedup().count()"""

    @staticmethod
    def expect(data, index, **_):
        indptr, nbr = data.out_lists
        first = nbr[indptr[index]:indptr[index + 1]]
        second = [nbr[indptr[u]:indptr[u + 1]] for u in first]
        return int(len(np.unique(np.concatenate(second)))) if second else 0

    @staticmethod
    def agrees(got, want) -> bool:
        return type(got) is int and got == want


class OutDegreeCount:
    """g.V(v).out().count(): out-edges, duplicates counted."""

    @staticmethod
    def expect(data, index, **_):
        indptr, _ = data.out_lists
        return int(indptr[index + 1] - indptr[index])

    agrees = TwoHopDistinctCount.agrees


REFERENCES = {
    "pagerank": PageRank,
    "bfs-distances": BfsDistances,
    "two-hop-distinct-count": TwoHopDistinctCount,
    "out-degree-count": OutDegreeCount,
}
