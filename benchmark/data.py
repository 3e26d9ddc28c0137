"""The benchmark's own data: a Graph500 R-MAT edge list made from --seed.

A copy of the numpy form of `janusgraph_tpu.olap.generators.rmat_edges`
(same constants, same bit recursion, permuted ids), kept here so that a
change to the program's generator or its native library cannot change what
a cell runs on. It differs from the original in arithmetic (float32 draws,
int32 accumulators, the draws made chunk by chunk so that a chunk stays in
the cache: 2 s at scale 20 against 10.6 s for the plain loop) and in what
the seed does.

The recursion draws from the configuration's `structure_seed`; `--seed`
draws the permutation of vertex ids that Graph500 asks for, and the order
of the traffic. Every seed therefore runs a differently labelled copy of
one graph: other ids, other answers, other bytes on the device, and the
same degrees, the same pack shapes, the same compiled programs and the
same amount of work. A graph drawn anew from each seed gives each seed
its own pack shapes, hence its own compile (38 s of set-up at scale 20)
and its own submit time (0.5% apart, my chip runs, PR 24).
"""

from __future__ import annotations

import numpy as np

#: Graph500 specification, Kronecker generator initiator
A, B, C = 0.57, 0.19, 0.19
_CHUNK = 1 << 18


def rmat_edges(scale: int, edge_factor: int, structure_seed: int, seed: int):
    """(n, src, dst, perm): n = 2**scale vertices, n * edge_factor directed
    edges in the generator's order, int32, and the permutation (drawn from
    `seed`) that gave vertex i of the recursion the id perm[i]. The same
    arguments give the same arrays."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(structure_seed)
    a, ab, abc = np.float32(A), np.float32(A + B), np.float32(A + B + C)
    src = np.empty(m, np.int32)
    dst = np.empty(m, np.int32)
    for lo in range(0, m, _CHUNK):
        k = min(_CHUNK, m - lo)
        draws = rng.random((scale, k), dtype=np.float32)
        s = np.zeros(k, np.int32)
        d = np.zeros(k, np.int32)
        for r in draws:
            lower = r >= ab  # quadrants C and D: the source's bit
            s <<= 1
            s |= lower
            d <<= 1
            d |= ((r >= a) & ~lower) | (r >= abc)  # quadrants B and D
        src[lo:lo + k] = s
        dst[lo:lo + k] = d
    perm = np.random.default_rng(seed).permutation(n).astype(np.int32)
    return n, perm[src], perm[dst], perm


class EdgeList:
    """The generated graph as the drivers and references read it: the edge
    list, the permutation that labelled it, and the out-adjacency
    (duplicates kept) built on first use."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray, perm=None):
        self.n, self.src, self.dst, self.perm = n, src, dst, perm
        self._out = None

    @property
    def m(self) -> int:
        return len(self.src)

    @property
    def out_degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n)

    @property
    def out_lists(self):
        """(indptr, neighbours) of the out-adjacency."""
        if self._out is None:
            order = np.argsort(self.src, kind="stable")
            indptr = np.zeros(self.n + 1, np.int64)
            np.cumsum(np.bincount(self.src, minlength=self.n), out=indptr[1:])
            self._out = indptr, self.dst[order]
        return self._out

    def digest(self) -> str:
        import hashlib

        h = hashlib.sha256(self.src.tobytes())
        h.update(self.dst.tobytes())
        return h.hexdigest()[:16]
