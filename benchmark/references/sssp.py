"""Plain reference for Graph500 v3 kernel 3, single-source shortest paths
(graph500.org, specification v3: the Kronecker edge list with one weight in
[0, 1) per generated edge, treated as undirected, searched from one key at a
time, each search returning a parent array and a distance array that pass
the specification's validation). int64 / float32 / float64 numpy; nothing
imported from the package.

The system states its distances as the greatest fixpoint below the start
vector of `d[v] = min(d[v], fl32(d[u] + w))` over every edge read from both
ends, a parallel edge once per copy with its own weight. The operator is
monotone, so that fixpoint does not depend on the order of relaxations and
is defined bit for bit: `fixpoint` computes it by Bellman-Ford rounds in
float32; the same rounds in float64, or scipy's Dijkstra (`dijkstra64`,
what `expect` uses at the cell's size), say how far float32 lies from the
real shortest distances."""

from __future__ import annotations

import numpy as np

#: float32 distances against float64 ones. NOT a tolerance on the system's
#: answer (that is compared bit for bit): it states the error of the
#: float32 model itself, a sum of at most a few dozen weights each rounded
#: to 2**-24 relative, and fails a system that computes in a narrower type.
F64_RTOL = 1e-5
#: the program's "unreached" (janusgraph_tpu ShortestPathProgram's INF)
UNREACHED = 1e18


def closure(src, dst, weight, undirected=True):
    """(sender, receiver, weight) of every relaxation the edge list allows,
    as int64 / int64 / float32: each edge once, and once more from its other
    end where `undirected`."""
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    weight = np.asarray(weight, np.float32)
    if not undirected:
        return src, dst, weight
    return (np.concatenate([src, dst]), np.concatenate([dst, src]),
            np.concatenate([weight, weight]))


def by_receiver(sender, receiver, weight):
    """The closure sorted by receiver (in any order within one: `min` does
    not care), with the first slot of every receiver that has one: what
    `fixpoint` folds over."""
    order = np.argsort(receiver)
    sender, receiver, weight = sender[order], receiver[order], weight[order]
    starts = np.flatnonzero(np.r_[True, receiver[1:] != receiver[:-1]])
    return sender, receiver, weight, starts


def fixpoint(n, sender, receiver, weight, root, dtype=np.float32,
             sorted_closure=None):
    """Distances from `root` by Bellman-Ford rounds in `dtype` to the
    fixpoint; unreached vertices are infinite. One add per relaxation, in
    `dtype`; `min` is exact (`np.minimum.reduceat` over the closure sorted
    by receiver). A round folds every slot, or, once few vertices changed
    in the round before, only the slots whose sender did: a slot whose
    sender kept its distance offers what it offered when that distance was
    set, so leaving it out changes no round's result."""
    sender, receiver, weight, starts = sorted_closure or by_receiver(
        sender, receiver, weight)
    weight = weight.astype(dtype)
    heads = receiver[starts] if len(starts) else starts
    dist = np.full(n, np.inf, dtype)
    dist[root] = 0
    changed = np.zeros(n, bool)
    changed[root] = True
    for _ in range(n + 1):
        if not changed.any() or len(sender) == 0:
            return dist
        if 8 * int(changed.sum()) < n:
            live = np.flatnonzero(changed[sender])
            if len(live) == 0:
                return dist
            to = receiver[live]
            first = np.flatnonzero(np.r_[True, to[1:] != to[:-1]])
            best = np.minimum.reduceat(
                dist[sender[live]] + weight[live], first)
            to = to[first]
        else:
            best = np.minimum.reduceat(dist[sender] + weight, starts)
            to = heads
        better = best < dist[to]
        changed[:] = False
        changed[to[better]] = True
        dist[to[better]] = best[better]
    raise AssertionError("no fixpoint within n rounds: a negative weight?")


def dijkstra64(n, sorted_closure, root, undirected=True):
    """Shortest distances from `root` in float64 by scipy's Dijkstra, over
    the closure sorted by receiver as a CSR that keeps parallel edges
    apart (row = receiver: the transpose of the graph, which is the graph
    where it is read from both ends): what float32's fixpoint is held
    against (`F64_RTOL`). The float64 fixpoint of `fixpoint` is the same
    numbers (each a path's weights added in path order) at several times
    the cost on the cell's graph."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra

    sender, receiver, weight, _ = sorted_closure
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(receiver, minlength=n), out=indptr[1:])
    graph = sp.csr_matrix(
        (weight.astype(np.float64), sender, indptr), shape=(n, n))
    if not undirected:
        graph = graph.T.tocsr()
    return dijkstra(graph, directed=True, indices=root)


def validate(n, sender, receiver, w, root, parent, dist, undirected=True):
    """Graph500's validation of one search's (parent, distance), as a list
    of the rules broken (empty: valid), over the slots of `closure` (every
    generated edge, from both ends where `undirected`). `dist` is float32
    with infinity for unreached, `parent` int64 with -1 for unreached.

    1. the root is its own parent at distance 0;
    2. every other reached vertex has a parent joined to it by an edge of
       the generated list whose weight explains its distance:
       `fl32(dist[parent] + w) == dist[vertex]`;
    3. the parent pointers form a tree: from every reached vertex they lead
       to the root (pointer jumping, no recursion);
    4. every generated edge has both ends reached or neither (one end only
       where directed: a reached sender reaches its receiver), and the
       distances of its ends differ by at most its weight, up to one
       float32 rounding of the sum;
    5. an unreached vertex has parent -1, a reached one a vertex."""
    broken = []
    parent = np.asarray(parent, np.int64)
    dist = np.asarray(dist, np.float32)
    reached = np.isfinite(dist)
    if parent.shape != (n,) or dist.shape != (n,):
        return ["shape"]
    if not (reached[root] and parent[root] == root and dist[root] == 0):
        broken.append("root")
    if np.any(parent[~reached] != -1) or np.any(
            (parent[reached] < 0) | (parent[reached] >= n)):
        broken.append("unreached-or-range")
        return broken  # the rules below index by parent

    # 2: among the closure's slots find, per vertex, one from its parent
    # whose weight explains its distance
    with np.errstate(invalid="ignore"):
        explains = (
            (sender == parent[receiver]) & reached[receiver]
            & ((dist[sender] + w).astype(np.float32) == dist[receiver])
        )
    explained = np.zeros(n, bool)
    explained[receiver[explains]] = True
    tree = reached.copy()
    tree[root] = False
    if np.any(tree & ~explained):
        broken.append("parent-edge")

    # 3: pointer jumping; after ceil(log2 n) + 1 doublings every pointer of
    # a tree rests on the root, and one caught in a cycle never does
    hop = np.where(reached, parent, root)
    for _ in range(int(np.ceil(np.log2(max(n, 2)))) + 1):
        hop = hop[hop]
    if np.any(hop[reached] != root):
        broken.append("cycle")

    # 4: edges
    if undirected:
        if np.any(reached[sender] != reached[receiver]):
            broken.append("edge-half-reached")
    elif np.any(reached[sender] & ~reached[receiver]):
        broken.append("edge-half-reached")
    both = reached[sender] & reached[receiver]
    reach = (dist[sender[both]] + w[both]).astype(np.float32)
    if np.any(dist[receiver[both]] > reach):
        broken.append("edge-relaxable")
    return broken


class KernelThree:
    """`expect` gives a key's reference answers; `agrees` holds the
    system's (distance, parent) to them."""

    @staticmethod
    def expect(data, seed_index, undirected=True, **_):
        # the closure and its sort are the data's, not the key's: kept on
        # the data for the next key
        kept = getattr(data, "_kernel3_closure", None)
        if kept is None or kept[0] != undirected:
            sender, receiver, w = closure(
                data.src, data.dst, data.weight, undirected)
            kept = data._kernel3_closure = (
                undirected, (sender, receiver, w),
                by_receiver(sender, receiver, w))
        _, (sender, receiver, w), sorted_closure = kept
        return {
            "n": data.n, "root": seed_index, "undirected": undirected,
            "closure": (sender, receiver, w),
            "f32": fixpoint(data.n, sender, receiver, w, seed_index,
                            sorted_closure=sorted_closure),
            "f64": dijkstra64(data.n, sorted_closure, seed_index,
                              undirected),
        }

    @staticmethod
    def agrees(got, want) -> bool:
        return not KernelThree.disagreements(got, want)

    @staticmethod
    def disagreements(got, want) -> list:
        """What `agrees` found wrong, by name (empty: agrees). `got` is
        {"distance", "predecessor"} as the program returned them."""
        n = want["n"]
        distance = np.asarray(got["distance"])
        parent = np.asarray(got["predecessor"])
        if distance.shape != (n,) or parent.shape != (n,):
            return ["shape"]
        if distance.dtype != np.float32:
            return ["distance-not-float32"]
        if parent.dtype.kind == "f" and not bool(
                np.all(np.isfinite(parent) & (parent == np.floor(parent)))):
            return ["parent-not-integral"]
        dist = np.where(distance >= np.float32(UNREACHED), np.float32(np.inf),
                        distance)
        wrong = []
        # tolerance 0: the fixpoint is defined bit for bit, so a reordered
        # or narrower sum shows here
        if not np.array_equal(dist.view(np.uint32),
                              want["f32"].view(np.uint32)):
            wrong.append("distance-bits")
        finite = np.isfinite(want["f64"])
        if not np.array_equal(np.isfinite(dist), finite) or np.any(
                np.abs(dist[finite] - want["f64"][finite])
                > F64_RTOL * want["f64"][finite]):
            wrong.append("distance-f64")
        wrong += validate(n, *want["closure"], want["root"],
                          parent.astype(np.int64), dist, want["undirected"])
        return wrong


REFERENCES = {"graph500-kernel3": KernelThree}
