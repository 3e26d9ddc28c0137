"""Single-source shortest path / BSP BFS (BASELINE config #3 workload).

Reference behavior modeled: TinkerPop ShortestPathVertexProgram as run by
FulgoraGraphComputer (special-cased at FulgoraGraphComputer.java:249-253)
and janusgraph-backend-testutils .../olap/ShortestDistanceVertexProgram.java:
min-combined distance relaxation until fixpoint. Unweighted mode is BFS
hop counting; weighted mode adds the edge weight in flight.
"""

from __future__ import annotations

from janusgraph_tpu.olap.vertex_program import (
    Combiner,
    EdgeTransform,
    VertexProgram,
)

INF = 1e18


class ShortestPathProgram(VertexProgram):
    """Min-relaxation SSSP / BFS.

    track_paths=True additionally materializes a predecessor array so actual
    paths can be reconstructed on host (reference: TinkerPop
    ShortestPathVertexProgram materializes paths, special-cased at
    FulgoraGraphComputer.java:249-253; the TPU-native form is a predecessor
    index per vertex + host chain-walk, not per-traverser path objects).
    Unweighted: at superstep t the frontier is exactly {dist == t}, so
    the message is the sender's own (global) index where it is on the
    frontier and +inf elsewhere; MIN-combining yields, at each newly reached
    vertex, the smallest-index frontier neighbor as its predecessor —
    float32-exact (indices < 2^24), no wide encodings needed.

    The modes, and who runs them:

    - ``weighted=False`` (BFS hop counts), with or without ``track_paths``
      and ``undirected``: every executor.
    - ``weighted=True`` (distances only), directed or ``undirected``: every
      executor. ``distance`` is the greatest fixpoint below the start
      vector of ``d[v] = min(d[v], fl32(d[u] + w(u, v)))`` over every edge
      (from both ends where ``undirected``; a parallel edge once per copy):
      float32, one add a relaxation, defined bit for bit whatever the
      order of relaxations.
    - ``weighted=True, track_paths=True`` (Graph500 kernel 3's result with
      ``undirected=True``): the single-device frontier engine
      (``olap/frontier.py``, what ``frontier="auto"`` picks). It returns
      ``distance`` as above and ``predecessor``: for a reached vertex ``v``
      other than the root a vertex ``p`` joined to ``v`` by an edge of some
      weight ``w`` with ``fl32(distance[p] + w) == distance[v]``; the root
      is its own predecessor; unreached is -1; following predecessors from
      any reached vertex ends at the root, also over an edge of weight 0
      or one the addition absorbs (``FrontierEngine._parent_fn`` has the
      rule). Which of several valid parents comes back is the engine's
      choice (the smallest index). A weighted parent is an arg-min, which
      no per-column monoid fold gives, so the dense superstep path
      (``frontier="off"``, checkpointed or delta-fused runs), ``CPUExecutor``
      and the sharded executor refuse the combination by name
      (``require_dense_capable``); ``weighted_predecessors`` derives a
      parent array on the host from distances any of them computed.
    """

    compute_keys = ("distance",)
    combiner = Combiner.MIN
    setup_only_params = ("seed_index",)

    def __init__(
        self,
        seed_index: int,
        weighted: bool = False,
        undirected: bool = False,
        max_iterations: int = 100,
        track_paths: bool = False,
    ):
        self.seed_index = seed_index
        self.weighted = weighted
        self.track_paths = track_paths
        self.edge_transform = (
            EdgeTransform.ADD_WEIGHT if weighted else EdgeTransform.NONE
        )
        self.undirected = undirected
        self.max_iterations = max_iterations
        if track_paths:
            self.compute_keys = ("distance", "predecessor")

    def require_dense_capable(self, path: str) -> None:
        if self.weighted and self.track_paths:
            raise ValueError(
                "ShortestPathProgram(weighted=True, track_paths=True) "
                "returns a weighted parent array, an arg-min over a "
                f"vertex's relaxations that no monoid fold gives: {path} "
                "folds messages with a monoid — run it on the single-"
                "device frontier engine (executor='tpu', frontier='auto' "
                "or 'always', no checkpoint, no pending delta overlay), or "
                "run distances alone and derive parents with "
                "weighted_predecessors(csr, result, seed)"
            )

    def setup(self, graph, xp):
        idx = xp.arange(graph.local_num_vertices) + graph.global_offset
        dist = xp.where(idx == self.seed_index, 0.0, INF)
        state = {"distance": dist}
        if self.track_paths:
            if graph.num_vertices >= (1 << 24):
                raise ValueError(
                    "track_paths stores vertex indices in float32 state, "
                    "exact only below 2^24 vertices; run distances without "
                    "paths at this scale"
                )
            # seed points at itself; unreached at -1
            state["predecessor"] = xp.where(
                idx == self.seed_index, float(self.seed_index), -1.0
            )
        return state, {"changed": (Combiner.SUM, xp.asarray(1.0))}

    def message(self, state, superstep, graph, xp):
        if self.track_paths:
            idx = xp.arange(graph.local_num_vertices) + graph.global_offset
            on_frontier = state["distance"] == superstep
            return xp.where(on_frontier, idx.astype(state["distance"].dtype), INF)
        if self.weighted:
            return state["distance"]
        return state["distance"] + 1.0

    def apply(self, state, aggregated, superstep, memory_in, graph, xp):
        old = state["distance"]
        if self.track_paths:
            newly = (old >= INF) & (aggregated < INF)
            dist = xp.where(newly, superstep + 1.0, old)
            pred = xp.where(newly, aggregated, state["predecessor"])
            changed = xp.sum(xp.where(newly, 1.0, 0.0))
            return (
                {"distance": dist, "predecessor": pred},
                {"changed": (Combiner.SUM, changed)},
            )
        new = xp.minimum(old, aggregated)
        changed = xp.sum(xp.where(new < old, 1.0, 0.0))
        return {"distance": new}, {"changed": (Combiner.SUM, changed)}

    def terminate(self, memory):
        return memory.get("changed", 1.0) == 0.0

    def terminate_device(self, values, steps_done, xp):
        return values["changed"] == 0.0


def reconstruct_path(result, target_index: int):
    """Walk the predecessor chain host-side: [seed, ..., target], or None if
    the target was never reached. `result` is a run() output of a
    track_paths=True program."""
    import numpy as np

    pred = np.asarray(result["predecessor"]).astype(np.int64)
    dist = np.asarray(result["distance"])
    if target_index >= len(pred) or dist[target_index] >= INF:
        return None
    path = [int(target_index)]
    v = int(target_index)
    for _ in range(len(pred)):
        p = int(pred[v])
        if p < 0:
            return None
        if p == v:  # seed reached
            return list(reversed(path))
        path.append(p)
        v = p
    return None  # cycle guard — malformed predecessor array


def weighted_predecessors(csr, result, seed_index: int):
    """Predecessor array for a WEIGHTED run, derived host-side from the
    converged distances in one vectorized O(E) pass: v's predecessor is
    any in-neighbor u with dist[u] + w(u,v) == dist[v] (ties broken by
    first slot). The dense device program cannot carry predecessors in
    weighted mode (its frontier-index encoding is hop-count-based), but at a
    FIXPOINT the relaxation equation identifies them exactly — so paths
    come from distances, not from extra device state. (The single-device
    frontier engine now returns weighted parents itself, exact and on the
    device: `ShortestPathProgram(weighted=True, track_paths=True)`; this
    pass stays for distances from any other executor.) Returns an array
    shaped like the unweighted tracker: pred[seed] = seed, -1 where
    unreached, ready for reconstruct_path (reference capability:
    TinkerPop ShortestPathVertexProgram with the distance modulator).
    Float tolerance: weights accumulate in f32 on device, so the
    equality check allows 1e-4 relative slack."""
    import numpy as np

    dist = np.asarray(result["distance"], dtype=np.float64)
    n = csr.num_vertices
    if csr.in_edge_weight is None:
        raise ValueError(
            "weighted_predecessors needs a weight-materialized CSR "
            "(load_csr(..., weight_key=...))"
        )
    src = csr.in_src.astype(np.int64)
    w = csr.in_edge_weight.astype(np.float64)
    dstv = np.repeat(
        np.arange(n, dtype=np.int64), np.diff(csr.in_indptr)
    )
    cand = dist[src] + w
    ok = np.abs(cand - dist[dstv]) <= 1e-4 * np.maximum(
        1.0, np.abs(dist[dstv])
    )
    ok &= dist[dstv] < INF
    ok &= src != dstv  # a self-loop must never be its own predecessor
    pred = np.full(n, -1, dtype=np.int64)
    pred[seed_index] = seed_index
    # Phase 1 — STRICT edges (dist[u] < dist[v]): any satisfying slot is
    # a valid predecessor; chains strictly decrease in distance, so no
    # cycles are possible.
    strict = ok & (dist[src] < dist[dstv])
    s_slots = np.nonzero(strict)[0][::-1]  # first slot wins
    mask = pred[dstv[s_slots]] == -1
    # the seed's pred stays itself even if a strict in-edge matches
    mask &= dstv[s_slots] != seed_index
    pred[dstv[s_slots][mask]] = src[s_slots][mask]
    # Phase 2 — zero-weight (sub-tolerance) equality edges: dist[u] ==
    # dist[v]. Naive slot-order picks can form u<->v cycles; instead BFS
    # from the already-assigned set through these edges, so every
    # assignment points strictly toward the seed along a real shortest
    # path (the entering vertex of each equal-distance class was
    # assigned in phase 1, or IS the seed).
    eq_slots = np.nonzero(ok & (dist[src] >= dist[dstv]))[0]
    if len(eq_slots):
        from collections import defaultdict, deque

        out_eq = defaultdict(list)  # u -> [v] over equality edges
        for i in eq_slots:
            out_eq[int(src[i])].append(int(dstv[i]))
        # graphlint: disable=JG206 -- BFS work queue: each vertex enqueues at most once (pred guard), so the bound is the vertex count
        queue = deque(int(v) for v in np.nonzero(pred != -1)[0])
        while queue:
            u = queue.popleft()
            for v in out_eq.get(u, ()):
                if pred[v] == -1:
                    pred[v] = u
                    queue.append(v)
    return pred
