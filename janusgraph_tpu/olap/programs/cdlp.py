"""Community detection by label propagation, exactly (LDBC Graphalytics
CDLP; ROADMAP C9).

Reference behavior modeled: LDBC Graphalytics specification v1.0
(arXiv:2011.15028), algorithm CDLP — synchronous and deterministic:

    L_0(v) = v
    L_i(v) = min(argmax_l (|{u in N_in(v):  L_{i-1}(u) = l}|
                         + |{u in N_out(v): L_{i-1}(u) = l}|))

a vertex without neighbours keeps its label; in a directed graph a
neighbour reached in both directions counts twice; a fixed number of
rounds; validated by exact match of every vertex's label. It is the exact
form of TinkerPop's PeerPressureVertexProgram (`peer_pressure.py`, which
stays as it is: K label buckets, approximate once more than K labels are
live).

One superstep is one round: every vertex sends its label along both
orientations of every edge (`undirected`: the executors' symmetric-closure
view IS the directed rule, multiplicities kept) and `Combiner.MODE` folds
each vertex's whole multiset to its most frequent label, the smallest on
ties. Labels are int32 end to end.

Departures from the specification, each deliberate:
- the initial label is the vertex's dense index in the snapshot (0..n-1),
  not its 64-bit id: `result.csr.vertex_ids[label]` maps a label back;
- a multigraph's parallel edge counts once per copy and a self loop twice
  (once per orientation): Graphalytics' datasets hold neither;
- vertices are limited to 2^31 - 1 by the int32 label (the specification
  has 64-bit ids).
"""

from __future__ import annotations

from janusgraph_tpu.olap.vertex_program import Combiner, VertexProgram


class CDLPProgram(VertexProgram):
    compute_keys = ("label",)
    combiner = Combiner.MODE
    undirected = True

    def __init__(self, max_iterations: int = 10):
        self.max_iterations = max_iterations

    def setup(self, graph, xp):
        if graph.num_vertices >= Combiner.NO_MESSAGE:
            raise ValueError(
                "CDLP labels are int32 vertex indices: at most 2^31 - 2 "
                f"vertices (got {graph.num_vertices})"
            )
        label = xp.arange(graph.local_num_vertices, dtype=xp.int32) + (
            xp.asarray(graph.global_offset, dtype=xp.int32)
        )
        return {"label": label}, {}

    def message(self, state, superstep, graph, xp):
        return state["label"]

    def apply(self, state, aggregated, superstep, memory_in, graph, xp):
        # no message (no neighbour in either direction): keep the label
        label = xp.where(
            aggregated == Combiner.NO_MESSAGE, state["label"], aggregated
        )
        return {"label": label}, {}

    # Graphalytics runs every round (labels may oscillate between two
    # colourings for ever), so there is no convergence test: the run stops
    # at max_iterations, which the executors' loops bound by themselves
    def terminate(self, memory):
        return False

    def terminate_device(self, values, steps_done, xp):
        return xp.asarray(False)
