"""Connected components by min-label propagation (BASELINE config #2).

Reference behavior modeled: TinkerPop ConnectedComponentVertexProgram via
FulgoraGraphComputer — every vertex starts with its own label and adopts the
minimum label among itself and its (undirected) neighbors until fixpoint.
Labels are global dense vertex indices (exactly representable in float64 up
to 2^53), mapped back to 64-bit vertex ids after the run.

`max_iterations=None` (the default) runs to the fixpoint: the single-device
executor's frontier engine answers with Afforest (`olap/frontier.py`
`run_cc`), every other path propagates labels until no vertex changes, at
most n supersteps (propagation settles within n - 1). An integer is a cut:
the labels after that many supersteps of propagation, which only a
superstep path has, so it never routes to Afforest.
"""

from __future__ import annotations

from typing import Optional

from janusgraph_tpu.olap.vertex_program import Combiner, VertexProgram


class ConnectedComponentsProgram(VertexProgram):
    compute_keys = ("component",)
    combiner = Combiner.MIN
    undirected = True

    def __init__(self, max_iterations: Optional[int] = None):
        self.max_iterations = max_iterations

    def setup(self, graph, xp):
        labels = (
            xp.arange(graph.local_num_vertices) + graph.global_offset
        ) * 1.0
        return {"component": labels}, {
            "changed": (Combiner.SUM, xp.asarray(1.0))
        }

    def message(self, state, superstep, graph, xp):
        return state["component"]

    def apply(self, state, aggregated, superstep, memory_in, graph, xp):
        old = state["component"]
        new = xp.minimum(old, aggregated)
        changed = xp.sum(xp.where(new < old, 1.0, 0.0))
        return {"component": new}, {"changed": (Combiner.SUM, changed)}

    def terminate(self, memory):
        return memory.get("changed", 1.0) == 0.0

    def terminate_device(self, values, steps_done, xp):
        return values["changed"] == 0.0
