"""Local clustering coefficient, exactly (LDBC Graphalytics LCC; ROADMAP
C9b, M4).

Reference behavior modeled: LDBC Graphalytics specification v1.0
(arXiv:2011.15028), algorithm LCC. With N(v) the SET of vertices other
than v joined to v by an edge in either direction and d = |N(v)|:

    lcc(v) = 2 T(v) / (d (d - 1)),   0 where d < 2

where T(v) is the number of unordered pairs {u, w} of N(v) joined by an
edge in either direction (the triangles through v). Parallel edges count
once, self loops never, direction is ignored, isolated vertices read 0.

No message / fold / apply superstep computes it: a common-neighbour count
reads two adjacency rows against each other. The single-device executor
runs the program on its intersection engine (`olap/intersect.py`), one
compiled pass a submit; every executor whose run is supersteps refuses it
by name (`require_dense_capable`). States: `triangles` (int32, T(v),
exact) and `lcc` (`float32(T) / float32(d (d - 1) / 2)`).

Departure from the specification: a graph with a vertex of degree 65,536
or more is refused (its count of neighbour pairs passes int32).
"""

from __future__ import annotations

from janusgraph_tpu.olap.vertex_program import VertexProgram


class LCCProgram(VertexProgram):
    compute_keys = ("triangles", "lcc")
    undirected = True
    max_iterations = 1
    #: the mesh's executor runs supersteps: auto-routing keeps the program
    #: on one device (olap/computer.py), as it keeps an sddmm program
    sharded_compatible = False
    #: a pending overlay is folded into fresh arrays before the program
    #: runs (olap/delta.program_delta_compatible)
    fuses_delta_overlay = False

    def require_dense_capable(self, path: str) -> None:
        raise ValueError(
            "LCCProgram counts common neighbours, two adjacency rows read "
            f"against each other, which no message fold gives: {path} runs "
            "message / fold / apply supersteps — run it on the single-"
            "device executor's intersection engine (executor='tpu', "
            "frontier='auto', no checkpoint, no pending delta overlay)"
        )
