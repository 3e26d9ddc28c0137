"""Betweenness centrality from a few sources, exactly (GAP Benchmark Suite
kernel BC; Brandes 2001; ROADMAP M1's frontier half).

Reference behavior modeled: the GAP Benchmark Suite (Beamer, Asanovic,
Patterson; arXiv:1508.03619), kernel BC, and TinkerPop's "Centrality"
recipe, which documents betweenness as a Gremlin traversal. The graph is
read as GAP's builder reads it: undirected, parallel edges once, self loops
dropped. With sigma_s(v) the number of shortest paths from s to v and
pred_s(w) the neighbours of w one level nearer s:

    delta_s(v) = sum over w with v in pred_s(w) of
                 sigma_s(v) / sigma_s(w) * (1 + delta_s(w))
    betweenness(v) = sum over s in sources of delta_s(v)

A source's own delta_s(s) is not added (Brandes' definition); the scores
are not normalised (GAP divides by the largest afterwards, one host
scalar). State `betweenness`: float32 per vertex.

No message / fold / apply superstep computes it: the path counts are
summed forward level by level and the dependencies summed BACK over the
same levels in reverse. The single-device executor runs the program on
its frontier engine (`FrontierEngine.run_brandes`, by its route in
`olap/routes.py`), the sources as the columns of one `(n, K)` run; every executor whose run is supersteps
refuses it by name (`require_dense_capable`).
"""

from __future__ import annotations

from janusgraph_tpu.olap.vertex_program import VertexProgram

#: sources a run carries as columns
MAX_SOURCES = 8


class BetweennessCentralityProgram(VertexProgram):
    compute_keys = ("betweenness",)
    undirected = True

    def __init__(self, sources):
        sources = tuple(int(s) for s in sources)
        if not 1 <= len(sources) <= MAX_SOURCES:
            raise ValueError(
                f"BetweennessCentralityProgram takes 1 to {MAX_SOURCES} "
                f"sources, got {len(sources)}"
            )
        self.sources = sources

    def require_dense_capable(self, path: str) -> None:
        raise ValueError(
            "BetweennessCentralityProgram sums path counts forward over the "
            "levels of a breadth-first search and dependencies back over "
            f"them in reverse, which no message fold gives: {path} runs "
            "message / fold / apply supersteps — run it on the single-"
            "device executor's frontier engine (executor='tpu', "
            "frontier='auto', no checkpoint, no pending delta overlay, "
            "|E| < 2^30, |V| < 2^24)"
        )
