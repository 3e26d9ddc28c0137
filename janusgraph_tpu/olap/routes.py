"""Which engine runs a program, under which guards: one table keyed by the
program's exact type (a subclass may override message / apply, so it is
never routed).

A routed program runs on an engine of its own (`olap/frontier.py`,
`olap/intersect.py`), whose entry returns the states and the run record's
fields it owns; every other program runs dense supersteps. A run whose
frontier mode is "off", that checkpoints or that has a delta overlay
pending takes no route (`TPUExecutor._route`). A program with no superstep
form is then refused by name (`require_dense_capable`), as on every
executor whose run is supersteps; auto-routing keeps it off the mesh, and
a pending overlay is folded before it runs.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

from janusgraph_tpu.olap.frontier import FrontierEngine
from janusgraph_tpu.olap.programs.betweenness import (
    BetweennessCentralityProgram,
)
from janusgraph_tpu.olap.programs.connected_components import (
    ConnectedComponentsProgram,
)
from janusgraph_tpu.olap.programs.lcc import LCCProgram
from janusgraph_tpu.olap.programs.shortest_path import ShortestPathProgram

#: float32 vertex indices (labels, predecessors) are exact below this
EXACT_VERTICES = 1 << 24

#: graphs below this edge count run CC through the fused dense path under
#: frontier="auto": Afforest's two executables, its host sync and its
#: finish's tier compile only amortize once a dense superstep costs more
#: than dispatch (BFS keeps frontier at every size — its dense path
#: rescans |E| for hops that touch a handful of vertices)
FRONTIER_CC_MIN_EDGES = 1 << 20

CHECKPOINT_REFUSAL = (
    "frontier='always' cannot be combined with checkpointing (the frontier "
    "loop does not checkpoint) — drop checkpoint_path or use "
    "frontier='auto'"
)


class Route(NamedTuple):
    #: the executor's engine, made on first use by its `_<engine>()`
    engine: str
    #: the engine's method that runs the program
    entry: str
    #: (program, n, m) -> whether the guards admit n vertices (the mesh's
    #: padded n) and m edges
    fits: Callable[[object, int, int], bool]
    #: whether a superstep path may run the program instead
    superstep_form: bool = True
    #: the entry answers to the fixpoint only: a superstep cap asks for a
    #: state Afforest never has (the mesh's propagation has it)
    fixpoint_only: bool = False
    #: under frontier="auto", taken from FRONTIER_CC_MIN_EDGES edges on
    sized: bool = False

    def admits(self, program, n, m, mode, min_edges=FRONTIER_CC_MIN_EDGES):
        return self.fits(program, n, m) and (
            mode == "always" or not self.sized or m >= min_edges
        )


def _hops_fit(program, n, m) -> bool:
    """int32 expansion, and float32-exact vertex indices."""
    return m < FrontierEngine.MAX_EDGES and n < EXACT_VERTICES


def _sssp_fits(program, n, m) -> bool:
    # predecessors are float32 indices, the dense path's setup() raises
    # from 2^24 vertices: a search without them has no vertex bound
    return m < FrontierEngine.MAX_EDGES and not (
        program.track_paths and n >= EXACT_VERTICES
    )


ROUTES = {
    ShortestPathProgram: Route("frontier", "run", _sssp_fits),
    ConnectedComponentsProgram: Route(
        "frontier", "run_cc", _hops_fit, fixpoint_only=True, sized=True
    ),
    BetweennessCentralityProgram: Route(
        "frontier", "run_brandes", _hops_fit, superstep_form=False
    ),
    # the degree guard refuses inside the engine, by name
    LCCProgram: Route(
        "intersect", "run", lambda program, n, m: True, superstep_form=False
    ),
}


def route_of(program):
    """The program's Route, None for the dense superstep path."""
    return ROUTES.get(type(program))


def has_superstep_form(program) -> bool:
    route = route_of(program)
    return route is None or route.superstep_form
