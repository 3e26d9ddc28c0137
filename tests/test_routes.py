"""Which path runs a program on the single-device executor, and what that
path writes: one small graph a route, the run record's `path`, its exact
set of keys, the slot and round totals, and the moves of the registry
counters the engines and the dense pack count (`olap.frontier.*`,
`olap.intersect.*`, `olap.pack.row_gathers`): an unweighted search
moves the bottom-up counters where its hops went bottom-up, a weighted one
never does."""

import numpy as np
import pytest

from janusgraph_tpu.observability import registry
from janusgraph_tpu.olap import csr_from_edges
from janusgraph_tpu.olap.programs import (
    CDLPProgram,
    ConnectedComponentsProgram,
    PageRankProgram,
    PeerPressureProgram,
    ShortestPathProgram,
)
from janusgraph_tpu.olap.programs.betweenness import (
    BetweennessCentralityProgram,
)
from janusgraph_tpu.olap.programs.lcc import LCCProgram
from janusgraph_tpu.olap.tpu_executor import TPUExecutor

COUNTERS = (
    "olap.frontier.rounds", "olap.frontier.relaxed_slots",
    "olap.frontier.tier_slots", "olap.frontier.wide_rounds",
    "olap.frontier.bottom_up_rounds", "olap.frontier.bottom_up_slots",
    "olap.intersect.runs", "olap.intersect.candidates",
    "olap.intersect.probe_slots", "olap.pack.row_gathers",
)

#: every run record carries these, whichever path wrote it
SHARED = {
    "combiner", "compile_cache", "d2h_bytes", "device_count", "device_kind",
    "device_memory", "ell_pad_ratio", "h2d_arg_bytes", "pad_ratio", "path",
    "platform", "resources", "retraces", "roofline", "roofline_by_tier",
    "slowest_superstep", "superstep_records", "supersteps", "wall_s",
}
DENSE = SHARED | {"autotune", "gather_words", "strategy_resolved"}
FUSED = DENSE | {"compile_in_first_dispatch", "first_dispatch_s"}
HOPS = SHARED | {
    "autotune", "relaxed_slots", "rounds", "tier_slots", "tiers",
    "wide_rounds",
}
#: a search's record (`FrontierEngine.run`), weighted or not
SEARCH = HOPS | {
    "bottom_up_rounds", "bottom_up_slots", "bottom_up_tier_slots",
    "examined_slots", "gather_words", "parent_slots",
}


def graph(seed=5, n=90, m=360, weights=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = rng.uniform(0.5, 2.0, m).astype(np.float32) if weights else None
    return csr_from_edges(n, src, dst, w)


ROUTES = {
    "dense-fused-pagerank": dict(
        program=lambda: PageRankProgram(max_iterations=5),
        path="fused", keys=FUSED,
        values=dict(supersteps=5, gather_words=2, strategy_resolved="hybrid",
                    combiner="sum", pad_ratio=1.0361),
        moved={"olap.pack.row_gathers": 5},
    ),
    "host-loop-peer-pressure": dict(
        program=lambda: PeerPressureProgram(rounds=4),
        path="host-loop", keys=DENSE,
        values=dict(supersteps=8, gather_words=64, strategy_resolved="hybrid",
                    combiner="sum+min", pad_ratio=1.0097),
        moved={},
    ),
    "frontier-bfs": dict(
        program=lambda: ShortestPathProgram(seed_index=0),
        path="frontier", keys=SEARCH,
        values=dict(supersteps=6, rounds=6, relaxed_slots=10,
                    tier_slots=746, wide_rounds=2, parent_slots=0,
                    bottom_up_rounds=4, bottom_up_slots=473,
                    bottom_up_tier_slots=1980, examined_slots=483,
                    gather_words=2, combiner="min", pad_ratio=1.0361),
        moved={"olap.frontier.rounds": 6, "olap.frontier.relaxed_slots": 10,
               "olap.frontier.tier_slots": 746,
               "olap.frontier.wide_rounds": 2, "olap.pack.row_gathers": 2,
               "olap.frontier.bottom_up_rounds": 4,
               "olap.frontier.bottom_up_slots": 473},
    ),
    "frontier-bfs-undirected-parents": dict(
        program=lambda: ShortestPathProgram(
            seed_index=0, undirected=True, track_paths=True),
        path="frontier", keys=SEARCH,
        values=dict(supersteps=5, rounds=5, relaxed_slots=9,
                    tier_slots=1454, wide_rounds=2, parent_slots=0,
                    bottom_up_rounds=3, bottom_up_slots=541,
                    bottom_up_tier_slots=1620, examined_slots=550,
                    gather_words=2, combiner="min", pad_ratio=1.0097),
        moved={"olap.frontier.rounds": 5, "olap.frontier.relaxed_slots": 9,
               "olap.frontier.tier_slots": 1454,
               "olap.frontier.wide_rounds": 2, "olap.pack.row_gathers": 2,
               "olap.frontier.bottom_up_rounds": 3,
               "olap.frontier.bottom_up_slots": 541},
    ),
    "frontier-weighted-sssp-parents": dict(
        program=lambda: ShortestPathProgram(
            seed_index=0, weighted=True, track_paths=True, undirected=True),
        graph=dict(weights=True),
        path="frontier", keys=SEARCH,
        values=dict(supersteps=5, rounds=5, relaxed_slots=875,
                    tier_slots=3635, wide_rounds=5, parent_slots=727,
                    bottom_up_rounds=0, bottom_up_slots=0,
                    bottom_up_tier_slots=0, examined_slots=875,
                    gather_words=2, combiner="min", pad_ratio=1.0097),
        moved={"olap.frontier.rounds": 5, "olap.frontier.relaxed_slots": 875,
               "olap.frontier.tier_slots": 3635,
               "olap.frontier.wide_rounds": 5, "olap.pack.row_gathers": 5},
    ),
    "afforest-cc-always": dict(
        program=lambda: ConnectedComponentsProgram(),
        graph=dict(n=120, m=150), run=dict(frontier="always"),
        path="frontier", keys=HOPS | {
            "algorithm", "closure_slots", "compress_jumps", "finish_slots",
            "finish_vertices", "giant_share", "link_passes",
            "sample_vertices", "vertices",
        },
        values=dict(supersteps=6, rounds=1, relaxed_slots=10, tier_slots=300,
                    wide_rounds=0, finish_slots=10, finish_vertices=6,
                    closure_slots=300, sample_vertices=116, vertices=120,
                    giant_share=110 / 120, algorithm="afforest",
                    link_passes={"sample": 5, "finish": 1},
                    compress_jumps={"sample": 13, "finish": 2},
                    combiner="min", pad_ratio=None),
        moved={"olap.frontier.rounds": 1, "olap.frontier.relaxed_slots": 10,
               "olap.frontier.tier_slots": 300},
    ),
    "capped-cc-dense": dict(
        program=lambda: ConnectedComponentsProgram(max_iterations=3),
        path="fused", keys=FUSED,
        values=dict(supersteps=3, gather_words=2, strategy_resolved="hybrid",
                    combiner="min", pad_ratio=1.0097),
        moved={"olap.pack.row_gathers": 3},
    ),
    "intersect-lcc": dict(
        program=lambda: LCCProgram(),
        path="intersect", keys=SHARED | {
            "candidates", "dispatches", "intersect", "probe_slots",
            "simple_edges", "triangles_total",
        },
        values=dict(supersteps=1, dispatches=1, candidates=817,
                    probe_slots=8388608, simple_edges=346,
                    triangles_total=86, combiner="sum", pad_ratio=None),
        moved={"olap.intersect.runs": 1, "olap.intersect.candidates": 817,
               "olap.intersect.probe_slots": 8388608},
    ),
    "brandes-bc": dict(
        program=lambda: BetweennessCentralityProgram(sources=(0, 3, 7)),
        path="brandes", keys=HOPS | {
            "backward_rounds", "closure_slots", "forward_rounds",
            "gather_words", "levels", "sources",
        },
        values=dict(supersteps=8, rounds=8, relaxed_slots=2543,
                    tier_slots=5608, wide_rounds=8, closure_slots=692,
                    levels=4, forward_rounds=5, backward_rounds=3,
                    sources=[0, 3, 7], gather_words=3, combiner="sum",
                    pad_ratio=1.013),
        moved={"olap.frontier.rounds": 8, "olap.frontier.relaxed_slots": 2543,
               "olap.frontier.tier_slots": 5608,
               "olap.frontier.wide_rounds": 8},
    ),
    "cdlp-always-runs-fused": dict(
        program=lambda: CDLPProgram(3), run=dict(frontier="always"),
        path="fused", keys=FUSED | {"mode_fold"},
        values=dict(supersteps=3, gather_words=2, strategy_resolved="hybrid",
                    combiner="mode", pad_ratio=1.0097,
                    mode_fold={"torso_slots": 720, "tail_slots": 0,
                               "rows_folded_whole": 0}),
        moved={"olap.pack.row_gathers": 3},
    ),
}


@pytest.mark.parametrize("name", list(ROUTES))
def test_each_route_writes_its_own_record(name):
    case = ROUTES[name]
    ex = TPUExecutor(graph(**case.get("graph", {})))
    before = {c: registry.counter(c).count for c in COUNTERS}
    ex.run(case["program"](), **case.get("run", {}))
    info = ex.last_run_info
    moved = {
        c: registry.counter(c).count - before[c] for c in COUNTERS
        if registry.counter(c).count != before[c]
    }
    assert info["path"] == case["path"]
    assert set(info) == case["keys"]
    assert {k: info[k] for k in case["values"]} == case["values"]
    assert moved == case["moved"]
    # one record a superstep, a hop or a tier; each carries the frontier
    assert len(info["superstep_records"]) == (
        len(info["tiers"]) if "tiers" in info else info["supersteps"]
    )
    assert registry.last_run("olap") == info
