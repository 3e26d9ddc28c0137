"""GAP BC, exactly: `BetweennessCentralityProgram` through
`graph.compute().program(...).submit()` on the single-device executor's
frontier engine (`FrontierEngine.run_brandes`) against the benchmark's
plain float64 reference, which shares no code with the package; the
engine's own agreements (columns against single sources, wide rounds
against narrow hops, exact zeros, the path-length identity); and every
path that runs supersteps refusing the program by name.

Tolerances, and why: scores are float32 sums of positive terms in the
order the scatter or the pack's tree gives, so the program is held to the
reference's `agrees` (1e-4 relative of float64, exact 0.0 wherever float64
reads 0), and two runs of the program that sum in other orders to 1e-5
relative (a few float32 roundings a level, a dozen levels)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

from references import bc as reference  # noqa: E402

from janusgraph_tpu.core.graph import open_graph  # noqa: E402
from janusgraph_tpu.olap import delta as D  # noqa: E402
from janusgraph_tpu.olap import intersect  # noqa: E402
from janusgraph_tpu.olap.cpu_executor import CPUExecutor  # noqa: E402
from janusgraph_tpu.olap.csr import (  # noqa: E402
    csr_from_edges,
    load_csr_snapshot,
    simple_closure,
)
from janusgraph_tpu.olap.programs import (  # noqa: E402
    BetweennessCentralityProgram,
)
from janusgraph_tpu.olap.tpu_executor import TPUExecutor  # noqa: E402

agrees = reference.GapBc.agrees
#: two float32 runs of the program that sum in other orders
RUNS_RTOL = 1e-5
#: the narrow rungs' floors: every hop of a small graph is otherwise wide
NARROW = {"frontier_e_min": 16, "frontier_f_min": 8}


class Edges:
    def __init__(self, n, src, dst):
        self.n = n
        self.src = np.asarray(src, np.int32)
        self.dst = np.asarray(dst, np.int32)


def expected(data, sources, **kw):
    return reference.GapBc.expect(data, sources=sources, **kw)


def submit(data, sources):
    """Through the normal path, on a snapshot adopted warm (as the
    benchmark's `gap-trials` driver warms one)."""
    g = open_graph({"storage.backend": "inmemory"})
    try:
        csr = csr_from_edges(data.n, data.src, data.dst)
        D.get_snapshot(g).adopt(csr, g.backend.mutation_epoch())
        return g.compute().program(
            BetweennessCentralityProgram(sources)).submit()
    finally:
        g.close()


def run(data, sources, **executor):
    ex = TPUExecutor(csr_from_edges(data.n, data.src, data.dst), **executor)
    return ex.run(BetweennessCentralityProgram(sources))["betweenness"], ex


def rmat(scale, seed=None):
    from janusgraph_tpu.olap.generators import rmat_edges

    return Edges(*rmat_edges(scale, 8, seed=scale if seed is None else seed))


def with_an_edge(data, k, seed=0):
    """k distinct vertices with an edge that is no self loop."""
    lo, hi = simple_closure(data.n, data.src, data.dst)
    able = np.unique(np.r_[lo, hi])
    return tuple(int(v) for v in np.random.default_rng(seed).choice(
        able, k, replace=False))


def grid(side):
    """A side x side grid: path counts are binomials, far above 2^8."""
    i = np.arange(side * side).reshape(side, side)
    src = np.r_[i[:, :-1].ravel(), i[:-1, :].ravel()]
    dst = np.r_[i[:, 1:].ravel(), i[1:, :].ravel()]
    return Edges(side * side, src, dst)


PATH = Edges(7, np.arange(6), np.arange(1, 7))
GRAPHS = {
    "path": (PATH, (0, 3)),
    "star": (Edges(12, np.zeros(11, int), np.arange(1, 12)), (1, 2)),
    "even-cycle": (Edges(6, np.arange(6), (np.arange(6) + 1) % 6), (0,)),
    "k33-and-a-tail": (Edges(
        8, [0, 0, 0, 1, 1, 1, 2, 2, 2, 5, 6],
        [3, 4, 5, 3, 4, 5, 3, 4, 5, 6, 7]), (0, 7, 4)),
    "two-components": (Edges(6, [0, 1, 3, 4, 5], [1, 2, 4, 5, 3]), (0, 3)),
    "parallel-edges-and-loops": (Edges(
        7, np.r_[np.arange(6), np.arange(1, 7), [2, 2, 5], [4]],
        np.r_[np.arange(1, 7), np.arange(6), [2, 2, 5], [3]]), (0, 3)),
    "rmat-8": (rmat(8), None),
    "rmat-9": (rmat(9), None),
    "rmat-10": (rmat(10), None),
}

HAND = {
    # from 0 a vertex scores what lies beyond it; from 3 likewise on
    # each side; the sources' own dependencies are left out
    "path": [0, 6, 6, 3, 4, 2, 0],
    # every pair of leaves meets at the centre: 10 other leaves a source
    "star": [20] + [0] * 11,
    # vertex 3 is reached by TWO paths (sigma 2): each side carries half
    "even-cycle": [0, 1.5, 0.5, 0, 0.5, 1.5],
    # from 0 three paths (via 3, 4, 5) to each of 1 and 2, and the tail
    # hangs off 5: 3 and 4 carry 2/3, 5 carries 8/3, 6 carries 1; from 7
    # everything passes 6 (6) and 5 (5), and 0, 1, 2 each a third of two;
    # from 4: 0, 1, 2 a third of 3 and all of 5's side (4/3), 5 2, 6 1
    "k33-and-a-tail": [2, 2, 2, 2 / 3, 2 / 3, 29 / 3, 8, 0],
    # the other component is unreached: 0, exactly
    "two-components": [0, 1, 0, 0, 0, 0],
    # its simple form is PATH: parallel copies and loops change nothing
    "parallel-edges-and-loops": [0, 6, 6, 3, 4, 2, 0],
}


def _sources(name):
    data, sources = GRAPHS[name]
    return data, sources or with_an_edge(data, 4, seed=len(name))


@pytest.mark.parametrize("narrow", [False, True], ids=["wide", "narrow"])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_submit_equals_the_plain_reference(name, narrow):
    data, sources = _sources(name)
    want = expected(data, sources)
    if name in HAND:  # the reference itself, against sums made by hand
        np.testing.assert_allclose(want["betweenness"], HAND[name],
                                   rtol=1e-12)
    if narrow:
        got, ex = run(data, sources, **NARROW)
        info = ex.last_run_info
    else:
        result = submit(data, sources)
        got, info = result.states["betweenness"], result.run_info
        assert info["routing"]["routed"] == "tpu"
        assert "fallback" not in info["routing"]
    assert got.dtype == np.float32 and got.shape == (data.n,)
    assert agrees(got, want), reference.GapBc.errors(got, want)
    assert info["path"] == "brandes" and info["sources"] == list(sources)
    # the deepest level any column reached, as the reference counts it
    assert info["levels"] == want["depth"].max()


def test_columns_equal_the_sum_of_single_sources():
    data = rmat(10)
    sources = with_an_edge(data, 4, seed=3)
    together, _ = run(data, sources, **NARROW)
    alone = sum(run(data, (s,), **NARROW)[0].astype(np.float64)
                for s in sources)
    np.testing.assert_allclose(together, alone, rtol=RUNS_RTOL)
    assert np.array_equal(together == 0, alone == 0)


def test_wide_rounds_equal_narrow_hops():
    """The same graph with every hop wide (a small graph's one rung) and
    with the floors lowered so that most hops are narrow."""
    data = rmat(10)
    sources = with_an_edge(data, 4, seed=5)
    wide, ex_wide = run(data, sources)
    narrow, ex_narrow = run(data, sources, **NARROW)
    wide_tiers = [t["wide"] for t in ex_wide.last_run_info["tiers"]]
    narrow_tiers = [t["wide"] for t in ex_narrow.last_run_info["tiers"]]
    assert all(wide_tiers) and narrow_tiers.count(False) >= 4
    np.testing.assert_allclose(wide, narrow, rtol=RUNS_RTOL)
    assert np.array_equal(wide == 0, narrow == 0)
    # both sweeps walk the same levels, at the forward hop's tier
    for info in (ex_wide.last_run_info, ex_narrow.last_run_info):
        forward = {t["hop"]: t for t in info["tiers"]
                   if t["sweep"] == "forward"}
        for t in info["tiers"]:
            if t["sweep"] == "backward":
                assert t == dict(forward[t["hop"]], sweep="backward")


def test_leaves_and_the_unreached_score_exactly_zero():
    data = rmat(9)
    sources = with_an_edge(data, 4, seed=1)
    got, _ = run(data, sources)
    lo, hi = simple_closure(data.n, data.src, data.dst)
    degree = np.bincount(np.r_[lo, hi], minlength=data.n)
    leaves = (degree <= 1) & ~np.isin(np.arange(data.n), sources)
    assert leaves.sum() > 0 and (degree == 0).sum() > 0
    assert (got[leaves] == 0.0).all()
    want = expected(data, sources)
    assert np.array_equal(got == 0.0, want["betweenness"] == 0.0)


@pytest.mark.parametrize("scale", [8, 10])
def test_scores_sum_to_the_interior_vertices_of_every_path(scale):
    """sum_v betweenness(v) = sum_s sum over t reached, t != s, of
    (d(s, t) - 1): each shortest path from s to t has d - 1 interior
    vertices, and the dependencies split them over the paths."""
    data = rmat(scale)
    sources = with_an_edge(data, 4, seed=scale)
    got, _ = run(data, sources, **NARROW)
    depth = expected(data, sources)["depth"]
    interior = int(np.where(depth > 0, depth - 1, 0).sum())
    assert interior > 0
    assert got.sum(dtype=np.float64) == pytest.approx(interior, rel=1e-5)


def test_bfloat16_path_counts_break_the_tolerance():
    """The precision below the program's: the reference with sigma kept in
    bfloat16 (2^-9 a rounding) misses 1e-4 relative by far."""
    import ml_dtypes

    data = grid(16)
    want = expected(data, (0, 255, 17))
    coarse = expected(data, (0, 255, 17), sigma_dtype=ml_dtypes.bfloat16)
    assert not agrees(coarse["betweenness"].astype(np.float32), want)
    error, _ = reference.GapBc.errors(coarse["betweenness"], want)
    assert error > 10 * reference.RTOL
    got, _ = run(data, (0, 255, 17))
    assert agrees(got, want)


def test_the_run_record_counts_both_sweeps():
    from janusgraph_tpu.observability import registry

    data = rmat(9)
    sources = with_an_edge(data, 4, seed=2)
    got, ex = run(data, sources, **NARROW)
    info = ex.last_run_info
    assert registry.last_run("olap")["path"] == "brandes"
    trace = info["tiers"]
    forward = [t for t in trace if t["sweep"] == "forward"]
    backward = [t for t in trace if t["sweep"] == "backward"]
    # the last forward hop reaches nothing; levels L ... 2 run backward
    assert info["forward_rounds"] == len(forward) == info["levels"] + 1
    assert info["backward_rounds"] == len(backward) == info["levels"] - 1
    assert [t["hop"] for t in backward] == list(range(info["levels"], 1, -1))
    assert info["rounds"] == len(trace) == info["supersteps"]
    assert info["wide_rounds"] == sum(t["wide"] for t in trace)
    assert info["relaxed_slots"] == sum(t["edges"] for t in trace)
    assert info["tier_slots"] >= info["relaxed_slots"]
    lo, _ = simple_closure(data.n, data.src, data.dst)
    assert info["closure_slots"] == 2 * len(lo)
    assert info["autotune"]["e_schedule"][-1] == 2 * len(lo)
    assert "strategy_resolved" not in info  # no dense superstep ran


def test_second_submit_builds_transfers_and_compiles_nothing():
    data = rmat(9)
    sources = with_an_edge(data, 4, seed=4)
    ex = TPUExecutor(csr_from_edges(data.n, data.src, data.dst))
    first = ex.run(BetweennessCentralityProgram(sources))
    closure, pack = ex._simple, ex._hybrid_packs[ex.SIMPLE_VIEW]
    other = with_an_edge(data, 4, seed=44)
    ex.run(BetweennessCentralityProgram(other))
    again = ex.run(BetweennessCentralityProgram(sources))
    assert ex._simple is closure and ex._hybrid_packs[ex.SIMPLE_VIEW] is pack
    assert ex.last_run_info["retraces"] == 0
    np.testing.assert_array_equal(first["betweenness"], again["betweenness"])


def test_relabelled_graph_gives_the_relabelled_answer_and_one_shape():
    """`--seed` relabels one structure: the answer under a permutation is
    the permuted answer, every tier is the same, and nothing compiles
    anew (ROADMAP S3)."""
    data = rmat(9)
    sources = with_an_edge(data, 4, seed=6)
    want = expected(data, sources)
    runs = []
    for seed in (1, 2):
        perm = np.random.default_rng(seed).permutation(data.n)
        relabelled = Edges(data.n, perm[data.src], perm[data.dst])
        got, ex = run(relabelled, tuple(int(perm[s]) for s in sources),
                      **NARROW)
        assert agrees(got[perm], want)
        runs.append([(t["sweep"], t["F_cap"], t["E_cap"], t["wide"])
                     for t in ex.last_run_info["tiers"]])
    assert runs[0] == runs[1]


def test_the_sweeps_are_named_and_the_min_steps_are_not_touched():
    data = rmat(8)
    ex = TPUExecutor(csr_from_edges(data.n, data.src, data.dst), **NARROW)
    ex.run(BetweennessCentralityProgram(with_an_edge(data, 2)))
    keys = [k for k in ex._compiled if isinstance(k, tuple)]
    brandes = [k for k in keys if k[0] == "brandes"]
    assert {k[1] for k in brandes} == {"forward", "backward"}
    assert not [k for k in keys if k[0].startswith("frontier-")]
    engine = ex._frontier_engine
    for key in brandes:
        fn = ex._compiled[key]
        _, sweep, f_cap, e_cap, k = key
        n = data.n
        state = (np.zeros((n, k), np.int32), np.zeros((n, k), np.float32))
        third = (np.zeros(n, bool) if sweep == "forward"
                 else np.zeros((n, k), np.float32))
        args = (ex._hybrid_pack(ex.SIMPLE_VIEW).arrays if e_cap == 0
                else engine._simple_args())
        text = fn.lower(*state, third, np.int32(1), args).as_text(
            debug_info=True)
        assert f"jit_brandes_{sweep}" in text
        assert f"brandes.{sweep}" in text
        assert "bf16" not in text and "f64" not in text


def test_the_host_loop_keeps_the_executor_phases():
    from janusgraph_tpu.observability import registry

    def counts():
        snap = registry.snapshot()
        return {p: snap.get(f"phase.executor.{p}", {}).get("count", 0)
                for p in ("setup", "dispatch", "tier", "sync", "fetch",
                          "publish")}

    data = rmat(9)
    before = counts()
    _, ex = run(data, with_an_edge(data, 4, seed=8))
    moved = {k: v - before[k] for k, v in counts().items()}
    forward = ex.last_run_info["forward_rounds"]
    # the tier choice lies under `executor.dispatch` (`executor.tier` is
    # the frontier path's): a plan and its sync a forward hop and one for
    # the empty union that ends the sweep; the backward sweep and the wait
    # for its last level once
    assert moved["tier"] == 0 and moved["setup"] >= 1
    assert moved["dispatch"] == forward + 2
    assert moved["sync"] == forward + 2
    assert moved["fetch"] == 1 and moved["publish"] == 1


def test_the_shared_closure_builder_is_the_intersection_engines():
    """One builder: the LCC tables read `csr.simple_closure`, and the
    Brandes view holds both orientations of each of its pairs."""
    data = rmat(9)
    src, dst = data.src.astype(np.int64), data.dst.astype(np.int64)
    lo, hi = simple_closure(data.n, src, dst)
    want_lo, want_hi = reference.simple_closure(data.n, src, dst)
    np.testing.assert_array_equal(lo, want_lo)
    np.testing.assert_array_equal(hi, want_hi)
    engine = intersect.IntersectEngine
    view = intersect.IntersectView(
        data.n, src, dst, engine.WIDTHS, engine.WORD_NS,
        engine.CANDIDATE_NS, engine.TABLE_BYTES_LIMIT, engine.CLASS_FLOOR)
    assert view.simple_edges == len(lo)
    ex = TPUExecutor(csr_from_edges(data.n, data.src, data.dst))
    s, d = ex._simple_closure()
    assert (np.diff(s) >= 0).all()  # by source: an out-CSR
    pairs = np.sort(s * data.n + d)
    np.testing.assert_array_equal(
        pairs, np.sort(np.r_[lo * data.n + hi, hi * data.n + lo]))


def test_loaded_through_the_store():
    rng = np.random.default_rng(7)
    g = open_graph({"schema.default": "auto"})
    try:
        tx = g.new_transaction()
        vs = [tx.add_vertex() for _ in range(30)]
        for _ in range(70):
            a, b = rng.integers(0, 30, 2)
            tx.add_edge(vs[int(a)], "knows", vs[int(b)])
        tx.commit()
        csr = load_csr_snapshot(g)[0]
        src = np.repeat(np.arange(csr.num_vertices), np.diff(csr.out_indptr))
        data = Edges(csr.num_vertices, src, csr.out_dst)
        sources = with_an_edge(data, 3)
        result = g.compute().program(
            BetweennessCentralityProgram(sources)).submit()
        want = expected(data, sources)
        assert agrees(result.states["betweenness"], want)
        assert result.run_info["path"] == "brandes"
        assert want["betweenness"].sum() > 0
    finally:
        g.close()


@pytest.mark.parametrize("sources", [(), tuple(range(9))],
                         ids=["none", "nine"])
def test_one_to_eight_sources(sources):
    with pytest.raises(ValueError, match="1 to 8 sources"):
        BetweennessCentralityProgram(sources)


def test_a_source_outside_the_graph_is_refused():
    with pytest.raises(ValueError, match="not all vertex indices"):
        run(PATH, (0, 7))


# ----------------------------------------------- who refuses it, by name
def _mesh(csr, **kwargs):
    import jax
    from jax.sharding import Mesh

    from janusgraph_tpu.parallel import ShardedExecutor

    return ShardedExecutor(
        csr, mesh=Mesh(np.array(jax.devices()[:8]), ("p",)), **kwargs)


def _with_overlay(csr, program):
    """A pending overlay, handed to the executor fused."""
    g = open_graph({"schema.default": "auto"})
    try:
        tx = g.new_transaction()
        vs = [tx.add_vertex() for _ in range(8)]
        for a in range(7):
            tx.add_edge(vs[a], "knows", vs[a + 1])
        tx.commit()
        base, epoch = load_csr_snapshot(g)
        tx = g.new_transaction()
        tx.add_edge(tx.get_vertex(vs[0].id), "knows",
                    tx.get_vertex(vs[5].id))
        tx.commit()
        overlay, _ = D.overlay_since(g, epoch)
        return TPUExecutor(base, delta=D.OverlayView(base, overlay)).run(
            program)
    finally:
        g.close()


@pytest.mark.parametrize("runner,named", [
    (lambda csr, p: CPUExecutor(csr).run(p), "the CPU executor"),
    (lambda csr, p: CPUExecutor(csr, strategy="hybrid").run(p),
     "the CPU executor"),
    (lambda csr, p: TPUExecutor(csr).run(p, frontier="off"),
     "dense superstep path of the single-device executor"),
    (lambda csr, p: TPUExecutor(csr, frontier="off").run(p),
     "dense superstep path of the single-device executor"),
    (lambda csr, p: TPUExecutor(csr).run(
        p, checkpoint_path="/nonexistent/ck", checkpoint_every=1),
     "dense superstep path of the single-device executor"),
    (lambda csr, p: _mesh(csr).run(p), "the sharded executor"),
    (lambda csr, p: _mesh(csr, exchange="blocked").run(p),
     "the sharded executor"),
    (lambda csr, p: _with_overlay(csr, p), "the fused delta overlay"),
], ids=["cpu-scalar", "cpu-hybrid", "tpu-frontier-off-run",
        "tpu-frontier-off-executor", "tpu-checkpointed", "mesh",
        "mesh-halo-exchange", "delta-overlay"])
def test_other_paths_refuse_the_program_by_name(runner, named):
    data = rmat(8)
    with pytest.raises(ValueError) as refused:
        runner(csr_from_edges(data.n, data.src, data.dst),
               BetweennessCentralityProgram((0, 1)))
    assert named in str(refused.value)
    assert "BetweennessCentralityProgram" in str(refused.value)


def test_a_pending_overlay_is_materialized_before_the_program_runs():
    rng = np.random.default_rng(3)
    g = open_graph({"schema.default": "auto"})
    try:
        tx = g.new_transaction()
        vs = [tx.add_vertex() for _ in range(24)]
        for _ in range(60):
            a, b = rng.integers(0, 24, 2)
            tx.add_edge(vs[int(a)], "knows", vs[int(b)])
        tx.commit()
        g.compute().program(BetweennessCentralityProgram((0, 1))).submit()
        tx = g.new_transaction()
        for _ in range(10):
            a, b = rng.integers(0, 24, 2)
            tx.add_edge(tx.get_vertex(vs[int(a)].id), "knows",
                        tx.get_vertex(vs[int(b)].id))
        tx.commit()
        assert not D.program_delta_compatible(
            BetweennessCentralityProgram((0, 1)))
        second = g.compute().program(
            BetweennessCentralityProgram((0, 1))).submit()
        assert "delta" not in second.run_info  # no fused overlay ran
        fresh = load_csr_snapshot(g)[0]
        src = np.repeat(
            np.arange(fresh.num_vertices), np.diff(fresh.out_indptr))
        want = expected(Edges(fresh.num_vertices, src, fresh.out_dst), (0, 1))
        assert agrees(second.states["betweenness"], want)
    finally:
        g.close()


def test_a_graph_past_the_guards_is_refused_by_name(monkeypatch):
    from janusgraph_tpu.olap.frontier import FrontierEngine

    data = rmat(8)
    monkeypatch.setattr(FrontierEngine, "MAX_EDGES", data.src.size)
    with pytest.raises(ValueError) as refused:
        run(data, (0, 1))
    assert "BetweennessCentralityProgram" in str(refused.value)
    assert "|E| < 2^30" in str(refused.value)
