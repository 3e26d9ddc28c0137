"""LDBC Graphalytics CDLP, exactly: `Combiner.MODE` (the first combiner that
is no monoid) and `CDLPProgram` on every path that folds whole multisets,
against a plain reference that shares no code with the executors; and every
path that would fold it from partials refusing it by name.

The plain reference is `reference_cdlp` below: per vertex a
`collections.Counter` over the labels of its in- and out-neighbours, the
smallest label on ties, synchronous."""

from collections import Counter

import numpy as np
import pytest

from janusgraph_tpu.core.graph import open_graph
from janusgraph_tpu.olap import delta as D
from janusgraph_tpu.olap import kernels
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.csr import csr_from_edges, load_csr_snapshot
from janusgraph_tpu.olap.programs import CDLPProgram, PageRankProgram
from janusgraph_tpu.olap.routes import route_of
from janusgraph_tpu.olap.tpu_executor import TPUExecutor
from janusgraph_tpu.olap.vertex_program import Combiner

NO = Combiner.NO_MESSAGE


def reference_cdlp(n, src, dst, rounds, history=False):
    """Plain CDLP under the directed rule: every edge delivers src's label
    to dst and dst's to src (so a reciprocal pair counts twice, a parallel
    edge once per copy, a self loop twice)."""
    label = list(range(n))
    seen = [list(label)]
    for _ in range(rounds):
        received = [[] for _ in range(n)]
        for s, d in zip(src, dst):
            received[int(d)].append(label[int(s)])
            received[int(s)].append(label[int(d)])
        new = []
        for v in range(n):
            if not received[v]:
                new.append(label[v])  # no neighbour: keeps its label
                continue
            counts = Counter(received[v])
            most = max(counts.values())
            new.append(min(lb for lb, c in counts.items() if c == most))
        label = new
        seen.append(list(label))
    return seen if history else np.asarray(label, dtype=np.int64)


def adopted(n, src, dst, **options):
    """A graph whose snapshot is the edge list, as the benchmark's
    `submit-loop` driver warms one (no store scan)."""
    g = open_graph({"storage.backend": "inmemory", **options})
    csr = csr_from_edges(n, np.asarray(src), np.asarray(dst))
    D.get_snapshot(g).adopt(csr, g.backend.mutation_epoch())
    return g


def submit(n, src, dst, rounds, **options):
    g = adopted(n, src, dst, **options)
    try:
        return g.compute().program(CDLPProgram(rounds)).submit()
    finally:
        g.close()


def every_case_graph(seed, n=96):
    """A seeded random graph that holds every case the fold must get right:
    multi-edges, self loops, reciprocal edges, isolated vertices, ties at
    every count, a vertex whose neighbours all differ, and hubs far above a
    small hub cutoff / row capacity."""
    rng = np.random.default_rng(seed)
    src, dst = [], []

    def edge(a, b, copies=1):
        src.extend([a] * copies)
        dst.extend([b] * copies)

    live = n - 8  # the last 8 vertices stay isolated
    for _ in range(4 * live):  # background, skewed towards low ids
        a = int(rng.integers(0, live) * rng.random())
        edge(a, int(rng.integers(0, live)))
    for hub in (0, 1, 2):  # hubs: hundreds of neighbours, some repeated
        for v in rng.integers(3, live, 150 + 60 * hub):
            edge(int(v), hub, copies=int(rng.integers(1, 3)))
    for v in rng.integers(0, live, 6):  # self loops: own label twice
        edge(int(v), int(v))
    for _ in range(10):  # reciprocal pairs: the neighbour counts twice
        a, b = (int(x) for x in rng.integers(0, live, 2))
        edge(a, b)
        edge(b, a)
    # a vertex whose neighbours all differ (takes the minimum), and ties at
    # counts 2 and 3 between two labels
    edge(live - 1, live - 2), edge(live - 3, live - 1), edge(live - 4, live - 1)
    for copies in (2, 3):
        t = live - 5 - copies
        edge(10 + copies, t, copies), edge(t, 20 + copies, copies)
    return n, np.asarray(src), np.asarray(dst)


# --------------------------------------------------------------- the rule
def test_ldbc_directed_rule_on_a_hand_written_graph():
    """Expected labels written out round by round. Edges: 0->1, 1->0 (a
    reciprocal pair: counts twice), 2->1, 3->2, 3->4, 4->3, 4->5; vertex 6
    has no neighbour."""
    src = [0, 1, 2, 3, 3, 4, 4]
    dst = [1, 0, 1, 2, 4, 3, 5]
    # round 1: v0 hears {1,1} -> 1; v1 hears {0,0,2} -> 0; v2 hears {1,3}
    # (tie) -> 1; v3 hears {2,4,4} -> 4; v4 hears {3,3,5} -> 3; v5 hears
    # {4} -> 4; v6 nothing -> 6
    # round 2: v0 {0,0} -> 0; v1 {1,1,1} -> 1; v2 {0,4} -> 0; v3 {1,3,3}
    # -> 3; v4 {4,4,4} -> 4; v5 {3} -> 3
    # round 3: v0 {1,1} -> 1; v1 {0,0,0} -> 0; v2 {1,3} -> 1; v3 {0,4,4}
    # -> 4; v4 {3,3,3} -> 3; v5 {4} -> 4
    want = [
        [0, 1, 2, 3, 4, 5, 6],
        [1, 0, 1, 4, 3, 4, 6],
        [0, 1, 0, 3, 4, 3, 6],
        [1, 0, 1, 4, 3, 4, 6],
    ]
    assert reference_cdlp(7, src, dst, 3, history=True) == want
    for rounds in (0, 1, 2, 3):
        for executor in ("cpu", "tpu"):
            got = submit(7, src, dst, rounds,
                         **{"computer.executor": executor})
            assert got.states["label"].tolist() == want[rounds], (
                rounds, executor)


# ------------------------------------------- reference = cpu = tpu, exactly
PINNED = {
    "cpu-scalar": {"computer.executor": "cpu"},
    "default": {},
    # every destination in the exact-width torso: no tail at all
    "torso-only": {"computer.autotune-hub-cutoff": 1024},
    # hubs above the cutoff (a chunked tail) and rows above the capacity
    # (split rows), both configured small
    "tail-and-split": {
        "computer.autotune-hub-cutoff": 8,
        "computer.autotune-tail-chunk": 4, "computer.ell-max-capacity": 32},
    # one chunk a row of the capacity's width: every hub is split rows
    "split": {"computer.autotune-hub-cutoff": 8,
              "computer.ell-max-capacity": 16},
}


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("pinned", sorted(PINNED))
def test_submit_equals_the_plain_reference(pinned, seed):
    n, src, dst = every_case_graph(seed)
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    assert deg.max() > 128 and (deg == 0).sum() >= 8  # hubs, isolated
    result = submit(n, src, dst, 4, **PINNED[pinned])
    got = result.states["label"]
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, reference_cdlp(n, src, dst, 4))
    info = result.run_info
    assert info["combiner"] == "mode"
    if "cpu" in pinned:
        assert info["path"] == "cpu"
        return
    assert info["path"] == "fused" and info["supersteps"] == 4
    assert info["routing"]["routed"] == "tpu"
    assert info["strategy_resolved"] == "hybrid"
    sizes = info["mode_fold"]
    assert sizes["torso_slots"] + sizes["tail_slots"] >= 2 * len(src)
    if "split" in pinned:
        # the hubs went through the whole-row fold, not chunk partials
        assert sizes["tail_slots"] > 0 and sizes["rows_folded_whole"] >= 3


@pytest.mark.parametrize("strategy", ["ell", "hybrid"])
def test_numpy_pack_paths_equal_the_reference(strategy):
    """The CPU oracle's pack strategies replay the kernels in numpy."""
    n, src, dst = every_case_graph(3)
    csr = csr_from_edges(n, src, dst)
    got = CPUExecutor(csr, strategy=strategy).run(CDLPProgram(3))["label"]
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, reference_cdlp(n, src, dst, 3))


@pytest.mark.parametrize("scale", [8, 10])
def test_rmat_ten_rounds(scale):
    from janusgraph_tpu.olap.generators import rmat_edges

    n, src, dst = rmat_edges(scale, 8, seed=scale)
    want = reference_cdlp(n, src, dst, 10)
    for options in ({}, {"computer.autotune-hub-cutoff": 16,
                         "computer.autotune-tail-chunk": 8,
                         "computer.ell-max-capacity": 64}):
        result = submit(n, src, dst, 10, **options)
        np.testing.assert_array_equal(result.states["label"], want)
        assert result.run_info["supersteps"] == 10


def test_ten_rounds_are_one_fused_dispatch():
    n, src, dst = every_case_graph(5)
    ex = TPUExecutor(csr_from_edges(n, src, dst))
    program = CDLPProgram()
    assert program.max_iterations == 10 and program.fused_eligible()
    assert program.undirected and program.combiner == Combiner.MODE
    assert program.compute_keys == ("label",)
    ex.run(program)
    info = ex.last_run_info
    assert info["path"] == "fused" and info["supersteps"] == 10
    assert len([k for k in ex._compiled if k[0] == "fused"]) == 1
    # one dispatch: the first carries all ten rounds
    assert info["first_dispatch_s"] > 0 and info["retraces"] == 1


# -------------------------------------------------------------- precision
def test_labels_above_2_pow_24_stay_exact():
    """n just above 2^24 with a handful of edges among the highest ids: a
    float32 label path collapses 2^24 and 2^24 + 1 and answers wrong."""
    n = (1 << 24) + 8
    top = n - 1
    src = np.asarray([top - 1, top - 2, top - 3, top - 5, top - 4])
    dst = np.asarray([top, top, top - 1, top - 4, top - 5])
    # the program's state at that size: int32, every id its own label
    graph = type("G", (), {"num_vertices": n, "local_num_vertices": n,
                           "global_offset": 0})()
    program = CDLPProgram(1)
    state, metrics = program.setup(graph, np)
    label = state["label"]
    assert label.dtype == np.int32 and metrics == {}
    assert label[top] == top and label[1 << 24] == 1 << 24
    # the fold over those labels, through the ELL replay's split-row
    # function (both orientations: every edge delivers twice)
    receiver = np.concatenate([dst, src]).astype(np.int32)
    sent = label[np.concatenate([src, dst])]
    want = np.full(n, NO, dtype=np.int64)
    rounds = reference_cdlp_sparse(src, dst)
    for v, lb in rounds.items():
        want[v] = lb
    got = kernels.segment_mode(sent, receiver, n)
    np.testing.assert_array_equal(got, want)
    new, _ = program.apply(state, got.astype(np.int32), 0, {}, graph, np)
    assert new["label"].dtype == np.int32
    assert new["label"][top] == top - 2        # min of {top-1, top-2}
    assert new["label"][top - 1] == top - 3    # tie {top, top-3}
    assert new["label"][0] == 0                # no message: kept
    # what a float32 path would have said: two distinct ids collapse
    as_float = sent.astype(np.float32)
    assert len(set(as_float.tolist())) < len(set(sent.tolist()))
    assert int(np.float32(top - 2)) != top - 2 or int(
        np.float32(top - 3)) != top - 3


def reference_cdlp_sparse(src, dst):
    """One round of the plain rule over a handful of edges, as a dict."""
    received = {}
    for s, d in zip(src.tolist(), dst.tolist()):
        received.setdefault(d, []).append(s)
        received.setdefault(s, []).append(d)
    out = {}
    for v, labels in received.items():
        counts = Counter(labels)
        most = max(counts.values())
        out[v] = min(lb for lb, c in counts.items() if c == most)
    return out


# ------------------------------------------------------------ the kernels
@pytest.mark.parametrize("axis", [0, 1])
def test_mode_along_ties_padding_and_empty_rows(axis):
    import jax.numpy as jnp

    rows = np.asarray([
        [5, 3, 5, 3, NO, NO, NO, NO],   # tie at 2 -> 3
        [7, 7, 7, 1, 1, NO, NO, NO],    # 7 wins 3:2
        [NO] * 8,                       # nothing: NO_MESSAGE
        [9, 8, 7, 6, 5, 4, 3, 2],       # all differ -> the minimum
        [4, NO, NO, NO, NO, NO, NO, NO],  # padding never wins
        [2, 2, 2, 2, 2, 2, 2, 2],
    ], dtype=np.int32)
    want = [3, 7, NO, 2, 4, 2]
    block = rows if axis == 1 else rows.T
    assert kernels.mode_along(np, block, axis).tolist() == want
    assert np.asarray(
        kernels.mode_along(jnp, jnp.asarray(block), axis)).tolist() == want


def test_run_lengths_count_from_the_run_start():
    s = np.asarray([1, 1, 1, 2, 3, 3, 3, 3, 3, 9], dtype=np.int32)
    assert kernels._run_lengths(np, (s,), 0).tolist() == [
        1, 2, 3, 1, 1, 2, 3, 4, 5, 1]


def test_hybrid_pack_folds_every_hub_whole():
    n, src, dst = every_case_graph(7)
    s2, d2 = np.concatenate([src, dst]), np.concatenate([dst, src])
    pack = kernels.HybridPack(s2, d2, None, n, hub_cutoff=8, tail_chunk=4,
                              max_capacity=32)
    deg = np.bincount(d2, minlength=n)
    assert "rowseg" in pack.arrays  # some hub was split into rows
    tables = pack.mode_tables()
    hubs = int((deg > 8).sum())
    assert sum(h for _k, h in pack.mode_tail_meta) == hubs
    # every chunk of the tail belongs to exactly one hub's row
    pieces = tables["mode_pieces"]
    real = pieces[pieces < pack.tail_chunks]
    assert sorted(real.tolist()) == list(range(pack.tail_chunks))
    assert sorted(tables["mode_unpermute"].tolist()) == list(range(n))
    assert kernels.mode_fold_sizes(pack)["rows_folded_whole"] == hubs
    # a monoid program's arrays are what they were: the tables ride apart
    assert not any(k.startswith("mode_") for k in pack.arrays)


def test_mode_messages_must_be_integer_labels():
    n, src, dst = every_case_graph(1)
    pack = kernels.HybridPack(src, dst, None, n)
    with pytest.raises(ValueError, match="MODE"):
        kernels.hybrid_aggregate(
            np, pack, np.ones(n, np.float32), Combiner.MODE)
    with pytest.raises(ValueError, match="MODE"):
        kernels.hybrid_aggregate(
            np, pack, np.ones((n, 2), np.int32), Combiner.MODE)


# --------------------------------------------------------------- refusals
def test_an_unknown_combiner_is_never_computed_as_a_maximum():
    pick = Combiner.monoid
    assert pick("sum", "x", 1, 2, 3) == 1 and pick("max", "x", 1, 2, 3) == 3
    with pytest.raises(ValueError, match="MODE.*some path"):
        pick(Combiner.MODE, "some path", 1, 2, 3)
    with pytest.raises(ValueError, match="unknown combiner 'median'"):
        pick("median", "some path", 1, 2, 3)
    m = np.ones((4, 3), np.float32)
    with pytest.raises(ValueError, match="MODE"):
        kernels.tree_reduce(np, m, Combiner.MODE, axis=0)
    with pytest.raises(ValueError, match="unknown combiner"):
        kernels.tree_reduce(np, m, "median", axis=0)
    with pytest.raises(ValueError, match="MODE"):
        kernels._segment_combine(np, Combiner.MODE, m, np.zeros(4, int), 2)


def test_sharded_executor_refuses_mode():
    from janusgraph_tpu.parallel.sharded import ShardedExecutor

    n, src, dst = every_case_graph(1)
    ex = ShardedExecutor(csr_from_edges(n, src, dst))
    with pytest.raises(ValueError, match="MODE.*sharded executor"):
        ex.run(CDLPProgram(2))
    # and submit() never routes a MODE program onto the mesh
    result = submit(n, src, dst, 2)
    assert result.run_info["routing"]["routed"] == "tpu"
    import jax

    if len(jax.devices()) > 1:
        assert result.run_info["routing"]["reason"] == "mode combiner"
        assert "fallback" not in result.run_info["routing"]


def test_halo_exchange_refuses_mode():
    from janusgraph_tpu.parallel import halo

    with pytest.raises(ValueError, match="MODE.*halo exchange"):
        halo._seg_reduce_np(
            Combiner.MODE, np.ones(4, np.float32), np.zeros(4, int), 2)
    with pytest.raises(ValueError, match="MODE.*halo exchange"):
        halo.replay_superstep(None, np.ones(4, np.float32), Combiner.MODE)


def test_frontier_engine_refuses_mode():
    from janusgraph_tpu.olap.frontier import FrontierEngine

    n, src, dst = every_case_graph(1)
    ex = TPUExecutor(csr_from_edges(n, src, dst))
    engine = FrontierEngine(ex)
    for entry in (engine.run, engine.run_cc):
        with pytest.raises(ValueError, match="MODE.*frontier engine"):
            entry(CDLPProgram(2))
    # the executor never sends it there: 'always' still runs it dense
    assert route_of(CDLPProgram(2)) is None
    ex.run(CDLPProgram(2), frontier="always")
    assert ex.last_run_info["path"] == "fused"


def test_delta_overlay_is_materialized_before_a_mode_program_runs():
    """The fused overlay merges lane partials, so a MODE program never
    consumes it: the executors refuse, and submit() folds the overlay into
    fresh arrays first and answers exactly."""
    g = open_graph({"schema.default": "auto"})
    try:
        rng = np.random.default_rng(4)
        tx = g.new_transaction()
        vs = [tx.add_vertex() for _ in range(40)]
        for _ in range(160):
            a, b = rng.integers(0, 40, 2)
            tx.add_edge(vs[int(a)], "link", vs[int(b)])
        tx.commit()
        first = g.compute().program(CDLPProgram(3)).submit()
        csr, epoch = load_csr_snapshot(g)
        tx = g.new_transaction()
        for _ in range(12):
            a, b = rng.integers(0, 40, 2)
            tx.add_edge(tx.get_vertex(vs[int(a)].id), "link",
                        tx.get_vertex(vs[int(b)].id))
        tx.commit()
        ov, _ = D.overlay_since(g, epoch)
        view = D.OverlayView(csr, ov)
        assert not D.program_delta_compatible(CDLPProgram(3))
        assert D.program_delta_compatible(PageRankProgram())
        with pytest.raises(ValueError, match="MODE.*delta overlay"):
            TPUExecutor(csr, delta=view).run(CDLPProgram(3))
        with pytest.raises(ValueError, match="MODE.*delta overlay"):
            CPUExecutor(csr, strategy="hybrid", delta=view).run(
                CDLPProgram(3))
        with pytest.raises(ValueError, match="MODE.*delta overlay"):
            D.fused_delta_aggregate(np, {}, {}, None, None, Combiner.MODE)
        # the warm path: pending writes, MODE program -> materialized
        second = g.compute().program(CDLPProgram(3)).submit()
        assert "delta" not in second.run_info  # no fused overlay ran
        fresh = load_csr_snapshot(g)[0]
        index = {int(v): i for i, v in enumerate(fresh.vertex_ids)}
        src = np.repeat(np.arange(fresh.num_vertices),
                        np.diff(fresh.out_indptr))
        want = reference_cdlp(fresh.num_vertices, src, fresh.out_dst, 3)
        np.testing.assert_array_equal(second.states["label"], want)
        assert len(index) == 40 and fresh.num_edges == 172
        assert first.states["label"].dtype == np.int32
    finally:
        g.close()


# --------------------------------------------------- scopes and write-back
@pytest.mark.parametrize("program", ["cdlp", "pagerank"])
def test_the_superstep_names_its_four_stages(program):
    """`jax.named_scope` in the shared superstep body: every dense
    program's gather and fold carry the names the benchmark's trace-scope
    reader sums, and none encloses another."""
    import jax
    import jax.numpy as jnp

    n, src, dst = every_case_graph(2)
    ex = TPUExecutor(csr_from_edges(n, src, dst))
    prog = CDLPProgram(2) if program == "cdlp" else PageRankProgram(
        max_iterations=2)
    op = prog.combiner
    state, init = prog.setup(ex.g, jnp)
    mem = {k: jnp.asarray(v, jnp.float32) for k, (_o, v) in init.items()}
    if program == "pagerank":
        mem["delta"] = jnp.asarray(0.0, jnp.float32)
    ex._used_view_keys(prog, op, state=state, mem0=mem)
    text = jax.jit(ex._superstep_body(prog, op)).lower(
        state, jnp.asarray(0, jnp.int32), mem, ex._graph_args(prog, op)
    ).as_text(debug_info=True)
    for stage in ("gather", "fold", "apply"):
        assert f"superstep.{stage}" in text, stage
    if program == "pagerank":
        assert "superstep.message" in text  # CDLP's message is its state
    for outer in ("message", "gather", "fold", "apply"):
        for inner in ("message", "gather", "fold", "apply"):
            assert f"superstep.{outer}/superstep.{inner}" not in text


def test_write_back_stores_integers():
    g = open_graph({"schema.default": "auto"})
    try:
        tx = g.new_transaction()
        vs = [tx.add_vertex() for _ in range(6)]
        for a, b in ((0, 1), (1, 2), (2, 0), (3, 4)):
            tx.add_edge(vs[a], "link", vs[b])
        tx.commit()
        result = g.compute().program(CDLPProgram(2)).submit()
        assert result.states["label"].dtype == np.int32
        assert isinstance(result.value("label", vs[0].id), int)
        assert all(isinstance(v, int)
                   for v in result.by_vertex("label").values())
        result.write_back()
        assert g.schema_cache.get_by_name("label").data_type is int
        tx = g.new_transaction()
        stored = [tx.get_vertex(v.id).value("label") for v in vs]
        assert all(type(x) is int for x in stored)
        ids = [int(i) for i in result.csr.vertex_ids]
        assert stored == [
            int(result.states["label"][ids.index(v.id)]) for v in vs]
    finally:
        g.close()
