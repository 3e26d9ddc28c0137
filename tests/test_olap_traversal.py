"""OLAP traversal execution (TraversalVertexProgram analogue — reference:
BASELINE config #5 3-hop counts via TraversalVertexProgram through Fulgora):
a step chain compiles into channel-per-superstep BSP over traverser-count
state. Oracle: the OLTP traversal DSL on the same graph.
"""

import numpy as np
import pytest

from janusgraph_tpu.core import gods
from janusgraph_tpu.core.graph import open_graph
from janusgraph_tpu.olap.csr import load_csr
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.programs import OLAPTraversalProgram, steps_from_spec
from janusgraph_tpu.olap.tpu_executor import TPUExecutor
from janusgraph_tpu.parallel import ShardedExecutor


@pytest.fixture(scope="module")
def mesh8():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("p",))


@pytest.fixture()
def g():
    graph = open_graph()
    gods.load(graph)
    yield graph
    graph.close()


def oltp_count(g, spec, seed_name=None):
    t = g.traversal()
    trav = t.V() if seed_name is None else t.V().has("name", seed_name)
    for item in spec:
        direction, labels = (item, ()) if isinstance(item, str) else (
            item[0], item[1] or ()
        )
        trav = {"out": trav.out, "in": trav.in_, "both": trav.both}[direction](
            *labels
        )
    return trav.count()


@pytest.mark.parametrize("spec", [
    [("out", ["father"]), ("out", ["father"])],
    [("out", ["brother"]), ("out", ["lives"])],
    [("out", None), ("in", None)],
    [("both", ["brother"]), ("both", ["brother"]), ("both", ["brother"])],
    [("in", ["battled"])],
])
def test_olap_traversal_counts_match_oltp(g, spec, mesh8):
    csr = load_csr(g)
    prog = lambda: OLAPTraversalProgram(steps_from_spec(g, spec))
    expect = oltp_count(g, spec)
    for runner in (
        lambda p: CPUExecutor(csr).run(p),
        lambda p: TPUExecutor(csr).run(p),
        lambda p: ShardedExecutor(csr, mesh=mesh8).run(p),
    ):
        res = runner(prog())
        assert int(np.asarray(res["count"]).sum()) == expect, spec


def test_olap_traversal_seeded(g):
    csr = load_csr(g)
    herc = csr.index_of(g.traversal().V().has("name", "hercules").next().id)
    prog = OLAPTraversalProgram(
        steps_from_spec(g, [("out", ["battled"])]), seed_indices=[herc]
    )
    res = CPUExecutor(csr).run(prog)
    assert int(res["count"].sum()) == 3
    # per-destination counts = group-count by vertex
    names = {
        csr.index_of(v.id): v.value("name")
        for v in g.new_transaction().vertices()
    }
    hit = {names[i] for i in np.nonzero(res["count"])[0]}
    assert hit == {"nemean", "hydra", "cerberus"}


def test_multi_hop_multiplicities_counted(g):
    """Traverser COUNTS, not reachability: revisits multiply."""
    csr = load_csr(g)
    # jupiter <-> neptune <-> pluto brothers: 3 hops from all vertices
    spec = [("out", ["brother"])] * 3
    expect = oltp_count(g, spec)
    res = CPUExecutor(csr).run(
        OLAPTraversalProgram(steps_from_spec(g, spec))
    )
    assert int(res["count"].sum()) == expect


def test_random_graph_khop_parity(mesh8):
    from janusgraph_tpu.olap import csr_from_edges

    rng = np.random.default_rng(4)
    n, m = 200, 900
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    et = rng.integers(0, 2, m).astype(np.int32)
    csr = csr_from_edges(n, src, dst, edge_types=et)

    # numpy oracle: count matrix-vector products with label masks
    def oracle(specs):
        counts = np.ones(n)
        for d, lab in specs:
            msk = np.ones(m, bool) if lab is None else np.isin(et, lab)
            nxt = np.zeros(n)
            if d in ("out", "both"):
                np.add.at(nxt, dst[msk], counts[src[msk]])
            if d in ("in", "both"):
                np.add.at(nxt, src[msk], counts[dst[msk]])
            counts = nxt
        return counts

    from janusgraph_tpu.olap.programs.olap_traversal import TraversalStep

    spec = [("out", (0,)), ("both", (1,)), ("in", None)]
    steps = [TraversalStep(d, lab) for d, lab in spec]
    expect = oracle(spec)
    for res in (
        CPUExecutor(csr).run(OLAPTraversalProgram(steps)),
        TPUExecutor(csr).run(OLAPTraversalProgram(steps)),
        ShardedExecutor(csr, mesh=mesh8).run(OLAPTraversalProgram(steps)),
    ):
        np.testing.assert_allclose(
            np.asarray(res["count"], np.float64), expect, rtol=1e-5
        )


def test_compute_traverse_facade(g):
    res = g.compute(executor="cpu").traverse(
        ("out", ["father"]), ("out", ["father"])
    ).submit()
    assert int(np.asarray(res.states["count"]).sum()) == oltp_count(
        g, [("out", ["father"]), ("out", ["father"])]
    )


def test_executor_reuse_does_not_alias_channels(g, mesh8):
    """Regression: two programs with the same generic channel names (s0...)
    on ONE reused executor must not share channel packs/views."""
    csr = load_csr(g)
    out_father = steps_from_spec(g, [("out", ["father"])])
    in_battled = steps_from_spec(g, [("in", ["battled"])])
    for ex in (TPUExecutor(csr), ShardedExecutor(csr, mesh=mesh8)):
        a = ex.run(OLAPTraversalProgram(out_father))
        b = ex.run(OLAPTraversalProgram(in_battled))
        assert int(np.asarray(a["count"]).sum()) == 2   # father edges
        assert int(np.asarray(b["count"]).sum()) == 3   # battled edges


def test_program_cache_key_value_equal(g):
    a = OLAPTraversalProgram(steps_from_spec(g, [("out", ["father"])]))
    b = OLAPTraversalProgram(steps_from_spec(g, [("out", ["father"])]))
    c = OLAPTraversalProgram(steps_from_spec(g, [("in", ["father"])]))
    assert a.cache_key() == b.cache_key()
    assert a.cache_key() != c.cache_key()


def test_unknown_label_raises(g):
    with pytest.raises(ValueError, match="unknown edge label"):
        steps_from_spec(g, [("out", ["knowz"])])


def test_channel_cache_bounded_and_eviction_safe(g, mesh8):
    """Eviction must actually FIRE (more distinct views than the cap) and
    both the LRU and the compiled-fn pruning must leave behavior exact."""
    csr = load_csr(g)
    labels = ["father", "mother", "brother", "battled", "lives", "pet"]
    # 12 distinct channel values (6 labels x 2 directions) > cap
    specs = [[(d, [lab])] for lab in labels for d in ("out", "in")]

    ex = TPUExecutor(csr)
    ex.CHANNEL_CACHE_SIZE = 4
    for spec in specs:
        ex.run(OLAPTraversalProgram(steps_from_spec(g, spec)))
    assert len(ex._channel_packs) <= 4
    # the FIRST spec was evicted long ago: rebuild must be exact
    res = ex.run(OLAPTraversalProgram(steps_from_spec(g, [("in", ["battled"])])))
    assert int(np.asarray(res["count"]).sum()) == 3

    sx = ShardedExecutor(csr, mesh=mesh8)
    sx.CHANNEL_CACHE_SIZE = 4
    for spec in specs[:6]:
        sx.run(OLAPTraversalProgram(steps_from_spec(g, spec)))
    assert len(sx._channel_views) <= 4
    res = sx.run(OLAPTraversalProgram(steps_from_spec(g, [("out", ["father"])])))
    assert int(np.asarray(res["count"]).sum()) == 2


# ------------------------------------------------------------- filtered OLAP
def oltp_filtered_count(g, seed_filters, spec):
    """OLTP oracle for filtered chains: g.V().has(...).out().has(...)..."""
    from janusgraph_tpu.core.traversal import P

    trav = g.traversal().V()
    for key, pred, val in seed_filters or ():
        trav = trav.has(key, P._of(pred, val, pred.name))
    for item in spec:
        direction = item[0] if not isinstance(item, str) else item
        labels = () if isinstance(item, str) else (item[1] or ())
        filters = item[2] if not isinstance(item, str) and len(item) > 2 else ()
        trav = {"out": trav.out, "in": trav.in_, "both": trav.both}[direction](
            *labels
        )
        for key, pred, val in filters:
            trav = trav.has(key, P._of(pred, val, pred.name))
    return trav.count()


def test_filtered_traversal_matches_oltp_gods(g, mesh8):
    """VERDICT r3 #4 gate: filtered multi-hop parity vs OLTP on gods."""
    from janusgraph_tpu.core.predicates import Cmp
    from janusgraph_tpu.olap.programs.olap_traversal import (
        build_olap_traversal,
    )

    csr = load_csr(g, property_keys=("age",))
    cases = [
        # demigod/god endpoints older than 100
        ((), [("out", ["father"], [("age", Cmp.GREATER_THAN, 100)])]),
        # start from old vertices, walk two hops
        ([("age", Cmp.GREATER_THAN, 100)],
         [("out", ["brother"]), ("out", ["lives"])]),
        # filter mid-chain between hops
        ((), [("out", None, [("age", Cmp.GREATER_THAN_EQUAL, 30)]),
              ("out", None)]),
    ]
    for seed_filters, spec in cases:
        expect = oltp_filtered_count(g, seed_filters, spec)
        prog = lambda: build_olap_traversal(  # noqa: E731
            g, csr, spec, seed_filters=seed_filters
        )
        for runner in (
            lambda p: CPUExecutor(csr).run(p),
            lambda p: TPUExecutor(csr).run(p),
            lambda p: ShardedExecutor(csr, mesh=mesh8).run(p),
        ):
            res = runner(prog())
            assert int(np.asarray(res["count"]).sum()) == expect, (
                seed_filters, spec
            )


def test_filtered_traversal_random_graph(mesh8):
    """Filter parity on a random property graph vs a numpy oracle."""
    from janusgraph_tpu.core.predicates import Cmp
    from janusgraph_tpu.olap import csr_from_edges
    from janusgraph_tpu.olap.programs.olap_traversal import (
        OLAPTraversalProgram,
        PropertyFilter,
        TraversalStep,
        evaluate_filter_mask,
    )

    rng = np.random.default_rng(9)
    n, m = 150, 700
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    score = rng.uniform(0, 10, n)
    csr = csr_from_edges(n, src, dst)
    csr.properties["score"] = score

    def oracle():
        counts = np.ones(n)
        nxt = np.zeros(n)
        np.add.at(nxt, dst, counts[src])
        nxt *= score > 5.0
        counts = nxt
        nxt = np.zeros(n)
        np.add.at(nxt, dst, counts[src])
        return nxt

    flt = (PropertyFilter("score", Cmp.GREATER_THAN, 5.0),)
    mask = evaluate_filter_mask(csr, flt)
    np.testing.assert_array_equal(mask, (score > 5.0).astype(np.float32))
    steps = [TraversalStep("out", None, flt), TraversalStep("out")]
    masks = np.stack(
        [mask, np.ones(n, dtype=np.float32)], axis=1
    )
    expect = oracle()
    for res in (
        CPUExecutor(csr).run(OLAPTraversalProgram(steps, step_masks=masks)),
        TPUExecutor(csr).run(OLAPTraversalProgram(steps, step_masks=masks)),
        ShardedExecutor(csr, mesh=mesh8).run(
            OLAPTraversalProgram(steps, step_masks=masks)
        ),
    ):
        np.testing.assert_allclose(
            np.asarray(res["count"], np.float64), expect, rtol=1e-5
        )


def test_group_count_by_label(g):
    """Terminal parity vs OLTP groupCount().by(label)."""
    from janusgraph_tpu.olap.programs.olap_traversal import (
        build_olap_traversal,
        group_count_by_label,
    )

    csr = load_csr(g)
    res = CPUExecutor(csr).run(build_olap_traversal(g, csr, ["out"]))
    got = group_count_by_label(g, csr, res["count"])
    # OLTP oracle
    expect = {}
    for v in g.traversal().V().out().to_list():
        lbl = v.label
        expect[lbl] = expect.get(lbl, 0) + 1
    assert got == {k: float(v) for k, v in expect.items()}


def test_text_filter_masks(g):
    """Non-numeric predicates (Text) work through the scalar path."""
    from janusgraph_tpu.core.predicates import Text
    from janusgraph_tpu.olap.programs.olap_traversal import (
        PropertyFilter,
        evaluate_filter_mask,
    )

    csr = load_csr(g, property_keys=("name",))
    mask = evaluate_filter_mask(
        csr, (PropertyFilter("name", Text.CONTAINS_PREFIX, "her"),)
    )
    names = csr.properties["name"]
    assert {names[i] for i in np.nonzero(mask)[0]} == {"hercules"}


def test_compute_traverse_filtered_facade(g):
    """compute().traverse() with filters builds masks at submit() — a
    filter-bearing spec must never run unfiltered (silent wrong counts)."""
    from janusgraph_tpu.core.predicates import Cmp
    from janusgraph_tpu.olap.programs.olap_traversal import (
        OLAPTraversalProgram,
        TraversalStep,
        PropertyFilter,
    )

    spec = ("out", ["father"], [("age", Cmp.GREATER_THAN, 100)])
    expect = oltp_filtered_count(g, (), [spec])
    res = g.compute().traverse(spec).submit()
    assert int(np.asarray(res.states["count"]).sum()) == expect
    # direct construction without masks refuses filter-bearing steps
    with pytest.raises(ValueError, match="build_olap_traversal"):
        OLAPTraversalProgram(
            (TraversalStep("out", None,
                           (PropertyFilter("age", Cmp.GREATER_THAN, 1),)),)
        )


def test_program_supersedes_earlier_traverse(g):
    """compute().traverse(...).program(p) runs p — program() must clear the
    deferred traverse spec, not let submit() silently rebuild over it."""
    from janusgraph_tpu.olap.programs.pagerank import PageRankProgram

    c = g.compute(executor="cpu").traverse(("out", ["father"]))
    c.program(PageRankProgram(max_iterations=3))
    res = c.submit()
    assert "rank" in res.states and "count" not in res.states


# -------------------------------------------------------------------- paths
# OLAP path()/select(): device reach masks + host backward enumeration
# (olap_traversal.enumerate_paths; VERDICT r4 #4, SURVEY §7 hard part (a)).


def oltp_paths(g, chain):
    trav = g.traversal().V()
    for direction, labels in chain:
        trav = {"out": trav.out, "in": trav.in_, "both": trav.both}[
            direction
        ](*(labels or ()))
    return sorted(
        tuple(v.id for v in p) for p in trav.path().to_list()
    )


@pytest.mark.parametrize("chain", [
    [("out", ["father"]), ("out", ["father"])],
    [("out", ["battled"]), ("in", ["battled"]), ("out", ["father"])],
    [("both", ["brother"]), ("out", ["lives"])],
])
def test_olap_paths_match_oltp_gods(g, chain):
    res = g.compute(executor="cpu").traverse(
        *[(d, l) for d, l in chain], paths=True
    ).submit()
    got = sorted(res.paths())
    want = oltp_paths(g, chain)
    assert got == want
    # the device count prices the enumeration exactly
    assert len(got) == int(np.asarray(res.states["count"]).sum())


def test_olap_paths_random_graph_all_executors(mesh8):
    from janusgraph_tpu.olap.csr import csr_from_edges
    from janusgraph_tpu.olap.programs.olap_traversal import (
        TraversalStep,
        enumerate_paths,
    )

    rng = np.random.default_rng(17)
    n, m = 60, 200
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    csr = csr_from_edges(n, src, dst)
    seeds = tuple(int(s) for s in rng.choice(n, 5, replace=False))
    prog = OLAPTraversalProgram(
        (TraversalStep("out"), TraversalStep("out"), TraversalStep("out")),
        seed_indices=seeds, record_reach=True,
    )
    # numpy oracle: explicit 3-hop chain enumeration
    adj = [[] for _ in range(n)]
    for s, d in zip(src, dst):
        adj[s].append(int(d))
    want = sorted(
        (a, b, c, d)
        for a in seeds for b in adj[a] for c in adj[b] for d in adj[c]
    )
    for make in (
        lambda: CPUExecutor(csr).run(prog),
        lambda: TPUExecutor(csr).run(prog),
        lambda: ShardedExecutor(csr, mesh=mesh8).run(prog),
    ):
        states = make()
        got = sorted(enumerate_paths(csr, prog, states))
        # vertex ids == indices for csr_from_edges-built graphs
        assert got == want
        assert len(got) == int(np.asarray(states["count"]).sum())


def test_olap_paths_respect_filters(g):
    """A mid-chain has()-filter (arrival-vertex property) must prune
    enumerated paths exactly like the OLTP filter step."""
    from janusgraph_tpu.core.predicates import Cmp
    from janusgraph_tpu.core.traversal import P

    res = g.compute(executor="cpu").traverse(
        ("out", ["battled"], [("name", Cmp.NOT_EQUAL, "hydra")]),
        ("in", ["battled"]),
        paths=True,
    ).submit()
    got = sorted(res.paths())
    trav = (
        g.traversal().V().out("battled")
        .has("name", P._of(Cmp.NOT_EQUAL, "hydra", "neq"))
        .in_("battled").path().to_list()
    )
    want = sorted(tuple(v.id for v in p) for p in trav)
    assert got == want and got  # non-empty: the filter prunes, not empties


def test_olap_select_labeled_steps(g):
    res = g.compute(executor="cpu").traverse(
        ("out", ["father"], (), "f"),
        ("out", ["father"], (), "gf"),
        paths=True, source_as="me",
    ).submit()
    rows = sorted(
        (d["me"], d["f"], d["gf"]) for d in res.select("me", "f", "gf")
    )
    assert rows == oltp_paths(
        g, [("out", ["father"]), ("out", ["father"])]
    )
    with pytest.raises(ValueError, match="match no as"):
        list(res.select("nope"))


def test_olap_paths_limit_and_missing_reach(g):
    res = g.compute(executor="cpu").traverse(
        ("out", ["battled"]), ("in", ["battled"]), paths=True
    ).submit()
    all_paths = list(res.paths())
    assert list(res.paths(limit=2)) == all_paths[:2]
    plain = g.compute(executor="cpu").traverse(("out", ["father"])).submit()
    with pytest.raises(ValueError, match="paths=True"):
        plain.paths()


def test_olap_paths_limit_zero_and_duplicate_label(g):
    res = g.compute(executor="cpu").traverse(
        ("out", ["father"]), paths=True
    ).submit()
    assert list(res.paths(limit=0)) == []
    dup = g.compute(executor="cpu").traverse(
        ("out", None, (), "x"), ("out", None, (), "x"), paths=True
    ).submit()
    with pytest.raises(ValueError, match="duplicate as"):
        list(dup.select("x"))


# --------------------------------------------------------------------- sack
# OLAP-side sack (withSack().sack(op).by(weight)): per-column edge
# transforms carry [count, sack(, w*count)] through one BSP run.


def test_olap_sack_matches_enumeration_all_executors(mesh8):
    from janusgraph_tpu.olap.csr import csr_from_edges
    from janusgraph_tpu.olap.programs.olap_traversal import TraversalStep

    rng = np.random.default_rng(5)
    n, m = 60, 200
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = rng.uniform(0.5, 2.0, m).astype(np.float32)
    csr = csr_from_edges(n, src, dst, weights=w)

    adj = [[] for _ in range(n)]
    for s, d, wt in zip(src, dst, w):
        adj[s].append((int(d), float(wt)))
    per_v_sum = np.zeros(n)
    per_v_mult = np.zeros(n)
    for a in range(n):
        for b, w1 in adj[a]:
            for c, w2 in adj[b]:
                per_v_sum[c] += w1 + w2
                per_v_mult[c] += w1 * w2

    steps = (TraversalStep("out"), TraversalStep("out"))
    for make in (
        lambda p: CPUExecutor(csr).run(p),
        lambda p: TPUExecutor(csr).run(p),
        lambda p: ShardedExecutor(csr, mesh=mesh8).run(p),
    ):
        rs = make(OLAPTraversalProgram(steps, sack="sum"))
        np.testing.assert_allclose(
            np.asarray(rs["sack"], np.float64), per_v_sum,
            rtol=1e-3, atol=1e-4,
        )
        rm = make(OLAPTraversalProgram(steps, sack="mult"))
        np.testing.assert_allclose(
            np.asarray(rm["sack"], np.float64), per_v_mult,
            rtol=1e-3, atol=1e-4,
        )


def test_olap_sack_matches_oltp_oracle(g):
    """g.withSack(0).V().outE('battled').sack(sum w).inV() — OLTP folds
    per traverser; the OLAP total sack mass must agree."""
    csr = load_csr(g, weight_key="time")
    prog = OLAPTraversalProgram(
        steps_from_spec(g, [("out", ["battled"])]), sack="sum",
    )
    res = CPUExecutor(csr).run(prog)
    olap_total = float(np.asarray(res["sack"], np.float64).sum())

    # OLTP oracle via edge iteration (sack == sum of traversed weights)
    tx = g.new_transaction()
    from janusgraph_tpu.core.codecs import Direction

    total = 0.0
    for v in tx.vertices():
        for e in tx.get_edges(v, Direction.OUT, ("battled",)):
            total += float(e.value("time"))
    tx.rollback()
    assert olap_total == pytest.approx(total, rel=1e-6)


def test_olap_sack_with_filters_and_facade(g):
    """Facade: compute().weight('time').traverse(..., sack='sum') — step
    filters drop rejected traversers' sack mass too."""
    from janusgraph_tpu.core.predicates import Cmp

    res = g.compute(executor="cpu").weight("time").traverse(
        ("out", ["battled"], [("name", Cmp.EQUAL, "hydra")]),
        sack="sum",
    ).submit()
    # only the hercules->hydra battle (time=2) survives the filter
    tx = g.new_transaction()
    from janusgraph_tpu.core.codecs import Direction

    want = 0.0
    for v in tx.vertices():
        for e in tx.get_edges(v, Direction.OUT, ("battled",)):
            if e.in_vertex.value("name") == "hydra":
                want += float(e.value("time"))
    tx.rollback()
    assert float(
        np.asarray(res.states["sack"], np.float64).sum()
    ) == pytest.approx(want, rel=1e-6)
    assert np.asarray(res.states["count"]).sum() == 1


def test_olap_sack_tiny_weight_exact_and_unweighted_refused(g):
    """Per-column MUL must stay exact for |w-1| below f32 eps (the
    where-select form), and sack on a weightless CSR fails fast."""
    import numpy as np

    from janusgraph_tpu.olap.vertex_program import (
        EdgeTransform,
        apply_edge_transform,
    )

    msgs = np.ones((1, 2), np.float32)
    w = np.asarray([1e-8], np.float32)
    out = apply_edge_transform(
        np, msgs, w, EdgeTransform.NONE,
        (EdgeTransform.NONE, EdgeTransform.MUL_WEIGHT),
    )
    assert out[0, 0] == 1.0 and out[0, 1] == np.float32(1e-8)

    from janusgraph_tpu.olap.programs.olap_traversal import (
        build_olap_traversal,
    )

    csr = load_csr(g)  # no weight_key -> no weight column
    with pytest.raises(ValueError, match="weight"):
        build_olap_traversal(g, csr, [("out", ["battled"])], sack="sum")


def test_compute_facade_sharded_executor(g):
    """graph.compute(executor='sharded'): the mesh executor behind the
    same facade (computer.executor config or explicit arg), with
    computer.exchange/agg selecting the comm/agg strategy."""
    from janusgraph_tpu.olap.programs import PageRankProgram

    res = g.compute(executor="sharded").traverse(
        ("out", ["father"]), ("out", ["father"])
    ).submit()
    assert int(np.asarray(res.states["count"]).sum()) == oltp_count(
        g, [("out", ["father"]), ("out", ["father"])]
    )
    # config-driven default executor + ring exchange
    g.config.local["computer.executor"] = "sharded"
    g.config.local["computer.exchange"] = "ring"
    g.config.local["computer.agg"] = "segment"
    res2 = g.compute().program(
        PageRankProgram(max_iterations=5, tol=0.0)
    ).submit()
    cpu = g.compute(executor="cpu").program(
        PageRankProgram(max_iterations=5, tol=0.0)
    ).submit()
    np.testing.assert_allclose(
        np.asarray(res2.states["rank"], np.float64),
        np.asarray(cpu.states["rank"], np.float64), rtol=1e-4, atol=1e-6,
    )


def test_sack_on_weightless_csr_refused_by_every_executor(g, mesh8):
    """The guard lives at run() entry, not just the builder: direct
    OLAPTraversalProgram construction cannot silently fold w=1."""
    csr = load_csr(g)  # weightless
    prog = OLAPTraversalProgram(
        steps_from_spec(g, [("out", ["battled"])]), sack="sum",
    )
    for ex in (
        CPUExecutor(csr), TPUExecutor(csr),
        ShardedExecutor(csr, mesh=mesh8),
    ):
        with pytest.raises(ValueError, match="no edge weights"):
            ex.run(prog)


# ------------------------------------------------- an (n, K) start: K chains
def _column_graph():
    from janusgraph_tpu.olap import csr_from_edges
    from janusgraph_tpu.olap.programs.olap_traversal import TraversalStep

    rng = np.random.default_rng(37)
    n, m = 300, 3000
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    et = rng.integers(0, 2, m).astype(np.int32)
    csr = csr_from_edges(n, src, dst, edge_types=et)
    steps = [TraversalStep("out", (0,)), TraversalStep("both")]
    # arrival vectors as the planner's: small integer counts, column 3
    # empty (an absent member of a batch)
    starts = rng.integers(0, 4, (n, 4)).astype(np.float32)
    starts[:, 3] = 0.0
    return csr, steps, starts


@pytest.mark.parametrize("executor", ["numpy", "numpy-pack", "tpu"])
def test_columns_of_a_wide_start_are_the_narrow_runs_bit_for_bit(executor):
    """An (n, K) start gives, column for column and bit for bit, the (n,)
    run of each column: on the numpy executor (the scalar loop and the
    pack's replay) and on the `tpu` executor (under JAX_PLATFORMS=cpu
    here), whose prepared steps tell the two widths apart."""
    csr, steps, starts = _column_graph()
    ex = {
        "numpy": lambda: CPUExecutor(csr),
        "numpy-pack": lambda: CPUExecutor(csr, strategy="hybrid"),
        "tpu": lambda: TPUExecutor(csr),
    }[executor]()
    wide = np.asarray(
        ex.run(OLAPTraversalProgram(steps, seed_mask=starts))["count"])
    assert wide.shape == starts.shape
    assert wide[:, :3].any() and not wide[:, 3].any()
    for j in range(starts.shape[1]):
        narrow = np.asarray(ex.run(OLAPTraversalProgram(
            steps, seed_mask=starts[:, j].copy()))["count"])
        assert narrow.shape == (csr.num_vertices,)
        assert narrow.dtype == wide.dtype
        assert narrow.tobytes() == wide[:, j].tobytes(), j
    if executor == "tpu":
        widths = sorted(dict(key[0][2])["width"] for key in ex._prepared)
        assert widths == [0, 0, 4, 4]  # two steps, each at two widths


def test_stacked_builds_the_wide_start_from_plain_chains():
    csr, steps, starts = _column_graph()
    plain = [
        OLAPTraversalProgram(steps, seed_mask=starts[:, j].copy())
        for j in range(3)
    ]
    assert all(p.stackable() and p.width == 0 for p in plain)
    wide = OLAPTraversalProgram.stacked(plain, 4)
    assert wide.width == 4 and not wide.stackable()
    assert wide.cache_key() != plain[0].cache_key()
    got = np.asarray(CPUExecutor(csr).run(wide)["count"])
    want = np.asarray(CPUExecutor(csr).run(
        OLAPTraversalProgram(steps, seed_mask=starts))["count"])
    assert got.tobytes() == want.tobytes()
    # stacked into the caller's own array: the columns it wrote are the
    # chains', what the others held disturbs nobody
    kept = np.full(starts.shape, 7.0, np.float32)
    again = np.asarray(CPUExecutor(csr).run(
        OLAPTraversalProgram.stacked(plain[:2], 4, out=kept))["count"])
    assert again[:, :2].tobytes() == want[:, :2].tobytes()
    assert (kept[:, 2:] == 7.0).all() and kept[:, 0].tobytes() == (
        starts[:, 0].tobytes())
    with pytest.raises(ValueError, match="at most `width` stackable"):
        OLAPTraversalProgram.stacked(plain, 2)
    other = OLAPTraversalProgram(steps[:1], seed_mask=starts[:, 0].copy())
    with pytest.raises(ValueError, match="same steps"):
        OLAPTraversalProgram.stacked([plain[0], other], 4)
    for unfit in (
        OLAPTraversalProgram(steps),  # starts everywhere: no vector
        OLAPTraversalProgram(steps, seed_indices=[1, 2]),
        OLAPTraversalProgram(
            steps, seed_mask=starts[:, 0].copy(),
            step_masks=np.ones((csr.num_vertices, 2), np.float32)),
        OLAPTraversalProgram(
            steps, seed_mask=starts[:, 0].copy(), record_reach=True),
        OLAPTraversalProgram(steps, seed_mask=starts[:, 0].copy(), sack="sum"),
    ):
        assert not unfit.stackable()


@pytest.mark.parametrize("extra,name", [
    ({"sack": "sum"}, "sack"),
    ({"step_masks": np.ones((300, 2), np.float32)}, "step_masks"),
    ({"record_reach": True}, "record_reach"),
    ({"seed_indices": [0]}, "seed_indices"),
])
def test_a_wide_start_refuses_what_it_does_not_carry_by_name(extra, name):
    _, steps, starts = _column_graph()
    with pytest.raises(ValueError, match=f"K plain chains: {name}"):
        OLAPTraversalProgram(steps, seed_mask=starts, **extra)
