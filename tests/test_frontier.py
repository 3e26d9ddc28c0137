"""Frontier-compacted SSSP/BFS (olap/frontier.py).

Parity gates: the frontier path must be step-for-step identical to both the
scalar CPU oracle and the dense TPU BSP path (frontier="off") — the
ShortestPath special-case must never change results, only cost (reference
model: FulgoraGraphComputer.java:249-253 special-casing ShortestPath).
"""

import numpy as np
import pytest

from janusgraph_tpu.olap import csr_from_edges
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.frontier import _tier
from janusgraph_tpu.olap.programs import ShortestPathProgram
from janusgraph_tpu.olap.programs.shortest_path import reconstruct_path
from janusgraph_tpu.olap.routes import route_of
from janusgraph_tpu.olap.tpu_executor import TPUExecutor


def random_graph(n=300, m=1500, seed=7, weights=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = rng.uniform(0.5, 2.0, m).astype(np.float32) if weights else None
    return csr_from_edges(n, src, dst, w)


def supernode_graph(n=400, seed=3):
    """Vertex 0 is a hub (out-edges to everyone), many deg-0 vertices, plus
    a sparse tail — exercises deg-0 collapse in the ownership scatter and
    uneven tier growth."""
    rng = np.random.default_rng(seed)
    hub_dst = np.arange(1, n // 2, dtype=np.int32)
    hub_src = np.zeros(len(hub_dst), dtype=np.int32)
    tail_src = rng.integers(1, n // 2, 200).astype(np.int32)
    tail_dst = rng.integers(0, n, 200).astype(np.int32)
    return csr_from_edges(
        n,
        np.concatenate([hub_src, tail_src]),
        np.concatenate([hub_dst, tail_dst]),
    )


def _dist(res):
    d = np.asarray(res["distance"])
    return np.where(d >= 1e17, np.inf, d)


#: rungs under the ladder's top. The default E floor (8192) lies above
#: these graphs' edge counts, which makes the ladder the single rung (m,)
#: and every hop a wide round on the pack: a test that means to drive the
#: narrow step (compaction, `capped_expand`, the scatter-min) forces rungs.
RUNGS = dict(frontier_e_min=64, frontier_f_min=16)


CASES = [
    ("bfs", dict()),
    ("bfs_undirected", dict(undirected=True)),
    ("weighted", dict(weighted=True)),
    ("weighted_undirected", dict(weighted=True, undirected=True)),
    ("tracked", dict(track_paths=True)),
]


@pytest.mark.parametrize("name,kw", CASES, ids=[c[0] for c in CASES])
def test_frontier_matches_cpu_and_dense(name, kw):
    csr = random_graph(weights=kw.get("weighted", False))
    prog = lambda: ShortestPathProgram(seed_index=0, **kw)  # noqa: E731
    cpu = CPUExecutor(csr).run(prog())
    dense = TPUExecutor(csr, frontier="off").run(prog())
    ex = TPUExecutor(csr, **RUNGS)
    assert ex._route(prog(), "auto", None).entry == "run"
    sparse = ex.run(prog())
    assert not all(t["wide"] for t in ex.last_run_info["tiers"])
    np.testing.assert_allclose(_dist(sparse), _dist(cpu), rtol=1e-6)
    np.testing.assert_allclose(_dist(sparse), _dist(dense), rtol=1e-6)
    if "predecessor" in sparse:
        # a bottom-up hop's parent is the first frontier neighbour by row
        # position: valid, not always the dense path's smallest index
        assert_bfs_parents(csr, sparse, 0, kw.get("undirected", False))
        ex._frontier().direction = "top_down"
        np.testing.assert_array_equal(
            ex.run(prog())["predecessor"], dense["predecessor"]
        )


def assert_bfs_parents(csr, result, root, undirected):
    """Every reached vertex but the root has a parent joined to it by an
    edge (either way where undirected) one level up; the root is its own
    parent and an unreached vertex has -1."""
    level = _dist(result)
    parent = np.asarray(result["predecessor"]).astype(np.int64)
    src = np.repeat(np.arange(csr.num_vertices), np.diff(csr.out_indptr))
    edges = set(zip(src.tolist(), csr.out_dst.tolist()))
    assert parent[root] == root and level[root] == 0
    for v in np.flatnonzero(np.isfinite(level)):
        if v != root:
            p = parent[v]
            assert level[p] == level[v] - 1, v
            assert (p, v) in edges or (undirected and (v, p) in edges), v
    assert np.all(parent[~np.isfinite(level)] == -1)


def test_frontier_supernode_deg0():
    csr = supernode_graph()
    prog = lambda: ShortestPathProgram(seed_index=0)  # noqa: E731
    cpu = CPUExecutor(csr).run(prog())
    ex = TPUExecutor(csr, **RUNGS)
    sparse = ex.run(prog())
    # the hub's 199 out-edges on the rung of 256, then the sparse tail
    assert not any(t["wide"] for t in ex.last_run_info["tiers"])
    np.testing.assert_allclose(_dist(sparse), _dist(cpu), rtol=1e-6)


def test_per_run_frontier_override():
    """One executor serves both paths: run(frontier='off') forces dense."""
    csr = random_graph(n=80, m=300)
    ex = TPUExecutor(csr)
    sparse = ex.run(ShortestPathProgram(seed_index=0))
    dense = ex.run(ShortestPathProgram(seed_index=0), frontier="off")
    np.testing.assert_allclose(_dist(sparse), _dist(dense), rtol=1e-6)


@pytest.mark.parametrize("max_iter", [0, 1, 2, 3])
def test_frontier_step_parity_at_cutoff(max_iter):
    """Per-superstep parity, not just fixpoint parity: truncated runs must
    agree with the dense path at every intermediate hop."""
    csr = random_graph(n=120, m=500, seed=11)
    mk = lambda: ShortestPathProgram(seed_index=0, max_iterations=max_iter)  # noqa: E731
    dense = TPUExecutor(csr, frontier="off").run(mk())
    sparse = TPUExecutor(csr, **RUNGS).run(mk())
    np.testing.assert_allclose(_dist(sparse), _dist(dense), rtol=1e-6)


def test_frontier_weighted_cutoff_parity():
    csr = random_graph(n=120, m=500, seed=13, weights=True)
    for it in (1, 2, 4):
        mk = lambda: ShortestPathProgram(  # noqa: E731
            seed_index=5, weighted=True, max_iterations=it
        )
        dense = TPUExecutor(csr, frontier="off").run(mk())
        sparse = TPUExecutor(csr, **RUNGS).run(mk())
        np.testing.assert_allclose(_dist(sparse), _dist(dense), rtol=1e-6)


def test_frontier_path_reconstruction():
    csr = random_graph(n=150, m=700, seed=19)
    res = TPUExecutor(csr, **RUNGS).run(
        ShortestPathProgram(seed_index=0, track_paths=True)
    )
    dist = _dist(res)
    reached = [v for v in range(csr.num_vertices) if np.isfinite(dist[v])]
    assert len(reached) > 1
    for v in reached[:20]:
        path = reconstruct_path(res, v)
        assert path is not None and path[0] == 0 and path[-1] == v
        assert len(path) == int(dist[v]) + 1
        # every hop is a real edge
        for a, b in zip(path, path[1:]):
            row = csr.out_dst[csr.out_indptr[a]:csr.out_indptr[a + 1]]
            assert b in row.tolist()


def test_frontier_line_graph_many_hops():
    """Tiny frontier (1 vertex) for many hops — the compaction sweet spot;
    also crosses tier boundaries as the hop index grows."""
    n = 40
    src = np.arange(n - 1, dtype=np.int32)
    dst = np.arange(1, n, dtype=np.int32)
    csr = csr_from_edges(n, src, dst)
    ex = TPUExecutor(csr, frontier_e_min=8, frontier_f_min=4)
    res = ex.run(ShortestPathProgram(seed_index=0))
    assert not any(t["wide"] for t in ex.last_run_info["tiers"])
    np.testing.assert_allclose(_dist(res), np.arange(n, dtype=np.float32))


def test_frontier_isolated_seed_and_empty_graph():
    csr = csr_from_edges(5, np.zeros(0, np.int32), np.zeros(0, np.int32))
    res = TPUExecutor(csr).run(ShortestPathProgram(seed_index=2))
    d = _dist(res)
    assert d[2] == 0 and np.all(np.isinf(np.delete(d, 2)))


def test_frontier_off_and_subclass_fall_back_dense():
    csr = random_graph(n=50, m=200)
    ex = TPUExecutor(csr, frontier="off")
    assert ex._frontier_cfg == "off"

    class Custom(ShortestPathProgram):
        pass

    # subclasses may override message/apply — never special-case them,
    # not even under "always"
    assert route_of(Custom(seed_index=0)) is None
    assert TPUExecutor(csr)._route(Custom(seed_index=0), "always", None) is None


def test_tier_ladder():
    assert _tier(1, 1 << 10, 1 << 20) == 1 << 10
    assert _tier((1 << 10) + 1, 1 << 10, 1 << 20) == 1 << 12
    assert _tier(1 << 19, 1 << 10, 1 << 20) == 1 << 20
    # hi below the pow-4 ladder: clamps to hi (callers ensure hi >= need)
    assert _tier(100, 1 << 10, 500) == 500


# --------------------------------------------------------- frontier CC
def test_frontier_cc_auto_heuristic():
    """Under 'auto', small-graph CC keeps the fused dense path (host-RTT
    per frontier superstep would dominate); 'always' forces frontier."""
    from janusgraph_tpu.olap.programs import ConnectedComponentsProgram

    small = random_graph(n=50, m=120)
    ex = TPUExecutor(small)
    assert ex._route(ConnectedComponentsProgram(), "auto", None) is None
    route = ex._route(ConnectedComponentsProgram(), "always", None)
    assert route.entry == "run_cc"
    # an explicit cap is propagation's cut: never Afforest's
    capped = ConnectedComponentsProgram(max_iterations=5)
    assert ex._route(capped, "auto", None) is None
    with pytest.raises(ValueError, match="Afforest never has"):
        ex._route(capped, "always", None)
    # BFS keeps frontier at every size
    bfs = ex._route(ShortestPathProgram(seed_index=0), "auto", None)
    assert bfs.entry == "run"


def test_frontier_cc_matches_cpu_and_dense():
    from janusgraph_tpu.olap.programs import ConnectedComponentsProgram

    csr = random_graph(n=250, m=600, seed=23)
    mk = lambda: ConnectedComponentsProgram(max_iterations=None)  # noqa: E731
    cpu = CPUExecutor(csr).run(mk())
    dense = TPUExecutor(csr, frontier="off").run(mk())
    ex = TPUExecutor(csr, frontier="always", **RUNGS)
    assert ex._route(mk(), "always", None) is not None
    sparse = ex.run(mk())
    info = ex.last_run_info
    assert info["path"] == "frontier" and info["link_passes"]["sample"] >= 1
    assert not any(t["wide"] for t in info["tiers"])
    np.testing.assert_array_equal(
        np.asarray(sparse["component"]), np.asarray(cpu["component"])
    )
    np.testing.assert_array_equal(
        np.asarray(sparse["component"]), np.asarray(dense["component"])
    )


def test_frontier_cc_step_cutoff_parity():
    """An explicit cap keeps its cut semantics, on the dense path: under
    'auto' it routes there (as 'off' does) and answers propagation's
    labels after that many supersteps; under 'always' it is refused by
    the cap's name, since Afforest has no state after k supersteps."""
    from janusgraph_tpu.olap.programs import ConnectedComponentsProgram

    csr = random_graph(n=120, m=260, seed=29)
    for it in (1, 2, 3):
        mk = lambda: ConnectedComponentsProgram(max_iterations=it)  # noqa: E731
        dense = TPUExecutor(csr, frontier="off").run(mk())
        cpu = CPUExecutor(csr).run(mk())
        ex = TPUExecutor(csr, frontier_cc_min_edges=1, **RUNGS)
        auto = ex.run(mk())
        assert ex.last_run_info["path"] == "fused"
        assert ex.last_run_info["supersteps"] == it
        np.testing.assert_array_equal(
            np.asarray(auto["component"]), np.asarray(dense["component"])
        )
        np.testing.assert_array_equal(
            np.asarray(auto["component"]),
            np.asarray(cpu["component"]).astype(np.float32),
        )
        with pytest.raises(ValueError, match=f"max_iterations={it}\\)"):
            TPUExecutor(csr, frontier="always").run(mk())
    # the cut is a cut: one superstep leaves labels to fall
    full = TPUExecutor(csr, frontier="always").run(
        ConnectedComponentsProgram())
    assert (np.asarray(full["component"]) < np.asarray(auto["component"])).any()


def test_frontier_cc_disconnected_and_isolated():
    from janusgraph_tpu.olap.programs import ConnectedComponentsProgram

    # two chains + isolated vertices
    src = np.array([0, 1, 5, 6], np.int32)
    dst = np.array([1, 2, 6, 7], np.int32)
    csr = csr_from_edges(10, src, dst)
    res = TPUExecutor(csr, frontier="always").run(ConnectedComponentsProgram())
    comp = np.asarray(res["component"])
    assert comp[0] == comp[1] == comp[2] == 0
    assert comp[5] == comp[6] == comp[7] == 5
    for iso in (3, 4, 8, 9):
        assert comp[iso] == iso


def test_frontier_cc_on_ldbc_proxy():
    from janusgraph_tpu.olap.generators import ldbc_snb_csr
    from janusgraph_tpu.olap.programs import ConnectedComponentsProgram

    csr = ldbc_snb_csr(11)
    mk = lambda: ConnectedComponentsProgram(max_iterations=None)  # noqa: E731
    sparse = TPUExecutor(csr, frontier="always").run(mk())
    cpu = CPUExecutor(csr).run(mk())
    np.testing.assert_array_equal(
        np.asarray(sparse["component"]), np.asarray(cpu["component"])
    )


def test_frontier_fuzz_vs_dense():
    """Property sweep: random graphs x seeds x cutoffs — the frontier path
    must match the dense path everywhere, not just on the curated cases."""
    from janusgraph_tpu.olap.programs import ConnectedComponentsProgram

    rng = np.random.default_rng(101)
    for trial in range(6):
        n = int(rng.integers(20, 400))
        m = int(rng.integers(0, 6 * n))
        weights = bool(rng.integers(0, 2))
        csr = csr_from_edges(
            n,
            rng.integers(0, n, m).astype(np.int32),
            rng.integers(0, n, m).astype(np.int32),
            rng.uniform(0.1, 3.0, m).astype(np.float32) if weights else None,
        )
        seed = int(rng.integers(0, n))
        it = int(rng.integers(1, 12))
        und = bool(rng.integers(0, 2))
        mk = lambda: ShortestPathProgram(  # noqa: B023,E731
            seed_index=seed, weighted=weights, undirected=und,
            max_iterations=it,
        )
        dense = TPUExecutor(csr, frontier="off").run(mk())
        # every hop wide (the default ladder is (m,) here), then rungs
        for rungs in ({}, RUNGS):
            sparse = TPUExecutor(csr, frontier="always", **rungs).run(mk())
            np.testing.assert_allclose(
                _dist(sparse), _dist(dense), rtol=1e-6,
                err_msg=f"trial={trial} n={n} m={m} w={weights} und={und} "
                        f"it={it} rungs={rungs}",
            )
        cc_d = TPUExecutor(csr, frontier="off").run(
            ConnectedComponentsProgram(max_iterations=None)
        )
        cc_s = TPUExecutor(csr, frontier="always", **RUNGS).run(
            ConnectedComponentsProgram(max_iterations=None)
        )
        np.testing.assert_array_equal(
            np.asarray(cc_s["component"]), np.asarray(cc_d["component"]),
            err_msg=f"cc trial={trial} n={n} m={m}",
        )


# ------------------------------------------- the wide round (the top rung)
def awkward_multigraph(seed, n=260, m=1100, island=30):
    """A weighted multigraph with the cases a relaxation has to survive:
    the last `island` vertices joined among themselves only, ten self
    loops, sixty parallel edges under other weights, forty weights of 0
    and forty of 2**-26 (absorbed by any distance of 2**-2 or more). 1100
    edges, so that a rung of 1024 lies just under the ladder's top."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n - island, m).astype(np.int32)
    dst = rng.integers(0, n - island, m).astype(np.int32)
    src[:20] = rng.integers(n - island, n, 20)
    dst[:20] = rng.integers(n - island, n, 20)
    weight = rng.random(m, dtype=np.float32)
    weight[rng.integers(20, m, 40)] = 0.0
    weight[rng.integers(20, m, 40)] = np.float32(2.0 ** -26)
    src[100:110] = dst[100:110]
    src[200:260], dst[200:260] = src[300:360], dst[300:360]
    return csr_from_edges(n, src, dst, weight)


def _components(**kw):
    from janusgraph_tpu.olap.programs import ConnectedComponentsProgram

    return ConnectedComponentsProgram(max_iterations=None, **kw)


#: flavour -> (program factory, the oracle that folds in the same float)
WIDE_FLAVOURS = {
    "bfs": (lambda: ShortestPathProgram(seed_index=3), {}),
    "bfs-paths": (
        lambda: ShortestPathProgram(seed_index=3, track_paths=True), {}),
    "weighted": (
        lambda: ShortestPathProgram(
            seed_index=3, weighted=True, max_iterations=1000),
        {"strategy": "ell"}),
    # labels on a WEIGHTED graph: a label never absorbs a weight
    "cc-weighted-graph": (_components, {}),
}

#: ladder -> (executor options, which hops must be wide). The E ladder is
#: pow2 rungs from the floor, then m = 1100.
WIDE_LADDERS = {
    # (1024, m): no hop of a search holds more than 1024 edges
    "narrow": (dict(frontier_e_min=1024, frontier_f_min=16), "none"),
    # (128, m): the hops in the middle of a search are wide
    "mixed": (dict(frontier_e_min=64, frontier_f_min=16,
                   autotune_max_tiers=2), "some"),
    # the default floor lies above m: the ladder is (m,), every hop wide
    "default": ({}, "all"),
}

_oracles = {}


def _oracle(flavour, seed):
    """(CPUExecutor's result, the dense path's), once a flavour and seed."""
    if (flavour, seed) not in _oracles:
        make, cpu_options = WIDE_FLAVOURS[flavour]
        csr = awkward_multigraph(seed)
        _oracles[flavour, seed] = (
            CPUExecutor(csr, **cpu_options).run(make()),
            TPUExecutor(csr, frontier="off").run(make()),
        )
    return _oracles[flavour, seed]


def assert_hop_mix(info, num_edges, expect):
    """The record says which hops were wide, by the rule (the rung), and
    the run is the mix of wide and narrow hops the case means to drive.
    CC is Afforest: no hop, no wide round; its record says whether its
    finish (the rows outside the giant) was narrow or read every slot of
    the closure, the top rung: narrow under the narrow ladder, top-rung
    under the default one, whose one rung is the closure."""
    if "link_passes" in info:
        (finish,) = info["tiers"]
        assert info["wide_rounds"] == 0 and not finish["wide"]
        assert finish["top_rung"] == (
            finish["E_cap"] == info["closure_slots"])
        if expect == "all":
            assert finish["top_rung"]
        elif expect == "none":
            assert not finish["top_rung"]
        return
    wide = [t["wide"] for t in info["tiers"]]
    assert wide == [t["E_cap"] == num_edges for t in info["tiers"]]
    assert info["wide_rounds"] == sum(wide)
    if expect == "all":
        assert all(wide)
    elif expect == "some":
        assert any(wide) and not all(wide)
    else:
        assert not any(wide)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("ladder", list(WIDE_LADDERS))
@pytest.mark.parametrize("flavour", list(WIDE_FLAVOURS))
def test_wide_round_is_the_narrow_step_bit_for_bit(flavour, ladder, seed):
    """The top rung runs on the pack; whatever mix of wide and narrow hops
    a ladder gives, the result is CPUExecutor's and the dense path's, bit
    for bit."""
    make, _ = WIDE_FLAVOURS[flavour]
    options, expect = WIDE_LADDERS[ladder]
    csr = awkward_multigraph(seed)
    ex = TPUExecutor(csr, frontier="always", **options)
    # top-down hops alone: the rule's bottom-up hops read no rung
    # (tests/test_bottom_up.py holds them to the same levels)
    ex._frontier().direction = "top_down"
    got = ex.run(make())
    assert ex.last_run_info["path"] == "frontier"
    assert_hop_mix(ex.last_run_info, csr.num_edges, expect)
    cpu, dense = _oracle(flavour, seed)
    assert set(got) == set(dense)
    for key in got:
        np.testing.assert_array_equal(
            np.asarray(got[key]).view(np.uint32),
            np.asarray(dense[key]).view(np.uint32), err_msg=key)
    state = "component" if "component" in got else "distance"
    np.testing.assert_array_equal(
        np.asarray(got[state]), np.asarray(cpu[state]).astype(np.float32))
    # the island is never reached, and never joins the rest
    if state == "distance":
        assert np.all(np.asarray(got[state])[-30:] >= 1e17)
    else:
        assert np.all(np.asarray(got[state])[-30:] >= csr.num_vertices - 30)


def test_wide_executable_is_one_per_flavour_and_top_rung_steps_are_gone():
    """One wide executable serves every top-rung hop of a flavour; no
    narrow step is built at `E_cap == m`; the pack is the executor's own
    (built on the first wide hop, the dense path's afterwards)."""
    csr = awkward_multigraph(2)
    ex = TPUExecutor(csr, frontier_e_min=64, frontier_f_min=16,
                     autotune_max_tiers=2)
    ex._frontier().direction = "top_down"  # hops that read the rungs
    assert ex._hybrid_packs == {}
    for root in (3, 5, 9):
        ex.run(ShortestPathProgram(seed_index=root))
        assert ex.last_run_info["wide_rounds"] >= 1
    keys = list(ex._compiled)
    assert [k for k in keys if k[0] == "frontier-wide"] == [
        ("frontier-wide", False, False, False, False)]
    assert all(k[2] < csr.num_edges for k in keys if k[0] == "frontier-step")
    assert list(ex._hybrid_packs) == [False]
    pack = ex._hybrid_packs[False]
    assert all(t["tier_slots"] == pack.slots
               for t in ex.last_run_info["tiers"] if t["wide"])
    # an undirected BFS on the same (weighted) graph and weighted SSSP
    # read the closure pack, without and with its weights: two
    # executables, one pack
    ex.run(ShortestPathProgram(seed_index=3, undirected=True))
    ex.run(ShortestPathProgram(seed_index=3, weighted=True, undirected=True,
                               max_iterations=1000))
    wide = sorted(k for k in ex._compiled if k[0] == "frontier-wide")
    assert wide == [
        ("frontier-wide", False, False, False, False),
        ("frontier-wide", False, False, True, False),
        ("frontier-wide", True, False, True, True),
    ]
    assert sorted(ex._hybrid_packs) == [False, True]


def test_frontier_always_refuses_checkpointing(tmp_path):
    csr = random_graph(n=50, m=200)
    ex = TPUExecutor(csr, frontier="always")
    with pytest.raises(ValueError, match="checkpoint"):
        ex.run(
            ShortestPathProgram(seed_index=0),
            checkpoint_path=str(tmp_path / "ck"),
            checkpoint_every=2,
        )
    # auto quietly uses the (checkpointable) dense path
    res = TPUExecutor(csr).run(
        ShortestPathProgram(seed_index=0),
        checkpoint_path=str(tmp_path / "ck2"),
        checkpoint_every=2,
    )
    assert "distance" in res


def test_last_run_info_records_paths_and_tiers():
    csr = random_graph(n=200, m=900, seed=31)
    ex = TPUExecutor(csr)
    ex.run(ShortestPathProgram(seed_index=0, max_iterations=4))
    info = ex.last_run_info
    assert info["path"] == "frontier"
    assert 1 <= info["supersteps"] <= 4
    assert info["tiers"][0]["frontier"] == 1  # hop 0: the seed alone
    for t in info["tiers"]:
        if t["direction"] == "bottom_up":
            # its blocks held what it read, of the unreached rows' slots
            assert t["bottom_up_slots"] <= t["bottom_up_tier_slots"]
            assert t["bottom_up_slots"] <= t["unreached_slots"]
        else:
            assert t["E_cap"] >= t["edges"]
    from janusgraph_tpu.olap.programs import PageRankProgram

    ex.run(PageRankProgram(max_iterations=5, tol=0.0))
    assert ex.last_run_info["path"] == "fused"
    assert ex.last_run_info["supersteps"] == 5
