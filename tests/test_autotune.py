"""ISSUE 6 gate: degree-bucketed hybrid format + profiler-driven autotuner.

Three contracts:

1. **Determinism** — `autotune.decide` is a pure function: identical
   (GraphStats, device_kind, overrides, measured) give an identical
   AutotuneDecision, and stats built twice from the same CSR are equal.
2. **Bitwise identity** — the hybrid strategy's results are bit-for-bit
   equal to the pure-ELL path (PageRank/BFS/CC oracles, weighted and
   unweighted, supernode row-split, 2-D messages), on the device executor
   AND the CPU executor's numpy replay of the same pack arithmetic.
3. **Wiring** — the decision lands in `run_info["autotune"]`, the
   `computer.autotune-*` keys override it, and the frontier engine prices
   hops against the tuner's tier schedule.
"""

import numpy as np
import pytest

from janusgraph_tpu.olap import csr_from_edges, run_on
from janusgraph_tpu.olap.autotune import (
    AutotuneDecision,
    GraphStats,
    decide,
    decide_tiers,
    pick_tier,
)
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.kernels import (
    ELLPack,
    HybridPack,
    ell_aggregate,
    hybrid_aggregate,
    tree_reduce,
)
from janusgraph_tpu.olap.programs import (
    ConnectedComponentsProgram,
    PageRankProgram,
    ShortestPathProgram,
)
from janusgraph_tpu.olap.tpu_executor import TPUExecutor
from janusgraph_tpu.olap.vertex_program import Combiner, EdgeTransform


def skewed_graph(n=600, m=12000, seed=7, weights=False):
    """Heavy-tailed destinations: a torso plus genuine hubs."""
    rng = np.random.default_rng(seed)
    dst = (rng.zipf(1.35, m) % n).astype(np.int64)
    src = rng.integers(0, n, m).astype(np.int64)
    w = rng.uniform(0.25, 2.0, m).astype(np.float32) if weights else None
    return csr_from_edges(n, src, dst, w)


# ----------------------------------------------------------- determinism
def test_decision_deterministic():
    csr = skewed_graph()
    s1 = GraphStats.from_csr(csr)
    s2 = GraphStats.from_csr(csr)
    assert s1 == s2
    d1 = decide(s1, "cpu")
    d2 = decide(s2, "cpu")
    assert d1 == d2
    assert isinstance(d1, AutotuneDecision)
    # overrides and measurements are part of the function's inputs: same
    # inputs, same decision — and they do change it
    ov = {"hub_cutoff": 32, "min_gain": 0.0}
    assert decide(s1, "cpu", overrides=ov) == decide(s2, "cpu", overrides=ov)
    meas = {"superstep_ms": 12.5, "pad_ratio": 1.47}
    dm1 = decide(s1, "cpu", measured=meas)
    dm2 = decide(s1, "cpu", measured=meas)
    assert dm1 == dm2
    assert dm1.source == "measured+model"


def test_decision_device_kind_sensitivity():
    """device_kind is a decision input: the record carries it, and the
    roofline peaks it selects are what the model prices against."""
    s = GraphStats.from_csr(skewed_graph())
    d_cpu = decide(s, "cpu")
    d_tpu = decide(s, "TPU v5 lite")
    assert d_cpu.device_kind != d_tpu.device_kind
    assert d_cpu == decide(s, "cpu")


def test_stats_shape():
    csr = skewed_graph()
    s = GraphStats.from_csr(csr)
    assert s.num_vertices == csr.num_vertices
    assert s.num_edges == csr.num_edges
    assert s.ell_slots >= s.num_edges
    # every candidate's hybrid footprint is at least the edge count and at
    # most the ELL footprint's worst case
    for _cutoff, slots, _hubs, _buckets, chunk_rows in s.hybrid_by_cutoff:
        assert slots >= s.num_edges - s.num_vertices  # deg-0 rows are free
        assert chunk_rows >= 0
    und = GraphStats.from_csr(csr, undirected=True)
    assert und.num_edges == 2 * csr.num_edges


def test_config_overrides_force_choice():
    s = GraphStats.from_csr(skewed_graph())
    forced = decide(s, "cpu", overrides={"strategy": "segment"})
    assert forced.strategy == "segment" and forced.source == "config"
    cut = decide(
        s, "cpu", overrides={"strategy": "hybrid", "hub_cutoff": 64}
    )
    assert cut.strategy == "hybrid" and cut.hub_cutoff == 64
    # a tiny budget pushes the auto choice off the packed layouts
    tiny = decide(s, "cpu", overrides={"budget_bytes": 1024})
    assert tiny.strategy == "segment"


def test_tier_schedules_pow2_and_bounded():
    s = GraphStats.from_csr(skewed_graph())
    f_sched, e_sched = decide_tiers(s, {"max_tiers": 4})
    for sched, hi in ((f_sched, s.num_vertices), (e_sched, s.num_edges)):
        assert len(sched) <= 4 + 1
        assert list(sched) == sorted(sched)
        for t in sched[:-1]:
            assert t & (t - 1) == 0, f"non-pow2 tier {t}"
    # pick_tier: smallest tier covering the need; top = dense fallback
    assert pick_tier(1, e_sched, s.num_edges) == e_sched[0]
    assert pick_tier(10 ** 9, e_sched, s.num_edges) == s.num_edges
    # measured refinement: a mid tier with ~zero utilization is pruned
    mid = e_sched[1] if len(e_sched) > 2 else None
    if mid is not None:
        _f2, e2 = decide_tiers(
            s, {"max_tiers": 4},
            measured={"roofline_by_tier": {
                str(mid): {"roofline_utilization": 0.0},
            }},
        )
        assert mid not in e2


# ------------------------------------------------------- one price list
def _graph500_stats(scale):
    """Degree statistics of the benchmark's own graph (benchmark/data.py:
    Graph500 R-MAT .57/.19/.19/.05, edge factor 16, structure seed 500)."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "data.py",
    )
    spec = importlib.util.spec_from_file_location("benchmark_data", path)
    data = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(data)
    n, _src, dst, _perm = data.rmat_edges(scale, 16, 500, 7)
    return GraphStats.from_degrees(
        np.bincount(dst, minlength=n), len(dst), weighted=False
    )


@pytest.mark.parametrize("device_kind", ["cpu", "TPU v5 lite"])
def test_every_pack_is_priced_from_one_device_column(device_kind):
    """`decide` prices ell and hybrid with the SAME device's constants:
    less the per-bucket and per-chunk terms, their modeled times are in
    the ratio of their slot counts, on a tpu kind and on a cpu kind. (The
    ELL pack was once priced at the cpu's gather cost on every device, so
    `auto` on a TPU could never pick the pack that gathers less.)"""
    from janusgraph_tpu.olap import autotune

    kind = "tpu" if "TPU" in device_kind else "cpu"
    s = GraphStats.from_csr(skewed_graph(n=2000, m=40000))
    cutoff, slots, hubs, buckets, chunk_rows = s.hybrid_by_cutoff[3]
    d = decide(s, device_kind, overrides={"hub_cutoff": cutoff})
    per_bucket = autotune._BUCKET_OVERHEAD_S[kind] * 1e3
    ell = d.modeled_ms["ell"] - len(s.degree_hist) * per_bucket
    hyb = (
        d.modeled_ms["hybrid"]
        - (buckets + (1 if hubs else 0)) * per_bucket
        - chunk_rows * autotune._TAIL_CHUNK_COST_S[kind] * 1e3
    )
    assert slots < s.ell_slots
    assert hyb / ell == pytest.approx(slots / s.ell_slots, rel=1e-9)
    assert ell == pytest.approx(
        s.ell_slots * autotune._GATHER_COST_S[kind] * 1e3, rel=1e-3
    )


#: what the parent commit decided on a cpu kind for the benchmark's graph
PARENT_CPU_DECISIONS = {
    12: ("hybrid", 1024, 256, 1.0008,
         {"ell": 0.3091, "hybrid": 0.2421, "segment": 0.541}),
    13: ("hybrid", 512, 256, 1.0109,
         {"ell": 0.6138, "hybrid": 0.4727, "segment": 1.0818}),
    14: ("hybrid", 1024, 256, 1.0058,
         {"ell": 1.2634, "hybrid": 0.9206, "segment": 2.1634}),
}


@pytest.mark.parametrize("scale", sorted(PARENT_CPU_DECISIONS))
def test_auto_picks_the_zero_padding_pack_on_the_chip(scale):
    """On the benchmark's degree distribution `auto` on a v5e resolves to
    the hybrid pack, at most 1.15 slots an edge, with no option set; and a
    cpu kind decides what it decided before the price lists were joined
    (the default column WAS the cpu's)."""
    s = _graph500_stats(scale)
    tpu = decide(s, "TPU v5 lite")
    assert tpu.strategy == "hybrid" and tpu.source == "model"
    assert tpu.pad_ratio_est <= 1.15
    assert tpu.modeled_ms["hybrid"] < 0.95 * tpu.modeled_ms["ell"]
    cpu = decide(s, "cpu").as_dict()
    strategy, cutoff, chunk, pad, modeled = PARENT_CPU_DECISIONS[scale]
    assert (
        cpu["strategy"], cpu["hub_cutoff"], cpu["tail_chunk"],
        cpu["pad_ratio_est"], cpu["modeled_ms"],
    ) == (strategy, cutoff, chunk, pad, modeled)


# ------------------------------------------------- bitwise result identity
BITWISE_PROGRAMS = [
    ("pagerank", lambda: PageRankProgram(max_iterations=12, tol=0.0), "rank"),
    ("bfs", lambda: ShortestPathProgram(seed_index=3, max_iterations=6),
     "distance"),
    ("cc", lambda: ConnectedComponentsProgram(max_iterations=40),
     "component"),
]


@pytest.mark.parametrize("weights", [False, True], ids=["unweighted", "w"])
@pytest.mark.parametrize(
    "name,make,key", BITWISE_PROGRAMS, ids=[p[0] for p in BITWISE_PROGRAMS]
)
def test_hybrid_bitwise_equals_ell_device(name, make, key, weights):
    """The tentpole contract: hybrid and pure-ELL runs are bit-for-bit
    identical on the device executor (frontier off so the dense BSP path
    is what's compared)."""
    g = skewed_graph(weights=weights)
    ell = TPUExecutor(g, strategy="ell").run(make(), frontier="off")
    hyb = TPUExecutor(g, strategy="hybrid").run(make(), frontier="off")
    assert set(ell) == set(hyb)
    for k in ell:
        np.testing.assert_array_equal(
            np.asarray(hyb[k]), np.asarray(ell[k]),
            err_msg=f"device:{name}:{k}",
        )


@pytest.mark.parametrize(
    "name,make,key", BITWISE_PROGRAMS, ids=[p[0] for p in BITWISE_PROGRAMS]
)
def test_hybrid_bitwise_equals_ell_cpu(name, make, key):
    """Same contract on the CPU executor's numpy replay of the packs —
    and both pack strategies agree with the scalar oracle to float32
    tolerance."""
    g = skewed_graph(seed=11)
    oracle = CPUExecutor(g).run(make())
    ell = CPUExecutor(g, strategy="ell").run(make())
    hyb = CPUExecutor(g, strategy="hybrid").run(make())
    for k in oracle:
        np.testing.assert_array_equal(
            np.asarray(hyb[k]), np.asarray(ell[k]),
            err_msg=f"cpu:{name}:{k}",
        )
        np.testing.assert_allclose(
            np.asarray(ell[k], dtype=np.float64), oracle[k],
            rtol=1e-4, atol=1e-5, err_msg=f"cpu-oracle:{name}:{k}",
        )


def test_hybrid_bitwise_supernode_row_split():
    """Hubs past max_capacity row-split; the tail's chunked partial fold
    must reproduce the split rows' segment combine bit-for-bit."""
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    n, m = 300, 8000
    dst = np.concatenate([
        np.zeros(5000, dtype=np.int64),  # one monster hub
        (rng.zipf(1.4, m - 5000) % n).astype(np.int64),
    ])
    src = rng.integers(0, n, m)
    msgs = rng.uniform(-1, 1, n).astype(np.float32)
    ell = ELLPack(src, dst, None, n, max_capacity=64)
    hyb = HybridPack(
        src, dst, None, n, hub_cutoff=8, tail_chunk=16, max_capacity=64
    )
    for op in (Combiner.SUM, Combiner.MIN, Combiner.MAX):
        a = np.asarray(ell_aggregate(jnp, ell, jnp.asarray(msgs), op))
        b = np.asarray(hybrid_aggregate(jnp, hyb, jnp.asarray(msgs), op))
        np.testing.assert_array_equal(b, a, err_msg=op)


def test_hybrid_pad_ratio_beats_ell():
    """The point of the format: on a heavy-tailed graph the hybrid pack
    moves <1.15x the edge count where pow2 ELL moves ~1.5x."""
    g = skewed_graph(n=2000, m=40000)
    fp = TPUExecutor.ell_footprint(g)
    dst = np.repeat(
        np.arange(g.num_vertices, dtype=np.int64), np.diff(g.in_indptr)
    )
    hyb = HybridPack(g.in_src.astype(np.int64), dst, None, g.num_vertices)
    assert fp["pad_ratio"] > 1.3
    assert hyb.pad_ratio < 1.15
    assert hyb.pad_ratio < fp["pad_ratio"]


def test_tree_reduce_fixed_tree():
    """tree_reduce is the adjacent-pair tree: chunked evaluation of an
    aligned pow2 sub-range equals the sub-tree, the identity property the
    hybrid tail rests on. Non-pow2 widths are refused."""
    rng = np.random.default_rng(0)
    x = rng.uniform(0.001, 1.0, (3, 64)).astype(np.float32)
    whole = tree_reduce(np, x, Combiner.SUM)
    chunks = x.reshape(3, 4, 16)
    partial = np.stack(
        [tree_reduce(np, chunks[:, j], Combiner.SUM) for j in range(4)],
        axis=1,
    )
    np.testing.assert_array_equal(
        tree_reduce(np, partial, Combiner.SUM), whole
    )
    with pytest.raises(ValueError):
        tree_reduce(np, x[:, :60], Combiner.SUM)


# ----------------------------------------------------------------- wiring
def test_run_info_records_decision():
    g = skewed_graph()
    ex = TPUExecutor(g)
    ex.run(PageRankProgram(max_iterations=4, tol=0.0))
    rec = ex.last_run_info.get("autotune")
    assert rec is not None
    assert rec["strategy"] in ("ell", "hybrid", "segment")
    assert rec["source"] in ("model", "config", "measured+model")
    assert rec["e_schedule"] == sorted(rec["e_schedule"])
    assert ex.last_run_info["pad_ratio"] == ex.last_run_info["ell_pad_ratio"]
    # explicit strategies still record provenance
    ex2 = TPUExecutor(g, strategy="ell")
    ex2.run(PageRankProgram(max_iterations=4, tol=0.0))
    assert ex2.last_run_info["autotune"]["source"] == "config"
    assert ex2.last_run_info["strategy_resolved"] == "ell"


def test_frontier_uses_tuned_schedule():
    g = skewed_graph(n=3000, m=30000)
    ex = TPUExecutor(g)
    ex.run(ShortestPathProgram(seed_index=0, max_iterations=4))
    info = ex.last_run_info
    assert info["path"] == "frontier"
    sched = tuple(info["autotune"]["e_schedule"])
    for tier in info["tiers"]:
        assert tier["tier_source"] == "autotune"
        assert tier["E_cap"] in sched or tier["E_cap"] == g.num_edges
    # tuner off -> legacy ladder
    ex2 = TPUExecutor(g, autotune=False)
    ex2.run(ShortestPathProgram(seed_index=0, max_iterations=4))
    assert all(
        t["tier_source"] == "static" for t in ex2.last_run_info["tiers"]
    )


def test_computer_config_keys_flow_through():
    """graph.compute() forwards the computer.autotune-* keys."""
    from janusgraph_tpu.core.graph import open_graph

    g = open_graph({
        "storage.backend": "inmemory",
        "computer.autotune-hub-cutoff": 16,
        "computer.autotune-tail-chunk": 32,
        "computer.strategy": "hybrid",
    })
    tx = g.new_transaction()
    prev = None
    for _ in range(12):
        v = tx.add_vertex()
        if prev is not None:
            tx.add_edge(prev, "next", v)
        prev = v
    tx.commit()
    res = (
        g.compute(executor="tpu")
        .program(PageRankProgram(max_iterations=3, tol=0.0))
        .submit()
    )
    assert len(res.states["rank"]) == 12
    g.close()


def test_run_on_cpu_strategy_plumbs():
    g = skewed_graph(seed=4)
    scalar = run_on(g, PageRankProgram(max_iterations=5, tol=0.0), "cpu")
    hyb = run_on(
        g, PageRankProgram(max_iterations=5, tol=0.0), "cpu",
        cpu_strategy="hybrid",
    )
    np.testing.assert_allclose(
        hyb["rank"], scalar["rank"], rtol=1e-4, atol=1e-6
    )


def test_hybrid_2d_messages_and_transform_bitwise():
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    n, m, k = 120, 2400, 4
    dst = (rng.zipf(1.5, m) % n).astype(np.int64)
    src = rng.integers(0, n, m)
    w = rng.uniform(0.1, 3.0, m).astype(np.float32)
    msgs = rng.uniform(0, 1, (n, k)).astype(np.float32)
    ell = ELLPack(src, dst, w, n)
    hyb = HybridPack(src, dst, w, n, hub_cutoff=8, tail_chunk=8)
    for tr in (EdgeTransform.MUL_WEIGHT, EdgeTransform.ADD_WEIGHT):
        a = np.asarray(
            ell_aggregate(jnp, ell, jnp.asarray(msgs), Combiner.SUM, tr)
        )
        b = np.asarray(
            hybrid_aggregate(jnp, hyb, jnp.asarray(msgs), Combiner.SUM, tr)
        )
        np.testing.assert_array_equal(b, a, err_msg=tr)


def test_hybrid_pack_rejects_bad_shapes():
    g = skewed_graph(seed=3)
    dst = np.repeat(
        np.arange(g.num_vertices, dtype=np.int64), np.diff(g.in_indptr)
    )
    with pytest.raises(ValueError):
        HybridPack(
            g.in_src.astype(np.int64), dst, None, g.num_vertices,
            tail_chunk=100,
        )
    with pytest.raises(ValueError):
        HybridPack(
            g.in_src.astype(np.int64), dst, None, g.num_vertices,
            hub_cutoff=0,
        )
