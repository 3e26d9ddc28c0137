"""ISSUE 6 gate: degree-bucketed hybrid format + profiler-driven autotuner.

Three contracts:

1. **Determinism** — `autotune.decide` is a pure function: identical
   (GraphStats, device_kind, overrides, measured) give an identical
   AutotuneDecision, and stats built twice from the same CSR are equal;
   on the benchmark's graphs it decides what the commit before the
   strategy switch went (40a31da) decided.
2. **Bitwise identity** — the hybrid pack's results are bit-for-bit
   equal to the pure-ELL replay (BFS/CC against the CPU oracle's, the
   aggregations themselves weighted and unweighted, supernode row-split,
   2-D messages); tests/test_pack_contract.py holds the device executor's
   own pack to the same replay.
3. **Wiring** — the decision lands in `run_info["autotune"]`, the
   `computer.autotune-*` keys override it, and the frontier engine prices
   hops against the tuner's tier schedule.
"""

import functools

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
    ov = {"hub_cutoff": 32}
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


def test_config_overrides_force_the_packs_sizes():
    csr = skewed_graph()
    s = GraphStats.from_csr(csr)
    cut = decide(s, "cpu", overrides={"hub_cutoff": 64})
    assert cut.hub_cutoff == 64 and cut.tail_chunk == 128
    assert cut.source == "model"
    # the chunk never outgrows the narrowest hub's tree
    assert decide(s, "cpu", overrides={"tail_chunk": 16}).tail_chunk == 16
    assert decide(
        s, "cpu", overrides={"hub_cutoff": 8, "tail_chunk": 256}
    ).tail_chunk == 16
    # a cutoff that is no pow2 candidate is priced when the statistics
    # were built for it (the executor's), and refused when they were not
    with pytest.raises(ValueError, match="hub cutoff 48"):
        decide(s, "cpu", overrides={"hub_cutoff": 48})
    s48 = GraphStats.from_csr(csr, hub_cutoff=48)
    odd = decide(s48, "cpu", overrides={"hub_cutoff": 48})
    assert odd.hub_cutoff == 48 and odd.tail_chunk == 64
    # (the closed form leaves out the few slots that make the index
    # vector's length a prime)
    assert odd.pad_ratio_est == pytest.approx(
        HybridPack(*in_edges(csr), None, csr.num_vertices,
                   hub_cutoff=48, tail_chunk=64).pad_ratio, abs=2e-3)
    # and the candidate it adds changes no other decision
    assert decide(s48, "cpu") == decide(s, "cpu")


def in_edges(csr):
    dst = np.repeat(
        np.arange(csr.num_vertices, dtype=np.int64), np.diff(csr.in_indptr)
    )
    return csr.in_src.astype(np.int64), dst


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
@functools.lru_cache(maxsize=None)
def graph500(scale):
    """The benchmark's own graph (benchmark/data.py: Graph500 R-MAT
    .57/.19/.19/.05, edge factor 16, structure seed 500; --seed 1)."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "data.py",
    )
    spec = importlib.util.spec_from_file_location("benchmark_data", path)
    data = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(data)
    n, src, dst, _perm = data.rmat_edges(scale, 16, 500, 1)
    return csr_from_edges(n, src, dst)


@pytest.mark.parametrize("device_kind", ["cpu", "TPU v5 lite"])
def test_every_pack_is_priced_from_one_device_column(device_kind):
    """`decide` prices the pack it sizes and the ELL pack it reports
    beside it with the SAME device's constants: less the per-bucket and
    per-chunk terms, their modeled times are in the ratio of their slot
    counts, on a tpu kind and on a cpu kind. (The ELL pack was once priced
    at the cpu's gather cost on every device, so a TPU never got the pack
    that gathers less.)"""
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


#: what commit 40a31da (the last with the strategy switch; it chose
#: "hybrid" in every row) decided for the benchmark's graph, written down
#: from a run of that commit: (scale, undirected view) -> hub cutoff, tail
#: chunk, F and E schedules, slots an edge, modeled ms by device kind, and
#: the first 16 hex digits of the sha256 of the index vector of the pack
#: its executor built
PARENT_DECISIONS = {
    (12, False): (1024, 256, [1024, 2048, 4096], [16384, 32768, 65536],
                  1.0008, {"TPU v5 lite": 0.4986, "cpu": 0.2421},
                  65599, "318676701183da76"),
    (12, True): (1024, 256, [1024, 2048, 4096], [32768, 65536, 131072],
                 1.0113, {"TPU v5 lite": 1.0082, "cpu": 0.4778},
                 132589, "a2ffb7d7861dfd45"),
    (13, False): (512, 256, [1024, 2048, 4096, 8192],
                  [16384, 32768, 65536, 131072],
                  1.0109, {"TPU v5 lite": 1.0077, "cpu": 0.4727},
                  132511, "4a180c6167a63b44"),
    (13, True): (1024, 256, [1024, 2048, 4096, 8192],
                 [32768, 65536, 131072, 262144],
                 1.0101, {"TPU v5 lite": 2.0137, "cpu": 0.9279},
                 264811, "dfd137a66c48137e"),
    (14, False): (1024, 256, [1024, 2048, 4096, 8192, 16384],
                  [16384, 32768, 65536, 131072, 262144],
                  1.0058, {"TPU v5 lite": 2.0048, "cpu": 0.9206},
                  263657, "22e402b62fb3441a"),
    (14, True): (512, 256, [1024, 2048, 4096, 8192, 16384],
                 [32768, 65536, 131072, 262144, 524288],
                 1.0304, {"TPU v5 lite": 4.1114, "cpu": 1.8754},
                 540251, "cc4c7375ba6b8a65"),
}


@pytest.mark.parametrize("device_kind", ["TPU v5 lite", "cpu"])
@pytest.mark.parametrize(
    "scale,undirected", sorted(PARENT_DECISIONS),
    ids=[f"s{s}-{'closure' if u else 'directed'}"
         for s, u in sorted(PARENT_DECISIONS)],
)
def test_decide_sizes_the_pack_as_the_parent_did(
    scale, undirected, device_kind
):
    """On the benchmark's degree distribution, with no option set, the
    pack has the sizes and the frontier engine the tiers they had while
    `auto` still chose among strategies: at most 1.15 slots an edge, and
    under the ELL pack's modeled time by more than the old hysteresis."""
    cutoff, chunk, f_sched, e_sched, pad, modeled, _slots, _sha = (
        PARENT_DECISIONS[(scale, undirected)]
    )
    stats = GraphStats.from_csr(graph500(scale), undirected=undirected)
    d = decide(stats, device_kind)
    rec = d.as_dict()
    assert "strategy" not in rec and d.source == "model"
    assert (
        rec["hub_cutoff"], rec["tail_chunk"], rec["f_schedule"],
        rec["e_schedule"], rec["pad_ratio_est"], rec["modeled_ms"]["hybrid"],
    ) == (cutoff, chunk, f_sched, e_sched, pad, modeled[device_kind])
    assert d.pad_ratio_est <= 1.15
    assert d.modeled_ms["hybrid"] < 0.95 * d.modeled_ms["ell"]


@pytest.mark.parametrize(
    "scale,undirected", sorted(PARENT_DECISIONS),
    ids=[f"s{s}-{'closure' if u else 'directed'}"
         for s, u in sorted(PARENT_DECISIONS)],
)
def test_executor_builds_the_parents_pack(scale, undirected):
    """The index vector the executor ships is the parent's to the byte, so
    the lowered superstep is the parent's and a compile cache it filled
    serves this code."""
    import hashlib

    cutoff, chunk, _f, _e, _pad, _ms, slots, sha = (
        PARENT_DECISIONS[(scale, undirected)]
    )
    pack = TPUExecutor(graph500(scale))._hybrid_pack(undirected)
    idx = np.asarray(pack.arrays["idx"])
    assert (pack.hub_cutoff, pack.tail_chunk, pack.slots, len(idx)) == (
        cutoff, chunk, slots, slots)
    assert sorted(pack.arrays) == ["idx", "slot", "unpermute"]
    assert hashlib.sha256(idx.tobytes()).hexdigest()[:16] == sha


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
    "name,make,key", BITWISE_PROGRAMS[1:],
    ids=[p[0] for p in BITWISE_PROGRAMS[1:]],
)
def test_device_run_bitwise_equals_the_ell_replay(name, make, key, weights):
    """The device executor's dense run (frontier off) against the CPU
    oracle's replay of the ELL tree, for the programs whose apply is exact
    in any float width (the oracle keeps its state in float64, so
    PageRank's ranks agree to rounding only; its aggregation is held
    bitwise in tests/test_pack_contract.py)."""
    g = skewed_graph(weights=weights)
    ell = CPUExecutor(g, strategy="ell").run(make())
    dev = TPUExecutor(g).run(make(), frontier="off")
    assert set(ell) == set(dev)
    for k in ell:
        np.testing.assert_array_equal(
            np.asarray(dev[k], dtype=np.float32),
            np.asarray(ell[k], dtype=np.float32),
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
    src, dst = in_edges(g)
    ell = ELLPack(src, dst, None, g.num_vertices)
    hyb = HybridPack(src, dst, None, g.num_vertices)
    assert ell.pad_ratio > 1.3
    assert hyb.pad_ratio < 1.15
    # and the statistics count the ELL pack's slots without building it
    assert GraphStats.from_csr(g).ell_slots == ell.slots


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
    assert "strategy" not in rec
    assert rec["source"] in ("model", "measured+model")
    assert rec["e_schedule"] == sorted(rec["e_schedule"])
    info = ex.last_run_info
    assert info["pad_ratio"] == info["ell_pad_ratio"]
    assert info["strategy_resolved"] == "hybrid"
    # the record's sizes are the pack's
    pack = ex._hybrid_pack(False)
    assert (rec["hub_cutoff"], rec["tail_chunk"]) == (
        pack.hub_cutoff, pack.tail_chunk)
    assert info["pad_ratio"] == round(pack.pad_ratio, 4)
    # configured sizes hold, a cutoff that is no pow2 candidate included
    ex2 = TPUExecutor(g, hub_cutoff=48, tail_chunk=32)
    ex2.run(PageRankProgram(max_iterations=4, tol=0.0))
    rec2 = ex2.last_run_info["autotune"]
    assert (rec2["hub_cutoff"], rec2["tail_chunk"]) == (48, 32)
    assert ex2._hybrid_pack(False).hub_cutoff == 48


def test_frontier_uses_tuned_schedule():
    g = skewed_graph(n=3000, m=30000)
    ex = TPUExecutor(g)
    ex.run(ShortestPathProgram(seed_index=0, max_iterations=4))
    info = ex.last_run_info
    assert info["path"] == "frontier"
    sched = tuple(info["autotune"]["e_schedule"])
    rows = tuple(info["autotune"]["f_schedule"])
    for tier in info["tiers"]:
        assert tier["tier_source"] == "autotune"
        if tier["direction"] == "bottom_up":
            # no edge rung: its unreached rows on the F ladder
            assert tier["F_cap"] in rows or tier["F_cap"] == g.num_vertices
        else:
            assert tier["E_cap"] in sched or tier["E_cap"] == g.num_edges


def test_computer_config_keys_flow_through():
    """graph.compute() forwards the computer.autotune-* keys."""
    from janusgraph_tpu.core.graph import open_graph

    g = open_graph({
        "storage.backend": "inmemory",
        "computer.autotune-hub-cutoff": 16,
        "computer.autotune-tail-chunk": 32,
        "computer.sharded-auto": False,  # conftest shows eight devices
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
    assert res.run_info["autotune"]["hub_cutoff"] == 16
    assert res.run_info["autotune"]["tail_chunk"] == 32
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
