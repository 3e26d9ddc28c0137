"""ISSUE 7 gate: dense-feature vertex-program tier (fused SDDMM–SpMM).

Four contracts:

1. **Bitwise identity** — GCN forward and embedding-update runs are
   bit-for-bit equal across {TPUExecutor, CPUExecutor} x {ell, hybrid}
   for every message mode (copy / weighted / sddmm): the fused dense
   kernels reduce through the shared fixed adjacent-pair tree and every
   product feeding an add is fp-fenced, so no backend contraction (fused
   multiply-add) can change bits.
2. **Resumability** — a preempted dense run auto-resumes from the
   checkpoint and finishes bitwise-identical to a fault-free run, on
   both executors.
3. **Autotune** — decide() is deterministic in its new feature-dim
   input, records the padded tier, and the executor persists measured
   records across lifetimes (computer.autotune-persist).
4. **Observability** — run_info carries per-superstep `mxu_flops` /
   `mxu_utilization` and a run-level `mxu` block on both executors.
"""

from __future__ import annotations

import numpy as np
import pytest

from janusgraph_tpu.olap import csr_from_edges, run_on
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.features.dense_program import (
    DenseVertexProgram,
    MessageMode,
)
from janusgraph_tpu.olap.features.kernels import (
    FEATURE_TIERS,
    ell_row_dsts,
    hybrid_row_dsts,
    pad_features,
    pick_feature_tier,
    sddmm_ell_aggregate,
    sddmm_hybrid_aggregate,
    tree_dot,
    tree_matmul,
)
from janusgraph_tpu.olap.kernels import ELLPack, HybridPack
from janusgraph_tpu.olap.programs.embedding import EmbeddingUpdateProgram
from janusgraph_tpu.olap.programs.gcn import GCNForwardProgram
from janusgraph_tpu.olap.tpu_executor import TPUExecutor


def skewed_graph(n=400, m=6000, seed=3, weights=False):
    """Heavy-tailed destinations so the hybrid pack has a real tail."""
    rng = np.random.default_rng(seed)
    dst = (rng.zipf(1.35, m) % n).astype(np.int64)
    src = rng.integers(0, n, m).astype(np.int64)
    w = rng.uniform(0.25, 2.0, m).astype(np.float32) if weights else None
    return csr_from_edges(n, src, dst, w)


# ----------------------------------------------------------- kernel units
def test_pick_feature_tier_ladder():
    assert pick_feature_tier(1) == 8
    assert pick_feature_tier(8) == 8
    assert pick_feature_tier(9) == 16
    assert pick_feature_tier(512) == 512
    assert pick_feature_tier(513) == 1024  # past the ladder: next pow2
    assert pick_feature_tier(12, forced=64) == 64
    with pytest.raises(ValueError):
        pick_feature_tier(0)
    with pytest.raises(ValueError):
        pick_feature_tier(12, forced=48)  # not pow2
    with pytest.raises(ValueError):
        pick_feature_tier(100, forced=64)  # truncates the logical dim


def test_pad_features_zero_padding():
    h = np.ones((5, 12), dtype=np.float32)
    p = pad_features(h, 16)
    assert p.shape == (5, 16)
    np.testing.assert_array_equal(p[:, :12], h)
    np.testing.assert_array_equal(p[:, 12:], 0.0)
    with pytest.raises(ValueError):
        pad_features(h, 8)  # would truncate
    with pytest.raises(ValueError):
        pad_features(np.ones(5, dtype=np.float32), 8)  # not 2-D


def test_tree_dot_is_fixed_tree():
    """Chunked evaluation of aligned pow2 sub-ranges equals the subtree
    fold — the property that makes the SDDMM coefficient layout-blind."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((7, 64)).astype(np.float32)
    b = rng.standard_normal((7, 64)).astype(np.float32)
    whole = tree_dot(np, a, b)
    parts = np.stack(
        [
            tree_dot(np, a[:, j * 16:(j + 1) * 16], b[:, j * 16:(j + 1) * 16])
            for j in range(4)
        ],
        axis=1,
    )
    from janusgraph_tpu.olap.kernels import tree_reduce

    np.testing.assert_array_equal(tree_reduce(np, parts, "sum"), whole)


def test_tree_matmul_matches_reference_and_jit():
    """Deterministic tree contraction: close to the BLAS result, bitwise
    equal between the numpy path and the jitted path (the fp fence), and
    row-chunking never changes bits."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    h = rng.standard_normal((333, 32)).astype(np.float32)
    w = rng.standard_normal((32, 16)).astype(np.float32)
    out = tree_matmul(np, h, w)
    np.testing.assert_allclose(out, h @ w, rtol=1e-5, atol=1e-5)
    jout = np.asarray(jax.jit(lambda h, w: tree_matmul(jnp, h, w))(h, w))
    np.testing.assert_array_equal(out, jout)
    with pytest.raises(ValueError):
        tree_matmul(np, h[:, :20], w[:20])  # non-pow2 contraction width


def test_sddmm_aggregate_layouts_bitwise_and_vs_dense():
    """ELL and hybrid fused SDDMM+SpMM agree bit-for-bit (numpy and jit),
    and both match a dense reference to float tolerance."""
    import jax
    import jax.numpy as jnp

    g = skewed_graph()
    n = g.num_vertices
    src = g.in_src.astype(np.int64)
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(g.in_indptr))
    rng = np.random.default_rng(1)
    msgs = rng.standard_normal((n, 16)).astype(np.float32)

    ell = ELLPack(src, dst, None, n)
    erows = ell_row_dsts(src, dst, n)
    hyb = HybridPack(g.in_src.astype(np.int64), dst, None, n,
                     hub_cutoff=16, tail_chunk=16)
    hrows = hybrid_row_dsts(src, dst, n, hub_cutoff=16, tail_chunk=16)

    a = sddmm_ell_aggregate(np, ell, erows, msgs)
    b = sddmm_hybrid_aggregate(np, hyb, hrows, msgs)
    np.testing.assert_array_equal(a, b)

    ell_d = ELLPack(src, dst, None, n).device_put(jnp)
    erows_d = [jnp.asarray(r) for r in erows]
    aj = np.asarray(
        jax.jit(lambda m: sddmm_ell_aggregate(jnp, ell_d, erows_d, m))(msgs)
    )
    np.testing.assert_array_equal(a, aj)
    hyb_d = HybridPack(src, dst, None, n,
                       hub_cutoff=16, tail_chunk=16).device_put(jnp)
    hrows_d = jnp.asarray(hrows)
    bj = np.asarray(
        jax.jit(lambda m: sddmm_hybrid_aggregate(jnp, hyb_d, hrows_d, m))(msgs)
    )
    np.testing.assert_array_equal(a, bj)

    # dense reference: sum_e <h_src, h_dst> h_src per destination
    ref = np.zeros_like(msgs, dtype=np.float64)
    m64 = msgs.astype(np.float64)
    for s, d in zip(src, dst):
        ref[d] += m64[s] * float(np.dot(m64[s], m64[d]))
    np.testing.assert_allclose(a, ref, rtol=1e-3, atol=1e-4)


def test_sddmm_rejects_bad_shapes():
    g = skewed_graph(n=32, m=100)
    n = g.num_vertices
    src = g.in_src.astype(np.int64)
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(g.in_indptr))
    ell = ELLPack(src, dst, None, n)
    rows = ell_row_dsts(src, dst, n)
    ok = np.ones((n, 16), dtype=np.float32)
    with pytest.raises(ValueError):
        sddmm_ell_aggregate(  # 12 is not a lane tier
            np, ell, rows, np.ones((n, 12), dtype=np.float32))
    with pytest.raises(ValueError):
        sddmm_ell_aggregate(np, ell, rows, ok, op="min")  # SUM-only
    with pytest.raises(ValueError):
        sddmm_ell_aggregate(np, ell, rows[:-1], ok)  # pack drift


# ---------------------------------------------- program-level constraints
def test_dense_program_validation():
    with pytest.raises(ValueError):
        GCNForwardProgram(attention=True, weighted=True)

    class BadSddmm(DenseVertexProgram):
        message_mode = MessageMode.SDDMM
        combiner = "min"

    with pytest.raises(ValueError):
        BadSddmm(feature_dim=8)

    p = GCNForwardProgram(feature_dim=12)
    assert p.d_pad == 16
    p.set_dim_tier(64)
    assert p.d_pad == 64
    assert p._w_stack.shape == (2, 64, 64)
    with pytest.raises(ValueError):
        EmbeddingUpdateProgram(mode="bogus")


def test_sddmm_undirected_rejected_on_both_executors():
    g = skewed_graph(n=64, m=400)
    p = EmbeddingUpdateProgram(feature_dim=8, max_iterations=1, mode="sddmm")
    p.undirected = True
    with pytest.raises(ValueError, match="in-CSR"):
        TPUExecutor(g).run(p)
    with pytest.raises(ValueError, match="in-CSR"):
        CPUExecutor(g, strategy="ell").run(p)


# ------------------------------------------------- executor bitwise matrix
GCN_MODES = [
    ("copy", {}, False),
    ("attention", {"attention": True}, False),
    ("weighted", {"weighted": True}, True),
]
EMB_MODES = [
    ("copy", {"mode": MessageMode.COPY}, False),
    ("sddmm", {"mode": MessageMode.SDDMM}, False),
    ("weighted", {"mode": MessageMode.WEIGHTED}, True),
]


def _run_matrix(make, key, weights):
    g = skewed_graph(weights=weights)
    # the reference: the CPU oracle's replay of the ELL tree
    ref = np.asarray(CPUExecutor(g, strategy="ell").run(make())[key])
    runs = {
        "tpu": TPUExecutor(g).run(make())[key],
        "tpu-tail": TPUExecutor(
            g, hub_cutoff=16, tail_chunk=16
        ).run(make())[key],
        "cpu-hybrid": CPUExecutor(g, strategy="hybrid").run(make())[key],
    }
    assert ref.dtype == np.float32
    for lbl, r in runs.items():
        np.testing.assert_array_equal(np.asarray(r), ref, err_msg=lbl)
    # the scalar per-edge loop is the independent semantic oracle
    oracle = CPUExecutor(g).run(make())[key]
    np.testing.assert_allclose(
        ref.astype(np.float64), oracle, rtol=1e-3, atol=1e-4,
        err_msg="scalar-oracle",
    )


@pytest.mark.parametrize(
    "name,kw,weights", GCN_MODES, ids=[m[0] for m in GCN_MODES]
)
def test_gcn_forward_bitwise_matrix(name, kw, weights):
    """2-layer GCN forward: device and CPU-oracle runs are bitwise equal
    on the ELL and hybrid formats, for every message mode."""
    _run_matrix(
        lambda: GCNForwardProgram(
            feature_dim=12, hidden_dim=12, out_dim=8, num_layers=2,
            seed=5, **kw
        ),
        "h", weights,
    )


@pytest.mark.parametrize(
    "name,kw,weights", EMB_MODES, ids=[m[0] for m in EMB_MODES]
)
def test_embedding_update_bitwise_matrix(name, kw, weights):
    """node2vec-style embedding update: same bitwise matrix, with the
    negative-sampling table as a dense side input."""
    _run_matrix(
        lambda: EmbeddingUpdateProgram(
            feature_dim=16, max_iterations=3, seed=9, **kw
        ),
        "emb", weights,
    )


def test_gcn_explicit_weights_and_activation():
    """User-provided layer weights land in the padded stacks and drive
    the output; identity activation and tanh accepted, junk rejected."""
    rng = np.random.default_rng(0)
    ws = [rng.standard_normal((6, 6)).astype(np.float32) for _ in range(2)]
    g = skewed_graph(n=64, m=500)
    p = GCNForwardProgram(
        feature_dim=6, hidden_dim=6, out_dim=6, num_layers=2,
        weights=ws, activation="identity",
    )
    assert p.d_pad == 8
    np.testing.assert_array_equal(p._w_stack[0, :6, :6], ws[0])
    out = TPUExecutor(g).run(p)["h"]
    assert np.isfinite(np.asarray(out)).all()
    with pytest.raises(ValueError):
        GCNForwardProgram(weights=[np.ones((3, 3))] * 2, feature_dim=6)
    from janusgraph_tpu.olap.features.kernels import dense_transform

    with pytest.raises(ValueError):
        dense_transform(np, np.ones((2, 8), np.float32),
                        np.ones((8, 8), np.float32), activation="gelu")


# ------------------------------------------- checkpoint/preemption resume
@pytest.mark.parametrize("executor", ["cpu", "tpu"])
def test_preempted_gcn_resumes_bitwise_identical(executor, tmp_path):
    """A dense program preempted mid-run auto-resumes from its checkpoint
    and produces bitwise-identical final feature blocks."""
    from janusgraph_tpu.storage.faults import FaultPlan

    g = skewed_graph(n=128, m=1500)
    mk = lambda: GCNForwardProgram(  # noqa: E731
        feature_dim=12, hidden_dim=12, out_dim=8, num_layers=4, seed=5
    )
    baseline = run_on(g, mk(), executor)

    plan = FaultPlan(seed=77, preempt_superstep=2)
    faulted = run_on(
        g, mk(), executor,
        checkpoint_path=str(tmp_path / f"gcn_{executor}.npz"),
        checkpoint_every=1, fault_hook=plan.olap_hook,
    )
    assert any(e["kind"] == "superstep" for e in plan.journal)
    for key in baseline:
        assert baseline[key].dtype == faulted[key].dtype
        np.testing.assert_array_equal(baseline[key], faulted[key],
                                      err_msg=key)


# -------------------------------------------------- autotune: feature dim
def test_decide_feature_dim_deterministic_and_recorded():
    from janusgraph_tpu.olap.autotune import GraphStats, decide

    g = skewed_graph()
    stats = GraphStats.from_csr(g)
    d0 = decide(stats, "cpu")
    assert d0.feature_dim == 0 and d0.feature_tier is None
    d1 = decide(stats, "cpu", feature_dim=12)
    d2 = decide(stats, "cpu", feature_dim=12)
    assert d1 == d2
    assert d1.feature_dim == 12 and d1.feature_tier == 16
    assert d1.as_dict()["feature_tier"] == 16
    # the tier scales modeled message traffic
    assert d1.modeled_ms["ell"] > d0.modeled_ms["ell"]
    # the override pins the tier
    d3 = decide(stats, "cpu", overrides={"feature_dim_tier": 64},
                feature_dim=12)
    assert d3.feature_tier == 64


def test_executor_keys_decisions_by_feature_tier():
    """A dense run's decision is cached separately from scalar runs (the
    tier changes modeled bytes), and run_info records the feature tier."""
    g = skewed_graph()
    ex = TPUExecutor(g)
    p = GCNForwardProgram(feature_dim=12, hidden_dim=12, out_dim=8,
                          num_layers=2)
    ex.run(p)
    info = ex.last_run_info
    assert info["autotune"]["feature_tier"] == 16
    assert (False, 16) in ex._autotune_decisions
    from janusgraph_tpu.olap.programs.pagerank import PageRankProgram

    ex.run(PageRankProgram(max_iterations=2))
    assert (False, 0) in ex._autotune_decisions
    assert ex.last_run_info["autotune"]["feature_tier"] is None


def test_forced_dim_tier_flows_from_executor():
    g = skewed_graph(n=64, m=500)
    p = GCNForwardProgram(feature_dim=12, hidden_dim=12, out_dim=8)
    ex = TPUExecutor(g, features_dim_tier=32)
    out = ex.run(p)
    assert p.d_pad == 32
    assert np.asarray(out["h"]).shape == (64, 32)


# ------------------------------------------ autotune: measured persistence
def test_measured_record_roundtrip(tmp_path):
    from janusgraph_tpu.olap.autotune import load_measured, save_measured

    path = str(tmp_path / "m.json")
    assert load_measured(path) is None
    save_measured(path, {"strategy": "hybrid", "pad_ratio": 1.02,
                         "superstep_ms": 12.5})
    rec = load_measured(path)
    assert rec["pad_ratio"] == 1.02 and rec["superstep_ms"] == 12.5
    # unreadable/garbage files degrade to None, never raise
    with open(path, "w") as f:
        f.write("{not json")
    assert load_measured(path) is None
    save_measured(path, {"strategy": "x"})  # missing calibration fields
    assert load_measured(path) is None


def test_autotune_persists_across_executor_lifetimes(tmp_path):
    """The ROADMAP #2 leftover: a run with a checkpoint path serializes
    its measured record next to the checkpoint, and the NEXT executor
    lifetime's decision is calibrated by it (source=measured+model)."""
    from janusgraph_tpu.olap.autotune import load_measured
    from janusgraph_tpu.olap.programs.pagerank import PageRankProgram

    g = skewed_graph()
    ck = str(tmp_path / "pr.npz")
    ex1 = TPUExecutor(g)
    ex1.run(PageRankProgram(max_iterations=3), checkpoint_path=ck,
            checkpoint_every=2)
    rec = load_measured(ck + ".autotune.json")
    assert rec is not None and rec["superstep_ms"] > 0

    ex2 = TPUExecutor(g)
    ex2.run(PageRankProgram(max_iterations=2), checkpoint_path=ck,
            checkpoint_every=2)
    assert ex2.last_run_info["autotune"]["source"] == "measured+model"

    # config off: no record is written
    ck2 = str(tmp_path / "pr2.npz")
    ex3 = TPUExecutor(g, autotune_persist=False)
    ex3.run(PageRankProgram(max_iterations=2), checkpoint_path=ck2,
            checkpoint_every=2)
    assert load_measured(ck2 + ".autotune.json") is None


# -------------------------------------------------------- mxu observability
def test_mxu_fields_in_run_info_both_executors():
    g = skewed_graph(n=128, m=1500)
    mk = lambda: GCNForwardProgram(  # noqa: E731
        feature_dim=12, hidden_dim=12, out_dim=8, num_layers=2
    )
    for ex, info in (
        (TPUExecutor(g), None),
        (CPUExecutor(g, strategy="ell"), None),
    ):
        ex.run(mk())
        info = ex.last_run_info
        mxu = info["mxu"]
        assert mxu["peak_mxu_flops"] > 0
        assert mxu["per_superstep_flops"] > 0
        assert mxu["mean_utilization"] is not None
        for r in info["superstep_records"]:
            assert r["mxu_flops"] > 0
            assert r["mxu_utilization"] is not None

    # scalar programs carry no mxu block
    from janusgraph_tpu.olap.programs.pagerank import PageRankProgram

    ex = TPUExecutor(g)
    ex.run(PageRankProgram(max_iterations=2))
    assert "mxu" not in ex.last_run_info


def test_device_peaks_mxu_column():
    from janusgraph_tpu.observability.profiler import (
        configure_roofline,
        device_peaks,
    )

    # "TPU v5 lite" is what a v5e reports as its device_kind
    for kind in ("TPU v5 lite", "cpu"):
        peaks = device_peaks(kind)
        assert peaks["peak_mxu_flops"] > 0, kind
    try:
        configure_roofline(peak_mxu_flops=123.0)
        assert device_peaks("cpu")["peak_mxu_flops"] == 123.0
        assert device_peaks("cpu")["source"] == "config"
    finally:
        configure_roofline(peak_mxu_flops=0.0)


# ------------------------------------------------------- end-to-end submit
def _feature_graph(n=24, **cfg):
    from janusgraph_tpu.core.graph import JanusGraphTPU
    from janusgraph_tpu.storage.inmemory import InMemoryStoreManager

    g = JanusGraphTPU(
        {"ids.authority-wait-ms": 0.0, **cfg},
        store_manager=InMemoryStoreManager(),
    )
    tx = g.new_transaction()
    vs = [tx.add_vertex() for _ in range(n)]
    for i in range(n):
        tx.add_edge(vs[i], "knows", vs[(i + 1) % n])
        if i % 3 == 0:
            tx.add_edge(vs[i], "knows", vs[0])
        if i % 4 == 1:
            tx.add_edge(vs[i], "knows", vs[(i * i + 2) % n])
    tx.commit()
    return g


@pytest.mark.parametrize("executor", ["cpu", "tpu"])
def test_gcn_and_embedding_through_submit(executor):
    """The acceptance path: both shipped dense programs run end-to-end
    through GraphComputer.submit() on both executors, honoring the
    computer.features-* keys (forced 32-lane tier here)."""
    g = _feature_graph(**{"computer.features-dim-tier": 32})
    try:
        res = g.compute(executor=executor).program(
            GCNForwardProgram(feature_dim=12, hidden_dim=12, out_dim=8)
        ).submit()
        h = np.asarray(res.states["h"])
        assert h.shape == (res.csr.num_vertices, 32)
        assert np.isfinite(h).all()
        # padded columns stay zero through the layers
        np.testing.assert_array_equal(h[:, 12:], 0.0)

        res2 = g.compute(executor=executor).program(
            EmbeddingUpdateProgram(feature_dim=16, max_iterations=2)
        ).submit()
        emb = np.asarray(res2.states["emb"])
        assert emb.shape == (res2.csr.num_vertices, 32)
        assert np.isfinite(emb).all()
    finally:
        g.close()


def test_native_matmul_config_flows_to_program():
    g = _feature_graph(**{"computer.features-native-matmul": True})
    try:
        p = GCNForwardProgram(feature_dim=8, hidden_dim=8, out_dim=8)
        assert p.native_matmul is False
        res = g.compute(executor="cpu").program(p).submit()
        assert p.native_matmul is True
        assert np.isfinite(np.asarray(res.states["h"])).all()
    finally:
        g.close()
