"""Aggregation-kernel parity: ELL / Pallas strategies vs the segment path
and the scalar CPU oracle (kernels are drop-in replacements for the
reference's combiner hash-map, FulgoraVertexMemory.java:91-99)."""

import numpy as np
import pytest

from janusgraph_tpu.olap import csr_from_edges, run_on
from janusgraph_tpu.olap.kernels import (
    ELLPack,
    HybridPack,
    ell_aggregate,
    hybrid_aggregate,
)
from janusgraph_tpu.olap.programs import (
    ConnectedComponentsProgram,
    PageRankProgram,
    ShortestPathProgram,
    TraversalCountProgram,
)
from janusgraph_tpu.olap.tpu_executor import TPUExecutor
from janusgraph_tpu.olap.vertex_program import Combiner, EdgeTransform


def random_graph(n=180, m=700, seed=11, weights=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = rng.uniform(0.5, 2.0, m).astype(np.float32) if weights else None
    return csr_from_edges(n, src, dst, w)


# ------------------------------------------------------------------ unit level
@pytest.mark.parametrize("op", [Combiner.SUM, Combiner.MIN, Combiner.MAX])
def test_ell_aggregate_matches_numpy(op):
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    n, m = 97, 450
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    w = rng.uniform(0.1, 2.0, m).astype(np.float32)
    msgs = rng.uniform(-1, 1, n).astype(np.float32)

    pack = ELLPack(src, dst, w, n)
    got = np.asarray(
        ell_aggregate(jnp, pack, jnp.asarray(msgs), op, EdgeTransform.MUL_WEIGHT)
    )

    ident = Combiner.IDENTITY[op]
    want = np.full(n, ident, dtype=np.float64)
    for s, d, wt in zip(src, dst, w):
        v = msgs[s] * wt
        if op == Combiner.SUM:
            want[d] += v
        elif op == Combiner.MIN:
            want[d] = min(want[d], v)
        else:
            want[d] = max(want[d], v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ell_aggregate_2d_messages():
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    n, m, k = 60, 240, 5
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    msgs = rng.uniform(0, 1, (n, k)).astype(np.float32)

    pack = ELLPack(src, dst, None, n)
    got = np.asarray(ell_aggregate(jnp, pack, jnp.asarray(msgs), Combiner.SUM))
    want = np.zeros((n, k))
    for s, d in zip(src, dst):
        want[d] += msgs[s]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ell_supernode_jumbo_bucket():
    """A hub vertex with degree above max_capacity row-splits into multiple
    capacity-sized rows folded by the rows-sized segment reduce."""
    import jax.numpy as jnp

    n = 40
    hub_deg = 70
    src = np.concatenate([np.arange(hub_deg) % (n - 1) + 1, [0, 0]])
    dst = np.concatenate([np.zeros(hub_deg, dtype=np.int64), [1, 2]])
    pack = ELLPack(src, dst, None, n, max_capacity=16)
    msgs = np.ones(n, dtype=np.float32)
    got = np.asarray(ell_aggregate(jnp, pack, jnp.asarray(msgs), Combiner.SUM))
    assert got[0] == hub_deg
    assert got[1] == 1 and got[2] == 1


def supernode_graph(weights):
    """One hub past max_capacity (row-split), a heavy tail, and vertices
    no edge reaches."""
    rng = np.random.default_rng(2)
    n, m = 300, 8000
    dst = np.concatenate([
        np.zeros(5000, dtype=np.int64),
        (rng.zipf(1.4, m - 5000) % (n - 40)).astype(np.int64),
    ])
    src = rng.integers(0, n, m)
    w = rng.uniform(0.25, 2.0, m).astype(np.float32) if weights else None
    assert (np.bincount(dst, minlength=n) == 0).sum() >= 40
    return n, src, dst, w


@pytest.mark.parametrize("weights", [False, True], ids=["unweighted", "w"])
@pytest.mark.parametrize("cols", [0, 4], ids=["scalar", "n-by-4"])
@pytest.mark.parametrize("op", [Combiner.SUM, Combiner.MIN, Combiner.MAX])
def test_single_gather_hybrid_bitwise_equals_ell(op, cols, weights):
    """The hybrid pack's one-gather aggregation gives the ELL pack's bits,
    jitted and in the numpy replay of the same body: row-split supernode,
    zero-degree vertices, scalar and (n, k) messages."""
    import jax
    import jax.numpy as jnp

    n, src, dst, w = supernode_graph(weights)
    rng = np.random.default_rng(5)
    msgs = rng.uniform(-1, 1, (n, cols) if cols else n).astype(np.float32)
    transform = EdgeTransform.MUL_WEIGHT if weights else EdgeTransform.NONE
    ell = ELLPack(src, dst, w, n, max_capacity=64)
    hyb = HybridPack(
        src, dst, w, n, hub_cutoff=8, tail_chunk=16, max_capacity=64
    )
    assert "rowseg" in hyb.arrays and hyb.num_zero >= 40
    want = np.asarray(ell_aggregate(jnp, ell, jnp.asarray(msgs), op, transform))
    replay = hybrid_aggregate(np, hyb, msgs, op, transform)
    np.testing.assert_array_equal(replay, want)
    hyb.device_put(jnp)
    got = jax.jit(
        lambda x: hybrid_aggregate(jnp, hyb, x, op, transform)
    )(msgs)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_dense_superstep_gathers_once_per_aggregation():
    """The lowered module of one dense superstep holds the message gather
    and the un-permutation of the result and no other gather, whatever the
    number of exact widths (a count of the module's ops, not a timing)."""
    import jax.numpy as jnp

    n, src, dst, _ = supernode_graph(False)
    g = csr_from_edges(n, src.astype(np.int32), dst.astype(np.int32), None)
    ex = TPUExecutor(g, hub_cutoff=64, tail_chunk=16)
    program = PageRankProgram(max_iterations=3, tol=0.0)
    op = program.combiner
    step = ex._superstep_body(program, op)
    state, metrics = program.setup(ex.g, jnp)
    memory = {k: jnp.float32(v) for k, (_o, v) in metrics.items()}
    text = ex.jax.jit(step).lower(
        state, jnp.int32(0), memory, ex._graph_args(program, op)
    ).as_text()
    count = text.count('"stablehlo.gather"') + text.count(" stablehlo.gather ")
    assert len(ex._hybrid_pack(False).torso_meta) > 20
    assert count == 2  # the messages, and the result's `stacked[unpermute]`


# ------------------------------------------------------------- program parity
STRATEGY_PROGRAMS = [
    ("pagerank", lambda: PageRankProgram(max_iterations=20)),
    ("sssp_weighted", lambda: ShortestPathProgram(seed_index=0, weighted=True)),
    ("cc", lambda: ConnectedComponentsProgram()),
    ("khop", lambda: TraversalCountProgram(hops=3)),
]


@pytest.mark.parametrize(
    "name,make", STRATEGY_PROGRAMS, ids=[p[0] for p in STRATEGY_PROGRAMS]
)
def test_executor_parity_vs_cpu_oracle(name, make):
    g = random_graph(weights=True)
    cpu = run_on(g, make(), "cpu")
    ex = TPUExecutor(g)
    got = ex.run(make())
    assert set(cpu) == set(got)
    for k in cpu:
        np.testing.assert_allclose(
            np.asarray(got[k], dtype=np.float64),
            cpu[k],
            rtol=1e-4,
            atol=1e-5,
            err_msg=f"{name}:{k}",
        )


# ------------------------------------------------------- fused vs host loop
@pytest.mark.parametrize(
    "name,make", STRATEGY_PROGRAMS, ids=[p[0] for p in STRATEGY_PROGRAMS]
)
def test_fused_whole_run_matches_host_loop(name, make):
    g = random_graph(seed=21, weights=True)
    ex = TPUExecutor(g)
    host = ex.run(make(), fused=False)
    fused = ex.run(make(), fused=True)
    for k in host:
        np.testing.assert_allclose(
            np.asarray(fused[k]), np.asarray(host[k]), rtol=1e-5, atol=1e-6,
            err_msg=f"fused:{name}:{k}",
        )


def test_fused_early_termination_device():
    """CC on a tiny path graph converges long before max_iterations; the
    on-device while_loop must stop at the fixpoint (same result)."""
    src = np.array([0, 1, 2, 3], dtype=np.int32)
    dst = np.array([1, 2, 3, 4], dtype=np.int32)
    g = csr_from_edges(6, src, dst, None)
    ex = TPUExecutor(g)
    res = ex.run(ConnectedComponentsProgram(max_iterations=100), fused=True)
    comp = np.asarray(res["component"])
    assert (comp[:5] == comp[0]).all() and comp[5] != comp[0]


def test_sharded_fused_matches_host_loop():
    from janusgraph_tpu.parallel import ShardedExecutor

    g = random_graph(seed=33, weights=True)
    ex = ShardedExecutor(g)
    host = ex.run(PageRankProgram(max_iterations=15), fused=False)
    fused = ex.run(PageRankProgram(max_iterations=15), fused=True)
    np.testing.assert_allclose(fused["rank"], host["rank"], rtol=1e-5, atol=1e-7)
