"""Propagation-blocked halo exchange (ISSUE 9): the sharded executor's
default fast path.

Contracts under test, on the 8-virtual-device CPU mesh:

* MIN-combiner programs (BFS/SSSP/CC) are BITWISE-identical between the
  blocked and eager exchanges (min is exactly order-insensitive), on both
  the dense and frontier paths.
* SUM programs (PageRank, dense feature blocks) are BITWISE-identical to
  the blocked plan's numpy replay oracle (halo.replay_superstep — the
  HybridPack-style same-arithmetic contract) and agree with the eager
  exchange and the scalar CPU oracle to float tolerance.
* Distributed CSR loading: per-host build_local blocks concatenate to the
  single-process plan, with only the compact pair metadata exchanged.
* Chaos interplay: dropped-halo-batch + preemption auto-resume stays
  bitwise under the batched exchange.
* decide_sharded is deterministic and its measured persistence is keyed
  by shard count.
* GraphComputer routing (computer.sharded-auto) picks the sharded
  executor on a mesh and records the decision in run_info["routing"].
"""

import numpy as np
import pytest

from janusgraph_tpu.olap import csr_from_edges, run_on
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.programs import (
    ConnectedComponentsProgram,
    GCNForwardProgram,
    PageRankProgram,
    ShortestPathProgram,
)
from janusgraph_tpu.olap.vertex_program import Combiner, VertexProgram
from janusgraph_tpu.parallel import ShardedExecutor, halo
from janusgraph_tpu.parallel.sharded import ShardedCSR


def random_graph(n=170, m=700, seed=11, weights=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = rng.uniform(0.5, 2.0, m).astype(np.float32) if weights else None
    return csr_from_edges(n, src, dst, w)


@pytest.fixture(scope="module")
def mesh8():
    import jax
    from jax.sharding import Mesh

    devices = np.array(jax.devices()[:8])
    assert len(devices) == 8, "conftest must provide 8 virtual devices"
    return Mesh(devices, ("p",))


# ------------------------------------------------------ bitwise: MIN family
@pytest.mark.parametrize("agg", ["ell", "segment"])
@pytest.mark.parametrize("name,make", [
    ("bfs", lambda: ShortestPathProgram(seed_index=0)),
    ("sssp_w", lambda: ShortestPathProgram(seed_index=3, weighted=True)),
    ("cc", lambda: ConnectedComponentsProgram()),
])
def test_blocked_bitwise_min_family_dense_path(mesh8, agg, name, make):
    """Blocked vs eager, dense (non-frontier) supersteps: min/max merges
    are exactly order-insensitive, so the exchange restructure must not
    change a single bit."""
    g = random_graph(weights=True)
    blocked = ShardedExecutor(g, mesh=mesh8, exchange="blocked", agg=agg)
    eager = ShardedExecutor(g, mesh=mesh8)  # a2a + ell, the PR 8 default
    rb = blocked.run(make(), frontier="off")
    re_ = eager.run(make(), frontier="off")
    assert set(rb) == set(re_)
    for k in rb:
        np.testing.assert_array_equal(
            np.asarray(rb[k]), np.asarray(re_[k]), err_msg=f"{name}:{k}"
        )
    cpu = CPUExecutor(g).run(make())
    for k in rb:
        np.testing.assert_allclose(
            np.asarray(rb[k], np.float64), cpu[k], rtol=1e-5, atol=1e-6,
        )


def test_blocked_frontier_bitwise_and_collapsed_expansion(mesh8):
    """The frontier engine under the blocked exchange: sender-merged
    relaxation bins, bitwise-identical hops, remote expansion collapsed
    to one edge per used bin (strictly fewer max edges than eager)."""
    g = random_graph(n=190, m=900, seed=5, weights=True)
    blocked = ShardedExecutor(g, mesh=mesh8, exchange="blocked")
    eager = ShardedExecutor(g, mesh=mesh8)
    for make in (
        lambda: ShortestPathProgram(seed_index=0),
        lambda: ShortestPathProgram(seed_index=3, weighted=True),
    ):
        rb = blocked.run(make())
        assert blocked.last_run_info["path"] == "frontier"
        re_ = eager.run(make())
        for k in rb:
            np.testing.assert_array_equal(rb[k], re_[k])
    tb = blocked._frontier_engine.last_trace
    assert all(h["exchange"] == "blocked" for h in tb)
    # predecessor tracking needs per-source identity: falls back to eager
    rt = blocked.run(ShortestPathProgram(seed_index=0, track_paths=True))
    rte = eager.run(ShortestPathProgram(seed_index=0, track_paths=True))
    np.testing.assert_array_equal(rt["predecessor"], rte["predecessor"])
    assert blocked._frontier_engine.last_trace[0]["exchange"] == "a2a"


# --------------------------------------------- bitwise: replay oracle (SUM)
class _PassthroughProgram(VertexProgram):
    """apply() returns the aggregate unchanged, so the state after ONE
    superstep IS the aggregation of the setup values — the harness that
    pins the device kernel against halo.replay_superstep bit-for-bit."""

    compute_keys = ("x",)
    combiner = Combiner.SUM
    max_iterations = 1

    def __init__(self, op=Combiner.SUM, cols=0):
        self.combiner = op
        self.cols = cols

    def setup(self, graph, xp):
        n = graph.local_num_vertices
        base = (xp.arange(n) % 89 + 1.0) / 7.0
        if self.cols:
            x = base[:, None] * (xp.arange(self.cols)[None, :] + 1.0)
        else:
            x = base
        return {"x": x * xp.asarray(graph.active if self.cols == 0 else 1.0)}, {}

    def message(self, state, step, graph, xp):
        return state["x"]

    def apply(self, state, agg, step, mem, graph, xp):
        return {"x": agg}, {}

    def terminate(self, memory):
        return False


@pytest.mark.parametrize("agg", ["ell", "segment"])
@pytest.mark.parametrize("op", [Combiner.SUM, Combiner.MIN])
@pytest.mark.parametrize("weights", [False, True])
def test_blocked_superstep_bitwise_vs_numpy_replay(mesh8, agg, op, weights):
    """One full device superstep (gather → fused bin merge → all_to_all →
    receiver combine) is bitwise-identical to the plan's numpy replay —
    the CPU-oracle side of the blocked contract, for both aggregation
    formats and both combiners."""
    g = random_graph(n=210, m=860, seed=7, weights=weights)
    ex = ShardedExecutor(g, mesh=mesh8, exchange="blocked", agg=agg)
    prog = _PassthroughProgram(op=op)
    out = ex.run(prog, fused=False, frontier="off")
    sc = ex._sharded(False)
    plan = sc.blocked_plan
    setup_state, _ = prog.setup(
        type("V", (), {
            "local_num_vertices": sc.padded_n, "active": sc.active,
        })(), np,
    )
    outgoing = np.asarray(setup_state["x"], dtype=np.float32)
    expect = halo.replay_superstep(
        plan, outgoing, op, has_weight=sc.has_weight, agg=agg
    )
    np.testing.assert_array_equal(out["x"], expect[: sc.real_n])


@pytest.mark.parametrize("agg", ["ell", "segment"])
def test_blocked_dense_feature_block_bitwise_vs_replay(mesh8, agg):
    """The same replay contract for [n, d] feature-block messages — the
    dense tier's halo exchange ships whole merged rows."""
    g = random_graph(n=130, m=520, seed=9, weights=True)
    ex = ShardedExecutor(g, mesh=mesh8, exchange="blocked", agg=agg)
    prog = _PassthroughProgram(op=Combiner.SUM, cols=8)
    out = ex.run(prog, fused=False, frontier="off")
    sc = ex._sharded(False)
    setup_state, _ = prog.setup(
        type("V", (), {
            "local_num_vertices": sc.padded_n, "active": sc.active,
        })(), np,
    )
    outgoing = np.asarray(setup_state["x"], dtype=np.float32)
    expect = halo.replay_superstep(
        sc.blocked_plan, outgoing, Combiner.SUM,
        has_weight=sc.has_weight, agg=agg,
    )
    np.testing.assert_array_equal(out["x"], expect[: sc.real_n])


@pytest.mark.parametrize("agg", ["ell", "segment"])
def test_blocked_pagerank_and_dense_match_oracle(mesh8, agg):
    """Full programs across the exchange restructure: PageRank and a GCN
    forward pass agree with the eager exchange and the CPU oracle to
    float tolerance (SUM associates per source shard under blocking)."""
    g = random_graph(n=180, m=760, seed=3)
    mk = lambda: PageRankProgram(max_iterations=15, tol=0.0)  # noqa: E731
    rb = ShardedExecutor(g, mesh=mesh8, exchange="blocked", agg=agg).run(mk())
    re_ = ShardedExecutor(g, mesh=mesh8).run(mk())
    np.testing.assert_allclose(rb["rank"], re_["rank"], rtol=1e-5, atol=1e-8)
    cpu = CPUExecutor(g).run(mk())
    np.testing.assert_allclose(rb["rank"], cpu["rank"], rtol=1e-4, atol=1e-6)

    gcn = lambda: GCNForwardProgram(  # noqa: E731
        feature_dim=16, hidden_dim=16, out_dim=16, num_layers=2, seed=1
    )
    db = ShardedExecutor(g, mesh=mesh8, exchange="blocked", agg=agg).run(gcn())
    dc = CPUExecutor(g).run(gcn())
    np.testing.assert_allclose(db["h"], dc["h"], rtol=1e-4, atol=1e-5)


def test_sddmm_refused_on_sharded(mesh8):
    g = random_graph()
    prog = GCNForwardProgram(
        feature_dim=8, hidden_dim=8, out_dim=8, attention=True
    )
    with pytest.raises(NotImplementedError, match="sddmm"):
        ShardedExecutor(g, mesh=mesh8, exchange="blocked").run(prog)


# ----------------------------------------------------- distributed loading
def test_blocked_plan_distributed_build_matches_full():
    """Each host builds ONLY its shard range's blocks from its own edges
    plus the exchanged compact pair metadata; the concatenation equals
    the single-process plan array-for-array."""
    g = random_graph(n=220, m=900, seed=13, weights=True)
    S = 8
    sc = ShardedCSR(g, S, False)
    src, dst, w = halo.edges_from_sharded(sc)
    full = halo.BlockedPlan.build(src, dst, w, S, sc.shard_size)

    # the metadata handshake: every host contributes its owners' lists
    lists = {}
    for lo, hi in ((0, 3), (3, 8)):
        lists.update(halo.pair_dst_lists(
            src, dst, S, sc.shard_size, owner_range=(lo, hi)
        ))
    assert set(lists) == set(full.pair_lists)
    hc = halo.halo_tier(lists)
    assert hc == full.halo_cap

    parts = []
    for lo, hi in ((0, 3), (3, 8)):
        owner = src // sc.shard_size
        m = (owner >= lo) & (owner < hi)
        part = halo.BlockedPlan.build_local(
            src[m], dst[m], w[m], S, sc.shard_size, (lo, hi),
            hc, full.edges_per_owner, lists,
        )
        parts.append(part)
    for name in ("blk_src_loc", "blk_seg", "blk_bin_seg", "blk_valid",
                 "blk_weight", "recv_dst"):
        got = np.concatenate([getattr(p, name) for p in parts])
        np.testing.assert_array_equal(
            got, getattr(full, name), err_msg=name
        )
    assert (
        sum(p.edges_by_owner[0] for p in parts) > 0
    )


def test_host_shard_range_couples_to_partition_range():
    from janusgraph_tpu.parallel.multihost import (
        host_partition_range,
        host_shard_range,
    )

    assert host_shard_range(8, 0, 2) == host_partition_range(8, 0, 2)
    lo0, hi0 = host_shard_range(8, 0, 3)
    lo1, hi1 = host_shard_range(8, 1, 3)
    lo2, hi2 = host_shard_range(8, 2, 3)
    assert (lo0, hi2) == (0, 8) and hi0 == lo1 and hi1 == lo2


# ------------------------------------------------------------------- chaos
@pytest.mark.parametrize("agg", ["ell", "segment"])
def test_blocked_halo_drop_and_preempt_resume_bitwise(mesh8, tmp_path, agg):
    """The PR 8 chaos contract on the blocked-exchange path: a dropped
    halo batch AND a shard preemption mid-run, absorbed by cross-shard
    auto-resume, final state bitwise-identical to the fault-free twin."""
    from janusgraph_tpu.storage.faults import FaultPlan

    g = random_graph(n=160, m=640, seed=2)
    mk = lambda: PageRankProgram(max_iterations=12, tol=0.0)  # noqa: E731
    base = ShardedExecutor(g, mesh=mesh8, exchange="blocked", agg=agg).run(
        mk(), fused=False, checkpoint_every=3,
        shard_checkpoint_dir=str(tmp_path / f"{agg}-base"),
    )
    plan = FaultPlan(seed=5, halo_drop_at=4, shard_preempt_superstep=8)
    ex = ShardedExecutor(g, mesh=mesh8, exchange="blocked", agg=agg)
    out = ex.run(
        mk(), fused=False, checkpoint_every=3,
        shard_checkpoint_dir=str(tmp_path / f"{agg}-chaos"),
        fault_hook=plan.sharded_hook,
    )
    kinds = {e["kind"] for e in plan.journal}
    assert "halo_drop" in kinds and "shard_preempt" in kinds
    assert ex.last_run_info["resumes"] == 2
    for k in base:
        np.testing.assert_array_equal(np.asarray(base[k]), np.asarray(out[k]))


# ------------------------------------------------- measured per-shard walls
def test_measured_walls_feed_skew_report(mesh8):
    g = random_graph(n=200, m=800, seed=4)
    ex = ShardedExecutor(g, mesh=mesh8, exchange="blocked")
    ex.run(PageRankProgram(max_iterations=4, tol=0.0), fused=False)
    shards = ex.last_run_info["shards"]
    assert shards["cost_source"] == "measured"
    assert all(p["cost_source"] == "measured" for p in shards["per_shard"])
    assert all(
        p["measured_ms"] is not None and p["measured_ms"] >= 0.0
        for p in shards["per_shard"]
    )
    from janusgraph_tpu.observability import registry

    assert registry.gauge("olap.shard.skew.measured").value == 1.0

    off = ShardedExecutor(g, mesh=mesh8, shard_measure=False)
    off.run(PageRankProgram(max_iterations=4, tol=0.0), fused=False)
    shards = off.last_run_info["shards"]
    assert shards["cost_source"] == "plan"
    assert all(p["measured_ms"] is None for p in shards["per_shard"])
    assert registry.gauge("olap.shard.skew.measured").value == 0.0


def test_exchange_info_recorded(mesh8):
    g = random_graph(n=150, m=600, seed=6)
    ex = ShardedExecutor(g, mesh=mesh8, exchange="blocked")
    ex.run(PageRankProgram(max_iterations=3, tol=0.0), fused=False)
    info = ex.last_run_info["exchange"]
    assert info["mode"] == "blocked"
    assert info["batches_per_superstep"] == 1
    assert info["elems_per_superstep"] == 8 * ex._sharded(False).halo_cap
    assert info["bytes_per_superstep"] == info["elems_per_superstep"] * 4
    # pow2 tier contract (JG301 family)
    hc = info["width"]
    assert hc > 0 and (hc & (hc - 1)) == 0


# ----------------------------------------------------------------- autotune
def test_decide_sharded_deterministic_and_keyed_by_shard_count(tmp_path):
    from janusgraph_tpu.olap import autotune

    g = random_graph(n=240, m=1100, seed=8, weights=True)
    sc = ShardedCSR(g, 8, False)
    src, dst, _w = halo.edges_from_sharded(sc)
    widths = halo.pair_widths(src, dst, 8, sc.shard_size)
    stats = autotune.GraphStats.from_csr(g)
    d1 = autotune.decide_sharded(stats, "cpu", 8, widths)
    d2 = autotune.decide_sharded(stats, "cpu", 8, widths)
    assert d1.as_dict() == d2.as_dict()
    assert d1.shard_count == 8
    assert set(d1.modeled_ms) == {
        "a2a-ell", "a2a-segment", "blocked-ell", "blocked-segment",
        "ring-segment", "gather-segment",
    }
    # forcing via overrides pins the layout and flips the source label
    df = autotune.decide_sharded(
        stats, "cpu", 8, widths, overrides={"exchange": "blocked"}
    )
    assert (df.exchange, df.source) == ("blocked", "config")

    # persistence: the sharded record carries the layout and stays keyed
    # by shard count (an 8-chip record must not leak into 4-chip reads)
    path = str(tmp_path / "a.autotune.json")
    autotune.save_measured(
        path,
        {"strategy": "sharded-blocked-ell", "pad_ratio": 1.1,
         "superstep_ms": 2.5, "roofline_by_tier": None,
         "exchange": "blocked", "agg": "ell", "halo_cap": 64},
        shard_count=8,
    )
    rec = autotune.load_measured(path, shard_count=8)
    assert rec["exchange"] == "blocked" and rec["halo_cap"] == 64
    assert autotune.load_measured(path, shard_count=4) is None
    dm = autotune.decide_sharded(stats, "cpu", 8, widths, measured=rec)
    assert dm.source == "measured+model"


def test_auto_exchange_resolves_and_records(mesh8):
    g = random_graph(n=200, m=900, seed=12)
    ex = ShardedExecutor(g, mesh=mesh8, exchange="auto")
    ex.run(PageRankProgram(max_iterations=3, tol=0.0), fused=False)
    assert ex.exchange in ("a2a", "blocked", "ring", "gather")
    rec = ex.last_run_info["autotune"]
    assert rec["shard_count"] == 8
    assert rec["exchange"] == ex.exchange and rec["agg"] == ex.agg
    # deterministic: a fresh executor resolves identically
    ex2 = ShardedExecutor(g, mesh=mesh8, exchange="auto")
    ex2.run(PageRankProgram(max_iterations=3, tol=0.0), fused=False)
    assert (ex2.exchange, ex2.agg) == (ex.exchange, ex.agg)


# ------------------------------------------------------------------ routing
def test_sharded_auto_routing_records_run_info():
    from janusgraph_tpu.core import gods
    from janusgraph_tpu.core.graph import open_graph

    g = open_graph({"ids.authority-wait-ms": 0.0})
    try:
        gods.load(g)
        res = g.compute().program(
            PageRankProgram(max_iterations=6)
        ).submit()
        routing = res.run_info["routing"]
        assert routing["requested"] == "tpu"
        assert routing["routed"] == "sharded"
        assert "mesh of 8" in routing["reason"]
        # the sharded record names every device of the mesh it ran on
        assert res.run_info["platform"] == "cpu"
        assert res.run_info["device_kind"] == "cpu"
        assert res.run_info["device_count"] == 8
        assert res.run_info["exchange"]["batches_per_superstep"] == 1
        assert abs(res.states["rank"].sum() - 1.0) < 1e-4
    finally:
        g.close()


def test_sharded_auto_off_keeps_single_device():
    from janusgraph_tpu.core import gods
    from janusgraph_tpu.core.graph import open_graph

    g = open_graph({
        "ids.authority-wait-ms": 0.0, "computer.sharded-auto": False,
    })
    try:
        gods.load(g)
        res = g.compute().program(
            PageRankProgram(max_iterations=6)
        ).submit()
        assert res.run_info["routing"]["routed"] == "tpu"
    finally:
        g.close()


def test_sddmm_program_not_routed():
    """Attention (sddmm) dense programs stay on the single-device
    executor — the halo exchange cannot ship dst features."""
    from janusgraph_tpu.core import gods
    from janusgraph_tpu.core.graph import open_graph

    g = open_graph({"ids.authority-wait-ms": 0.0})
    try:
        gods.load(g)
        res = g.compute().program(GCNForwardProgram(
            feature_dim=8, hidden_dim=8, out_dim=8, attention=True,
        )).submit()
        routing = res.run_info["routing"]
        assert routing["routed"] == "tpu"
        assert routing["reason"] == "sddmm program"
    finally:
        g.close()
