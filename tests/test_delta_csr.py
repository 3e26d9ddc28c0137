"""Incremental delta-CSR (ISSUE 14): commit-side change capture, fused
base+delta supersteps, zero-read materialization, compaction, spillover
delta refresh, and the staleness dedupe fix.

Contracts under test:
- capture completeness: materialize(base, overlay) is ARRAY-FOR-ARRAY
  identical to a fresh full load after any mix of edge adds/deletes and
  vertex add/removal (canonical-layout parity);
- base+delta fused results are bitwise-identical to the repacked CSR for
  the MIN family across {tpu, cpu, sharded} x {ell, hybrid}, and
  bitwise-identical to the numpy replay oracle for SUM;
- warm GraphComputer.submit() touches the store ZERO times;
- compaction folds the overlay at the threshold, off the superstep path;
- overlay/capture overflow falls back to a full repack, never to wrong
  numbers;
- spillover snapshot refresh is delta-apply (zero store reads) and stays
  read-your-writes; the staleness bound counts overlay lag, not commits.
"""

import numpy as np
import pytest

from janusgraph_tpu.core.codecs import Direction
from janusgraph_tpu.core.graph import open_graph
from janusgraph_tpu.olap import delta as D
from janusgraph_tpu.olap.csr import load_csr, load_csr_snapshot
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.programs import (
    ConnectedComponentsProgram,
    PageRankProgram,
    ShortestPathProgram,
)
from janusgraph_tpu.olap.tpu_executor import TPUExecutor
from janusgraph_tpu.observability import flight_recorder, registry


def _counter(name):
    return registry.snapshot().get(name, {}).get("count", 0)


@pytest.fixture
def g():
    graph = open_graph({
        "schema.default": "auto",
        "computer.sharded-auto": False,
    })
    yield graph
    graph.close()


def seed_chain(g, n=30):
    tx = g.new_transaction()
    vs = [tx.add_vertex(name=f"v{i}") for i in range(n)]
    for i in range(n - 1):
        tx.add_edge(vs[i], "link", vs[i + 1])
    tx.commit()
    return vs


def seed_random(g, n=160, m=640, seed=11):
    rng = np.random.default_rng(seed)
    tx = g.new_transaction()
    vs = [tx.add_vertex() for _ in range(n)]
    for _ in range(m):
        a, b = rng.integers(0, n, 2)
        tx.add_edge(vs[int(a)], "link", vs[int(b)])
    tx.commit()
    return vs


def edge_burst(g, vs, seed=5, adds=24, dels=4):
    """Edge-only mutation burst (keeps index alignment for CC bitwise)."""
    rng = np.random.default_rng(seed)
    tx = g.new_transaction()
    for _ in range(adds):
        a, b = rng.integers(0, len(vs), 2)
        tx.add_edge(
            tx.get_vertex(vs[int(a)].id), "link",
            tx.get_vertex(vs[int(b)].id),
        )
    removed = 0
    for i in rng.permutation(len(vs)):
        if removed >= dels:
            break
        es = tx.get_edges(
            tx.get_vertex(vs[int(i)].id), Direction.OUT, ("link",)
        )
        if es:
            tx.remove_edge(es[0])
            removed += 1
    tx.commit()


def assert_arrays_equal(a, b):
    np.testing.assert_array_equal(a.vertex_ids, b.vertex_ids)
    np.testing.assert_array_equal(a.out_indptr, b.out_indptr)
    np.testing.assert_array_equal(a.in_indptr, b.in_indptr)
    np.testing.assert_array_equal(a.out_dst, b.out_dst)
    np.testing.assert_array_equal(a.in_src, b.in_src)


# ---------------------------------------------------------------- capture
def test_capture_completeness_mixed_mutations(g):
    vs = seed_chain(g)
    csr, epoch = load_csr_snapshot(g)
    tx = g.new_transaction()
    tx.add_edge(tx.get_vertex(vs[0].id), "link", tx.get_vertex(vs[29].id))
    e = tx.get_edges(tx.get_vertex(vs[4].id), Direction.OUT, ("link",))[0]
    tx.remove_edge(e)
    nv = tx.add_vertex(name="new")
    tx.add_edge(nv, "link", tx.get_vertex(vs[7].id))
    tx.commit()
    tx = g.new_transaction()
    tx.remove_vertex(tx.get_vertex(vs[20].id))
    tx.commit()
    ov, _upto = D.overlay_since(g, epoch)
    assert len(ov.new_vertices) == 1 and len(ov.removed) == 1
    # canonical-layout parity: byte-for-byte the arrays a full reload packs
    assert_arrays_equal(D.materialize(csr, ov, idm=g.idm), load_csr(g))


def test_capture_property_only_commit_is_structurally_empty(g):
    vs = seed_chain(g, n=5)
    _csr, epoch = load_csr_snapshot(g)
    tx = g.new_transaction()
    tx.get_vertex(vs[2].id).property("name", "renamed")
    tx.commit()
    ov, _ = D.overlay_since(g, epoch)
    assert ov.size == 0  # no structural records, nothing to refresh


def test_overlay_add_then_delete_nets_out(g):
    vs = seed_chain(g, n=6)
    csr, epoch = load_csr_snapshot(g)
    tx = g.new_transaction()
    tx.add_edge(tx.get_vertex(vs[0].id), "link", tx.get_vertex(vs[3].id))
    tx.commit()
    tx = g.new_transaction()
    e2 = tx.get_edges(tx.get_vertex(vs[0].id), Direction.OUT, ("link",))
    tx.remove_edge([x for x in e2 if x.in_vertex.id == vs[3].id][0])
    tx.commit()
    ov, _ = D.overlay_since(g, epoch)
    # multiset counting: the delete cancels the pending add — net zero
    assert len(ov.add) == 0 and len(ov.tomb) == 0 and ov.size == 0
    assert_arrays_equal(D.materialize(csr, ov, idm=g.idm), load_csr(g))


def test_capture_overflow_serves_none(g):
    vs = seed_chain(g, n=10)
    _csr, epoch = load_csr_snapshot(g)
    g.change_capture.limit = 4
    for i in range(8):
        tx = g.new_transaction()
        tx.add_edge(
            tx.get_vertex(vs[i % 9].id), "link",
            tx.get_vertex(vs[(i + 1) % 10].id),
        )
        tx.commit()
    assert g.change_capture.records_since(epoch) is None
    assert D.overlay_since(g, epoch) is None


# ----------------------------------------------------- fused bitwise matrix
#: the device executor on its one pack, and the CPU oracle's two replays
FUSED_EXECUTORS = [("tpu", None), ("cpu", "ell"), ("cpu", "hybrid")]
FUSED_IDS = ["tpu", "cpu-ell", "cpu-hybrid"]


@pytest.mark.parametrize("executor,strategy", FUSED_EXECUTORS, ids=FUSED_IDS)
def test_base_plus_delta_bitwise_min_family(g, executor, strategy):
    """CC (undirected) and SSSP (directed) fused base+delta results are
    BITWISE-identical to runs over the freshly repacked CSR — min is
    exact and order-independent over the identical edge multiset."""
    vs = seed_random(g)
    csr, epoch = load_csr_snapshot(g)
    edge_burst(g, vs)
    ov, _ = D.overlay_since(g, epoch)
    assert ov.size > 0
    view = D.OverlayView(csr, ov)
    repack = load_csr(g)

    def run(graph, delta, program):
        if executor == "tpu":
            ex = TPUExecutor(graph, delta=delta)
        else:
            ex = CPUExecutor(graph, strategy=strategy, delta=delta)
        return ex.run(program)

    f = run(csr, view, ConnectedComponentsProgram(max_iterations=40))
    r = run(repack, None, ConnectedComponentsProgram(max_iterations=40))
    np.testing.assert_array_equal(f["component"], r["component"])

    seed_vid = int(csr.vertex_ids[5])
    si = int(np.searchsorted(repack.vertex_ids, seed_vid))
    f = run(csr, view, ShortestPathProgram(seed_index=5, max_iterations=40))
    r = run(
        repack, None, ShortestPathProgram(seed_index=si, max_iterations=40)
    )
    np.testing.assert_array_equal(f["distance"], r["distance"])


@pytest.mark.parametrize("executor,strategy", FUSED_EXECUTORS, ids=FUSED_IDS)
def test_base_plus_delta_sum_close_to_repack(g, executor, strategy):
    vs = seed_random(g)
    csr, epoch = load_csr_snapshot(g)
    edge_burst(g, vs)
    ov, _ = D.overlay_since(g, epoch)
    view = D.OverlayView(csr, ov)
    repack = load_csr(g)
    if executor == "tpu":
        f = TPUExecutor(csr, delta=view).run(
            PageRankProgram(max_iterations=10)
        )
        r = TPUExecutor(repack).run(
            PageRankProgram(max_iterations=10)
        )
    else:
        f = CPUExecutor(csr, strategy=strategy, delta=view).run(
            PageRankProgram(max_iterations=10)
        )
        r = CPUExecutor(repack, strategy=strategy).run(
            PageRankProgram(max_iterations=10)
        )
    np.testing.assert_allclose(f["rank"], r["rank"], rtol=1e-5, atol=1e-7)


def test_fused_merge_matches_replay_oracle_bitwise():
    """The SUM contract: the jitted fused merge is bitwise-identical to
    the numpy replay oracle on the same inputs (np.add.at == XLA CPU
    scatter — the PR 9 contract), for every monoid, scalar and 2-D."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    npad, nb = 272, 256
    meta = {"n_base": nb, "n_pad": npad}

    def lane(cap, hi):
        src = np.full(cap, npad, np.int32)
        dst = np.full(cap, npad, np.int32)
        k = int(rng.integers(1, cap))
        src[:k] = rng.integers(0, hi, k)
        dst[:k] = rng.integers(0, hi, k)
        return src, dst

    a_s, a_d = lane(32, npad)
    t_s, t_d = lane(16, nb)
    l_s, l_d = lane(64, nb)
    dirty = np.zeros(npad, np.float32)
    dirty[np.unique(t_d[t_d < npad])] = 1.0
    lanes = {
        "add_src": a_s, "add_dst": a_d, "tomb_src": t_s, "tomb_dst": t_d,
        "live_src": l_s, "live_dst": l_d, "dirty": dirty,
    }
    for op in ("sum", "min", "max"):
        for shape in ((npad,), (npad, 4)):
            msgs = rng.standard_normal(shape).astype(np.float32)
            base = rng.standard_normal((nb,) + shape[1:]).astype(np.float32)
            want = D.replay_fused_aggregate(lanes, meta, msgs, base, op)
            jl = {k: jnp.asarray(v) for k, v in lanes.items()}
            got = jax.jit(
                lambda lv, m, b, _op=op: D.fused_delta_aggregate(
                    jnp, lv, meta, m, b, _op
                )
            )(jl, jnp.asarray(msgs), jnp.asarray(base))
            np.testing.assert_array_equal(np.asarray(got), want)


def test_fused_vertex_add_remove_semantics(g):
    """Vertex adds/removals ride the fused path: results are id-aligned
    float-close to the repacked run over the SURVIVING vertex set."""
    vs = seed_chain(g, n=40)
    csr, epoch = load_csr_snapshot(g)
    tx = g.new_transaction()
    nv = tx.add_vertex()
    tx.add_edge(nv, "link", tx.get_vertex(vs[0].id))
    tx.commit()
    tx = g.new_transaction()
    tx.remove_vertex(tx.get_vertex(vs[20].id))
    tx.commit()
    ov, _ = D.overlay_since(g, epoch)
    view = D.OverlayView(csr, ov)
    f = TPUExecutor(csr, delta=view).run(PageRankProgram(max_iterations=8))
    f, rv = D.compact_result(view, f)
    repack = load_csr(g)
    r = TPUExecutor(repack).run(PageRankProgram(max_iterations=8))
    assert set(int(v) for v in rv.vertex_ids) == set(
        int(v) for v in repack.vertex_ids
    )
    for vid in rv.vertex_ids:
        np.testing.assert_allclose(
            f["rank"][rv.index_of(int(vid))],
            r["rank"][repack.index_of(int(vid))],
            rtol=1e-5, atol=1e-7,
        )


# ---------------------------------------------------------------- sharded
@pytest.fixture(scope="module")
def mesh8():
    import jax
    from jax.sharding import Mesh

    devices = np.array(jax.devices()[:8])
    assert len(devices) == 8
    return Mesh(devices, ("p",))


def test_sharded_base_plus_delta_bitwise(g, mesh8):
    """The sharded path consumes the delta by materializing base+overlay
    (zero store reads) — the resulting arrays are identical to a repack,
    so the mesh run is bitwise-identical by construction. Asserted
    end-to-end: sharded-on-materialized == sharded-on-repacked."""
    from janusgraph_tpu.parallel import ShardedExecutor

    vs = seed_random(g, n=120, m=480)
    csr, epoch = load_csr_snapshot(g)
    edge_burst(g, vs, adds=16, dels=3)
    ov, _ = D.overlay_since(g, epoch)
    mat = D.materialize(csr, ov, idm=g.idm)
    repack = load_csr(g)
    assert_arrays_equal(mat, repack)
    f = ShardedExecutor(mat, mesh=mesh8).run(
        ConnectedComponentsProgram(max_iterations=40)
    )
    r = ShardedExecutor(repack, mesh=mesh8).run(
        ConnectedComponentsProgram(max_iterations=40)
    )
    np.testing.assert_array_equal(
        np.asarray(f["component"]), np.asarray(r["component"])
    )


def test_route_overlay_owner_shard_coupling(g):
    """Every delta record routes to exactly one shard — the owner of its
    aggregation-side (dst) row under the contiguous dst // Np layout the
    sharded executor and host_shard_range share."""
    vs = seed_random(g, n=100, m=400)
    csr, epoch = load_csr_snapshot(g)
    edge_burst(g, vs, adds=20, dels=4)
    ov, _ = D.overlay_since(g, epoch)
    view = D.OverlayView(csr, ov)
    S = 4
    routed = D.route_overlay(view, S)
    assert len(routed) == S
    Np = -(-view.n_pad // S)
    tot_add = tot_tomb = 0
    for r in routed:
        lo, hi = r["row_range"]
        assert lo == r["shard"] * Np
        assert np.all((r["add_dst"] >= lo) & (r["add_dst"] < lo + Np))
        assert np.all((r["tomb_dst"] >= lo) & (r["tomb_dst"] < lo + Np))
        tot_add += len(r["add_dst"])
        tot_tomb += len(r["tomb_dst"])
    assert tot_add == len(view.add_dst)
    assert tot_tomb == len(view.tomb_dst)
    # host coupling: the per-host slice is the union of its shards'
    hostr = D.route_for_host(view, S, process_id=0, num_processes=2)
    lo_s, hi_s = hostr["shards"]
    want = sum(len(routed[s]["add_dst"]) for s in range(lo_s, hi_s))
    assert len(hostr["add_dst"]) == want


# ------------------------------------------------------------ warm submit
def test_warm_submit_skips_scan_entirely(g):
    seed_chain(g, n=25)
    r1 = g.compute().program(PageRankProgram(max_iterations=5)).submit()
    calls = []
    store = g.backend.edgestore
    orig = store.get_keys
    store.get_keys = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
    try:
        r2 = g.compute().program(PageRankProgram(max_iterations=5)).submit()
    finally:
        store.get_keys = orig
    assert not calls, "warm submit re-scanned the store"
    np.testing.assert_array_equal(r1.states["rank"], r2.states["rank"])


def test_fused_submit_zero_store_reads(g):
    vs = seed_chain(g, n=25)
    g.compute().program(PageRankProgram(max_iterations=5)).submit()
    tx = g.new_transaction()
    tx.add_edge(
        tx.get_vertex(vs[3].id), "link", tx.get_vertex(vs[17].id)
    )
    tx.commit()
    store = g.backend.edgestore
    scans, slices = [], []
    ok, osl = store.get_keys, store.get_slice
    store.get_keys = lambda *a, **k: (scans.append(1), ok(*a, **k))[1]
    store.get_slice = lambda *a, **k: (slices.append(1), osl(*a, **k))[1]
    try:
        r = g.compute().program(PageRankProgram(max_iterations=5)).submit()
    finally:
        store.get_keys, store.get_slice = ok, osl
    assert not scans and not slices, (
        f"delta submit read the store: {len(scans)} scans, "
        f"{len(slices)} slices"
    )
    assert r.run_info.get("delta", {}).get("fused") is True
    # read-your-writes: the new edge affected the result
    assert abs(float(np.sum(r.states["rank"])) - 1.0) < 1e-5


# ------------------------------------------------------------- compaction
def test_compaction_threshold_folds_overlay():
    g = open_graph({
        "schema.default": "auto",
        "computer.sharded-auto": False,
        "computer.delta-compact-threshold": 4,
    })
    try:
        vs = seed_chain(g, n=20)
        g.compute().program(PageRankProgram(max_iterations=3)).submit()
        before = _counter("olap.delta.compactions")
        tx = g.new_transaction()
        for i in range(6):
            tx.add_edge(
                tx.get_vertex(vs[i].id), "link",
                tx.get_vertex(vs[(i + 7) % 20].id),
            )
        tx.commit()
        g.compute().program(PageRankProgram(max_iterations=3)).submit()
        assert _counter("olap.delta.compactions") == before + 1
        assert any(
            e.get("category") == "delta_compact"
            for e in flight_recorder.snapshot()["events"]
        )
        snap = g._delta_snapshot
        # folded: the base now carries the burst, overlay drained
        got = D.overlay_since(g, snap.epoch)
        assert got is not None and got[0].size == 0
        assert_arrays_equal(snap.csr, load_csr(g))
    finally:
        g.close()


def test_compaction_persists_snapshot_tmp_rename(tmp_path):
    path = str(tmp_path / "delta.snapshot.npz")
    g = open_graph({
        "schema.default": "auto",
        "computer.sharded-auto": False,
        "computer.delta-compact-threshold": 2,
        "computer.delta-snapshot-path": path,
    })
    try:
        vs = seed_chain(g, n=12)
        g.compute().program(PageRankProgram(max_iterations=3)).submit()
        tx = g.new_transaction()
        tx.add_edge(
            tx.get_vertex(vs[0].id), "link", tx.get_vertex(vs[5].id)
        )
        tx.add_edge(
            tx.get_vertex(vs[1].id), "link", tx.get_vertex(vs[6].id)
        )
        tx.commit()
        g.compute().program(PageRankProgram(max_iterations=3)).submit()
        loaded = D.load_snapshot(path)
        assert loaded is not None
        csr, _epoch = loaded
        assert_arrays_equal(csr, load_csr(g))
        # torn file -> cold start, never garbage
        with open(path, "wb") as f:
            f.write(b"\x00garbage")
        assert D.load_snapshot(path) is None
    finally:
        g.close()


def test_decide_delta_deterministic_and_overridable():
    from janusgraph_tpu.olap.autotune import decide_delta

    a = decide_delta(16_000_000, 1_000_000, "cpu")
    b = decide_delta(16_000_000, 1_000_000, "cpu")
    assert a == b
    t = a.compact_threshold
    assert t > 0 and (t & (t - 1)) == 0  # pow2 tier
    c = decide_delta(
        16_000_000, 1_000_000, "cpu",
        overrides={"compact_threshold": 777},
    )
    assert c.compact_threshold == 777 and c.source == "config"
    assert "materialize_s" in a.cells and "repack_s" in a.cells


# ----------------------------------------------------- overflow fallbacks
def test_capture_overflow_submit_falls_back_to_repack():
    g = open_graph({
        "schema.default": "auto",
        "computer.sharded-auto": False,
        "computer.delta-capture-limit": 4,
    })
    try:
        vs = seed_chain(g, n=20)
        g.compute().program(PageRankProgram(max_iterations=3)).submit()
        before = _counter("olap.delta.capture_overflow")
        tx = g.new_transaction()
        for i in range(12):
            tx.add_edge(
                tx.get_vertex(vs[i % 20].id), "link",
                tx.get_vertex(vs[(i + 3) % 20].id),
            )
        tx.commit()
        r = g.compute().program(PageRankProgram(max_iterations=3)).submit()
        assert _counter("olap.delta.capture_overflow") == before + 1
        # the fallback repack still sees every write
        assert r.csr.num_edges == load_csr(g).num_edges
    finally:
        g.close()


def test_executor_refuses_incompatible_programs(g):
    vs = seed_chain(g, n=10)
    csr, epoch = load_csr_snapshot(g)
    tx = g.new_transaction()
    tx.add_edge(tx.get_vertex(vs[0].id), "link", tx.get_vertex(vs[5].id))
    tx.commit()
    ov, _ = D.overlay_since(g, epoch)
    view = D.OverlayView(csr, ov)
    with pytest.raises(ValueError, match="scalar"):
        CPUExecutor(csr, strategy="scalar", delta=view)
    from janusgraph_tpu.olap.programs.olap_traversal import (
        OLAPTraversalProgram,
        steps_from_spec,
    )

    prog = OLAPTraversalProgram(
        steps_from_spec(g, [("out", ["link"]), ("out", ["link"])])
    )
    with pytest.raises(ValueError, match="default-edge-view"):
        TPUExecutor(csr, delta=view).run(prog)


# ------------------------------------------------- spillover delta refresh
def _promoted_planner(g, vs):
    """Promote the 2-hop count shape onto the spillover planner."""
    planner = g.spillover_planner
    planner.min_cost_ms = 0.0
    planner.min_seen = 1

    def burst():
        return g.traversal().V(vs[0].id).out("link").out("link").count()

    burst()
    burst()
    return planner, burst


def test_spillover_refresh_is_delta_apply_zero_row_reads(g):
    vs = seed_chain(g, n=40)
    planner, burst = _promoted_planner(g, vs)
    before = burst()
    assert planner._csr is not None  # spilled at least once
    refreshes0 = _counter("olap.spillover.delta_refreshes")
    tx = g.new_transaction()
    tx.add_edge(
        tx.get_vertex(vs[1].id), "link", tx.get_vertex(vs[30].id)
    )
    tx.commit()
    store = g.backend.edgestore
    slices = []
    osl = store.get_slice
    store.get_slice = lambda *a, **k: (slices.append(1), osl(*a, **k))[1]
    try:
        after = burst()
    finally:
        store.get_slice = osl
    # read-your-writes across commits: the spilled result sees the edge
    assert after == before + 1
    assert _counter("olap.spillover.delta_refreshes") == refreshes0 + 1
    assert not slices, (
        f"delta refresh re-read {len(slices)} rows from the store"
    )


def test_spillover_staleness_counts_overlay_lag_not_commits(g):
    """Satellite fix: repeated property-only commits (same row, zero
    structural change) used to bump the epoch once each and trip the
    staleness bound, forcing spurious full repacks. Lag now measures
    pending overlay records (deduped per (tx, row) at the tracker), so
    the snapshot refreshes in place."""
    vs = seed_chain(g, n=40)
    planner, burst = _promoted_planner(g, vs)
    planner.max_staleness = 4
    burst()
    stale0 = _counter("olap.spillover.stale")
    packs0 = _counter("olap.spillover.packs")
    for i in range(12):  # 3x the bound, all epoch bumps, zero structure
        tx = g.new_transaction()
        tx.get_vertex(vs[7].id).property("name", f"spin{i}")
        tx.commit()
    burst()
    assert _counter("olap.spillover.stale") == stale0
    assert _counter("olap.spillover.packs") == packs0
    snap = registry.snapshot()
    assert snap["olap.spillover.staleness"]["value"] == 0.0


def test_touched_count_since_dedupes_rows(g):
    vs = seed_chain(g, n=10)
    epoch = g.backend.mutation_epoch()
    for i in range(5):
        tx = g.new_transaction()
        tx.get_vertex(vs[3].id).property("name", f"r{i}")
        tx.commit()
    assert g.backend.mutation_epoch() - epoch == 5  # commits counted
    assert g.backend.touched_count_since(epoch) == 1  # rows deduped


# ------------------------------------------------------- metrics / SLO
def test_slo_freshness_spec_tracks_overlay_lag_unchanged(g):
    """The PR 13 freshness spec (gauge olap.spillover.staleness) tracks
    the delta-overlay lag with ZERO spec changes: stock default_specs,
    stock gauge name — the planner's snapshot path now feeds the gauge
    pending overlay records instead of raw commit counts."""
    import itertools

    from janusgraph_tpu.observability.slo import SLOEngine, default_specs
    from janusgraph_tpu.observability.timeseries import MetricsHistory

    vs = seed_chain(g, n=30)
    planner, burst = _promoted_planner(g, vs)
    burst()
    planner.max_staleness = 5  # lag 10 > 5 -> the stale path fires
    tx = g.new_transaction()
    for i in range(10):
        tx.add_edge(
            tx.get_vertex(vs[i].id), "link",
            tx.get_vertex(vs[(i + 11) % 30].id),
        )
    tx.commit()
    assert g.change_capture.depth_since(planner._epoch) == 10
    # the spilled attempt falls back stale — but first it published the
    # overlay lag through the UNCHANGED freshness gauge
    burst()
    assert registry.snapshot()["olap.spillover.staleness"]["value"] == 10.0
    spec = [
        s for s in default_specs(freshness_max_staleness=5.0)
        if s.kind == "freshness"
    ][0]
    assert spec.gauge == "olap.spillover.staleness"  # stock spec, untouched
    clock = itertools.count(1000.0, 1.0)
    h = MetricsHistory(
        registry, capacity=16, interval_s=1.0,
        clock=lambda: float(next(clock)),
        wall_clock=lambda: float(next(clock)),
    )
    eng = SLOEngine(h, [spec])
    h.sample()
    alert = eng.evaluate()[0]
    assert alert["name"] == "olap_freshness"
    assert alert["fast_burn"] > 1.0  # 10 pending records vs bound 5 burns


def test_delta_metrics_and_flight_event():
    g = open_graph({
        "schema.default": "auto",
        "computer.sharded-auto": False,
        "computer.delta-compact-threshold": 2,
    })
    try:
        vs = seed_chain(g, n=12)
        g.compute().program(PageRankProgram(max_iterations=3)).submit()
        tx = g.new_transaction()
        tx.add_edge(
            tx.get_vertex(vs[0].id), "link", tx.get_vertex(vs[6].id)
        )
        tx.add_edge(
            tx.get_vertex(vs[2].id), "link", tx.get_vertex(vs[8].id)
        )
        tx.commit()
        g.compute().program(PageRankProgram(max_iterations=3)).submit()
        snap = registry.snapshot()
        assert "olap.delta.overlay_depth" in snap
        assert snap["olap.delta.compactions"]["count"] >= 1
        ev = [
            e for e in flight_recorder.snapshot()["events"]
            if e.get("category") == "delta_compact"
        ]
        assert ev and ev[-1]["depth"] >= 2
    finally:
        g.close()


# --------------------------------------------------------- persistence etc
def test_save_load_snapshot_roundtrip(tmp_path, g):
    seed_chain(g, n=15)
    csr, epoch = load_csr_snapshot(g)
    path = str(tmp_path / "snap.npz")
    D.save_snapshot(path, csr, epoch)
    loaded = D.load_snapshot(path)
    assert loaded is not None
    csr2, e2 = loaded
    assert e2 == epoch
    assert_arrays_equal(csr, csr2)
