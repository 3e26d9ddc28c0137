"""GraphFilter, MapReduce stage, and superstep checkpointing tests
(reference: FulgoraGraphComputer map-reduce phase :288-357, GraphFilter via
vertices()/edges(); checkpointing exceeds parity per SURVEY.md §5.4)."""

import numpy as np
import pytest

from janusgraph_tpu.core import gods
from janusgraph_tpu.core.graph import open_graph
from janusgraph_tpu.olap import (
    ClusterCountMapReduce,
    StatsMapReduce,
    TopKMapReduce,
    csr_from_edges,
    load_csr,
    load_checkpoint,
    run_map_reduce,
)
from janusgraph_tpu.olap.programs import (
    ConnectedComponentsProgram,
    PageRankProgram,
)
from janusgraph_tpu.olap.tpu_executor import TPUExecutor
from janusgraph_tpu.parallel import ShardedExecutor


@pytest.fixture(scope="module")
def gods_graph():
    g = open_graph({"ids.authority-wait-ms": 0.0})
    gods.load(g)
    yield g
    g.close()


def random_graph(n=150, m=600, seed=7):
    rng = np.random.default_rng(seed)
    return csr_from_edges(
        n, rng.integers(0, n, m).astype(np.int32),
        rng.integers(0, n, m).astype(np.int32), None,
    )


# -------------------------------------------------------------- GraphFilter
def test_vertex_label_filter(gods_graph):
    full = load_csr(gods_graph)
    only_gods = load_csr(gods_graph, vertex_labels=("god",))
    assert only_gods.num_vertices < full.num_vertices
    names = load_csr(
        gods_graph, vertex_labels=("god",), property_keys=("name",)
    ).properties["name"]
    assert set(names.tolist()) == {"jupiter", "neptune", "pluto"}
    # edges incident to non-god vertices are gone; brother edges remain
    assert only_gods.num_edges == 6  # 3 gods x 2 brother edges each


def test_vertex_filter_via_computer(gods_graph):
    res = (
        gods_graph.compute()
        .vertices("monster")
        .program(ConnectedComponentsProgram(max_iterations=5))
        .submit()
    )
    assert res.csr.num_vertices == 3  # nemean, hydra, cerberus


# ---------------------------------------------------------------- MapReduce
def test_cluster_count_map_reduce():
    csr = csr_from_edges(
        6,
        np.array([0, 1, 3, 4], dtype=np.int32),
        np.array([1, 2, 4, 5], dtype=np.int32),
        None,
    )
    ex = TPUExecutor(csr)
    states = ex.run(ConnectedComponentsProgram(max_iterations=20))
    out = run_map_reduce(ClusterCountMapReduce("component"), states, csr)
    assert out["count"] == 2
    assert sorted(out["sizes"].values()) == [3.0, 3.0]


def test_stats_and_topk_map_reduce(gods_graph):
    res = (
        gods_graph.compute()
        .program(PageRankProgram(max_iterations=20))
        .map_reduce(StatsMapReduce("rank"))
        .map_reduce(TopKMapReduce("rank", k=3))
        .submit()
    )
    stats = res.memory["stats"]
    assert stats["count"] == 12
    assert abs(stats["sum"] - 1.0) < 1e-3
    top = res.memory["topK"]
    assert len(top) == 3
    assert top[0][1] >= top[1][1] >= top[2][1]


# ------------------------------------------------------------ checkpointing
def test_checkpoint_resume_matches_uninterrupted(tmp_path):
    csr = random_graph()
    path = str(tmp_path / "ck.npz")
    prog = lambda: PageRankProgram(max_iterations=24, tol=0.0)

    direct = TPUExecutor(csr).run(prog())

    # run with checkpoints every 5 steps, "crash" after the first chunk by
    # reloading from the checkpoint and resuming with a fresh executor
    ex1 = TPUExecutor(csr)
    ex1.run(prog(), checkpoint_path=path, checkpoint_every=5)
    st, mem, steps = load_checkpoint(path)
    assert steps == 24 and "rank" in st

    # simulate interruption: rewind by saving a mid-run checkpoint
    from janusgraph_tpu.olap.checkpoint import save_checkpoint

    ex2 = TPUExecutor(csr)
    # produce a genuine mid-run state: run 2 chunks of 5 then stop
    p = PageRankProgram(max_iterations=10, tol=0.0)
    mid = ex2.run(p, checkpoint_path=path, checkpoint_every=5)
    st, mem, steps = load_checkpoint(path)
    assert steps == 10

    resumed = TPUExecutor(csr).run(
        prog(), checkpoint_path=path, checkpoint_every=5, resume=True
    )
    np.testing.assert_allclose(
        resumed["rank"], direct["rank"], rtol=1e-5, atol=1e-7
    )


def test_checkpoint_resume_sharded(tmp_path):
    csr = random_graph(seed=13)
    path = str(tmp_path / "ck_sharded.npz")
    direct = ShardedExecutor(csr).run(PageRankProgram(max_iterations=16, tol=0.0))

    ex = ShardedExecutor(csr)
    ex.run(
        PageRankProgram(max_iterations=8, tol=0.0),
        checkpoint_path=path, checkpoint_every=4,
    )
    _st, _mem, steps = load_checkpoint(path)
    assert steps == 8

    resumed = ShardedExecutor(csr).run(
        PageRankProgram(max_iterations=16, tol=0.0),
        checkpoint_path=path, checkpoint_every=4, resume=True,
    )
    np.testing.assert_allclose(
        resumed["rank"], direct["rank"], rtol=1e-5, atol=1e-7
    )


def test_checkpoint_early_termination_preserved(tmp_path):
    """A program that converges inside a chunk stops and the checkpoint
    records the true step count."""
    src = np.array([0, 1, 2], dtype=np.int32)
    dst = np.array([1, 2, 3], dtype=np.int32)
    csr = csr_from_edges(5, src, dst, None)
    path = str(tmp_path / "cc.npz")
    ex = TPUExecutor(csr)
    res = ex.run(
        ConnectedComponentsProgram(max_iterations=50),
        checkpoint_path=path, checkpoint_every=10,
    )
    _st, _mem, steps = load_checkpoint(path)
    assert steps < 50
    comp = np.asarray(res["component"])
    assert (comp[:4] == comp[0]).all()


def test_checkpoint_resume_host_loop_path(tmp_path):
    """Phase-alternating programs (host loop) also checkpoint + resume."""
    from janusgraph_tpu.olap.programs import PeerPressureProgram

    csr = random_graph(seed=41)
    path = str(tmp_path / "pp.npz")
    direct = TPUExecutor(csr).run(
        PeerPressureProgram(num_buckets=128, rounds=6)
    )
    ex = TPUExecutor(csr)
    ex.run(
        PeerPressureProgram(num_buckets=128, rounds=3),
        checkpoint_path=path, checkpoint_every=2,
    )
    _st, _mem, steps = load_checkpoint(path)
    assert steps > 0
    resumed = TPUExecutor(csr).run(
        PeerPressureProgram(num_buckets=128, rounds=6),
        checkpoint_path=path, checkpoint_every=2, resume=True,
    )
    np.testing.assert_allclose(resumed["cluster"], direct["cluster"])
