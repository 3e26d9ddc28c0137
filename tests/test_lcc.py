"""LDBC Graphalytics LCC, exactly: `LCCProgram` through
`graph.compute().program(...).submit()` on the single-device executor's
intersection engine (`olap/intersect.py`) against the benchmark's plain
reference, which shares no code with the package; and every path that
runs supersteps refusing the program by name.

Small graphs make every vertex a hub (a bit row covers them all), so the
tests that must meet the tail (the expansion and the binary search) narrow
the engine's ladder of row widths to one or two words."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

from references import lcc as reference  # noqa: E402

from janusgraph_tpu.core.graph import open_graph  # noqa: E402
from janusgraph_tpu.olap import delta as D  # noqa: E402
from janusgraph_tpu.olap import intersect  # noqa: E402
from janusgraph_tpu.olap.cpu_executor import CPUExecutor  # noqa: E402
from janusgraph_tpu.olap.csr import (  # noqa: E402
    csr_from_edges,
    load_csr_snapshot,
)
from janusgraph_tpu.olap.programs import LCCProgram  # noqa: E402
from janusgraph_tpu.olap.tpu_executor import TPUExecutor  # noqa: E402

agrees = reference.GraphalyticsLcc.agrees


class Edges:
    def __init__(self, n, src, dst):
        self.n = n
        self.src = np.asarray(src, np.int32)
        self.dst = np.asarray(dst, np.int32)


def expected(data):
    return reference.GraphalyticsLcc.expect(data)


def submit(data):
    """Through the normal path, on a snapshot adopted warm (as the
    benchmark's `submit-loop` driver warms one)."""
    g = open_graph({"storage.backend": "inmemory"})
    try:
        csr = csr_from_edges(data.n, data.src, data.dst)
        D.get_snapshot(g).adopt(csr, g.backend.mutation_epoch())
        return g.compute().program(LCCProgram()).submit()
    finally:
        g.close()


def rmat(scale, seed=None):
    from janusgraph_tpu.olap.generators import rmat_edges

    return Edges(*rmat_edges(scale, 8, seed=scale if seed is None else seed))


def ring_lattice(n, k):
    """Every vertex joined to its k next ones: one degree for all, so a
    bit row narrower than n leaves no hub at all."""
    i = np.arange(n)
    return Edges(n, np.tile(i, k),
                 np.concatenate([(i + j) % n for j in range(1, k + 1)]))


def clique(k):
    a, b = np.triu_indices(k, 1)
    return Edges(k + 2, a, b)  # two isolated vertices beside it


GRAPHS = {
    "rmat-8": lambda: rmat(8),
    "rmat-9": lambda: rmat(9),
    "rmat-10": lambda: rmat(10),
    "clique": lambda: clique(9),
    "ring-lattice": lambda: ring_lattice(5000, 3),
    "star": lambda: Edges(12, np.zeros(11, int), np.arange(1, 12)),
    "path": lambda: Edges(7, np.arange(6), np.arange(1, 7)),
    "two-triangles-sharing-an-edge": lambda: Edges(
        4, [0, 1, 2, 1, 3], [1, 2, 0, 3, 2]),
    "both-directions-and-twice": lambda: Edges(
        5, [0, 1, 0, 1, 2, 2, 0, 3, 3], [1, 0, 1, 2, 0, 0, 0, 3, 4]),
    "empty": lambda: Edges(6, [], []),
}

HAND = {
    "clique": lambda: np.r_[np.full(9, 28), 0, 0],
    "ring-lattice": lambda: np.full(5000, 9),
    "star": lambda: np.zeros(12, int),
    "path": lambda: np.zeros(7, int),
    "two-triangles-sharing-an-edge": lambda: np.array([1, 2, 2, 1]),
    "both-directions-and-twice": lambda: np.array([1, 1, 1, 0, 0]),
    "empty": lambda: np.zeros(6, int),
}


NARROW = {"WIDTHS": (4, 8), "CLASS_FLOOR": 1}


@pytest.fixture(params=[{}, {"WIDTHS": (1, 2)}, NARROW],
                ids=["all-hubs", "with-a-tail", "with-classes"])
def widths(request, monkeypatch):
    for name, value in request.param.items():
        monkeypatch.setattr(intersect.IntersectEngine, name, value)
    return request.param


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_submit_equals_the_plain_reference(name, widths):
    data = GRAPHS[name]()
    want = expected(data)
    if name in HAND:  # the reference itself, against counts made by hand
        np.testing.assert_array_equal(want["triangles"], HAND[name]())
    result = submit(data)
    triangles, lcc = result.states["triangles"], result.states["lcc"]
    assert triangles.dtype == np.int32 and lcc.dtype == np.float32
    np.testing.assert_array_equal(triangles, want["triangles"])
    assert agrees(lcc, want)
    pairs = want["degree"] * (want["degree"] - 1) // 2
    np.testing.assert_array_equal(
        lcc, np.where(pairs > 0, triangles.astype(np.float32)
                      / np.maximum(pairs, 1).astype(np.float32), 0))
    info = result.run_info
    assert info["path"] == "intersect" and info["supersteps"] == 1
    assert info["routing"]["routed"] == "tpu"
    assert "fallback" not in info["routing"]
    assert info["triangles_total"] == want["triangles"].sum() // 3
    lo, _ = reference.simple_closure(data.n, data.src, data.dst)
    assert info["simple_edges"] == len(lo)
    if widths and name.startswith("rmat"):
        assert info["intersect"]["tail_candidates"] > 0
    if widths is NARROW and name.startswith("rmat"):
        assert len(info["intersect"]["classes"]) > 1


def test_the_run_record_counts_what_the_pass_reads(widths):
    from janusgraph_tpu.observability import registry

    def counters():
        snap = registry.snapshot()
        return {k: snap.get(f"olap.intersect.{k}", {}).get("count", 0)
                for k in ("runs", "candidates", "probe_slots")}

    data = rmat(9)
    ex = TPUExecutor(csr_from_edges(data.n, data.src, data.dst))
    before = counters()
    ex.run(LCCProgram())
    info, sizes = ex.last_run_info, ex.last_run_info["intersect"]
    assert registry.last_run("olap")["path"] == "intersect"
    assert info["dispatches"] == 1 and "autotune" not in info
    classes = sizes["classes"]
    assert info["probe_slots"] == (
        sum(2 * c["edge_slots"] * c["words"] for c in classes)
        + sizes["tail_candidates"] * (sizes["search_steps"] + 1))
    assert sizes["edge_slots"] == sum(c["edge_slots"] for c in classes)
    assert classes[0]["words"] <= sizes["words"]
    assert (len(classes) > 1) == (widths is NARROW)
    shapes = _argument_shapes(ex)
    for c in classes:  # each class in whole chunks of its own scan
        assert shapes[f"edge_u.{c['words']}"] == shapes[
            f"edge_v.{c['words']}"] == (
            (c["edge_slots"] // c["chunk"], c["chunk"]), "int32")
    assert info["candidates"] >= sizes["tail_candidates"]
    moved = {k: v - before[k] for k, v in counters().items()}
    assert moved == {"runs": 1, "candidates": info["candidates"],
                     "probe_slots": info["probe_slots"]}


def test_second_submit_builds_transfers_and_compiles_nothing():
    data = rmat(9)
    ex = TPUExecutor(csr_from_edges(data.n, data.src, data.dst))
    first = ex.run(LCCProgram())
    engine, args = ex._intersect_engine, ex._intersect_engine.args
    second = ex.run(LCCProgram())
    assert ex._intersect_engine is engine and engine.args is args
    assert ex.last_run_info["retraces"] == 0
    assert ex.last_run_info["h2d_arg_bytes"] == 0
    for key in first:
        np.testing.assert_array_equal(first[key], second[key])


def test_loaded_through_the_store():
    rng = np.random.default_rng(7)
    g = open_graph({"schema.default": "auto"})
    try:
        tx = g.new_transaction()
        vs = [tx.add_vertex() for _ in range(30)]
        for _ in range(140):
            a, b = rng.integers(0, 30, 2)
            tx.add_edge(vs[int(a)], "knows", vs[int(b)])
        tx.commit()
        result = g.compute().program(LCCProgram()).submit()
        csr = load_csr_snapshot(g)[0]
        src = np.repeat(np.arange(csr.num_vertices), np.diff(csr.out_indptr))
        want = expected(Edges(csr.num_vertices, src, csr.out_dst))
        np.testing.assert_array_equal(
            result.states["triangles"], want["triangles"])
        assert agrees(result.states["lcc"], want)
        assert result.run_info["path"] == "intersect"
        assert want["triangles"].sum() > 0
    finally:
        g.close()


def test_a_pending_overlay_is_materialized_before_the_program_runs():
    rng = np.random.default_rng(3)
    g = open_graph({"schema.default": "auto"})
    try:
        tx = g.new_transaction()
        vs = [tx.add_vertex() for _ in range(24)]
        for _ in range(90):
            a, b = rng.integers(0, 24, 2)
            tx.add_edge(vs[int(a)], "knows", vs[int(b)])
        tx.commit()
        g.compute().program(LCCProgram()).submit()
        tx = g.new_transaction()
        for _ in range(10):
            a, b = rng.integers(0, 24, 2)
            tx.add_edge(tx.get_vertex(vs[int(a)].id), "knows",
                        tx.get_vertex(vs[int(b)].id))
        tx.commit()
        assert not D.program_delta_compatible(LCCProgram())
        second = g.compute().program(LCCProgram()).submit()
        assert "delta" not in second.run_info  # no fused overlay ran
        fresh = load_csr_snapshot(g)[0]
        src = np.repeat(
            np.arange(fresh.num_vertices), np.diff(fresh.out_indptr))
        want = expected(Edges(fresh.num_vertices, src, fresh.out_dst))
        np.testing.assert_array_equal(
            second.states["triangles"], want["triangles"])
    finally:
        g.close()


# --------------------------------------------- the classes, on the tables
def _view(data, widths, floor):
    src, dst = data.src.astype(np.int64), data.dst.astype(np.int64)
    engine = intersect.IntersectEngine
    return intersect.IntersectView(
        data.n, src, dst, widths, engine.WORD_NS, engine.CANDIDATE_NS,
        engine.TABLE_BYTES_LIMIT, floor)


@pytest.mark.parametrize("scale", [9, 10])
@pytest.mark.parametrize("ladder", [(2, 4), (4, 8)])
def test_an_edge_skips_only_words_that_are_zero(scale, ladder):
    """The exactness argument, on the tables themselves (numpy alone): the
    words of `bits[v]` below an edge's class start are all zero, so the AND
    loses nothing; and each class holds only the edges whose suffix fits
    its width and not the next narrower one."""
    floor = 1
    view = _view(rmat(scale), ladder, floor)
    W, base = view.words, view.hub_base
    # the table is kept once, in column blocks cut where the classes begin
    assert [a for a, _ in view.blocks] == [
        sum(w for _, w in view.blocks[:k]) for k in range(len(view.blocks))]
    bits = np.concatenate(
        [view.tables[f"bits.{a}"] for a, _ in view.blocks], axis=1)
    assert bits.shape == (view.rows, W)
    zero_row = int(view.tables["row_of"].max())
    assert not bits[zero_row].any()
    widths = [c["words"] for c in view.classes]
    assert len(widths) > 1 and widths == sorted(widths, reverse=True)
    # every class's suffix begins at a block's first word
    assert {W - w for w in widths} <= {a for a, _ in view.blocks}
    ends = {}
    for c in view.classes:
        # a chunk takes every n-th edge: read down the columns, the class
        # lies in (u, v) order with its padding at the end
        u = view.tables[f"edge_u.{c['words']}"].T.reshape(-1)
        v = view.tables[f"edge_v.{c['words']}"].T.reshape(-1)
        assert len(u) == c["edge_slots"] and len(u) % c["chunk"] == 0
        real = u != zero_row
        assert (v[~real] == zero_row).all()  # padding reads the zero row
        assert len(u) - np.count_nonzero(real) < c["chunk"]
        assert real[:np.count_nonzero(real)].all()
        ends[c["words"]] = u[real].astype(np.int64), v[real].astype(np.int64)
    assert sum(len(u) for u, _ in ends.values()) == view.simple_edges
    # the rows rise by degree: the first row of each row's degree
    all_u = np.concatenate([u for u, _ in ends.values()])
    all_v = np.concatenate([v for _, v in ends.values()])
    row_degree = np.bincount(all_u, minlength=zero_row) + np.bincount(
        all_v, minlength=zero_row)
    assert (np.diff(row_degree) >= 0).all()
    first = np.searchsorted(row_degree, row_degree)
    for width, (u, v) in ends.items():
        assert (u < v).all()
        assert (np.diff(u * view.rows + v) > 0).all()  # (u, v) order
        # what the scan leaves out of the higher end's row is zero, and so
        # nothing of the AND is lost
        assert not bits[v, :W - width].any()
        assert not (bits[u] & bits[v])[:, :W - width].any()
        # the words the edge can need start at the first row of v's degree
        # (a tail edge needs them all): they fit this class ...
        need = np.where(v >= base, W - (first[v] - base) // 32, W)
        assert (need <= width).all()
        # ... and not the next narrower one
        if width // 2 >= floor and width % 2 == 0:
            assert (need > width // 2).all()


def _argument_shapes(ex):
    """Shape and dtype of every argument of the compiled pass."""
    return {k: (tuple(v.shape), str(v.dtype))
            for k, v in ex._intersect_engine.args.items()}


def test_relabelled_graph_gives_the_relabelled_answer_and_one_shape(widths):
    """`--seed` relabels one structure: the answer under a permutation of
    the ids is the permuted answer, and every shape of the pass is the
    same, so nothing compiles anew (ROADMAP S3)."""
    data = rmat(9)
    runs = []
    for seed in (1, 2, 3):
        perm = np.random.default_rng(seed).permutation(data.n)
        relabelled = Edges(data.n, perm[data.src], perm[data.dst])
        ex = TPUExecutor(csr_from_edges(
            relabelled.n, relabelled.src, relabelled.dst))
        out = ex.run(LCCProgram())
        sizes = dict(ex.last_run_info["intersect"])
        runs.append((perm, out, _argument_shapes(ex), sizes))
    want = expected(data)
    for perm, out, shapes, sizes in runs:
        np.testing.assert_array_equal(
            out["triangles"][perm], want["triangles"])
        assert shapes == runs[0][2]
        assert sizes == runs[0][3]


def test_the_pass_names_its_three_stages(widths):
    data = rmat(9)
    ex = TPUExecutor(csr_from_edges(data.n, data.src, data.dst))
    ex.run(LCCProgram())
    engine = ex._intersect_engine
    text = engine._pass_fn().lower(engine.args).as_text(debug_info=True)
    stages = ("expand", "intersect", "credit")
    for stage in stages:
        # a graph whose vertices are all hubs expands no candidate list
        assert (f"lcc.{stage}" in text) == (
            stage != "expand" or bool(engine.view.tail_candidates)), stage
    for outer in stages:
        for inner in stages:
            assert f"lcc.{outer}/lcc.{inner}" not in text
    assert "bf16" not in text and "f64" not in text


# ----------------------------------------------- who refuses it, by name
def _mesh(csr, **kwargs):
    import jax
    from jax.sharding import Mesh

    from janusgraph_tpu.parallel import ShardedExecutor

    return ShardedExecutor(
        csr, mesh=Mesh(np.array(jax.devices()[:8]), ("p",)), **kwargs)


def _with_overlay(csr, program):
    """A pending overlay, handed to the executor fused."""
    g = open_graph({"schema.default": "auto"})
    try:
        tx = g.new_transaction()
        vs = [tx.add_vertex() for _ in range(8)]
        for a in range(7):
            tx.add_edge(vs[a], "knows", vs[a + 1])
        tx.commit()
        base, epoch = load_csr_snapshot(g)
        tx = g.new_transaction()
        tx.add_edge(tx.get_vertex(vs[0].id), "knows",
                    tx.get_vertex(vs[5].id))
        tx.commit()
        overlay, _ = D.overlay_since(g, epoch)
        return TPUExecutor(base, delta=D.OverlayView(base, overlay)).run(
            program)
    finally:
        g.close()


def _hub_of_65536():
    leaves = np.arange(1, intersect.MAX_DEGREE + 1)
    return csr_from_edges(
        len(leaves) + 1, np.zeros(len(leaves), np.int64), leaves)


@pytest.mark.parametrize("runner,named", [
    (lambda csr, p: CPUExecutor(csr).run(p), "the CPU executor"),
    (lambda csr, p: CPUExecutor(csr, strategy="hybrid").run(p),
     "the CPU executor"),
    (lambda csr, p: TPUExecutor(csr).run(p, frontier="off"),
     "dense superstep path of the single-device executor"),
    (lambda csr, p: TPUExecutor(csr, frontier="off").run(p),
     "dense superstep path of the single-device executor"),
    (lambda csr, p: TPUExecutor(csr).run(
        p, checkpoint_path="/nonexistent/ck", checkpoint_every=1),
     "dense superstep path of the single-device executor"),
    (lambda csr, p: _mesh(csr).run(p), "the sharded executor"),
    (lambda csr, p: _mesh(csr, exchange="blocked").run(p),
     "the sharded executor"),
    (lambda csr, p: _with_overlay(csr, p), "the fused delta overlay"),
    (lambda csr, p: TPUExecutor(_hub_of_65536()).run(p),
     "degree 65536"),
], ids=["cpu-scalar", "cpu-hybrid", "tpu-frontier-off-run",
        "tpu-frontier-off-executor", "tpu-checkpointed", "mesh",
        "mesh-halo-exchange", "delta-overlay", "degree-guard"])
def test_other_paths_refuse_the_program_by_name(runner, named):
    data = rmat(8)
    with pytest.raises(ValueError) as refused:
        runner(csr_from_edges(data.n, data.src, data.dst), LCCProgram())
    assert named in str(refused.value)
    assert "LCCProgram" in str(refused.value)


def test_a_hub_just_under_the_guard_is_counted():
    """Degree 65,535: d (d - 1) / 2 = 2,147,385,345 fits int32."""
    d = intersect.MAX_DEGREE - 1
    leaves = np.arange(1, d + 1)
    src = np.r_[np.zeros(d, np.int64), [1]]
    dst = np.r_[leaves, [2]]
    out = TPUExecutor(csr_from_edges(d + 1, src, dst)).run(LCCProgram())
    assert out["triangles"][:3].tolist() == [1, 1, 1]
    assert out["triangles"].sum() == 3
    assert out["lcc"][0] == np.float32(1) / np.float32(d * (d - 1) // 2)
    assert out["lcc"][1] == 1.0 and out["lcc"][3] == 0.0
