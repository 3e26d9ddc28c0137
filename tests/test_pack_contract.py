"""The single-device executor's pack equals the ELL replay, bitwise.

The executor aggregates every program, edge view and typed edge channel
over one structure, the hybrid pack, sized by `autotune.decide`. Until
commit 40a31da an `auto` strategy chose among four; these are the graphs
it sent to the ELL pack on one price column or the other (written down
from that commit: the regular graph and the closures of the star and the
chain on a v5e's column; the chain and the graph with empty rows on the
cpu's, the column these tests run under). One program echoes a given
vector through ONE superstep of the executor — pack built, shipped and
folded as for any program — and the answer must equal, bit for bit, the
plain replay: `ell_aggregate` in numpy over an `ELLPack` of the same edge
list, the fixed adjacent-pair tree the CPU oracle keeps as the reference.
"""

import numpy as np
import pytest

from janusgraph_tpu.olap import csr_from_edges
from janusgraph_tpu.olap.kernels import ELLPack, ell_aggregate
from janusgraph_tpu.olap.tpu_executor import TPUExecutor
from janusgraph_tpu.olap.vertex_program import (
    Combiner,
    EdgeChannel,
    EdgeTransform,
    VertexProgram,
)


# ------------------------------------------------------------------ graphs
def regular(n=64):
    """Every in-degree 4 and every out-degree 4: powers of two in either
    view, the ELL pack's zero-padding home."""
    offsets = (1, 5, 11, 23)
    dst = np.repeat(np.arange(n), len(offsets))
    return n, (dst + np.tile(offsets, n)) % n, dst


def star(n=80):
    """One hub every spoke points at, and that points back at half."""
    spokes = np.arange(1, n)
    back = spokes[spokes % 2 == 1]
    return (
        n,
        np.concatenate([spokes, np.zeros(len(back), int)]),
        np.concatenate([np.zeros(n - 1, int), back]),
    )


def holes(n=50):
    """Twenty vertices without an edge, ten self loops, parallel edges."""
    rng = np.random.default_rng(3)
    src, dst = rng.integers(0, 30, 120), rng.integers(0, 30, 120)
    loops = np.arange(10)
    return (
        n,
        np.concatenate([src, loops, src[:15]]),
        np.concatenate([dst, loops, dst[:15]]),
    )


def chain(n=12):
    return n, np.arange(n - 1), np.arange(1, n)


GRAPHS = {"regular": regular, "star": star, "holes": holes, "chain": chain}

#: (combiner, message shape): MODE folds one int32 label a vertex
FOLDS = {
    "sum": (Combiner.SUM, ()),
    "sum-n-by-4": (Combiner.SUM, (4,)),
    "min": (Combiner.MIN, ()),
    "min-n-by-4": (Combiner.MIN, (4,)),
    "max": (Combiner.MAX, ()),
    "mode": (Combiner.MODE, ()),
}

#: the program's edge view: in-edges, the symmetric closure, or one typed
#: channel (label 1 of two, traversers moving along the edge)
VIEWS = ("directed", "undirected", "channel")


class Echo(VertexProgram):
    """One superstep: every vertex sends `x`, and keeps what it folded."""

    max_iterations = 1

    def __init__(self, x, op, transform, undirected, channel):
        self.x = x
        self.combiner = op
        self.edge_transform = transform
        self.undirected = undirected
        if channel:
            self.edge_channels = {"typed": EdgeChannel("out", labels=(1,))}

    def channel_for(self, superstep):
        return "typed" if self.edge_channels else None

    def setup(self, graph, xp):
        return {"x": xp.asarray(self.x)}, {}

    def message(self, state, superstep, graph, xp):
        return state["x"]

    def apply(self, state, aggregated, superstep, memory_in, graph, xp):
        return {"x": aggregated}, {}

    def terminate(self, memory):
        return False


def _replay_edges(csr, view):
    """(src, dst, w) of the view, from the CSR's own arrays in the order
    the executors read them: in-edges, then (the closure) out-edges."""
    n = csr.num_vertices
    ids = np.arange(n, dtype=np.int64)
    src = csr.in_src.astype(np.int64)
    dst = np.repeat(ids, np.diff(csr.in_indptr))
    w = csr.in_edge_weight
    if view == "channel":
        keep = csr.in_edge_type == 1
        return src[keep], dst[keep], None if w is None else w[keep]
    if view == "undirected":
        src = np.concatenate([src, csr.out_dst.astype(np.int64)])
        dst = np.concatenate([dst, np.repeat(ids, np.diff(csr.out_indptr))])
        if w is not None:
            w = np.concatenate([w, csr.out_edge_weight])
    return src, dst, w


@pytest.mark.parametrize("view", VIEWS)
@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "w"])
@pytest.mark.parametrize("fold", sorted(FOLDS))
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_executor_pack_equals_the_ell_replay_bitwise(
    graph, fold, weighted, view
):
    n, src, dst = GRAPHS[graph]()
    rng = np.random.default_rng(len(src))
    weights = (
        rng.uniform(0.25, 2.0, len(src)).astype(np.float32)
        if weighted else None
    )
    csr = csr_from_edges(
        n, src, dst, weights, edge_types=np.arange(len(src)) % 2
    )
    op, shape = FOLDS[fold]
    if op == Combiner.MODE:
        x = rng.integers(0, 7, n).astype(np.int32)  # few labels: ties
        transform = EdgeTransform.NONE  # labels ride untransformed
    else:
        x = rng.uniform(-1.0, 1.0, (n,) + shape).astype(np.float32)
        transform = EdgeTransform.NONE if not weighted else (
            EdgeTransform.MUL_WEIGHT if op == Combiner.SUM
            else EdgeTransform.ADD_WEIGHT
        )
    program = Echo(x, op, transform, view == "undirected", view == "channel")
    ex = TPUExecutor(csr)
    got = np.asarray(ex.run(program)["x"])

    e_src, e_dst, e_w = _replay_edges(csr, view)
    with np.errstate(invalid="ignore"):  # identity * weight, then masked
        want = ell_aggregate(
            np, ELLPack(e_src, e_dst, e_w, n), x, op, transform
        )
    assert got.dtype == want.dtype == x.dtype
    np.testing.assert_array_equal(got, want)
    # the replay is the rule itself where the arithmetic is exact
    if op == Combiner.MODE:
        for v in range(n):
            labels = x[e_src[e_dst == v]]
            if len(labels):
                counts = np.bincount(labels)
                assert want[v] == int(np.argmax(counts)), v
            else:
                assert want[v] == Combiner.NO_MESSAGE
    info = ex.last_run_info
    assert info["path"] == "host-loop" and info["supersteps"] == 1
    assert info["strategy_resolved"] == "hybrid"
    pack = ex._resolve_pack(program, program.channel_for(0))
    assert pack.num_edges == len(e_src)
    assert info["pad_ratio"] == round(pack.slots / max(1, len(e_src)), 4)
