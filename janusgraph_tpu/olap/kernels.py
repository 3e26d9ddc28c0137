"""TPU aggregation kernels for the BSP superstep.

The superstep's hot op is `combine({msg(src) for (src,dst) edges}) by dst` —
the reference runs it as NonBlockingHashMapLong insert-with-combiner per
message (reference: FulgoraVertexMemory.java:91-99); the straightforward XLA
translation is gather + `segment_sum`, whose scatter-add lowering serializes
poorly on TPU. Two packed layouts here:

1. **Degree-bucketed ELL** (`ELLPack` / `ell_aggregate`): in-edges are packed
   per destination into power-of-two-capacity row buckets (ELLPACK layout).
   Aggregation becomes gather + dense axis-1 reduction — no scatter at all,
   every monoid (sum/min/max) supported, padding overhead < 2× by the
   power-of-two bucketing. One gather per bucket. The mesh's shards
   (parallel/) aggregate over it, and the CPU oracle replays it as the
   reference the hybrid pack must equal.

2. **Degree-bucketed HYBRID** (`HybridPack` / `hybrid_aggregate`), the
   single-device executor's one aggregation structure, sized by
   olap/autotune.decide: the
   ELL pack's power-of-two bucket rounding gathers 1.40-1.47 slots an edge
   on Graph500 R-MAT graphs, and a v5e pays 7.3-8.1 ns for a slot, padding
   or not (PERF.md section 6, PR 26: 180.2 ms a PageRank superstep at
   scale 20 on the ELL pack, 136.9 ms on this one at 1.01 slots an edge).
   The hybrid keeps an ELL-shaped torso packed at EXACT degree widths
   (zero padding) for vertices at or below a degree cutoff, and routes hub
   vertices through a chunked CSR tail: contiguous `tail_chunk`-wide slices
   of the destination-sorted edge array, folded into per-row partial tables.
   Results are BITWISE-IDENTICAL to the pure-ELL path because both reduce
   through the same fixed adjacent-pair tree (`tree_reduce`): a width-2^k
   ELL row's reduction tree decomposes exactly into the per-chunk subtrees
   plus the partial-table fold, and in-kernel identity padding reproduces
   the sentinel slots leaf-for-leaf. The whole pack is ONE index vector, so
   an aggregation is one gather whatever the number of exact widths.

`Combiner.MODE` (the most frequent label, smallest on ties) is no monoid,
so it rides the ELL and hybrid packs' ONE gather and then folds each
destination's WHOLE multiset: a data-oblivious sort along a block's width,
run lengths, and an arg-max by (count, then smaller label) — see
`mode_along`, `hybrid_mode_fold`, and `segment_mode` (the ELL replay's
split rows, numpy only).

Both are built once per (graph, orientation) and reused across supersteps.
The aggregation entry points take the array module (`jnp` or plain numpy)
as their first argument, so the CPU oracle can run the identical pack
arithmetic for cross-executor bitwise checks.
"""

from __future__ import annotations

import contextlib
import itertools
from typing import List, Optional, Tuple

import numpy as np

from janusgraph_tpu.olap.vertex_program import (
    Combiner,
    EdgeTransform,
    apply_edge_transform,
)


# --------------------------------------------------------------------------
# Degree-bucketed ELL packing
# --------------------------------------------------------------------------

def fill_ell_rows(cap, starts_r, degs_r, src32, w32, idx, wmat, valid):
    """Fill one ELL bucket's (rows, cap) matrices in place — native fast
    path with a numpy fallback. Callers pre-fill idx with the sentinel and
    wmat/valid with zeros; wmat/valid are None for unweighted packs (the
    sentinel slot alone provides the monoid identity on device)."""
    from janusgraph_tpu import native

    if native.ell_fill(cap, starts_r, degs_r, src32, w32, idx, wmat, valid):
        return
    total = int(np.asarray(degs_r).sum())
    if not total:
        return
    degs_r = np.asarray(degs_r, dtype=np.int64)
    starts_r = np.asarray(starts_r, dtype=np.int64)
    rows = len(starts_r)
    row_ids = np.repeat(np.arange(rows), degs_r)
    col_ids = np.arange(total) - np.repeat(
        np.cumsum(degs_r) - degs_r, degs_r
    )
    edge_pos = np.repeat(starts_r, degs_r) + col_ids
    idx[row_ids, col_ids] = src32[edge_pos]
    if valid is not None:
        valid[row_ids, col_ids] = 1.0
    if wmat is not None:
        wmat[row_ids, col_ids] = w32[edge_pos] if w32 is not None else 1.0


def split_rows(
    members: np.ndarray,
    deg_m: np.ndarray,
    starts_m: np.ndarray,
    cap: int,
):
    """Row-split supernode edge ranges into chunks of at most `cap` edges.

    Returns (starts, degs, rowseg): one entry per row; rowseg maps each row
    to its owner's slot index (position within `members`). Vertices with
    degree <= cap keep one row. This bounds ELL padding at < 2× regardless
    of max degree — a supernode costs ceil(d/cap) dense rows, not a bucket
    padded to the global max degree (supernodes: SURVEY.md §5.7).
    """
    n_rows = np.maximum(1, -(-deg_m // cap)).astype(np.int64)
    total = int(n_rows.sum())
    rowseg = np.repeat(np.arange(len(members), dtype=np.int64), n_rows)
    chunk = (
        np.arange(total, dtype=np.int64)
        - np.repeat(np.cumsum(n_rows) - n_rows, n_rows)
    )
    starts = np.repeat(starts_m, n_rows) + chunk * cap
    degs = np.minimum(cap, np.repeat(deg_m, n_rows) - chunk * cap)
    degs = np.maximum(degs, 0)
    return starts, degs, rowseg


class ELLPack:
    """Host-side ELLPACK layout of an edge list grouped by destination.

    For each power-of-two capacity bucket c: the destinations whose in-degree
    d satisfies prev_c < d <= c, with a (rows, c) matrix of source indices
    (padded with a sentinel slot) and a (rows, c) weight/validity matrix.
    Destinations with degree > max_capacity are ROW-SPLIT into ceil(d/cap)
    rows of the top bucket; `rowseg` then folds row partials into one slot
    per destination with a small (rows-sized, not edges-sized) segment
    reduction.

    Bucket tuple: (idx, wmat, valid, rowseg, num_slots); rowseg is None when
    rows == slots (no split rows in that bucket).

    `sentinel` is index `n` — callers extend the per-vertex message vector by
    one identity element so padded slots read the monoid identity.
    """

    def __init__(
        self,
        src: np.ndarray,
        dst: np.ndarray,
        weight: Optional[np.ndarray],
        num_vertices: int,
        max_capacity: int = 1 << 14,
    ):
        n = num_vertices
        self.num_vertices = n
        self.sentinel = n
        self.has_weight = weight is not None
        order = np.argsort(dst, kind="stable")
        src = np.asarray(src, dtype=np.int64)[order]
        dst = np.asarray(dst, dtype=np.int64)[order]
        w = (
            np.asarray(weight, dtype=np.float32)[order]
            if weight is not None
            else None
        )
        deg = np.bincount(dst, minlength=n).astype(np.int64)
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(deg, out=indptr[1:])

        # bucket capacity per vertex: next power of two >= degree (min 1),
        # clamped to max_capacity (larger degrees row-split, see split_rows)
        caps = np.maximum(1, 1 << np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64))
        caps = np.minimum(caps, max_capacity)

        self.buckets: List[Tuple] = []
        self.vertex_order_parts: List[np.ndarray] = []
        src32 = np.ascontiguousarray(src, dtype=np.int32)
        w32 = (
            np.ascontiguousarray(w, dtype=np.float32) if w is not None else None
        )
        for c in sorted(set(int(c) for c in np.unique(caps))):
            members = np.nonzero(caps == c)[0]
            if len(members) == 0:
                continue
            deg_m = deg[members]
            starts_m = indptr[members]
            if c == max_capacity and int(deg_m.max()) > c:
                starts_r, degs_r, rowseg = split_rows(members, deg_m, starts_m, c)
            else:
                starts_r, degs_r, rowseg = starts_m, deg_m, None
            rows = len(starts_r)
            idx = np.full((rows, c), self.sentinel, dtype=np.int32)
            # unweighted packs carry idx ONLY: padded slots point at the
            # sentinel, which reads the monoid identity — wmat/valid would
            # triple HBM footprint and transfer for nothing (s23: 2.3GB
            # -> 0.76GB measured)
            if self.has_weight:
                wmat = np.zeros((rows, c), dtype=np.float32)
                valid = np.zeros((rows, c), dtype=np.float32)
            else:
                wmat = valid = None
            fill_ell_rows(c, starts_r, degs_r, src32, w32, idx, wmat, valid)
            self.buckets.append(
                (
                    idx,
                    wmat,
                    valid,
                    rowseg.astype(np.int32) if rowseg is not None else None,
                    len(members),
                )
            )
            self.vertex_order_parts.append(members)

        vertex_order = (
            np.concatenate(self.vertex_order_parts)
            if self.vertex_order_parts
            else np.zeros(0, dtype=np.int64)
        )
        # inverse permutation: position of vertex i in the bucketed output
        pos = np.zeros(n, dtype=np.int64)
        pos[vertex_order] = np.arange(len(vertex_order), dtype=np.int64)
        self.unpermute = pos.astype(np.int32)
        #: gathered slots, padding included, and their ratio to the edges
        self.num_edges = len(src)
        self.slots = sum(int(b[0].size) for b in self.buckets)
        self.pad_ratio = self.slots / max(1, self.num_edges)

    def device_put(self, jnp, sharding=None):
        """Move index/weight matrices to device once (optionally sharded)."""
        put = (lambda a: a) if sharding is None else (
            lambda a: __import__("jax").device_put(a, sharding)
        )
        self.buckets = [
            (
                put(jnp.asarray(i)),
                put(jnp.asarray(w)) if w is not None else None,
                put(jnp.asarray(v)) if v is not None else None,
                put(jnp.asarray(rs)) if rs is not None else None,
                ns,
            )
            for (i, w, v, rs, ns) in self.buckets
        ]
        self.unpermute = put(jnp.asarray(self.unpermute))
        return self


# graphlint: traced -- called from every compiled superstep body
def flat_take(jnp, tab, idx):
    """Gather rows/values of `tab` by a 2-D index matrix via a FLAT 1-D
    take + reshape. Identical semantics to tab[idx], but the (rows, 1) 2-D
    gather shape compiles pathologically on TPU (measured 197s for a
    667k-row cap-1 bucket vs 0.5s flat; the run's time is the same, 7.3-8.1
    ns a gathered element on a v5e: PERF.md section 6, PR 26). Shared by
    the single-chip and sharded pack paths."""
    flat = idx.reshape(-1)
    if tab.ndim == 1:
        return jnp.take(tab, flat).reshape(idx.shape)
    return jnp.take(tab, flat, axis=0).reshape(idx.shape + tab.shape[1:])


def _next_pow2(v: int) -> int:
    return 1 << max(0, int(v) - 1).bit_length() if v > 1 else 1


def _is_jax(xp) -> bool:
    """jnp vs plain numpy — the aggregation bodies are xp-generic so the
    CPU oracle can replay the exact pack arithmetic in numpy."""
    return "jax" in getattr(xp, "__name__", "")


# graphlint: traced -- the pack aggregation's 1-D gathers
def row_take(xp, tab, idx):
    """`flat_take(tab, idx)`, with a 1-D `tab` on the jax path read as
    rows of two words: `(len, 2)`, the second word a zero, gathered whole
    and column 0 kept. On the v5e XLA's 1-D gather is the slow path and a
    row gather runs at about twice its rate whatever the row holds
    (docs/tpu_notes.md "The gather wall"). Both barriers keep XLA from
    seeing that column 1 is dead and narrowing the gather back to single
    elements; column 0 is a slice, since a strided view lowers to a
    gather. Every index of the pack is in bounds (the sentinel is the
    table's last row), so `clip` changes no index and spares the default
    mode's select over the leaves. A gather moves values exactly, so the
    bits are flat_take's. numpy and a 2-D `tab` take flat_take's own
    path."""
    if not (_is_jax(xp) and tab.ndim == 1):
        return flat_take(xp, tab, idx)
    import jax

    rows = jax.lax.optimization_barrier(
        xp.stack([tab, xp.zeros_like(tab)], axis=1)
    )
    got = jax.lax.optimization_barrier(
        xp.take(rows, idx.reshape(-1), axis=0, mode="clip")
    )
    return jax.lax.index_in_dim(got, 0, axis=1, keepdims=False).reshape(
        idx.shape
    )


def superstep_scope(xp, name: str):
    """`jax.named_scope("superstep.<name>")` around a stage of the compiled
    superstep (message / gather / fold / apply, none enclosing another): it
    names the stage's device operations in a profile and changes nothing
    else; nothing at all on the numpy path."""
    if _is_jax(xp):
        import jax

        return jax.named_scope("superstep." + name)
    return contextlib.nullcontext()


def frontier_scope(name: str):
    """`jax.named_scope("frontier.<name>")` around a stage of the frontier
    engine's compiled hop (expand / relax / scatter / parent, none
    enclosing another): `superstep_scope`'s sibling for the engine that
    has no numpy path."""
    import jax

    return jax.named_scope("frontier." + name)


def brandes_scope(name: str):
    """`jax.named_scope("brandes.<name>")` around a hop of the frontier
    engine's Brandes sweeps (forward / backward; the hop's `frontier.*`
    stages nest inside): `frontier_scope`'s sibling."""
    import jax

    return jax.named_scope("brandes." + name)


def afforest_scope(name: str):
    """`jax.named_scope("afforest.<name>")` around one of the frontier
    engine's two Afforest executables (sample / finish; neither encloses
    the other): `frontier_scope`'s sibling."""
    import jax

    return jax.named_scope("afforest." + name)


def intersect_scope(name: str):
    """`jax.named_scope("lcc.<name>")` around a stage of the intersection
    engine's compiled pass (expand / intersect / credit, none enclosing
    another): `frontier_scope`'s sibling."""
    import jax

    return jax.named_scope("lcc." + name)


# graphlint: traced -- the fp-contraction fence of product-fed reductions
def fp_fence(xp, a):
    """Add an optimizer-opaque zero to `a` — the fp-contraction fence.

    LLVM's CPU backend may contract a float multiply into a following add
    as one fused multiply-add (single rounding), silently changing bits vs
    the numpy oracle's separately-rounded mul+add; HLO-level barriers and
    bitcasts do not survive to the emitted loop, so the fence works
    arithmetically instead: any contraction of a product into THIS add
    computes round(a*b + 0) == round(a*b) — the plain multiply's bits —
    and every downstream add sees a non-multiply operand, which cannot
    contract. The zero rides through `optimization_barrier` so the HLO
    simplifier can't fold the add away before the backend sees it. The
    numpy path adds a real zero, so both sides also normalize -0.0 to
    +0.0 identically."""
    if _is_jax(xp):
        import jax

        z = jax.lax.optimization_barrier(xp.zeros((), dtype=a.dtype))
        return a + z
    return a + a.dtype.type(0.0)


# graphlint: traced -- the shared reduction tree of every compiled superstep
def tree_reduce(xp, m, op: str, axis: int = 1):
    """Reduce `axis` (1, or 0) of `m` (width MUST be a power of two)
    through a fixed adjacent-pair halving tree:
    [a,b,c,d] -> [a+b, c+d] -> [(a+b)+(c+d)].

    This tree — not the backend's reduce — is the strategies' bitwise
    contract: any aligned power-of-two-sized contiguous sub-range of the
    leaves is a complete subtree, so a row evaluated whole (ELL) and the
    same row evaluated as chunk partials folded afterwards (hybrid tail)
    produce identical bits, on any backend that preserves elementwise
    float semantics (all of them). The axis is the layout's business only:
    (rows, width) reduced along 1 and (width, rows) along 0 pair the same
    operands."""
    width = m.shape[axis]
    if width & (width - 1):
        raise ValueError(f"tree_reduce width {width} is not a power of two")
    lead = (slice(None),) * axis
    if axis == 0 and _is_jax(xp):
        # lax's strided slice: jnp's `m[0::2]` lowers to a gather by an
        # iota, and the TPU compiler keeps it one (88 gathers in the
        # optimized module of one hybrid aggregation, compiled here for
        # the v5e). Axis 1, the ELL pack's, is left as the chip last
        # measured it.
        from jax.lax import slice_in_dim

        def every_other(m, start):
            return slice_in_dim(m, start, None, 2, axis)
    else:
        def every_other(m, start):
            return m[lead + (slice(start, None, 2),)]
    pair = Combiner.monoid(
        op, "tree_reduce (pack chunks folded pairwise)",
        xp.add, xp.minimum, xp.maximum,
    )
    while m.shape[axis] > 1:
        m = pair(every_other(m, 0), every_other(m, 1))
    return m[lead + (0,)]


def _segment_combine(xp, op: str, values, seg, num_segments: int):
    """Per-slot monoid fold of row partials (rows-sized, not edges-sized).
    jax path: XLA segment ops; numpy path: unbuffered ufunc.at — each
    executor's two strategies share one implementation, so hybrid-vs-ELL
    stays bitwise-identical within either executor."""
    if _is_jax(xp):
        import jax

        seg_fn = Combiner.monoid(
            op, "the fold of split-row partials",
            jax.ops.segment_sum, jax.ops.segment_min, jax.ops.segment_max,
        )
        return seg_fn(values, seg, num_segments=num_segments)
    return _segment_combine_host(xp, op, values, seg, num_segments)


# graphlint: host -- numpy-only branch, unreachable from traced code
def _segment_combine_host(xp, op: str, values, seg, num_segments: int):
    out = xp.full(
        (num_segments,) + values.shape[1:], Combiner.IDENTITY[op],
        dtype=values.dtype,
    )
    ufunc = Combiner.monoid(
        op, "the fold of split-row partials",
        xp.add, xp.minimum, xp.maximum,
    )
    ufunc.at(out, seg, values)
    return out


# graphlint: traced -- the ELL aggregation body of every compiled superstep
def ell_aggregate(
    jnp,
    pack: ELLPack,
    msgs,
    op: str,
    edge_transform: str = EdgeTransform.NONE,
    edge_transform_cols=None,
):
    """Aggregate per-vertex messages over an ELLPack.

    msgs: (n,) or (n, k) per-source message array. Returns (n,) / (n, k)
    aggregated-by-destination, monoid identity where a vertex has no edges.
    `edge_transform_cols`: per-column transforms for k-column messages
    (see vertex_program.apply_edge_transform).
    """
    identity = Combiner.IDENTITY[op]
    if op == Combiner.MODE:
        _check_mode_messages(msgs, edge_transform, edge_transform_cols)
    if not pack.has_weight:
        # mirror the segment path: transforms only apply when weights exist
        edge_transform = EdgeTransform.NONE
        edge_transform_cols = None
    # sentinel slot so padded indices read the identity
    pad_shape = (1,) + tuple(msgs.shape[1:])
    msgs_ext = jnp.concatenate(
        [msgs, jnp.full(pad_shape, identity, dtype=msgs.dtype)], axis=0
    )
    parts = []
    for idx, w, valid, rowseg, num_slots in pack.buckets:
        with superstep_scope(jnp, "gather"):
            m = flat_take(jnp, msgs_ext, idx)
            # labels ride untransformed, and a weighted pack's padded
            # slots index the sentinel like any other's: no mask for MODE
            if w is not None and op != Combiner.MODE:
                # weighted pack: apply the transform, then force padded
                # slots back to the identity (a transform can disturb it,
                # e.g. identity*0 = nan for MIN's +inf)
                valid_ = valid[:, :, None] if m.ndim == 3 else valid
                if edge_transform_cols is not None:
                    m = apply_edge_transform(
                        jnp, m, w, edge_transform, edge_transform_cols
                    )
                else:
                    w_ = w[:, :, None] if m.ndim == 3 else w
                    if edge_transform == EdgeTransform.MUL_WEIGHT:
                        m = m * w_
                    elif edge_transform == EdgeTransform.ADD_WEIGHT:
                        m = m + w_
                m = jnp.where(valid_ > 0, m, identity)
                # fence the transformed leaves so no backend contracts the
                # weight product into the reduction tree (and every layout
                # normalizes -0.0 the same way)
                m = fp_fence(jnp, m)
            # unweighted pack: padded slots index the sentinel, which
            # already reads the identity — no mask needed
        with superstep_scope(jnp, "fold"):
            if op == Combiner.MODE:
                if rowseg is None:
                    r = mode_along(jnp, m, 1)
                else:
                    # supernode rows: the owner's WHOLE multiset, never
                    # row partials — a segmented sort by (owner, label)
                    # (the CPU oracle's replay only: numpy)
                    owners = jnp.broadcast_to(rowseg[:, None], m.shape)
                    r = segment_mode(
                        m.reshape(-1), owners.reshape(-1), num_slots
                    )
            else:
                r = tree_reduce(jnp, m, op)
                if rowseg is not None:
                    # fold supernode row partials into one slot per
                    # destination — a rows-sized reduction, negligible
                    # next to the edge gather
                    r = _segment_combine(jnp, op, r, rowseg, num_slots)
        parts.append(r)
    if not parts:
        out_shape = msgs.shape
        return jnp.full(out_shape, identity, dtype=msgs.dtype)
    with superstep_scope(jnp, "fold"):
        stacked = jnp.concatenate(parts, axis=0)
        return stacked[pack.unpermute]


# --------------------------------------------------------------------------
# Degree-bucketed hybrid: exact-width ELL torso + chunked CSR tail
# --------------------------------------------------------------------------

class HybridPack:
    """Hybrid layout of an edge list grouped by destination degree.

    Torso (in-degree 1..hub_cutoff): one bucket per EXACT degree d — a
    block of rows x d source indices with no padded slots at all; the
    reduction pads to next-pow2(d) with the monoid identity *in-kernel*
    (registers/VMEM, never gathered), reproducing the pure-ELL bucket's
    leaves exactly. Zero-degree vertices contribute an identity constant
    and zero slots.

    Tail (hub vertices, in-degree > hub_cutoff): the hubs' destination-
    sorted CSR edge ranges are cut into contiguous `tail_chunk`-wide
    chunks (the last chunk of a row sentinel-padded — static tail capacity
    tiers); chunk partials scatter into an identity-filled partial table
    (cap/tail_chunk entries per row) and fold down the remaining tree
    levels. Degrees above `max_capacity` row-split first, exactly like
    ELLPack (shared `split_rows`), so the final rows-sized segment fold
    sees the same operand sequence.

    Both `tail_chunk` and every tree width are powers of two, so every
    vertex reduces through the identical `tree_reduce` tree the ELL path
    uses — hybrid and ELL results are bitwise-equal by construction.
    Slots actually gathered: m_torso exact + ceil-per-hub-row chunk
    padding, i.e. pad_ratio ~ 1 + tail_chunk/(2*mean hub degree) instead
    of ELL's pow2 rounding.

    The pack ships as ONE flat index vector — the torso buckets in
    ascending width, then every tail chunk — so an aggregation is one
    gather whatever the number of exact widths, and the buckets are static
    slices of the gathered vector (`arrays`: "idx", with "w" beside it and
    "valid" for the tail's slots on weighted packs; "slot" per tail chunk,
    "rowseg" when the widest tail bucket row-splits; "unpermute"). Every
    block lies SLOT-major — (width, rows), slot j of all its rows, then
    slot j+1 — and reduces along axis 0: the rows, of which a narrow
    bucket has many, fill the device's minor (lane) dimension, where
    (rows, 2) row-major would leave 126 lanes of 128 empty.
    """

    def __init__(
        self,
        src: np.ndarray,
        dst: np.ndarray,
        weight: Optional[np.ndarray],
        num_vertices: int,
        hub_cutoff: int = 64,
        tail_chunk: int = 256,
        max_capacity: int = 1 << 14,
    ):
        n = num_vertices
        self.num_vertices = n
        self.sentinel = n
        self.has_weight = weight is not None
        self.hub_cutoff = int(hub_cutoff)
        tail_chunk = int(tail_chunk)
        if tail_chunk < 1 or tail_chunk & (tail_chunk - 1):
            raise ValueError(
                f"tail_chunk must be a power of two (got {tail_chunk})"
            )
        if self.hub_cutoff < 1:
            raise ValueError(f"hub_cutoff must be >= 1 (got {hub_cutoff})")
        # every hub's tree width is >= next_pow2(cutoff+1); the chunk must
        # divide it so chunks stay aligned subtrees
        T = self.tail_chunk = min(
            tail_chunk, _next_pow2(self.hub_cutoff + 1), int(max_capacity)
        )

        order = np.argsort(dst, kind="stable")
        src = np.asarray(src, dtype=np.int64)[order]
        dst = np.asarray(dst, dtype=np.int64)[order]
        deg = np.bincount(dst, minlength=n).astype(np.int64)
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(deg, out=indptr[1:])
        src32 = np.ascontiguousarray(src, dtype=np.int32)
        w32 = (
            np.ascontiguousarray(
                np.asarray(weight, dtype=np.float32)[order]
            )
            if weight is not None
            else None
        )

        # torso: vertices by (degree, id), one (d, rows) block per degree
        in_torso = np.nonzero((deg >= 1) & (deg <= self.hub_cutoff))[0]
        members = in_torso[np.argsort(deg[in_torso], kind="stable")]
        widths, rows_per = np.unique(deg[members], return_counts=True)
        #: static (width, tree cap, rows) per exact-degree bucket
        self.torso_meta: List[Tuple[int, int, int]] = [
            (int(d), _next_pow2(int(d)), int(r))
            for d, r in zip(widths, rows_per)
        ]
        torso_pos, row = [], 0
        for d, _cap, rows in self.torso_meta:
            starts = indptr[members[row:row + rows]]
            row += rows
            torso_pos.append(
                (starts[None, :] + np.arange(d, dtype=np.int64)[:, None])
                .reshape(-1)
            )
        torso_pos = (
            np.concatenate(torso_pos) if torso_pos
            else np.zeros(0, dtype=np.int64)
        )
        self.torso_slots = len(torso_pos)
        vertex_order_parts: List[np.ndarray] = [members]

        zero_members = np.nonzero(deg == 0)[0]
        self.num_zero = len(zero_members)
        vertex_order_parts.append(zero_members)

        # tail: every hub row's chunks, bucket after bucket, as one (T,
        # chunks) block; `slot` is each chunk's place in the partial table,
        # whose per-bucket (cap/T, rows) blocks lie end to end
        #: static (tree cap, partials per row, rows, slots) per tail bucket
        self.tail_meta: List[Tuple[int, int, int, int]] = []
        ch_starts, ch_degs, slots = [], [], []
        own_vertex, own_chunks = [], []  # per hub: id, chunks (all its rows)
        row_dsts = [members]  # each row's destination: torso rows, chunks
        rowseg = None
        table_rows = 0
        hub = deg > self.hub_cutoff
        if hub.any():
            caps = np.minimum(
                1 << np.ceil(
                    np.log2(np.maximum(deg, 1))
                ).astype(np.int64),
                int(max_capacity),
            )
            for c in sorted(int(x) for x in np.unique(caps[hub])):
                members = np.nonzero(hub & (caps == c))[0]
                deg_m = deg[members]
                starts_m = indptr[members]
                if c == int(max_capacity) and int(deg_m.max()) > c:
                    # only the widest bucket can row-split
                    starts_r, degs_r, rowseg = split_rows(
                        members, deg_m, starts_m, c
                    )
                else:
                    starts_r, degs_r = starts_m, deg_m
                rows = len(starts_r)
                ppr = c // T  # partial-table width per row
                nch = -(-degs_r // T)  # real chunks per row (degs_r >= 1)
                total = int(nch.sum())
                row_of = np.repeat(np.arange(rows, dtype=np.int64), nch)
                posr = (
                    np.arange(total, dtype=np.int64)
                    - np.repeat(np.cumsum(nch) - nch, nch)
                )
                ch_starts.append(starts_r[row_of] + posr * T)
                ch_degs.append(np.minimum(T, degs_r[row_of] - posr * T))
                slots.append(table_rows + posr * rows + row_of)
                row_dsts.append(
                    members[row_of] if rows == len(members)
                    else members[rowseg[row_of]]
                )
                table_rows += rows * ppr
                self.tail_meta.append((c, ppr, rows, len(members)))
                vertex_order_parts.append(members)
                own_vertex.append(members)
                own_chunks.append(np.bincount(
                    np.arange(rows) if rows == len(members) else rowseg,
                    weights=nch, minlength=len(members),
                ).astype(np.int64))
        self.tail_chunks = sum(len(s) for s in ch_starts)
        #: the destination of each row of the flat index vector's order
        #: (torso rows bucket after bucket, then the tail's chunks), what a
        #: `hybrid_fold` leaf_fn's rows [row_lo, row_hi) belong to
        self.row_dsts = np.concatenate(row_dsts).astype(np.int32)
        self.table_rows = table_rows
        self._mode_tables = {}  # array module -> tables (mode_tables)
        self._mode_owners = (
            np.concatenate(own_vertex) if own_vertex
            else np.zeros(0, dtype=np.int64),
            np.concatenate(own_chunks) if own_chunks
            else np.zeros(0, dtype=np.int64),
        )
        #: static (chunks per hub padded to a power of two, hubs) per
        #: whole-row bucket of the MODE fold (`mode_tables`)
        self.mode_tail_meta: List[Tuple[int, int]] = [
            (int(k), int(r)) for k, r in zip(*np.unique(
                [_next_pow2(int(c)) for c in self._mode_owners[1]],
                return_counts=True,
            ))
        ]

        tail_idx = np.full((self.tail_chunks, T), self.sentinel, dtype=np.int32)
        if self.has_weight:
            tail_w = np.zeros((self.tail_chunks, T), dtype=np.float32)
            tail_valid = np.zeros((self.tail_chunks, T), dtype=np.float32)
        else:
            tail_w = tail_valid = None
        if self.tail_chunks:
            fill_ell_rows(
                T, np.concatenate(ch_starts), np.concatenate(ch_degs),
                src32, w32, tail_idx, tail_w, tail_valid,
            )

        # a few sentinel slots more bring the vector to a prime length: see
        # _prime_at_least
        real = self.torso_slots + self.tail_chunks * T
        fill = _prime_at_least(real) - real
        self.arrays = {
            "idx": np.concatenate([
                src32[torso_pos], tail_idx.T.reshape(-1),
                np.full(fill, self.sentinel, dtype=np.int32),
            ]),
        }
        if self.has_weight:
            no_weight = np.zeros(fill, dtype=np.float32)
            self.arrays["w"] = np.concatenate(
                [w32[torso_pos], tail_w.T.reshape(-1), no_weight]
            )
            self.arrays["valid"] = np.concatenate(
                [tail_valid.T.reshape(-1), no_weight]
            )
        if self.tail_chunks:
            self.arrays["slot"] = np.concatenate(slots).astype(np.int32)
        if rowseg is not None:
            self.arrays["rowseg"] = rowseg.astype(np.int32)

        vertex_order = np.concatenate(vertex_order_parts)
        pos = np.zeros(n, dtype=np.int64)
        pos[vertex_order] = np.arange(len(vertex_order), dtype=np.int64)
        self.arrays["unpermute"] = pos.astype(np.int32)
        #: gathered slots (the number the pad ratio prices); the partial
        #: table is rows-sized and excluded
        self.num_edges = len(src)
        self.slots = int(self.arrays["idx"].size)
        self.pad_ratio = self.slots / max(1, self.num_edges)

    def mode_tables(self, xp=np) -> dict:
        """Index tables of the MODE fold as `xp` arrays (built, and moved
        to the device, on first use). The tail's hubs are folded WHOLE, so
        each hub becomes one row of a block whose width is its chunk count
        padded to a power of two — every chunk of every row the pack split
        it into, never partials.
        "mode_pieces": per `mode_tail_meta` bucket a (hubs, K) matrix of
        chunk numbers, flattened and laid end to end, holes pointing at
        chunk number `tail_chunks` (an all-sentinel chunk the fold
        appends); "mode_unpermute": `unpermute` with the hubs in the order
        of those buckets."""
        if not self._mode_tables:
            vertex, chunks = self._mode_owners
            first = np.cumsum(chunks) - chunks  # hubs' chunks lie end to end
            width = np.asarray(
                [_next_pow2(int(c)) for c in chunks], dtype=np.int64
            )
            pieces, order = [], []
            for k, _hubs in self.mode_tail_meta:
                sel = np.nonzero(width == k)[0]
                j = np.arange(k, dtype=np.int64)[None, :]
                pieces.append(np.where(
                    j < chunks[sel, None], first[sel, None] + j,
                    self.tail_chunks,
                ).reshape(-1))
                order.append(vertex[sel])
            unpermute = np.array(self.arrays["unpermute"], dtype=np.int32)
            if order:
                order = np.concatenate(order)
                unpermute[order] = (
                    self.num_vertices - len(order)
                    + np.arange(len(order), dtype=np.int32)
                )
            self._mode_tables[np] = {
                "mode_pieces": (
                    np.concatenate(pieces) if pieces
                    else np.zeros(0, dtype=np.int64)
                ).astype(np.int32),
                "mode_unpermute": unpermute,
            }
        if xp not in self._mode_tables:
            self._mode_tables[xp] = {
                k: xp.asarray(v) for k, v in self._mode_tables[np].items()
            }
        return self._mode_tables[xp]

    def device_put(self, jnp, sharding=None):
        """Move the index/weight/slot vectors to device once."""
        put = (lambda a: a) if sharding is None else (
            lambda a: __import__("jax").device_put(a, sharding)
        )
        self.arrays = {k: put(jnp.asarray(v)) for k, v in self.arrays.items()}
        return self


def _prime_at_least(v: int) -> int:
    """The least prime >= v: the length the hybrid pack's flat vectors are
    padded to. A bucket is `gathered[a:b].reshape(width, rows)`; where
    `rows` divides the whole vector's length XLA hoists the reshape over
    the slice and materializes the WHOLE vector as (len / rows, rows), and
    for a bucket of 2 rows that is 64x its bytes in (8, 128) tiles and
    minutes of compile (compiled here for the v5e, s17 cutoff 128: 119 s
    and 602 MB of temporaries against 2.3 s and 0.5 MB at a prime length).
    No block's row count divides a prime."""
    v = max(2, int(v))
    while True:
        r = int(v ** 0.5)
        if v < 4 or (v % np.arange(2, r + 1)).all():
            return v
        v += 1


class HybridPackView:
    """HybridPack-shaped facade over the traced `arrays` (duck-typed for
    hybrid_aggregate), carrying the compiled variant's static metadata."""

    _STATIC = (
        "torso_meta", "torso_slots", "tail_meta", "tail_chunks",
        "tail_chunk", "table_rows", "num_zero", "has_weight", "slots",
        "mode_tail_meta",
    )
    __slots__ = ("arrays",) + _STATIC

    def __init__(self, arrays, pack: HybridPack):
        if arrays["idx"].shape != (pack.slots,):
            raise ValueError(
                f"graph-args hybrid index vector {arrays['idx'].shape} != "
                f"compiled metadata ({pack.slots},) (pack drift)"
            )
        self.arrays = arrays
        for name in self._STATIC:
            setattr(self, name, getattr(pack, name))


# graphlint: traced -- shared by the hybrid aggregation bodies
def hybrid_fold(
    xp, pack, leaves, op: str, out_shape, dtype, leaf_fn=None, leaf_cols=None
):
    """Fold a HybridPack's gathered (and transformed) `leaves`, shaped
    (slots[, k]) in the flat index vector's order, into the per-vertex
    result. Every bucket is a static slice of `leaves`, a (width, rows)
    block; the torso buckets of one pow2 tree width are identity-padded up
    to it *in-kernel* (the ELL bucket's sentinel slots, never gathered)
    and laid side by side, so there is one `tree_reduce` per tree width
    and not per exact width — same leaves, same tree as `ell_aggregate`.
    `leaf_fn(block, row_lo, row_hi)` (the SDDMM coefficient pass, the
    frontier engine's parent pass) maps a (width, rows[, k]) block of rows
    [row_lo, row_hi) of the pack's row order before it is reduced;
    `leaf_cols` is the column shape it maps a leaf to where that is not
    the leaves' own (`()`: one word)."""
    identity = Combiner.IDENTITY[op]
    cols = tuple(leaves.shape[1:])
    out = cols if leaf_cols is None else tuple(leaf_cols)
    no_pad = [(0, 0)] * (1 + len(cols))
    parts = []
    off = row = 0
    for cap, group in itertools.groupby(pack.torso_meta, key=lambda t: t[1]):
        blocks = []
        for d, _cap, rows in group:
            m = leaves[off:off + d * rows].reshape((d, rows) + cols)
            off += d * rows
            if cap > d:
                m = xp.pad(
                    m, [(0, cap - d)] + no_pad, constant_values=identity
                )
            blocks.append(m)
        m = blocks[0] if len(blocks) == 1 else xp.concatenate(blocks, axis=1)
        if leaf_fn is not None:
            m = leaf_fn(m, row, row + m.shape[1])
        row += m.shape[1]
        parts.append(tree_reduce(xp, m, op, axis=0))

    if pack.num_zero:
        parts.append(xp.full((pack.num_zero,) + out, identity, dtype=dtype))

    if pack.tail_chunks:
        m = leaves[off:off + pack.tail_chunk * pack.tail_chunks].reshape(
            (pack.tail_chunk, pack.tail_chunks) + cols
        )
        if leaf_fn is not None:
            m = leaf_fn(m, row, row + pack.tail_chunks)
        part = tree_reduce(xp, m, op, axis=0)  # (chunks[, k]): subtrees
        table = xp.full((pack.table_rows,) + out, identity, dtype=part.dtype)
        slot = pack.arrays["slot"]
        if _is_jax(xp):
            table = table.at[slot].set(part)
        else:
            table[slot] = part
        toff = 0
        for i, (cap, ppr, rows, num_slots) in enumerate(pack.tail_meta):
            # remaining upper tree levels: fold each row's partial vector
            r = tree_reduce(
                xp,
                table[toff:toff + ppr * rows].reshape((ppr, rows) + out),
                op, axis=0,
            )
            toff += ppr * rows
            if i == len(pack.tail_meta) - 1 and "rowseg" in pack.arrays:
                r = _segment_combine(
                    xp, op, r, pack.arrays["rowseg"], num_slots
                )
            parts.append(r)

    if not parts:
        return xp.full(out_shape, identity, dtype=dtype)
    stacked = xp.concatenate(parts, axis=0)
    if stacked.ndim == 1:
        return row_take(xp, stacked, pack.arrays["unpermute"])
    return stacked[pack.arrays["unpermute"]]


# graphlint: traced -- the one gather of the hybrid aggregation bodies
def hybrid_gather(
    xp,
    pack,
    msgs,
    op: str,
    edge_transform: str = EdgeTransform.NONE,
    edge_transform_cols=None,
):
    """Every slot of a HybridPack (or view) read with ONE gather,
    `row_take(msgs_ext, idx)`, and transformed in flight: the (slots[, k])
    leaves `hybrid_fold` reduces, in the flat index vector's order."""
    identity = Combiner.IDENTITY[op]
    pad_shape = (1,) + tuple(msgs.shape[1:])
    msgs_ext = xp.concatenate(
        [msgs, xp.full(pad_shape, identity, dtype=msgs.dtype)], axis=0
    )
    m = row_take(xp, msgs_ext, pack.arrays["idx"])  # (slots[, k])
    # labels ride untransformed, and a weighted pack's padded slots
    # index the sentinel like any other's: no mask for MODE
    if pack.has_weight and op != Combiner.MODE:
        # mirrors the ELL weighted path slot-for-slot: transform first,
        # then force the tail's padded slots back to the identity (a
        # transform can disturb it, e.g. identity*0 = nan for MIN's
        # +inf)
        m = apply_edge_transform(
            xp, m, pack.arrays["w"], edge_transform, edge_transform_cols
        )
        ts = pack.torso_slots
        valid = pack.arrays["valid"]
        valid_ = valid[:, None] if m.ndim == 2 else valid
        # same fence as the ELL weighted branch: the torso's unmasked
        # weight product would otherwise contract into the tree
        m = fp_fence(xp, xp.concatenate(
            [m[:ts], xp.where(valid_ > 0, m[ts:], identity)], axis=0
        ))
    return m


# graphlint: traced -- the hybrid aggregation body of compiled supersteps
def hybrid_aggregate(
    xp,
    pack,
    msgs,
    op: str,
    edge_transform: str = EdgeTransform.NONE,
    edge_transform_cols=None,
):
    """Aggregate per-vertex messages over a HybridPack (or view) with ONE
    gather (`hybrid_gather`) and one fold of its leaves.

    Same contract as ell_aggregate — msgs (n,) or (n, k), returns the
    per-destination monoid fold — and bitwise-identical results to it
    (both reduce through tree_reduce's fixed adjacent-pair tree)."""
    if op == Combiner.MODE:
        _check_mode_messages(msgs, edge_transform, edge_transform_cols)
    with superstep_scope(xp, "gather"):
        m = hybrid_gather(
            xp, pack, msgs, op, edge_transform, edge_transform_cols
        )
    with superstep_scope(xp, "fold"):
        if op == Combiner.MODE:
            return hybrid_mode_fold(xp, pack, m)
        return hybrid_fold(xp, pack, m, op, msgs.shape, msgs.dtype)


# --------------------------------------------------------------------------
# Combiner.MODE: whole-multiset folds (sort, run lengths, arg-max)
# --------------------------------------------------------------------------

def _check_mode_messages(msgs, edge_transform, edge_transform_cols) -> None:
    if msgs.ndim != 1 or msgs.dtype.kind not in "iu":
        raise ValueError(
            "Combiner.MODE folds one integer label per vertex: messages "
            f"must be a 1-D integer array, got {msgs.dtype}{msgs.shape}"
        )
    if edge_transform != EdgeTransform.NONE or edge_transform_cols:
        raise ValueError(
            "Combiner.MODE carries labels, which no edge transform applies to"
        )


# graphlint: traced -- called from the compiled MODE folds
def _shifted(xp, a, k: int, axis: int, fill):
    """`a` moved `k` places along `axis`: position i reads a[i - k], the
    first k read `fill`."""
    keep = [slice(None)] * a.ndim
    keep[axis] = slice(0, a.shape[axis] - k)
    head = list(a.shape)
    head[axis] = k
    return xp.concatenate(
        [xp.full(head, fill, dtype=a.dtype), a[tuple(keep)]], axis=axis
    )


# graphlint: traced -- called from the compiled MODE folds
def _run_lengths(xp, keys, axis: int):
    """For arrays sorted along `axis` (lexicographically by `keys`, all
    non-negative), each position's place in its run of equal keys, from 1:
    a run's last position holds the run's length. By doubling: after the
    round that looks `k` places back a position holds min(place, 2k), so
    ceil(log2(width)) rounds of a shifted compare and an add, whatever the
    values are."""
    width = keys[0].shape[axis]
    place = xp.ones(keys[0].shape, dtype=np.int32)
    k = 1
    while k < width:
        same = None
        for a in keys:
            eq = _shifted(xp, a, k, axis, -1) == a  # -1 is no key
            same = eq if same is None else same & eq
        place = place + xp.where(same, _shifted(xp, place, k, axis, 0), 0)
        k *= 2
    return place


# graphlint: traced -- the MODE fold of one block of whole rows
def mode_along(xp, block, axis: int):
    """Most frequent label along `axis` of an int32 block, the smallest on
    ties; `Combiner.NO_MESSAGE` entries are padding and never win (a row
    of nothing else gives NO_MESSAGE). A sort along the axis (XLA's), run
    lengths by doubling, and the arg-max by (count, then smaller label):
    no step's work depends on the labels' values (on the v5e the sort's
    time does not either: PERF.md section 6, PR 28)."""
    no = Combiner.NO_MESSAGE
    if _is_jax(xp):
        import jax

        s = jax.lax.sort(block, dimension=axis, is_stable=False)
    else:
        s = xp.sort(block, axis=axis)
    count = xp.where(s == no, 0, _run_lengths(xp, (s,), axis))
    best = count.max(axis=axis, keepdims=True)
    return xp.where(count == best, s, no).min(axis=axis)


# graphlint: host -- numpy only, unreachable from traced code
def segment_mode(labels, owners, num_owners: int):
    """MODE of `labels` grouped by `owners` (any order, both 1-D int32
    numpy arrays): one sort of the (owner, label) pairs, run lengths over
    the sorted pairs, and per owner the arg-max by (count, then smaller
    label) — each owner's whole multiset at once. An owner without labels,
    or with NO_MESSAGE padding only, reads NO_MESSAGE. The ELL replay's
    supernode rows fold through it (the CPU oracle; no device path splits
    a MODE row)."""
    no = Combiner.NO_MESSAGE
    order = np.lexsort((labels, owners))
    o, s = owners[order], labels[order]
    count = np.where(s == no, 0, _run_lengths(np, (o, s), 0))
    best = np.zeros(num_owners, dtype=count.dtype)
    np.maximum.at(best, o, count)
    out = np.full(num_owners, no, dtype=s.dtype)
    np.minimum.at(out, o, np.where(count == best[o], s, no))
    return out


# graphlint: traced -- the hybrid pack's MODE fold
def hybrid_mode_fold(xp, pack, leaves):
    """MODE over a HybridPack's gathered int32 `leaves` (slots,), padding
    slots holding NO_MESSAGE. Torso: the buckets of one pow2 width are
    sentinel-padded up to it in-kernel and laid side by side, as in
    `hybrid_fold`, and each (width, rows) block is folded by `mode_along`.
    Tail: a hub's chunks — of every row the pack split it into — are
    brought together as ONE row (`HybridPack.mode_tables`: a gather of
    whole 1-KB chunks by a static table) and folded whole; no chunk or row
    partial is ever merged."""
    no = Combiner.NO_MESSAGE
    tables = (
        pack.arrays if "mode_pieces" in pack.arrays else pack.mode_tables()
    )
    parts = []
    off = 0
    for cap, group in itertools.groupby(pack.torso_meta, key=lambda t: t[1]):
        blocks = []
        for d, _cap, rows in group:
            m = leaves[off:off + d * rows].reshape((d, rows))
            off += d * rows
            if cap > d:
                m = xp.pad(m, [(0, cap - d), (0, 0)], constant_values=no)
            blocks.append(m)
        m = blocks[0] if len(blocks) == 1 else xp.concatenate(blocks, axis=1)
        parts.append(mode_along(xp, m, 0))
    if pack.num_zero:
        parts.append(xp.full((pack.num_zero,), no, dtype=leaves.dtype))
    if pack.tail_chunks:
        T = pack.tail_chunk
        chunks = leaves[off:off + T * pack.tail_chunks].reshape(
            (T, pack.tail_chunks)
        ).T
        chunks = xp.concatenate(
            [chunks, xp.full((1, T), no, dtype=leaves.dtype)], axis=0
        )
        poff = 0
        for k, hubs in pack.mode_tail_meta:
            piece = tables["mode_pieces"][poff:poff + k * hubs]
            poff += k * hubs
            rows = xp.take(chunks, piece, axis=0).reshape((hubs, k * T))
            parts.append(mode_along(xp, rows, 1))
    if not parts:
        return xp.full((0,), no, dtype=leaves.dtype)
    stacked = xp.concatenate(parts, axis=0)
    return row_take(xp, stacked, tables["mode_unpermute"])


def mode_fold_sizes(pack: HybridPack) -> dict:
    """What a MODE run folds, as the pack knows it (static numbers for the
    run record): slots folded block by block, slots of destinations that
    are brought together first, and how many such destinations."""
    return {
        "torso_slots": int(pack.torso_slots),
        "tail_slots": int(pack.tail_chunks * pack.tail_chunk),
        "rows_folded_whole": int(sum(h for _k, h in pack.mode_tail_meta)),
    }
