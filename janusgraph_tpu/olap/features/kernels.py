"""Fused dense-feature kernels: SDDMM–SpMM supersteps over the packed formats.

The scalar-message tier (olap/kernels.py) aggregates an (n,) value per
vertex; this module lifts the same superstep to **[n, d] feature blocks** —
the FusedMM observation (PAPERS.md, arxiv 2011.06391) that one fused
gather -> elementwise/semiring multiply -> aggregate -> dense-transform
kernel shape covers graph-embedding training and GNN message passing.
Three message modes:

  copy      message = source feature row (plain SpMM over the pack)
  weighted  message = w_e * source row (rides the existing MUL_WEIGHT path)
  sddmm     message = <h_src, h_dst> * h_src — the per-edge coefficient is
            a sampled dense–dense matmul over the sparsity pattern
            (dot-attention), fused into the same gather pass

plus an optional post-aggregate **dense transform** (matmul + bias +
nonlinearity) — the op that actually feeds the MXU on TPU.

Bitwise contract (inherited from PR 6): every reduction that feeds vertex
state goes through the fixed adjacent-pair tree (`tree_reduce`), including
the SDDMM dot (`tree_dot`) and the dense matmul's contraction axis
(`tree_matmul`). All entry points are xp-generic (jnp or numpy), so the
CPU executor replays the identical arithmetic — device and oracle results
are bit-for-bit equal on both the ELL and hybrid formats, and ELL vs
hybrid stay bitwise-equal exactly as the scalar tier does. Feature dims
are padded to power-of-two lane tiers (`FEATURE_TIERS`) so the tree-dot
width is always a complete tree (graphlint JG304 enforces pow2 padded
dims); padded columns hold zeros and stay zero through every mode.

`tree_matmul` trades the backend's native dot (MXU) for the deterministic
tree contraction; `native=True` (computer.features-native-matmul) switches
to ``xp.matmul`` for peak MXU throughput at the cost of the cross-backend
bitwise guarantee.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from janusgraph_tpu.olap.kernels import (
    ELLPack,
    HybridPack,
    _is_jax,
    _next_pow2,
    _segment_combine,
    flat_take,
    fp_fence,
    hybrid_fold,
    tree_reduce,
)
from janusgraph_tpu.olap.vertex_program import Combiner

#: power-of-two lane-width tiers the feature dimension pads to — the
#: feature-axis analogue of the frontier E_cap ladder. 8 is the smallest
#: tree worth fusing; 512 covers every shipped program, and larger dims
#: fall through to the next power of two.
FEATURE_TIERS = (8, 16, 32, 64, 128, 256, 512)


def pick_feature_tier(d: int, forced: int = 0) -> int:
    """Smallest lane tier >= d (next pow2 above the ladder). ``forced``
    (computer.features-dim-tier) pins the tier; it must be a power of two
    and must not truncate the logical dim."""
    d = int(d)
    if d < 1:
        raise ValueError(f"feature_dim must be >= 1 (got {d})")
    if forced:
        forced = int(forced)
        if forced & (forced - 1) or forced < d:
            raise ValueError(
                f"features dim tier {forced} must be a power of two >= the "
                f"logical feature dim {d}"
            )
        return forced
    for t in FEATURE_TIERS:
        if t >= d:
            return t
    return _next_pow2(d)


def pad_features(h: np.ndarray, d_pad: int) -> np.ndarray:
    """Host-side zero-pad of an (n, d) float block to the (n, d_pad) lane
    tier. Padded columns are zero and every kernel mode preserves that."""
    h = np.asarray(h, dtype=np.float32)
    if h.ndim != 2:
        raise ValueError(f"feature block must be 2-D (got shape {h.shape})")
    n, d = h.shape
    if d == d_pad:
        return h
    if d > d_pad:
        raise ValueError(f"feature dim {d} exceeds padded tier {d_pad}")
    out = np.zeros((n, d_pad), dtype=np.float32)
    out[:, :d] = h
    return out


# graphlint: traced -- the SDDMM dot of every compiled dense superstep
def tree_dot(xp, a, b):
    """Row-wise dot product over the LAST axis (width must be a pow2 lane
    tier) through the fixed adjacent-pair tree — the feature-axis twin of
    `tree_reduce`, so the SDDMM coefficient is bitwise-identical however
    the slots were laid out (ELL row, hybrid torso, tail chunk). The
    product is fenced so the backend can't contract it into the first
    tree level as a bit-changing fused multiply-add."""
    prod = fp_fence(xp, a * b)
    flat = prod.reshape((-1, prod.shape[-1]))
    return tree_reduce(xp, flat, Combiner.SUM).reshape(prod.shape[:-1])


#: materialized (rows, k, j) product budget per matmul block — keeps the
#: tree contraction's intermediate in cache/VMEM-sized chunks
_MM_BLOCK_BYTES = 1 << 23


# graphlint: traced -- the dense-transform contraction of compiled supersteps
def tree_matmul(xp, h, w, native: bool = False):
    """(n, k) @ (k, j) with the contraction folded through the fixed
    adjacent-pair tree over k (k must be a pow2 lane tier). Row-chunked so
    the materialized (chunk, k, j) product stays ~_MM_BLOCK_BYTES; chunking
    never changes bits (rows reduce independently). ``native=True`` uses
    the backend dot instead — the MXU path, outside the bitwise contract."""
    if native:
        return xp.matmul(h, w)
    n, k = h.shape
    j = w.shape[1]
    if k & (k - 1):
        raise ValueError(f"tree_matmul contraction width {k} is not pow2")

    def block(hb):
        return tree_reduce(
            xp, fp_fence(xp, hb[:, :, None] * w[None, :, :]), Combiner.SUM
        )

    rows = max(1, _MM_BLOCK_BYTES // max(1, 4 * k * j))
    rows = 1 << (rows.bit_length() - 1)
    if n <= rows:
        return block(h)
    nb = -(-n // rows)
    pad = nb * rows - n
    if pad:
        h = xp.concatenate(
            [h, xp.zeros((pad, k), dtype=h.dtype)], axis=0
        )
    blocks = h.reshape(nb, rows, k)
    if _is_jax(xp):
        import jax

        out = jax.lax.map(block, blocks)
    else:
        out = xp.stack([block(b) for b in blocks])
    return out.reshape(nb * rows, j)[:n]


_ACTIVATIONS = ("identity", "relu", "tanh")


# graphlint: traced -- post-aggregate dense layer of compiled supersteps
def dense_transform(xp, h, w, b=None, activation: str = "identity",
                    native: bool = False):
    """The post-aggregate dense layer: ``act(h @ w + b)``. relu/identity
    are exact elementwise ops (inside the bitwise contract); tanh is
    backend-libm and documented as outside it."""
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    out = tree_matmul(xp, h, w, native=native)
    if b is not None:
        out = out + b
    if activation == "relu":
        out = xp.maximum(out, 0.0)
    elif activation == "tanh":
        out = xp.tanh(out)
    return out


# --------------------------------------------------------------------------
# SDDMM row-destination indices
# --------------------------------------------------------------------------
#
# Every slot in a pack row shares one destination vertex, so the SDDMM
# coefficient needs one dst index per ROW (per chunk row in the hybrid
# tail). The builders construct a shadow pack from the same (dst, dst)
# edge list — bucketing depends only on destination degrees, so the shadow
# layout is row-for-row identical to the real pack — and keep column 0 of
# each index matrix: the destination id (the sentinel for all-padding
# rows, whose gathered features read the zero identity).


def ell_row_dsts(
    src: np.ndarray, dst: np.ndarray, num_vertices: int,
    max_capacity: int = 1 << 14,
) -> List[np.ndarray]:
    """Per-bucket (rows,) destination-index vectors aligned with
    ``ELLPack(src, dst, ..., max_capacity)``'s bucket layout."""
    dst = np.asarray(dst, dtype=np.int64)
    shadow = ELLPack(dst, dst, None, num_vertices, max_capacity=max_capacity)
    return [np.ascontiguousarray(b[0][:, 0]) for b in shadow.buckets]


def hybrid_row_dsts(
    src: np.ndarray, dst: np.ndarray, num_vertices: int,
    hub_cutoff: int = 64, tail_chunk: int = 256,
    max_capacity: int = 1 << 14,
) -> np.ndarray:
    """One destination index per row of the equivalent ``HybridPack``, in
    the order of its flat index vector: torso rows, then tail chunks."""
    dst = np.asarray(dst, dtype=np.int64)
    return HybridPack(
        dst, dst, None, num_vertices,
        hub_cutoff=hub_cutoff, tail_chunk=tail_chunk,
        max_capacity=max_capacity,
    ).row_dsts


# --------------------------------------------------------------------------
# Fused SDDMM–SpMM aggregation
# --------------------------------------------------------------------------


def _check_sddmm(op: str, msgs) -> None:
    if op != Combiner.SUM:
        raise ValueError(
            f"sddmm aggregation is SUM-only (dot-attention coefficients "
            f"have no {op} semantics)"
        )
    d = msgs.shape[-1]
    if msgs.ndim != 2 or d & (d - 1):
        raise ValueError(
            f"sddmm needs (n, d) features with a pow2 lane-tier d "
            f"(got shape {tuple(msgs.shape)})"
        )


# graphlint: traced -- the ELL SDDMM body of compiled dense supersteps
def sddmm_ell_aggregate(xp, pack, row_dsts, msgs, op: str = Combiner.SUM):
    """Fused SDDMM+SpMM over an ELLPack (or view): for each in-edge,
    coefficient = <h_src, h_dst> (tree dot), message = coefficient * h_src,
    summed per destination through the shared reduction tree.

    ``row_dsts``: per-bucket (rows,) destination indices (ell_row_dsts).
    Sentinel slots gather the zero identity row, so their coefficient and
    message are exactly zero — the same leaves the hybrid path produces."""
    _check_sddmm(op, msgs)
    if len(row_dsts) != len(pack.buckets):
        raise ValueError(
            f"sddmm row-dst count {len(row_dsts)} != bucket count "
            f"{len(pack.buckets)} (pack drift)"
        )
    identity = Combiner.IDENTITY[op]
    pad_shape = (1,) + tuple(msgs.shape[1:])
    msgs_ext = xp.concatenate(
        [msgs, xp.full(pad_shape, identity, dtype=msgs.dtype)], axis=0
    )
    parts = []
    for (idx, _w, _valid, rowseg, num_slots), rdst in zip(
        pack.buckets, row_dsts
    ):
        m = flat_take(xp, msgs_ext, idx)           # (rows, c, d)
        dstf = flat_take(xp, msgs_ext, rdst)       # (rows, d)
        alpha = tree_dot(xp, m, dstf[:, None, :])  # (rows, c)
        r = tree_reduce(xp, fp_fence(xp, m * alpha[:, :, None]), op)
        if rowseg is not None:
            # split supernode rows share one destination, so each row's
            # alpha used the right dst; the fold just sums row partials
            r = _segment_combine(xp, op, r, rowseg, num_slots)
        parts.append(r)
    if not parts:
        return xp.full(msgs.shape, identity, dtype=msgs.dtype)
    stacked = xp.concatenate(parts, axis=0)
    return stacked[pack.unpermute]


# graphlint: traced -- the hybrid SDDMM body of compiled dense supersteps
def sddmm_hybrid_aggregate(xp, pack, row_dsts, msgs, op: str = Combiner.SUM):
    """Fused SDDMM+SpMM over a HybridPack (or view) — bitwise-identical to
    `sddmm_ell_aggregate` by the same aligned-subtree argument as the
    scalar tier: per-slot coefficients are elementwise, so the leaves of
    every row's reduction tree carry identical bits in both layouts.
    Two gathers: every slot's source row, and every row's destination
    (``row_dsts``: hybrid_row_dsts)."""
    _check_sddmm(op, msgs)
    rows_total = sum(r for _d, _cap, r in pack.torso_meta) + pack.tail_chunks
    if row_dsts.shape != (rows_total,):
        raise ValueError(
            f"sddmm row-dst vector {tuple(row_dsts.shape)} != hybrid "
            f"metadata ({rows_total},) (pack drift)"
        )
    identity = Combiner.IDENTITY[op]
    pad_shape = (1,) + tuple(msgs.shape[1:])
    msgs_ext = xp.concatenate(
        [msgs, xp.full(pad_shape, identity, dtype=msgs.dtype)], axis=0
    )
    m = flat_take(xp, msgs_ext, pack.arrays["idx"])   # (slots, d)
    dstf = flat_take(xp, msgs_ext, row_dsts)          # (rows_total, d)

    def coefficients(block, row_lo, row_hi):
        # (width, rows, d) sources against their rows' destinations; an
        # identity-padded slot is a zero row, as the ELL sentinel reads
        alpha = tree_dot(xp, block, dstf[row_lo:row_hi][None, :, :])
        return fp_fence(xp, block * alpha[:, :, None])

    return hybrid_fold(
        xp, pack, m, op, msgs.shape, msgs.dtype, leaf_fn=coefficients
    )


def sddmm_flops(num_edges: int, d_pad: int) -> float:
    """MXU-attributable flops of one SDDMM pass: a length-d dot (2d ops)
    plus the coefficient multiply (d ops) per edge."""
    return 3.0 * float(num_edges) * float(d_pad)


def matmul_flops(n: int, d_in: int, d_out: int) -> float:
    """MXU-attributable flops of one (n, d_in) @ (d_in, d_out) layer."""
    return 2.0 * float(n) * float(d_in) * float(d_out)
