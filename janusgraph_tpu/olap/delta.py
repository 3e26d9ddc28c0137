"""Incremental delta-CSR: O(delta) snapshot refresh and streaming-fresh
analytics without the repack (ROADMAP #4).

Every OLAP run and every spillover snapshot refresh used to pay a full
scan + CSR pack — r05 measured transfer+pack at 5.6 s at s20 against 75 ms
per superstep, so preprocessing dwarfed the compute it fed (exactly the
cost hardware-assisted propagation blocking, arXiv 2011.08451, targets).
This module makes the snapshot incremental:

- **Change capture** (:class:`ChangeCapture`): the WAL/existence-cell
  machinery from PR 3 already sees every mutation —
  ``BackendTransaction.commit`` taps the committed edgestore batch into a
  bounded per-graph capture ring. Decoding is vectorized through the same
  fixed-width bulk edge decoder the scan loader uses, so a bulk-load
  commit costs one numpy pass, not a per-cell Python loop. Records:
  edge adds, edge deletes (the tombstone lane), vertex add/remove.

- **Delta overlay** (:class:`DeltaOverlay` -> :class:`OverlayView`):
  pending records net out (multiset counting — a delete cancels a
  pending add of the same ``(src, dst, type)`` triple) into pow2-tiered
  COO lanes over the base CSR's index space: an **add lane**, a
  **tombstone lane**, and — for the MIN/MAX family, where a deleted
  edge's contribution cannot be subtracted — per-**dirty-row live
  lanes** that re-aggregate a tombstoned destination's surviving base
  edges. New vertices extend the domain in a pow2 ``vcap`` tier appended
  after the base rows (base indices stay stable, so the device-resident
  base packs are reused untouched).

- **Fused consumption** (:func:`fused_delta_aggregate`): executors run
  their base aggregation over the unchanged base pack (messages sliced
  to the base rows so the pack's sentinel slot stays the identity), then
  merge the delta lanes through the same ``_segment_combine`` contract
  as the blocked exchange's bins (PR 9 — a delta is just another bin
  source):

    SUM:      out = base + segsum(adds) - segsum(tombstones)
    MIN/MAX:  out = op(where(dirty, seg_op(live), base), seg_op(adds))

  MIN-family results are **bitwise-identical** to a freshly repacked CSR
  (min is exact and order-independent over the identical edge multiset);
  SUM results are bitwise-identical to the numpy replay oracle
  (:func:`replay_fused_aggregate` — ``np.add.at`` == XLA CPU scatter,
  the PR 9 contract) and float-close to the repack.

- **Materialization** (:func:`materialize`): fold the overlay into new
  CSR arrays with the SAME canonical edge layout a fresh load produces
  (lexsort by (src, type, dst) — refresh_csr parity), with **zero store
  reads**: unlike ``refresh_csr``'s whole-row re-derivation, the records
  alone carry the delta. This is the spillover snapshot's refresh path
  and the warm ``GraphComputer.submit()`` path when the overlay is too
  large (or the program too exotic) to consume fused.

- **Compaction** (:class:`DeltaSnapshot`): the overlay folds back into
  the base pack once its depth crosses an autotuner-decided threshold
  (``olap/autotune.decide_delta``; override ``computer.
  delta-compact-threshold``), off the superstep path, with the usual
  tmp+rename discipline when ``computer.delta-snapshot-path`` persists
  the pack. Every compaction is a ``delta_compact`` flight event and the
  ``olap.delta.compactions`` counter.

- **Sharded routing** (:func:`route_overlay`): each delta record routes
  to the shard owning its aggregation-side (destination) row through the
  same contiguous ``dst // Np`` coupling as ``multihost.
  host_shard_range`` / the blocked halo plan, so a distributed refresh
  applies only each host's slice.

Scope guards (all fall back to a full reload, never to wrong numbers):
weighted or filtered snapshots, capture overflow, decode surprises, and
programs with typed edge channels / sddmm message modes refuse the
overlay.
"""

from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from janusgraph_tpu.olap.vertex_program import Combiner

#: fused-domain extra-vertex capacity tier ladder: next pow2 (0 = none).
#: Named per the JG301 delta vocabulary — overlay tiers must be pow2 so
#: one compiled superstep executable serves every overlay that fits.
def overlay_tier(n: int) -> int:
    if n <= 0:
        return 0
    return 1 << max(0, int(n) - 1).bit_length()


# ---------------------------------------------------------------------------
# Change capture
# ---------------------------------------------------------------------------

class ChangeCapture:
    """Bounded per-graph ring of committed graph-structure deltas.

    Fed from ``BackendTransaction.commit`` (via ``Backend.
    register_change_capture``) with the committed edgestore mutation
    batch; batches decode vectorized and append in epoch order. Consumers
    call :meth:`records_since(epoch)`; ``None`` means the capture cannot
    serve that epoch (ring overflow past it, or a cell the decoder could
    not classify) and the caller must fall back to a full reload."""

    def __init__(self, graph, limit: int = 1 << 16):
        self.graph = graph
        self.limit = int(limit)
        self._lock = threading.Lock()
        self._batches: deque = deque()  # graphlint: disable=JG206 -- bounded by the record-count prune below (limit records, oldest batches dropped on overflow)
        self._count = 0
        #: records with epoch <= floor may have been dropped/undecodable
        self._floor = 0
        #: durable sinks fed every decoded batch (storage/cdc.py CDCLog.
        #: append); poison forwards as (epoch, None) so the sink can
        #: record the un-servable range honestly
        self._sinks: List = []

    def add_sink(self, fn) -> None:
        """Register a ``fn(epoch, batch_or_None)`` durable sink. Sinks
        ride the commit path, so failures are swallowed and counted —
        capture (and the commit) must never fail because a sink did."""
        with self._lock:
            self._sinks.append(fn)

    def _feed_sinks(self, epoch: int, batch: Optional[dict]) -> None:
        for fn in self._sinks:
            try:
                fn(epoch, batch)
            except Exception:  # noqa: BLE001 - never fail a commit
                from janusgraph_tpu.observability import registry

                registry.counter("olap.delta.sink_errors").inc()

    # -- write side ---------------------------------------------------------
    def on_commit(self, epoch: int, edge_rows: Dict[bytes, object]) -> None:
        """Called with the committed edgestore row mutations (under the
        backend's epoch lock, so batches land in epoch order)."""
        try:
            batch = self._decode(edge_rows)
        except Exception:  # noqa: BLE001 - capture must never fail a commit
            batch = None
        with self._lock:
            if batch is None:
                # poison: snapshots at or before this epoch cannot be
                # served incrementally any more
                self._batches.clear()
                self._count = 0
                self._floor = epoch
                from janusgraph_tpu.observability import registry

                registry.counter("olap.delta.capture_poisoned").inc()
                self._feed_sinks(epoch, None)
                return
            if not batch["n"]:
                return
            self._batches.append((epoch, batch))
            self._count += batch["n"]
            while self._count > self.limit and self._batches:
                e0, b0 = self._batches.popleft()
                self._count -= b0["n"]
                self._floor = e0
            self._feed_sinks(epoch, batch)

    def _decode(self, edge_rows) -> Optional[dict]:
        """One committed batch -> vid-space record arrays. Returns None
        when any cell resists classification (the capture then refuses to
        serve epochs at or before this batch — correctness over
        freshness)."""
        import struct as _struct

        from janusgraph_tpu.core.codecs import Direction, EDGE_COL_FIXED

        g = self.graph
        idm = g.idm
        st = g.system_types
        es = g.edge_serializer
        relidx = getattr(g, "relation_index_ids", frozenset())
        unpack_tid = _struct.Struct(">Q").unpack_from

        add_cols: List[bytes] = []
        add_vids: List[int] = []
        del_cols: List[bytes] = []
        del_vids: List[int] = []
        slow_add: List[Tuple[int, int, int]] = []
        slow_del: List[Tuple[int, int, int]] = []
        v_add: Dict[int, int] = {}
        v_del: List[int] = []

        def _slow(vid, col, val):
            from janusgraph_tpu.olap.csr import graph_codec_schema

            rc = es.parse_relation((col, val), graph_codec_schema(g))
            if not rc.is_edge or rc.direction != Direction.OUT:
                return None
            if rc.type_id in relidx:
                return None
            return (vid, int(rc.other_vertex_id), int(rc.type_id))

        for key, m in edge_rows.items():
            vid = idm.get_vertex_id(key)
            if not idm.is_user_vertex_id(vid):
                continue
            vid = idm.get_canonical_vertex_id(vid)
            for entry in m.additions:
                col, val = entry[0], entry[1]
                cat = col[0]
                if cat == 3:
                    if len(col) == EDGE_COL_FIXED:
                        add_cols.append(col)
                        add_vids.append(vid)
                    else:
                        t = _slow(vid, col, val)
                        if t is not None:
                            slow_add.append(t)
                elif cat == 0:
                    if unpack_tid(col, 1)[0] == st.EXISTS:
                        v_add.setdefault(vid, 0)
                elif cat == 2:
                    if unpack_tid(col, 1)[0] == st.VERTEX_LABEL_EDGE:
                        rc = es.parse_relation((col, val), st.type_info)
                        v_add[vid] = int(rc.other_vertex_id)
            for col in m.deletions:
                cat = col[0]
                if cat == 3:
                    if len(col) == EDGE_COL_FIXED:
                        del_cols.append(col)
                        del_vids.append(vid)
                    else:
                        # a deletion carries no value; the OUT-edge
                        # identity fields all live in the column, so the
                        # codec parse still resolves them
                        t = _slow(vid, col, b"")
                        if t is not None:
                            slow_del.append(t)
                elif cat == 0:
                    if unpack_tid(col, 1)[0] == st.EXISTS:
                        v_del.append(vid)

        def _bulk(cols, vids, slow):
            if cols:
                tids, dirs, others, _rels = es.bulk_decode_edges(cols)
                owner = np.asarray(vids, dtype=np.int64)
                mask = dirs == int(Direction.OUT)
                if relidx:
                    mask &= ~np.isin(
                        tids, np.fromiter(relidx, dtype=np.int64)
                    )
                src = owner[mask]
                dst = others[mask]
                et = tids[mask]
            else:
                src = dst = et = np.empty(0, np.int64)
            if slow:
                s = np.asarray(slow, dtype=np.int64).reshape(-1, 3)
                src = np.concatenate([src, s[:, 0]])
                dst = np.concatenate([dst, s[:, 1]])
                et = np.concatenate([et, s[:, 2]])
            return (
                np.asarray(src, dtype=np.int64),
                np.asarray(dst, dtype=np.int64),
                np.asarray(et, dtype=np.int64),
            )

        a_src, a_dst, a_et = _bulk(add_cols, add_vids, slow_add)
        d_src, d_dst, d_et = _bulk(del_cols, del_vids, slow_del)
        n = (
            len(a_src) + len(d_src) + len(v_add) + len(v_del)
        )
        return {
            "n": n,
            "add": (a_src, a_dst, a_et),
            "del": (d_src, d_dst, d_et),
            "v_add": dict(v_add),
            "v_del": list(v_del),
        }

    # -- read side ----------------------------------------------------------
    def records_since(self, epoch: int) -> Optional[List[dict]]:
        with self._lock:
            if epoch < self._floor:
                return None
            return [b for e, b in self._batches if e > epoch]

    def slice_since(self, epoch: int) -> Optional[Tuple[List[dict], int]]:
        """(batches past `epoch`, anchor epoch) — the anchor is the max
        epoch actually CONSUMED, so a consumer that re-anchors there can
        never double-apply a record committed during the read."""
        with self._lock:
            if epoch < self._floor:
                return None
            batches = [(e, b) for e, b in self._batches if e > epoch]
            upto = max((e for e, _ in batches), default=epoch)
            return [b for _, b in batches], upto

    def depth_since(self, epoch: int) -> Optional[int]:
        """Pending record count past `epoch` — the overlay-lag signal the
        staleness gauge tracks. None = cannot serve (overflow)."""
        with self._lock:
            if epoch < self._floor:
                return None
            return sum(b["n"] for e, b in self._batches if e > epoch)


# ---------------------------------------------------------------------------
# Delta overlay (vid space)
# ---------------------------------------------------------------------------

@dataclass
class DeltaOverlay:
    """Netted graph-structure delta in graph-id space: the multiset
    difference between the live graph and a base snapshot."""

    #: net edge additions, one row per surviving instance
    add: np.ndarray          # (a, 3) int64 (src vid, dst vid, type id)
    #: net edge deletions against the BASE multiset
    tomb: np.ndarray         # (t, 3) int64
    new_vertices: Dict[int, int] = field(default_factory=dict)
    removed: frozenset = frozenset()

    @property
    def size(self) -> int:
        return (
            len(self.add) + len(self.tomb)
            + len(self.new_vertices) + len(self.removed)
        )

    @classmethod
    def from_batches(cls, batches: List[dict]) -> "DeltaOverlay":
        """Net the capture batches: counts of adds minus deletes per
        (src, dst, type) triple — positive nets are the add lane,
        negative nets the tombstone lane (multiset counting is
        order-independent; the final multiset is base + adds - dels)."""
        adds = [b["add"] for b in batches]
        dels = [b["del"] for b in batches]

        def _stack(parts):
            if not parts or not any(len(p[0]) for p in parts):
                return np.empty((0, 3), dtype=np.int64)
            return np.stack([
                np.concatenate([p[i] for p in parts])
                for i in range(3)
            ], axis=1)

        a = _stack(adds)
        d = _stack(dels)
        if len(a) or len(d):
            uni, inv = np.unique(
                np.concatenate([a, d]), axis=0, return_inverse=True
            )
            cnt = np.bincount(inv[: len(a)], minlength=len(uni)).astype(
                np.int64
            ) - np.bincount(inv[len(a):], minlength=len(uni))
            net_add = np.repeat(
                uni[cnt > 0], cnt[cnt > 0], axis=0
            )
            net_del = np.repeat(
                uni[cnt < 0], -cnt[cnt < 0], axis=0
            )
        else:
            net_add = net_del = np.empty((0, 3), dtype=np.int64)
        # vertex records: last state wins across batches (epoch order)
        vfinal: Dict[int, Optional[int]] = {}
        for b in batches:
            for vid, label in b["v_add"].items():
                vfinal[vid] = label
            for vid in b["v_del"]:
                vfinal[vid] = None
        new_vertices = {
            vid: lab for vid, lab in vfinal.items() if lab is not None
        }
        removed = frozenset(
            vid for vid, lab in vfinal.items() if lab is None
        )
        return cls(
            add=net_add, tomb=net_del,
            new_vertices=new_vertices, removed=removed,
        )


def overlay_since(graph, epoch: int) -> Optional[Tuple[DeltaOverlay, int]]:
    """(pending overlay past `epoch`, anchor epoch) from the graph's
    change capture, or None when the capture cannot serve it (disabled /
    overflow / poisoned decode)."""
    cap = getattr(graph, "change_capture", None)
    if cap is None:
        return None
    sl = cap.slice_since(epoch)
    if sl is None:
        return None
    batches, upto = sl
    return DeltaOverlay.from_batches(batches), upto


# ---------------------------------------------------------------------------
# Materialization: overlay -> new CSR arrays, zero store reads
# ---------------------------------------------------------------------------

def _key_rank(idm, vertex_ids: np.ndarray) -> np.ndarray:
    """Per-vertex rank in STORE-KEY order (partition-prefixed row keys,
    core/ids.get_key) — the order an ordered scan visits rows in, and
    therefore the fresh load's global edge order. Vectorized twin of
    IDManager.get_key over the snapshot's (user-vertex) id vector."""
    from janusgraph_tpu.core.ids import TOTAL_BITS

    vids = np.asarray(vertex_ids, dtype=np.int64)
    pb = idm.partition_bits
    partition = (vids >> 3) & ((1 << pb) - 1)
    rest = ((vids >> (3 + pb)) << 3) | (vids & 0b111)
    key_int = (
        (partition.astype(np.uint64) << np.uint64(TOTAL_BITS - pb))
        | rest.astype(np.uint64)
    )
    rank = np.empty(len(vids), dtype=np.int64)
    rank[np.argsort(key_int, kind="stable")] = np.arange(len(vids))
    return rank


def materialize(csr, overlay: DeltaOverlay, idm=None):
    """Fold the overlay into fresh CSR arrays with the SAME canonical edge
    layout a full reload produces — from the captured records alone: zero
    store reads, unlike refresh_csr's whole-row re-derivation. With `idm`
    the merged edges sort in store-key scan order (key rank of the source
    row, then (type, destination) — exactly the ordered scan's layout),
    so executor runs over the materialized arrays are BITWISE-identical
    to runs over a repacked CSR for every monoid; without it, source-
    index order (row-set equal, within-row order monoid-irrelevant).
    Supports unfiltered, weightless snapshots only (the same envelope as
    refresh_csr)."""
    from janusgraph_tpu.olap.csr import csr_from_edges

    if csr.in_edge_weight is not None or csr.properties:
        raise ValueError(
            "delta materialize supports unfiltered snapshots without "
            "materialized properties/weights"
        )
    vids = csr.vertex_ids
    removed = overlay.removed
    extra = np.setdiff1d(
        np.fromiter(
            overlay.new_vertices.keys(), dtype=np.int64,
            count=len(overlay.new_vertices),
        ),
        vids,
    ) if overlay.new_vertices else np.empty(0, np.int64)
    keep_v = (
        ~np.isin(vids, np.fromiter(removed, dtype=np.int64))
        if removed else np.ones(len(vids), dtype=bool)
    )
    vertex_ids = np.unique(np.concatenate([vids[keep_v], extra]))
    n = len(vertex_ids)

    src_vid = np.repeat(vids, np.diff(csr.out_indptr)).astype(np.int64)
    dst_vid = vids[csr.out_dst].astype(np.int64)
    et = (
        csr.out_edge_type.astype(np.int64)
        if csr.out_edge_type is not None
        else np.zeros(len(src_vid), dtype=np.int64)
    )
    if len(overlay.tomb):
        # multiset subtraction: drop the first `tomb count` instances of
        # each (src, dst, type) token (same trick as spillover's
        # patched_csr — parallel edges are count-equivalent)
        m = len(src_vid)
        trip = np.stack([src_vid, dst_vid, et], axis=1)
        _, inv = np.unique(
            np.concatenate([trip, overlay.tomb]), axis=0,
            return_inverse=True,
        )
        etok, dtok = inv[:m], inv[m:]
        del_counts = np.bincount(dtok, minlength=int(inv.max()) + 1)
        order = np.argsort(etok, kind="stable")
        st = etok[order]
        first = np.searchsorted(st, st, side="left")
        rank = np.arange(m) - first
        keep = np.ones(m, dtype=bool)
        keep[order[rank < del_counts[st]]] = False
        src_vid, dst_vid, et = src_vid[keep], dst_vid[keep], et[keep]
    if len(overlay.add):
        src_vid = np.concatenate([src_vid, overlay.add[:, 0]])
        dst_vid = np.concatenate([dst_vid, overlay.add[:, 1]])
        et = np.concatenate([et, overlay.add[:, 2]])

    si = np.searchsorted(vertex_ids, src_vid)
    di = np.searchsorted(vertex_ids, dst_vid)
    valid = (
        (si < n) & (di < n)
        & (vertex_ids[np.minimum(si, n - 1)] == src_vid)
        & (vertex_ids[np.minimum(di, n - 1)] == dst_vid)
    )
    si = si[valid].astype(np.int32)
    di = di[valid].astype(np.int32)
    et = et[valid]
    # canonical layout parity with a fresh full load: the scan visits
    # rows in store-key order, and BOTH derived CSRs inherit the input's
    # global edge order through the stable sorts in native.build_csr
    src_key = _key_rank(idm, vertex_ids)[si] if idm is not None else si
    order = np.lexsort((di, et, src_key))
    si, di, et = si[order], di[order], et[order]

    labels = None
    if csr.labels is not None or overlay.new_vertices:
        labels = np.zeros(n, dtype=np.int64)
        if csr.labels is not None:
            pos = np.searchsorted(vertex_ids, vids)
            ok = (pos < n) & (
                vertex_ids[np.minimum(pos, n - 1)] == vids
            )
            labels[pos[ok]] = csr.labels[ok]
        for vid, lid in overlay.new_vertices.items():
            i = int(np.searchsorted(vertex_ids, vid))
            if i < n and vertex_ids[i] == vid:
                labels[i] = lid

    has_et = csr.out_edge_type is not None or len(overlay.add)
    out = csr_from_edges(
        n, si, di,
        edge_types=et.astype(np.int32) if has_et else None,
    )
    out.vertex_ids = vertex_ids
    out.labels = labels
    out._refreshable = getattr(csr, "_refreshable", True)
    return out


# ---------------------------------------------------------------------------
# Overlay view (index space): the fused-superstep consumable
# ---------------------------------------------------------------------------

class OverlayView:
    """The overlay translated into the base snapshot's index space, with
    pow2-tiered lane capacities — the static-shape pytree a compiled
    superstep consumes fused with the base pack.

    Domain layout (base indices stay stable so device packs are reused):
      [0, n_base)              base snapshot rows
      [n_base, n_base+n_extra) new vertices, in sorted-vid order
      [n_real, n_pad)          padding up to the vcap tier (inactive)
    """

    def __init__(self, csr, overlay: DeltaOverlay, max_lane_cells: int = 1 << 16):
        self.csr = csr
        self.overlay = overlay
        vids = csr.vertex_ids
        nb = len(vids)
        self.n_base = nb
        extra = np.setdiff1d(
            np.fromiter(
                overlay.new_vertices.keys(), dtype=np.int64,
                count=len(overlay.new_vertices),
            ),
            vids,
        ) if overlay.new_vertices else np.empty(0, np.int64)
        self.extra_ids = extra
        self.n_extra = len(extra)
        self.n_real = nb + self.n_extra
        self.vcap = overlay_tier(self.n_extra)
        self.n_pad = nb + self.vcap
        self.vertex_ids = np.concatenate([vids, extra])

        def _idx(v):
            """vid array -> fused index (or -1 when unknown)."""
            v = np.asarray(v, dtype=np.int64)
            i = np.searchsorted(vids, v)
            base_ok = (i < nb) & (vids[np.minimum(i, nb - 1)] == v)
            out = np.where(base_ok, i, -1)
            if self.n_extra:
                j = np.searchsorted(extra, v)
                ex_ok = (j < self.n_extra) & (
                    extra[np.minimum(j, self.n_extra - 1)] == v
                )
                out = np.where(ex_ok & ~base_ok, nb + j, out)
            return out.astype(np.int64)

        a = overlay.add
        asrc = _idx(a[:, 0]) if len(a) else np.empty(0, np.int64)
        adst = _idx(a[:, 1]) if len(a) else np.empty(0, np.int64)
        ok = (asrc >= 0) & (adst >= 0)
        self.add_src = asrc[ok]
        self.add_dst = adst[ok]
        self.add_et = a[ok, 2] if len(a) else np.empty(0, np.int64)
        t = overlay.tomb
        tsrc = _idx(t[:, 0]) if len(t) else np.empty(0, np.int64)
        tdst = _idx(t[:, 1]) if len(t) else np.empty(0, np.int64)
        tok = (tsrc >= 0) & (tdst >= 0) & (tsrc < nb) & (tdst < nb)
        self.tomb_src = tsrc[tok]
        self.tomb_dst = tdst[tok]
        # removed base rows -> inactive slots
        rm = (
            _idx(np.fromiter(
                overlay.removed, dtype=np.int64, count=len(overlay.removed)
            ))
            if overlay.removed else np.empty(0, np.int64)
        )
        self.removed_idx = rm[(rm >= 0) & (rm < nb)]
        self.max_lane_cells = int(max_lane_cells)
        #: capture anchor: max epoch folded into this view (set by the
        #: snapshot holder that built it)
        self.upto_epoch: Optional[int] = None
        self._lanes: Dict[bool, Optional[dict]] = {}
        self._device: Dict[Tuple, dict] = {}
        self._fused_degrees = None

    # -- degrees / activity (shared by both executors' fused views) ---------
    def fused_degrees(self):
        """(out_degree, in_degree, active) over [0, n_pad): base degrees
        patched by the lanes, extras from the add lane, padding zero.
        Integer-valued — bitwise-equal to the repacked CSR's degrees."""
        if self._fused_degrees is not None:
            return self._fused_degrees
        csr = self.csr
        nb, npad = self.n_base, self.n_pad
        outd = np.zeros(npad, dtype=np.int64)
        ind = np.zeros(npad, dtype=np.int64)
        outd[:nb] = np.diff(csr.out_indptr)
        ind[:nb] = np.diff(csr.in_indptr)
        np.subtract.at(outd, self.tomb_src, 1)
        np.subtract.at(ind, self.tomb_dst, 1)
        np.add.at(outd, self.add_src, 1)
        np.add.at(ind, self.add_dst, 1)
        active = np.zeros(npad, dtype=np.float64)
        active[: self.n_real] = 1.0
        if len(self.removed_idx):
            active[self.removed_idx] = 0.0
        self._fused_degrees = (
            np.maximum(outd, 0).astype(np.int32),
            np.maximum(ind, 0).astype(np.int32),
            active,
        )
        return self._fused_degrees

    @property
    def num_edges_real(self) -> int:
        return self.csr.num_edges - len(self.tomb_src) + len(self.add_src)

    @property
    def num_vertices_real(self) -> int:
        return self.n_real - len(self.removed_idx)

    @property
    def depth(self) -> int:
        return self.overlay.size

    # -- lanes --------------------------------------------------------------
    def lanes(self, undirected: bool) -> Optional[dict]:
        """Padded COO lanes for one aggregation orientation (the default
        in-CSR view, or the symmetric closure when `undirected`). None
        when the lanes would exceed max_lane_cells (a tombstoned hub row
        makes the live lane O(degree)) — the caller materializes
        instead."""
        if undirected in self._lanes:
            return self._lanes[undirected]
        lanes = self._build_lanes(undirected)
        self._lanes[undirected] = lanes
        return lanes

    def _build_lanes(self, undirected: bool) -> Optional[dict]:
        csr = self.csr
        npad = self.n_pad
        # aggregation-side (dst) adds; symmetric closure doubles the lanes
        a_src = self.add_src
        a_dst = self.add_dst
        t_src = self.tomb_src
        t_dst = self.tomb_dst
        if undirected:
            a_src = np.concatenate([a_src, self.add_dst])
            a_dst = np.concatenate([a_dst, self.add_src])
            t_src = np.concatenate([t_src, self.tomb_dst])
            t_dst = np.concatenate([t_dst, self.tomb_src])

        # MIN-family dirty rows: every destination with a tombstoned
        # incoming edge re-aggregates its surviving base edges via the
        # live lane (adds ride the add lane; min(x, x) = x makes the
        # double-merge of adds into a dirty row exact)
        dirty_rows = np.unique(t_dst)
        live_src_parts: List[np.ndarray] = []
        live_dst_parts: List[np.ndarray] = []
        in_indptr, in_src = csr.in_indptr, csr.in_src
        out_indptr, out_dst = csr.out_indptr, csr.out_dst

        def _survivors(srcs, rm):
            """Base neighbors minus the tombstoned multiset (one removal
            per tombstone instance — parallel edges with the same source
            are count-equivalent for aggregation)."""
            if not len(rm):
                return np.asarray(srcs, dtype=np.int64)
            srcs = np.sort(np.asarray(srcs, dtype=np.int64))
            keep = np.ones(len(srcs), dtype=bool)
            vals, cnts = np.unique(np.asarray(rm, dtype=np.int64),
                                   return_counts=True)
            for v, c in zip(vals, cnts):
                lo = int(np.searchsorted(srcs, v, side="left"))
                hi = int(np.searchsorted(srcs, v, side="right"))
                keep[lo: min(hi, lo + int(c))] = False
            return srcs[keep]

        # group tombstones by their aggregation row once
        if len(dirty_rows):
            order = np.argsort(t_dst, kind="stable")
            td_sorted = t_dst[order]
            ts_sorted = t_src[order]
            bounds = np.searchsorted(td_sorted, dirty_rows, side="left")
            bounds_hi = np.searchsorted(td_sorted, dirty_rows, side="right")
            for r, lo, hi in zip(dirty_rows, bounds, bounds_hi):
                r = int(r)
                rm = ts_sorted[lo:hi]
                neigh = in_src[in_indptr[r]: in_indptr[r + 1]].astype(
                    np.int64
                ) if r < self.n_base else np.empty(0, np.int64)
                if undirected and r < self.n_base:
                    # symmetric closure: out-neighbors of the row too —
                    # tombstones in t_* already carry both orientations,
                    # but the rm list here mixes them; subtract the
                    # multiset against the COMBINED neighbor list
                    neigh = np.concatenate([
                        neigh,
                        out_dst[
                            out_indptr[r]: out_indptr[r + 1]
                        ].astype(np.int64),
                    ])
                surv = _survivors(neigh, rm)
                live_src_parts.append(surv)
                live_dst_parts.append(
                    np.full(len(surv), r, dtype=np.int64)
                )
        live_src = (
            np.concatenate(live_src_parts)
            if live_src_parts else np.empty(0, np.int64)
        )
        live_dst = (
            np.concatenate(live_dst_parts)
            if live_dst_parts else np.empty(0, np.int64)
        )

        acap = overlay_tier(len(a_src))
        tcap = overlay_tier(len(t_src))
        lcap = overlay_tier(len(live_src))
        if acap + tcap + lcap > self.max_lane_cells:
            return None

        def _pad(arr, cap):
            out = np.full(cap, npad, dtype=np.int32)  # sentinel = n_pad
            out[: len(arr)] = arr
            return out

        dirty = np.zeros(npad, dtype=np.float32)
        if len(dirty_rows):
            dirty[dirty_rows] = 1.0
        return {
            "add_src": _pad(a_src, acap),
            "add_dst": _pad(a_dst, acap),
            "tomb_src": _pad(t_src, tcap),
            "tomb_dst": _pad(t_dst, tcap),
            "live_src": _pad(live_src, lcap),
            "live_dst": _pad(live_dst, lcap),
            "dirty": dirty,
            # static metadata (not shipped as traced leaves)
            "_meta": {
                "n_base": self.n_base,
                "n_pad": npad,
                "acap": acap,
                "tcap": tcap,
                "lcap": lcap,
            },
        }

    def sig(self, undirected: bool) -> Optional[Tuple]:
        """Static compile signature of the fused variant — part of every
        compiled-executable cache key."""
        lanes = self.lanes(undirected)
        if lanes is None:
            return None
        m = lanes["_meta"]
        return (
            m["n_base"], m["n_pad"], m["acap"], m["tcap"], m["lcap"],
            bool(undirected),
        )

    def device_args(self, jnp, undirected: bool):
        """The lane pytree as device arrays (cached) — shipped as jit
        ARGUMENTS like the base pack, never closed over."""
        key = ("dev", bool(undirected))
        cached = self._device.get(key)
        if cached is not None:
            return cached
        lanes = self.lanes(undirected)
        if lanes is None:
            return None
        dev = {
            k: jnp.asarray(v)
            for k, v in lanes.items() if not k.startswith("_")
        }
        self._device[key] = dev
        return dev


# graphlint: traced -- the fused delta merge of compiled superstep bodies
def fused_delta_aggregate(xp, lanes, meta, outgoing, base_agg, op):
    """Merge the delta lanes into a base aggregation — the fused
    base+delta superstep (module docstring: SUM subtracts tombstones,
    MIN/MAX replaces dirty rows from the live lane). xp-generic: the CPU
    executor replays the identical arithmetic in numpy, which is also the
    SUM contract's replay oracle."""
    from janusgraph_tpu.olap.kernels import _segment_combine

    Combiner.require_foldable(op, "the fused delta overlay")
    identity = Combiner.IDENTITY[op]
    nb, npad = meta["n_base"], meta["n_pad"]
    tail = npad - base_agg.shape[0]
    if tail:
        pad = xp.full(
            (tail,) + tuple(base_agg.shape[1:]), identity,
            dtype=base_agg.dtype,
        )
        base = xp.concatenate([base_agg, pad], axis=0)
    else:
        base = base_agg
    # sentinel slot: padded lane entries gather the identity and scatter
    # into the dropped row npad
    pad_shape = (1,) + tuple(outgoing.shape[1:])
    msgs_ext = xp.concatenate(
        [outgoing, xp.full(pad_shape, identity, dtype=outgoing.dtype)],
        axis=0,
    )
    add = _segment_combine(
        xp, op, msgs_ext[lanes["add_src"]], lanes["add_dst"], npad + 1
    )[:npad]
    if op == Combiner.SUM:
        sub = _segment_combine(
            xp, op, msgs_ext[lanes["tomb_src"]], lanes["tomb_dst"],
            npad + 1,
        )[:npad]
        return base + add - sub
    live = _segment_combine(
        xp, op, msgs_ext[lanes["live_src"]], lanes["live_dst"], npad + 1
    )[:npad]
    dirty = lanes["dirty"]
    if base.ndim == 2:
        dirty = dirty[:, None]
    merged = xp.where(dirty > 0, live, base)
    return Combiner.monoid(
        op, "the fused delta overlay", None, xp.minimum, xp.maximum
    )(merged, add)


def replay_fused_aggregate(lanes, meta, outgoing, base_agg, op):
    """Numpy replay oracle for the fused merge — np.add.at / ufunc.at is
    bitwise-identical to the XLA CPU scatter (the PR 9 contract), and
    fused_delta_aggregate with xp=numpy routes through the same
    _segment_combine ufunc path, so this IS the oracle arithmetic."""
    return fused_delta_aggregate(np, lanes, meta, outgoing, base_agg, op)


# ---------------------------------------------------------------------------
# Fused host view (program-facing graph facade over base + overlay)
# ---------------------------------------------------------------------------

class FusedHostView:
    """CSRGraph-shaped facade for a base snapshot + overlay: programs see
    the REAL vertex/edge counts and fused degree/active arrays sized to
    the padded domain, while the base index arrays stay untouched for the
    base aggregation (the executor slices messages to the base rows).
    Numpy arrays — the CPU executor consumes it directly, the TPU
    executor wraps fields to device."""

    def __init__(self, view: OverlayView):
        self._ov = view
        csr = view.csr
        outd, ind, active = view.fused_degrees()
        self.num_vertices = view.num_vertices_real
        self.local_num_vertices = view.n_pad
        self.global_offset = 0
        self.num_edges = view.num_edges_real
        self.out_degree = outd
        self.in_degree = ind
        self.active = active
        self.vertex_ids = view.vertex_ids
        # base index arrays (for the executors' base aggregation only)
        self.in_indptr = csr.in_indptr
        self.in_src = csr.in_src
        self.out_indptr = csr.out_indptr
        self.out_dst = csr.out_dst
        self.in_edge_weight = None
        self.out_edge_weight = None
        self.in_edge_type = csr.in_edge_type
        self.out_edge_type = csr.out_edge_type
        self.properties = {}
        self.labels = None

    def index_of(self, vid: int) -> int:
        v = self._ov.vertex_ids
        i = np.nonzero(v == vid)[0]
        if not len(i):
            raise KeyError(f"vertex id {vid} not in fused snapshot")
        return int(i[0])

    def id_of(self, index: int) -> int:
        return int(self._ov.vertex_ids[index])


# ---------------------------------------------------------------------------
# Sharded routing (host_shard_range coupling)
# ---------------------------------------------------------------------------

def route_overlay(view: OverlayView, num_shards: int) -> List[dict]:
    """Partition the overlay's index-space records by OWNER SHARD of the
    aggregation-side (destination) row — the same contiguous
    ``dst // Np`` coupling the sharded executor's layout and
    ``multihost.host_shard_range`` use, so a distributed refresh routes
    each record to the host that owns its rows without any O(E)
    redistribution."""
    Np = -(-max(view.n_pad, 1) // num_shards)
    out = []
    for s in range(num_shards):
        lo, hi = s * Np, (s + 1) * Np
        am = (view.add_dst >= lo) & (view.add_dst < hi)
        tm = (view.tomb_dst >= lo) & (view.tomb_dst < hi)
        out.append({
            "shard": s,
            "row_range": (lo, min(hi, view.n_pad)),
            "add_src": view.add_src[am],
            "add_dst": view.add_dst[am],
            "tomb_src": view.tomb_src[tm],
            "tomb_dst": view.tomb_dst[tm],
        })
    return out


def route_for_host(
    view: OverlayView,
    num_shards: int,
    process_id: Optional[int] = None,
    num_processes: Optional[int] = None,
) -> dict:
    """The concatenated routed records for THIS host's shard span
    (multihost.host_shard_range) — what a distributed snapshot refresh
    applies to its local blocks."""
    from janusgraph_tpu.parallel.multihost import host_shard_range

    lo_s, hi_s = host_shard_range(num_shards, process_id, num_processes)
    routed = route_overlay(view, num_shards)[lo_s:hi_s]
    return {
        "shards": (lo_s, hi_s),
        "add_src": np.concatenate(
            [r["add_src"] for r in routed]
        ) if routed else np.empty(0, np.int64),
        "add_dst": np.concatenate(
            [r["add_dst"] for r in routed]
        ) if routed else np.empty(0, np.int64),
        "tomb_src": np.concatenate(
            [r["tomb_src"] for r in routed]
        ) if routed else np.empty(0, np.int64),
        "tomb_dst": np.concatenate(
            [r["tomb_dst"] for r in routed]
        ) if routed else np.empty(0, np.int64),
    }


# ---------------------------------------------------------------------------
# Warm snapshot + compaction
# ---------------------------------------------------------------------------

class DeltaSnapshot:
    """Per-graph warm snapshot: base CSR + capture epoch + compaction
    policy. GraphComputer.submit() consults it to skip the store scan;
    the spillover planner shares the capture but keeps its own snapshot
    (its CSR carries no base-pack device residency)."""

    def __init__(self, graph):
        self.graph = graph
        cfg = graph.config
        self.max_overlay = int(cfg.get("computer.delta-max-overlay"))
        self.max_lane_cells = int(cfg.get("computer.delta-max-lane-cells"))
        self.compact_threshold = int(
            cfg.get("computer.delta-compact-threshold")
        )
        self.snapshot_path = cfg.get("computer.delta-snapshot-path") or None
        self._lock = threading.RLock()
        self.csr = None
        self.epoch = -1
        self._decision = None
        #: base-pack generation: bumped whenever `csr` is replaced (cold
        #: load, compaction, adopt, warm-up install) — the executor
        #: cache's invalidation edge
        self.generation = 0
        #: warm-submit executor cache (the PR 14 REMAINING): device-
        #: resident packs + compiled executables keyed by (executor kind,
        #: constructor signature), reused across submits over ONE base
        #: pack; cleared on every generation bump
        self._executors: Dict[Tuple, object] = {}

    # ------------------------------------------------------------- snapshot
    def acquire(self):
        """(csr, overlay_view | None, info): the current base snapshot
        plus the pending overlay. A cold cache (or a capture that cannot
        serve the cached epoch) pays one full scan; afterwards every
        acquire is O(delta)."""
        from janusgraph_tpu.observability import registry

        with self._lock:
            info = {"path": "cold"}
            if self.csr is not None:
                got = overlay_since(self.graph, self.epoch)
                if got is None:
                    registry.counter("olap.delta.capture_overflow").inc()
                    self.csr = None  # fall through to the full load
                else:
                    overlay, upto = got
                    registry.set_gauge(
                        "olap.delta.overlay_depth", float(overlay.size)
                    )
                    if overlay.size == 0:
                        info = {"path": "warm", "overlay": 0}
                        return self.csr, None, info
                    view = OverlayView(
                        self.csr, overlay,
                        max_lane_cells=self.max_lane_cells,
                    )
                    view.upto_epoch = upto
                    if overlay.size > self.max_overlay:
                        # too deep to consume fused: fold into the base
                        # (still zero store reads)
                        self._compact(view)
                        info = {
                            "path": "refresh",
                            "overlay": overlay.size,
                        }
                        return self.csr, None, info
                    info = {"path": "fused", "overlay": overlay.size}
                    return self.csr, view, info
            from janusgraph_tpu.olap.csr import load_csr_snapshot

            # graphlint: disable=JG403 -- single-repacker by design: acquire() holds _lock across the cold repack so concurrent submitters share ONE snapshot load instead of racing N repacks
            csr, epoch = load_csr_snapshot(self.graph)
            self._install(csr, epoch)
            registry.counter("olap.delta.packs").inc()
            registry.set_gauge("olap.delta.overlay_depth", 0.0)
            return self.csr, None, {"path": "cold"}

    def adopt(self, csr, epoch: int) -> None:
        """Install an externally materialized base (submit()'s
        materialize branch, or a fleet warm-up pack — server/fleet.py)
        so the next acquire resumes from it."""
        with self._lock:
            self._install(csr, epoch)

    def _install(self, csr, epoch: int) -> None:
        """Replace the base pack (lock held): generation bump invalidates
        every cached executor — their device packs cover the OLD base."""
        self.csr = csr
        self.epoch = epoch
        self.generation += 1
        # graphlint: disable=JG401 -- every caller (acquire, adopt) holds self._lock per this method's contract ("lock held"); the analyzer cannot see caller-held locks
        self._executors.clear()

    # ------------------------------------------------- warm executor cache
    def cached_executor(self, key: Tuple):
        """A previously stored executor for this base-pack generation, or
        None. Keys carry the executor kind + constructor signature; the
        overlay is NOT part of the key — callers swap it per submit via
        ``set_delta`` (compiled executables stay sig-keyed inside)."""
        from janusgraph_tpu.observability import registry

        with self._lock:
            ex = self._executors.get(key)
        if ex is not None:
            registry.counter("olap.executor.cache_hits").inc()
        return ex

    def store_executor(self, key: Tuple, ex, csr) -> None:
        """Cache one freshly built executor IF it was built over the
        CURRENT base pack (a concurrent compaction between acquire and
        build means the executor's device arrays are already stale —
        dropping it is the cheap correct answer)."""
        from janusgraph_tpu.observability import registry

        registry.counter("olap.executor.cache_misses").inc()
        with self._lock:
            if csr is self.csr:
                self._executors[key] = ex

    # ----------------------------------------------------------- compaction
    def _threshold(self) -> int:
        if self.compact_threshold:
            return self.compact_threshold
        if self._decision is None:
            from janusgraph_tpu.olap import autotune

            try:
                import jax

                kind = getattr(
                    jax.devices()[0], "device_kind", "cpu"
                )
            except Exception:  # noqa: BLE001 - jax may be unavailable
                kind = "cpu"
            self._decision = autotune.decide_delta(
                num_edges=self.csr.num_edges if self.csr is not None else 0,
                num_vertices=(
                    self.csr.num_vertices if self.csr is not None else 0
                ),
                device_kind=kind,
            )
        return self._decision.compact_threshold

    def maybe_compact(self) -> bool:
        """Fold the pending overlay into the base pack when it crosses
        the (autotuner-decided) threshold. Off the superstep path —
        submit() calls this AFTER the run returns."""
        with self._lock:
            if self.csr is None:
                return False
            got = overlay_since(self.graph, self.epoch)
            if got is None or got[0].size == 0:
                return False
            overlay, upto = got
            if overlay.size < self._threshold():
                return False
            view = OverlayView(
                self.csr, overlay, max_lane_cells=self.max_lane_cells
            )
            view.upto_epoch = upto
            self._compact(view)
            return True

    def _compact(self, view: OverlayView) -> None:
        """Materialize base+overlay into a fresh base pack (zero store
        reads), advance the epoch, persist with tmp+rename when
        configured. Call under the lock."""
        import time as _time

        from janusgraph_tpu.observability import flight_recorder, registry

        t0 = _time.perf_counter()
        depth = view.depth
        # anchor at the max epoch actually folded — records committed
        # mid-materialize stay pending instead of being lost
        self._install(
            materialize(
                self.csr, view.overlay,
                idm=getattr(self.graph, "idm", None),
            ),
            getattr(view, "upto_epoch", self.epoch),
        )
        wall_ms = (_time.perf_counter() - t0) * 1000.0
        registry.counter("olap.delta.compactions").inc()
        registry.set_gauge("olap.delta.overlay_depth", 0.0)
        flight_recorder.record(
            "delta_compact", depth=depth,
            edges=self.csr.num_edges, vertices=self.csr.num_vertices,
            wall_ms=round(wall_ms, 3), threshold=self._threshold(),
        )
        if self.snapshot_path:
            try:
                save_snapshot(self.snapshot_path, self.csr, self.epoch)
            except OSError:
                pass  # persistence is best-effort, the pack is in memory


def get_snapshot(graph) -> Optional[DeltaSnapshot]:
    """The graph's lazily created DeltaSnapshot (None when the delta
    machinery is disabled or the graph has no change capture)."""
    if getattr(graph, "change_capture", None) is None:
        return None
    snap = getattr(graph, "_delta_snapshot", None)
    if snap is None:
        snap = DeltaSnapshot(graph)
        graph._delta_snapshot = snap
    return snap


# ---------------------------------------------------------------------------
# Snapshot persistence (tmp+rename, same discipline as checkpoints)
# ---------------------------------------------------------------------------

def save_snapshot(path: str, csr, epoch: int) -> None:
    import os
    import tempfile

    arrays = {
        "vertex_ids": csr.vertex_ids,
        "out_indptr": csr.out_indptr,
        "out_dst": csr.out_dst,
        "in_indptr": csr.in_indptr,
        "in_src": csr.in_src,
        "out_degree": csr.out_degree,
        "epoch": np.asarray(epoch, dtype=np.int64),
    }
    if csr.labels is not None:
        arrays["labels"] = csr.labels
    if csr.out_edge_type is not None:
        arrays["out_edge_type"] = csr.out_edge_type
        arrays["in_edge_type"] = csr.in_edge_type
    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def load_snapshot(path: str):
    """(CSRGraph, epoch) or None. The epoch only binds to the writing
    process's backend instance — a reloaded snapshot in a fresh process
    is a warm PACK, not a warm epoch, so callers must re-anchor it."""
    import os

    from janusgraph_tpu.olap.csr import CSRGraph

    if not os.path.exists(path):
        return None
    try:
        z = np.load(path)
        csr = CSRGraph(
            vertex_ids=z["vertex_ids"],
            out_indptr=z["out_indptr"],
            out_dst=z["out_dst"],
            in_indptr=z["in_indptr"],
            in_src=z["in_src"],
            out_degree=z["out_degree"],
            labels=z["labels"] if "labels" in z else None,
            in_edge_type=(
                z["in_edge_type"] if "in_edge_type" in z else None
            ),
            out_edge_type=(
                z["out_edge_type"] if "out_edge_type" in z else None
            ),
        )
        return csr, int(z["epoch"])
    except Exception:  # noqa: BLE001 - torn/garbage file = cold start
        return None


class ResultView:
    """Minimal CSRGraph-shaped mapping for fused-run results: surviving
    vertex ids aligned row-for-row with the compacted state arrays
    (value()/by_vertex()/write_back read exactly these fields)."""

    def __init__(self, vertex_ids: np.ndarray):
        self.vertex_ids = np.asarray(vertex_ids, dtype=np.int64)
        self._order = np.argsort(self.vertex_ids, kind="stable")
        self._sorted = self.vertex_ids[self._order]

    @property
    def num_vertices(self) -> int:
        return len(self.vertex_ids)

    @property
    def local_num_vertices(self) -> int:
        return len(self.vertex_ids)

    def index_of(self, vid: int) -> int:
        i = int(np.searchsorted(self._sorted, vid))
        if i >= len(self._sorted) or self._sorted[i] != vid:
            raise KeyError(f"vertex id {vid} not in snapshot")
        return int(self._order[i])

    def id_of(self, index: int) -> int:
        return int(self.vertex_ids[index])


def compact_result(view: OverlayView, states: Dict[str, np.ndarray]):
    """(states filtered to surviving rows, ResultView): drops removed
    base slots from a fused run's output so results cover exactly the
    live vertex set (what a repacked run would have returned)."""
    _outd, _ind, active = view.fused_degrees()
    mask = active[: view.n_real] > 0
    filtered = {k: np.asarray(v)[mask] for k, v in states.items()}
    return filtered, ResultView(view.vertex_ids[mask])


def program_delta_compatible(program) -> bool:
    """Whether a vertex program can consume the overlay FUSED: default
    edge view only (typed channels aggregate over their own packs, which
    the lanes do not patch), no sddmm (row-dst vectors are base-layout),
    no MODE combiner (`fused_delta_aggregate` merges lane partials into
    the base aggregate, and a mode cannot be folded from partials: the
    overlay is materialized before such a program runs), no program that
    runs no superstep at all (`LCCProgram`: `fuses_delta_overlay`)."""
    from janusgraph_tpu.olap.vertex_program import Combiner, VertexProgram

    if getattr(program, "combiner", None) == Combiner.MODE:
        return False
    if not getattr(program, "fuses_delta_overlay", True):
        return False
    if getattr(program, "message_mode", None) == "sddmm":
        return False
    if getattr(program, "edge_channels", None):
        return False
    if type(program).channel_for is not VertexProgram.channel_for:
        return False
    return True
