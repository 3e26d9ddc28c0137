"""Bulk load: storage rows -> CSR adjacency blocks (the OLAP substrate).

This replaces the reference's rescan-per-superstep architecture
(reference: graphdb/olap/computer/FulgoraGraphComputer.java:210-230 re-runs a
full StandardScanner edge scan every BSP iteration, with messages pulled
through reversed slice queries — VertexProgramScanJob.java:114-135): we scan
ONCE, decode the adjacency into dense numpy CSR/CSC arrays, and run every
superstep over in-memory (then in-HBM) arrays. Ghost vertices (rows without
the vertex-existence cell) are skipped exactly like the reference's
VertexJobConverter.java:126 ghost check; partitioned (vertex-cut) vertices
are canonicalized during load, which subsumes the reference's
PartitionedVertexProgramExecutor merge pass.

Decoding is vectorized: fixed-width edge columns (the common case) decode via
one reshape + strided views (EdgeSerializer.bulk_decode_edges); only
sort-key-bearing columns fall back to per-entry parsing.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from janusgraph_tpu.core.codecs import EDGE_COL_FIXED, Direction
from janusgraph_tpu.storage.kcvs import SliceQuery


@dataclass
class CSRGraph:
    """Immutable columnar snapshot of the graph for OLAP.

    Vertices are densely indexed [0, n); `vertex_ids[i]` maps back to the
    64-bit graph id. Both edge orientations are kept:
      out CSR: out_indptr/out_dst  — messages pushed along out-edges
      in  CSR: in_indptr/in_src    — pull-based aggregation (the hot one)
    """

    vertex_ids: np.ndarray          # (n,) int64, sorted ascending
    out_indptr: np.ndarray          # (n+1,) int64
    out_dst: np.ndarray             # (m,) int32 vertex indices
    in_indptr: np.ndarray           # (n+1,) int64
    in_src: np.ndarray              # (m,) int32 vertex indices
    out_degree: np.ndarray          # (n,) int32
    in_edge_weight: Optional[np.ndarray] = None   # (m,) float32, aligned to in_src
    out_edge_weight: Optional[np.ndarray] = None  # (m,) float32, aligned to out_dst
    properties: Dict[str, np.ndarray] = field(default_factory=dict)
    labels: Optional[np.ndarray] = None  # (n,) int64 vertex-label schema ids
    # per-edge type (edge-label schema id) arrays — the substrate for typed
    # EdgeChannel views (reference: per-scope slice queries compiled at
    # VertexProgramScanJob.java:114-135 restrict each message round to one
    # edge label; here the restriction is an array mask over these)
    in_edge_type: Optional[np.ndarray] = None     # (m,) int32, aligned to in_src
    out_edge_type: Optional[np.ndarray] = None    # (m,) int32, aligned to out_dst

    @property
    def num_vertices(self) -> int:
        return len(self.vertex_ids)

    # uniform interface with sharded views: a single-chip CSRGraph is one
    # shard holding everything, with no padding
    @property
    def local_num_vertices(self) -> int:
        return len(self.vertex_ids)

    @property
    def global_offset(self) -> int:
        return 0

    @property
    def active(self):
        """1.0 for real vertices, 0.0 for SPMD padding slots. Programs whose
        global metrics would be polluted by padding mask with this."""
        return np.ones(len(self.vertex_ids))

    @property
    def num_edges(self) -> int:
        return len(self.out_dst)

    @property
    def in_degree(self) -> np.ndarray:
        """(n,) int32 in-degrees (derived from in_indptr, cached). The
        dense-feature tier's mean-aggregation normalizer; the device view
        exposes the same field as float32."""
        cached = getattr(self, "_in_degree_cache", None)
        if cached is None:
            cached = np.diff(self.in_indptr).astype(np.int32)
            object.__setattr__(self, "_in_degree_cache", cached)
        return cached

    def index_of(self, vid: int) -> int:
        i = int(np.searchsorted(self.vertex_ids, vid))
        if i >= len(self.vertex_ids) or self.vertex_ids[i] != vid:
            raise KeyError(f"vertex id {vid} not in snapshot")
        return i

    def id_of(self, index: int) -> int:
        return int(self.vertex_ids[index])


def load_csr(
    graph,
    edge_labels: Optional[Sequence[str]] = None,
    property_keys: Sequence[str] = (),
    weight_key: Optional[str] = None,
    partitions: Optional[Sequence[int]] = None,
    vertex_labels: Optional[Sequence[str]] = None,
) -> CSRGraph:
    """Scan the edgestore and build a CSRGraph.

    edge_labels: restrict to these labels (None = all user edges) — the
    reference's GraphFilter.edges equivalent.
    vertex_labels: restrict to vertices with these labels — the reference's
    GraphFilter.vertices equivalent (edges incident to excluded vertices are
    dropped with them).
    property_keys: vertex property columns to materialize as arrays.
    weight_key: edge property to materialize as edge weight (float).
    partitions: restrict the scan to these storage partitions (the unit that
    maps onto mesh shards).
    """
    idm = graph.idm

    label_ids: Optional[set] = None
    if edge_labels is not None:
        label_ids = set()
        for name in edge_labels:
            el = graph.schema_cache.get_by_name(name)
            if el is not None:
                label_ids.add(el.id)

    vlabel_ids: Optional[set] = None
    if vertex_labels is not None:
        vlabel_ids = set()
        for name in vertex_labels:
            vl = graph.schema_cache.get_by_name(name)
            if vl is not None:
                vlabel_ids.add(vl.id)

    prop_key_ids: Dict[int, str] = {}
    for name in property_keys:
        pk = graph.schema_cache.get_by_name(name)
        if pk is not None:
            prop_key_ids[pk.id] = name
    weight_key_id = None
    if weight_key is not None:
        pk = graph.schema_cache.get_by_name(weight_key)
        if pk is not None:
            weight_key_id = pk.id

    raw = _scan_raw(
        graph, label_ids, vlabel_ids, prop_key_ids, weight_key_id, partitions
    )
    return build_csr_from_raw(idm, [raw])


def _scan_raw(
    graph, label_ids, vlabel_ids, prop_key_ids, weight_key_id, partitions
):
    """Partition scan -> RAW vid-space arrays with NO endpoint validation:
    the unit of DISTRIBUTED loading. Each worker scans disjoint partitions;
    an edge's destination may live in another worker's partition set, so
    validation waits for the merge (build_csr_from_raw)."""
    es = graph.edge_serializer
    idm = graph.idm
    st = graph.system_types
    btx = graph.backend.begin_transaction()
    store_tx = btx.store_tx
    store = graph.backend.edgestore

    # ONE wide slice covering every cell category (sys-prop .. user-edge):
    # the whole row arrives with the scan, so there are no per-row get_slice
    # round trips at all (VERDICT r2: the previous loop issued 3-4 per
    # vertex; reference analogue: aligned multi-query row assembly,
    # StandardScannerExecutor.java:140-174, collapsed into one range here)
    import struct as _struct

    full_q = SliceQuery(bytes([0]), bytes([4]))
    exists_tid = st.EXISTS
    label_tid = st.VERTEX_LABEL_EDGE
    label_filter = (
        np.array(sorted(label_ids), dtype=np.int64)
        if label_ids is not None
        else None
    )
    # RelationTypeIndex cells duplicate edges under the index's type id —
    # invisible to untyped edge enumeration (they'd double-count otherwise)
    relidx_ids = getattr(graph, "relation_index_ids", frozenset())
    relidx_filter = (
        np.array(sorted(relidx_ids), dtype=np.int64)
        if (relidx_ids and label_ids is None)
        else None
    )

    src_ids: List[np.ndarray] = []
    dst_ids: List[np.ndarray] = []
    weights: List[np.ndarray] = []
    etypes: List[np.ndarray] = []
    vertex_id_list: List[int] = []
    vertex_labels: List[int] = []
    raw_props: Dict[str, Dict[int, object]] = {name: {} for name in prop_key_ids.values()}

    if partitions is None:
        ranges = [idm.partition_key_range(p) for p in range(idm.num_partitions)]
    else:
        ranges = [idm.partition_key_range(p) for p in partitions]

    from janusgraph_tpu.storage.kcvs import KeyRangeQuery

    canonicalize = idm.get_canonical_vertex_id

    ordered = graph.backend.manager.features.ordered_scan

    def _scan_rows():
        if ordered:
            # per-range retry + resume (same contract as StandardScanner):
            # a TemporaryBackendError mid-stream re-issues the range from
            # just past the last yielded key, so a killed scan worker (or
            # injected chaos) costs a reconnect, not the whole load
            from janusgraph_tpu.exceptions import TemporaryBackendError

            retries = 3
            cfg = getattr(graph, "config", None)
            if cfg is not None:
                retries = cfg.get("storage.scan-retries")
            for start, end in ranges:
                cursor = start
                attempt = 0
                while True:
                    try:
                        for key, entries in store.get_keys(
                            KeyRangeQuery(cursor, end, full_q), store_tx
                        ):
                            yield key, entries
                            cursor = key + b"\x00"
                        break
                    except TemporaryBackendError:
                        attempt += 1
                        if attempt > retries:
                            raise
                        from janusgraph_tpu.observability import registry

                        registry.counter("storage.scan.retries").inc()
        else:
            # unordered backends (sharded/CQL-analogue): one full scan,
            # key-range filtering client-side (reference: token-range
            # getKeys path used by VertexJobConverter on CQL)
            for key, entries in store.get_keys(full_q, store_tx):
                if any(s <= key < e for s, e in ranges):
                    yield key, entries

    # chunked bulk decode: fixed-width edge columns accumulate across rows
    # and decode in one numpy pass per chunk
    CHUNK = 1 << 16
    pend_cols: List[bytes] = []
    pend_vids: List[int] = []
    unpack_tid = _struct.Struct(">Q").unpack_from

    def _flush_edges():
        if not pend_cols:
            return
        tids, dirs, others, _rels = es.bulk_decode_edges(pend_cols)
        owner = np.array(pend_vids, dtype=np.int64)
        pend_cols.clear()
        pend_vids.clear()
        mask = dirs == int(Direction.OUT)
        if label_filter is not None:
            mask &= np.isin(tids, label_filter)
        elif relidx_filter is not None:
            mask &= ~np.isin(tids, relidx_filter)
        if not mask.any():
            return
        src_ids.append(owner[mask])
        dst_ids.append(others[mask])
        etypes.append(tids[mask].astype(np.int32))
        if weight_key_id is not None:
            weights.append(np.ones(int(mask.sum()), dtype=np.float32))

    for key, entries in _scan_rows():
            vid = idm.get_vertex_id(key)
            if not idm.is_user_vertex_id(vid):
                continue
            vid = canonicalize(vid)

            # single pass over the row's cells, classified by category byte
            exists = False
            label_id = 0
            row_edge_cols: List[bytes] = []
            slow_entries = []
            prop_entries = []
            for col, val in entries:
                cat = col[0]
                if cat == 3:  # user edge
                    if len(col) == EDGE_COL_FIXED and not val:
                        row_edge_cols.append(col)
                    else:
                        slow_entries.append((col, val))
                elif cat == 0:  # system property
                    if unpack_tid(col, 1)[0] == exists_tid:
                        exists = True
                elif cat == 2:  # system edge (vertex label)
                    if unpack_tid(col, 1)[0] == label_tid:
                        rc = es.parse_relation((col, val), st.type_info)
                        label_id = rc.other_vertex_id
                elif cat == 1 and prop_key_ids:  # user property
                    name = prop_key_ids.get(unpack_tid(col, 1)[0])
                    if name is not None:
                        prop_entries.append((name, col, val))

            # ghost check: only rows with the existence cell are real
            # vertices (reference: VertexJobConverter.java:126) — filtered
            # rows must not pay property decode either
            if not exists:
                continue
            if vlabel_ids is not None and label_id not in vlabel_ids:
                continue
            vertex_id_list.append(vid)
            vertex_labels.append(label_id)
            for name, col, val in prop_entries:
                rc = es.parse_relation((col, val), graph_codec_schema(graph))
                raw_props[name][vid] = rc.value

            if row_edge_cols:
                pend_cols.extend(row_edge_cols)
                pend_vids.extend([vid] * len(row_edge_cols))
                if len(pend_cols) >= CHUNK:
                    _flush_edges()
            for col, val in slow_entries:
                rc = es.parse_relation((col, val), graph_codec_schema(graph))
                if rc.direction != Direction.OUT or not rc.is_edge:
                    continue
                if label_ids is not None and rc.type_id not in label_ids:
                    continue
                if label_ids is None and rc.type_id in relidx_ids:
                    continue
                src_ids.append(np.array([vid], dtype=np.int64))
                dst_ids.append(np.array([rc.other_vertex_id], dtype=np.int64))
                etypes.append(np.array([rc.type_id], dtype=np.int32))
                if weight_key_id is not None:
                    w = 1.0
                    if rc.properties and weight_key_id in rc.properties:
                        w = float(rc.properties[weight_key_id])
                    weights.append(np.array([w], dtype=np.float32))

    _flush_edges()

    return {
        "vertex_id_list": vertex_id_list,
        "vertex_labels": vertex_labels,
        "src": np.concatenate(src_ids) if src_ids else np.empty(0, np.int64),
        "dst": np.concatenate(dst_ids) if dst_ids else np.empty(0, np.int64),
        "etype": np.concatenate(etypes) if etypes else None,
        "weights": np.concatenate(weights) if weights else None,
        "raw_props": raw_props,
    }


def build_csr_from_raw(idm, raws) -> CSRGraph:
    """Merge one or more _scan_raw outputs (e.g. from N loader processes
    over disjoint partition sets) into a validated CSRGraph."""
    vid_parts, vlabel_parts = [], []
    src_parts, dst_parts, et_parts, w_parts = [], [], [], []
    raw_props: Dict[str, Dict[int, object]] = {}
    any_et = any(r["etype"] is not None for r in raws)
    any_w = any(r["weights"] is not None for r in raws)
    for r in raws:
        vid_parts.append(np.asarray(r["vertex_id_list"], dtype=np.int64))
        vlabel_parts.append(np.asarray(r["vertex_labels"], dtype=np.int64))
        src_parts.append(r["src"])
        dst_parts.append(r["dst"])
        if any_et:
            et_parts.append(
                r["etype"] if r["etype"] is not None
                else np.zeros(len(r["src"]), dtype=np.int32)
            )
        if any_w:
            w_parts.append(
                r["weights"] if r["weights"] is not None
                else np.ones(len(r["src"]), dtype=np.float32)
            )
        for name, mapping in r["raw_props"].items():
            raw_props.setdefault(name, {}).update(mapping)
    src = np.concatenate(src_parts) if src_parts else np.empty(0, np.int64)
    dst = np.concatenate(dst_parts) if dst_parts else np.empty(0, np.int64)
    et = np.concatenate(et_parts) if any_et else None
    w = np.concatenate(w_parts) if any_w else None

    # vectorized vertex/label merge: one unique pass; return_index picks a
    # representative occurrence for each id's label (the loader targets
    # multi-million-vertex merges — no per-element Python)
    vids_all = (
        np.concatenate(vid_parts) if vid_parts else np.empty(0, np.int64)
    )
    vlabels_all = (
        np.concatenate(vlabel_parts) if vlabel_parts else np.empty(0, np.int64)
    )
    vertex_ids, first_idx = np.unique(vids_all, return_index=True)
    label_arr = vlabels_all[first_idx] if len(vlabels_all) else None
    n = len(vertex_ids)
    if len(src):
        # canonicalize partitioned-vertex endpoints on the dst side too
        if idm.partition_bits > 0 and _any_partitioned(idm, dst):
            dst = canonicalize_ids(idm, dst)
        # drop edges to vertices outside the snapshot (ghost endpoints)
        src_idx = np.searchsorted(vertex_ids, src)
        dst_idx = np.searchsorted(vertex_ids, dst)
        valid = (
            (src_idx < n)
            & (dst_idx < n)
            & (vertex_ids[np.minimum(src_idx, n - 1)] == src)
            & (vertex_ids[np.minimum(dst_idx, n - 1)] == dst)
        )
        src_idx = src_idx[valid].astype(np.int32)
        dst_idx = dst_idx[valid].astype(np.int32)
        if w is not None:
            w = w[valid]
        if et is not None:
            et = et[valid]
    else:
        src_idx = np.empty(0, dtype=np.int32)
        dst_idx = np.empty(0, dtype=np.int32)
        w = None
        et = None

    # build out-CSR (sorted by src) and in-CSR (sorted by dst)
    from janusgraph_tpu import native

    out_indptr, out_dst, out_order, in_indptr, in_src, in_order = (
        native.build_csr(n, src_idx, dst_idx)
    )
    out_degree = np.diff(out_indptr).astype(np.int32)

    props: Dict[str, np.ndarray] = {}
    for name, mapping in raw_props.items():
        vals = [mapping.get(int(v)) for v in vertex_ids]
        if all(isinstance(x, (int, float)) or x is None for x in vals):
            props[name] = np.array(
                [float(x) if x is not None else np.nan for x in vals],
                dtype=np.float64,
            )
        else:
            props[name] = np.array(vals, dtype=object)

    return CSRGraph(
        vertex_ids=vertex_ids,
        out_indptr=out_indptr,
        out_dst=out_dst,
        in_indptr=in_indptr,
        in_src=in_src,
        out_degree=out_degree,
        in_edge_weight=w[in_order] if w is not None else None,
        out_edge_weight=w[out_order] if w is not None else None,
        properties=props,
        labels=label_arr,
        in_edge_type=et[in_order] if et is not None else None,
        out_edge_type=et[out_order] if et is not None else None,
    )


def _any_partitioned(idm, ids: np.ndarray) -> bool:
    # partitioned suffix is 0b010 in the low 3 bits
    return bool(np.any((ids & 0b111) == 0b010))


def canonicalize_ids(idm, ids: np.ndarray) -> np.ndarray:
    """Vectorized IDManager.get_canonical_vertex_id over an int64 array:
    partition-copies of vertex-cut vertices map to the canonical
    representative (partition = count % num_partitions); everything else
    passes through unchanged."""
    ids = np.asarray(ids, dtype=np.int64)
    # 0b010 suffix identifies partitioned user vertices (schema ids end 0b111)
    part_mask = (ids & 0b111) == 0b010
    if not np.any(part_mask):
        return ids
    pb = idm.partition_bits
    count = ids >> (3 + pb)
    canonical = (((count << pb) | (count % (1 << pb))) << 3) | 0b010
    return np.where(part_mask, canonical, ids)


def graph_codec_schema(graph):
    def lookup(type_id: int):
        info = graph.system_types.type_info(type_id)
        if info is not None:
            return info
        el = graph.schema_cache.get_by_id(type_id)
        if el is None:
            raise KeyError(type_id)
        return el.type_info()

    return lookup


def csr_from_edges(
    n: int,
    src: np.ndarray,
    dst: np.ndarray,
    weights: Optional[np.ndarray] = None,
    edge_types: Optional[np.ndarray] = None,
) -> CSRGraph:
    """Build a CSRGraph directly from an edge list with dense [0,n) ids —
    the synthetic-graph path for benchmarks (graph500 RMAT etc.).

    edge_types: optional (m,) per-edge label ids, carried into the CSR's
    in_edge_type/out_edge_type arrays for EdgeChannel views."""
    from janusgraph_tpu import native

    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    out_indptr, out_dst, out_order, in_indptr, in_src, in_order = (
        native.build_csr(n, src, dst)
    )
    et = (
        np.asarray(edge_types, dtype=np.int32)
        if edge_types is not None
        else None
    )
    return CSRGraph(
        vertex_ids=np.arange(n, dtype=np.int64),
        out_indptr=out_indptr,
        out_dst=out_dst,
        in_indptr=in_indptr,
        in_src=in_src,
        out_degree=np.diff(out_indptr).astype(np.int32),
        in_edge_weight=weights[in_order].astype(np.float32) if weights is not None else None,
        out_edge_weight=weights[out_order].astype(np.float32) if weights is not None else None,
        in_edge_type=et[in_order] if et is not None else None,
        out_edge_type=et[out_order] if et is not None else None,
    )


def load_csr_snapshot(graph, **kwargs) -> Tuple[CSRGraph, int]:
    """load_csr plus the backend mutation epoch observed BEFORE the scan —
    the handle incremental refresh resumes from."""
    epoch = graph.backend.mutation_epoch()
    csr = load_csr(graph, **kwargs)
    # refresh_csr re-derives touched rows WITHOUT filters/materialization;
    # record whether this snapshot is eligible so a filtered one fails
    # loudly instead of refreshing into an inconsistent graph
    csr._refreshable = not any(
        kwargs.get(k)
        for k in (
            "edge_labels", "vertex_labels", "property_keys",
            "weight_key", "partitions",
        )
    )
    return csr, epoch


def refresh_csr(graph, csr: CSRGraph, since_epoch: int) -> Tuple[CSRGraph, int]:
    """Incrementally fold OLTP mutations into a CSR snapshot WITHOUT
    rescanning the store (SURVEY.md §7 hard part (e): "incremental load —
    mapping OLTP mutations into CSR deltas"; the reference has no analogue —
    Fulgora rescans everything every superstep).

    Only rows the backend's mutation-epoch tracker marked since the snapshot
    are re-read; their OUT-edges are re-derived and merged with the retained
    edges of untouched rows (an edge's identity lives in its source row's
    OUT cell, and any edge mutation touches both endpoint rows, so keeping
    edges whose source row is untouched is exact). Index arrays are rebuilt
    in one native pass — O(E) compute but zero store scan. Supports
    unfiltered snapshots (no edge_labels/vertex_labels/property
    materialization).
    """
    import struct as _struct

    if not getattr(csr, "_refreshable", True) or csr.properties or (
        csr.in_edge_weight is not None
    ):
        raise ValueError(
            "refresh_csr supports unfiltered snapshots without materialized "
            "properties/weights — reload with load_csr for filtered views"
        )
    es = graph.edge_serializer
    idm = graph.idm
    st = graph.system_types
    new_epoch = graph.backend.mutation_epoch()
    keys = graph.backend.touched_since(since_epoch)
    if keys is None:
        # tracker overflowed past the snapshot: epoch rebuild
        fresh, e2 = load_csr_snapshot(graph)
        return fresh, e2
    if not keys:
        return csr, new_epoch

    btx = graph.backend.begin_transaction()
    store_tx = btx.store_tx
    store = graph.backend.edgestore
    full_q = SliceQuery(bytes([0]), bytes([4]))
    unpack_tid = _struct.Struct(">Q").unpack_from
    relidx_ids = getattr(graph, "relation_index_ids", frozenset())
    canonicalize = idm.get_canonical_vertex_id

    touched: set = set()
    alive: Dict[int, int] = {}          # vid -> label id
    new_src: List[int] = []
    new_dst: List[int] = []
    new_et: List[int] = []
    for key in keys:
        vid = idm.get_vertex_id(key)
        if not idm.is_user_vertex_id(vid):
            continue
        vid = canonicalize(vid)
        touched.add(vid)
        exists = False
        label_id = 0
        from janusgraph_tpu.storage.kcvs import KeySliceQuery as _KSQ

        for col, val in store.get_slice(_KSQ(key, full_q), store_tx):
            cat = col[0]
            if cat == 0:
                if unpack_tid(col, 1)[0] == st.EXISTS:
                    exists = True
            elif cat == 2:
                if unpack_tid(col, 1)[0] == st.VERTEX_LABEL_EDGE:
                    rc = es.parse_relation((col, val), st.type_info)
                    label_id = rc.other_vertex_id
            elif cat == 3:
                if len(col) == EDGE_COL_FIXED and not val:
                    # fixed-width fast parse
                    tid = int.from_bytes(col[1:9], "big")
                    if (
                        col[9] == int(Direction.OUT)
                        and tid not in relidx_ids
                    ):
                        new_src.append(vid)
                        new_dst.append(int.from_bytes(col[11:19], "big"))
                        new_et.append(tid)
                else:
                    rc = es.parse_relation((col, val), graph_codec_schema(graph))
                    if (
                        rc.is_edge
                        and rc.direction == Direction.OUT
                        and rc.type_id not in relidx_ids
                    ):
                        new_src.append(vid)
                        new_dst.append(int(rc.other_vertex_id))
                        new_et.append(int(rc.type_id))
        if exists:
            alive[vid] = label_id

    # old edges in vid space; drop any whose SOURCE row was touched
    # (re-derived above) — destination-side deletions always touch the
    # source row too (both cells are written per mutation)
    old_src_vid = np.repeat(csr.vertex_ids, np.diff(csr.out_indptr))
    old_dst_vid = csr.vertex_ids[csr.out_dst]
    keep = ~np.isin(old_src_vid, np.fromiter(touched, dtype=np.int64))
    old_src_vid = old_src_vid[keep]
    old_dst_vid = old_dst_vid[keep]
    old_et = (
        csr.out_edge_type[keep] if csr.out_edge_type is not None else None
    )

    removed = {v for v in touched if v not in alive}
    vertex_ids = np.unique(np.concatenate([
        csr.vertex_ids[~np.isin(
            csr.vertex_ids, np.fromiter(removed, dtype=np.int64)
        )] if removed else csr.vertex_ids,
        np.fromiter(alive.keys(), dtype=np.int64, count=len(alive)),
    ]))

    src_vid = np.concatenate([old_src_vid, np.asarray(new_src, dtype=np.int64)])
    dst_vid = np.concatenate([old_dst_vid, np.asarray(new_dst, dtype=np.int64)])
    if idm.partition_bits > 0 and _any_partitioned(idm, dst_vid):
        dst_vid = canonicalize_ids(idm, dst_vid)
    et = None
    if old_et is not None or new_et:
        et = np.concatenate([
            old_et if old_et is not None
            else np.zeros(len(old_src_vid), dtype=np.int32),
            np.asarray(new_et, dtype=np.int32),
        ])

    n = len(vertex_ids)
    si = np.searchsorted(vertex_ids, src_vid)
    di = np.searchsorted(vertex_ids, dst_vid)
    valid = (
        (si < n) & (di < n)
        & (vertex_ids[np.minimum(si, n - 1)] == src_vid)
        & (vertex_ids[np.minimum(di, n - 1)] == dst_vid)
    )
    si = si[valid].astype(np.int32)
    di = di[valid].astype(np.int32)
    if et is not None:
        et = et[valid]
    # canonical layout parity with a fresh full load: within each source row
    # the store orders edge columns by (type, other-vid)
    order = np.lexsort(
        (di, et if et is not None else np.zeros(len(si), dtype=np.int32), si)
    )
    si, di = si[order], di[order]
    if et is not None:
        et = et[order]

    # labels: retained from old where known, overridden for touched rows
    labels = None
    if csr.labels is not None or alive:
        labels = np.zeros(n, dtype=np.int64)
        if csr.labels is not None:
            pos = np.searchsorted(vertex_ids, csr.vertex_ids)
            ok = (pos < n) & (vertex_ids[np.minimum(pos, n - 1)] == csr.vertex_ids)
            labels[pos[ok]] = csr.labels[ok]
        for vid, lid in alive.items():
            i = int(np.searchsorted(vertex_ids, vid))
            labels[i] = lid

    refreshed = csr_from_edges(n, si, di, edge_types=et)
    refreshed.vertex_ids = vertex_ids
    refreshed.labels = labels
    return refreshed, new_epoch


def channel_edges(
    csr: CSRGraph, channel
) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Flatten an EdgeChannel view into (src_idx, dst_idx, weight) arrays
    where messages flow src -> dst (aggregation happens at dst).

    direction "out": traversers move src->dst, so aggregation reads the
    in-CSR; "in" reverses the edges (aggregate at the source over its
    out-edges); "both" is the union. Label filtering requires the CSR to
    carry per-edge type arrays (load_csr / csr_from_edges edge_types).
    """
    parts_src: List[np.ndarray] = []
    parts_dst: List[np.ndarray] = []
    parts_w: List[np.ndarray] = []
    have_w = csr.in_edge_weight is not None or csr.out_edge_weight is not None

    def _select(src, dst, w, types):
        if channel.labels is not None:
            if types is None:
                raise ValueError(
                    "EdgeChannel with labels requires per-edge type arrays "
                    "(load the CSR with edge types)"
                )
            mask = np.isin(types, np.asarray(channel.labels, dtype=types.dtype))
            src, dst = src[mask], dst[mask]
            w = w[mask] if w is not None else None
        parts_src.append(src)
        parts_dst.append(dst)
        if have_w:
            parts_w.append(
                w if w is not None else np.ones(len(src), dtype=np.float32)
            )

    m = csr.num_edges
    if channel.direction in ("out", "both"):
        seg = np.repeat(
            np.arange(csr.num_vertices, dtype=np.int64), np.diff(csr.in_indptr)
        )
        _select(
            csr.in_src.astype(np.int64), seg, csr.in_edge_weight, csr.in_edge_type
        )
    if channel.direction in ("in", "both"):
        seg = np.repeat(
            np.arange(csr.num_vertices, dtype=np.int64), np.diff(csr.out_indptr)
        )
        _select(
            csr.out_dst.astype(np.int64), seg, csr.out_edge_weight, csr.out_edge_type
        )
    if channel.direction not in ("out", "in", "both"):
        raise ValueError(f"unknown channel direction {channel.direction!r}")
    src = np.concatenate(parts_src) if parts_src else np.empty(0, np.int64)
    dst = np.concatenate(parts_dst) if parts_dst else np.empty(0, np.int64)
    w = np.concatenate(parts_w) if have_w and parts_w else None
    return src, dst, w


def simple_closure(
    n: int, src: np.ndarray, dst: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """(lo, hi) of the simple undirected graph over an edge list: each
    unordered pair of distinct vertices joined by some edge in either
    direction, once, lo < hi, in rising (lo, hi) order, int64. The graph
    as GAP's builder and Graphalytics' files read it: parallel edges once,
    self loops dropped. The one builder of the intersection engine's
    tables and of the frontier engine's simple view
    (`TPUExecutor._simple_closure`)."""
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    key = np.unique((lo * n + hi)[lo != hi])
    return key // n, key % n
