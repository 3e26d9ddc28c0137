"""Multi-chip sharded BSP executor: shard_map over a device mesh.

This is the distributed-communication redesign mandated by SURVEY.md §2.4:
the reference has no NCCL/MPI — its "communication" is writing message cells
into the storage backend and re-scanning (KCVSLog for control plane). Here
the data plane is XLA collectives over ICI:

  - vertex state and in-edge CSR blocks are sharded over the mesh axis by
    contiguous vertex-index blocks (the analogue of the reference's
    partition-prefixed key ranges, IDManager.getKey:480);
  - each superstep exchanges ONLY boundary messages: at build time every
    (src-shard q → dst-shard s) pair gets a bucket of the distinct source
    vertices in q whose messages s actually needs (q's boundary set toward
    s); the superstep gathers those values and swaps buckets with ONE
    `lax.all_to_all` over ICI — per-shard comm volume is S·B elements
    (B = max boundary-bucket size) instead of the full O(n) vertex vector an
    all_gather would move. This replaces Fulgora's pull-based reversed slice
    rescans (VertexProgramScanJob.java:114-135) the way FulgoraVertexMemory
    holds only the messages each worker consumes (FulgoraVertexMemory.java:91-99);
  - local aggregation uses a degree-bucketed ELL layout (gather + dense
    axis-1 reduction, no scatter — see olap/kernels.py) whose bucket shapes
    are made uniform across shards so one SPMD program serves the mesh;
  - global aggregators reduce with psum/pmin/pmax at the superstep barrier —
    replacing FulgoraMemory's in-process sub-round barrier;
  - vertex-cut merging is subsumed at CSR-load canonicalization.

Shards are equal-sized (SPMD): vertices pad to S*Np, per-shard edge lists pad
to the max shard edge count with masked no-op entries. Programs see the same
interface as single-chip (`active` marks real vertices).

Runs identically on a real multi-chip mesh and on the CPU-device test mesh
(xla_force_host_platform_device_count) — the "multi-node without a cluster"
test technique.
"""

from __future__ import annotations

import inspect
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from janusgraph_tpu.olap.csr import CSRGraph
from janusgraph_tpu.olap.vertex_program import (
    Combiner,
    Memory,
    VertexProgram,
    apply_edge_transform,
)

_ELL_MAX_CAPACITY = 1 << 14

#: modeled per-shard skew (slowest/mean) above which the run leaves a
#: ``shard_skew`` event on the flight-recorder timeline even without an
#: injected straggler — a 2x-imbalanced mesh wastes half its silicon
SKEW_FLIGHT_THRESHOLD = 2.0


class ShardedCSR:
    """Host-side sharded/padded representation, ready for device placement.

    Arrays with leading dim S*Np (vertex-sharded) or S*Em (edge-sharded):
      out_degree   (S*Np,) float32
      active       (S*Np,) float32
      in_src_glob  (S*Em,) int32  — global (padded) source vertex index
      in_dst_loc   (S*Em,) int32  — destination index local to its shard
      in_valid     (S*Em,) float32
      in_weight    (S*Em,) float32 (all ones if unweighted)

    Boundary-exchange plan (the all-to-all schedule):
      boundary_width B — max distinct cross-shard sources any (q→s) pair needs
      send_idx     (S*S, B) int32 — row q*S+s: indices LOCAL TO q of the
                   sources q must send to s (padded with 0; padded slots are
                   transmitted but never referenced by any receiver)
      in_src_tab   (S*Em,) int32 — per-edge index into the superstep message
                   table [own outgoing (Np) ++ received buckets (S*B)]

    Uniform ELL pack (SPMD-identical bucket shapes across shards):
      ell_buckets  list of (idx (S*N_c, c) int32, w (S*N_c, c) f32,
                   valid (S*N_c, c) f32); idx indexes the message table,
                   sentinel = Np + S*B
      ell_unpermute (S*Np,) int32 — position of each local vertex in the
                   concatenated bucket output (local length sum_c N_c)
    """

    def __init__(
        self,
        csr: CSRGraph,
        num_shards: int,
        undirected: bool,
        edges: Optional[Tuple] = None,
    ):
        n = csr.num_vertices
        S = num_shards
        Np = -(-max(n, 1) // S)  # ceil
        self.csr = csr
        self.num_shards = S
        self.shard_size = Np
        self.padded_n = S * Np
        self.real_n = n

        if edges is not None:
            # pre-filtered edge view (EdgeChannel): messages flow src -> dst
            src, dst, w = edges
            src = np.asarray(src, dtype=np.int64)
            dst = np.asarray(dst, dtype=np.int64)
            self.has_weight = w is not None
            w = (
                np.asarray(w, dtype=np.float32)
                if w is not None
                else np.ones(len(src), dtype=np.float32)
            )
        else:
            src = csr.in_src.astype(np.int64)
            dst = np.repeat(
                np.arange(n, dtype=np.int64), np.diff(csr.in_indptr)
            )
            self.has_weight = csr.in_edge_weight is not None
            w = (
                csr.in_edge_weight.astype(np.float32)
                if csr.in_edge_weight is not None
                else np.ones(len(src), dtype=np.float32)
            )
            if undirected:
                # symmetric closure: aggregate both orientations in one pass
                src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
                w = np.concatenate([w, w])

        # sorting by dst groups edges by owning shard (shard = dst // Np is
        # monotone in dst) AND keeps each shard's edges dst-sorted, which the
        # ELL fill below requires
        order = np.argsort(dst, kind="stable")
        src, dst, w = src[order], dst[order], w[order]
        shard_of = dst // Np
        counts = np.bincount(shard_of, minlength=S)
        Em = int(counts.max()) if len(counts) else 0
        Em = max(Em, 1)
        self.edges_per_shard = Em
        offsets = np.zeros(S + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])

        in_src_glob = np.zeros(S * Em, dtype=np.int32)
        in_dst_loc = np.zeros(S * Em, dtype=np.int32)
        in_valid = np.zeros(S * Em, dtype=np.float32)
        in_weight = np.ones(S * Em, dtype=np.float32)
        for s in range(S):
            lo, hi = offsets[s], offsets[s + 1]
            k = hi - lo
            base = s * Em
            in_src_glob[base : base + k] = src[lo:hi]
            in_dst_loc[base : base + k] = dst[lo:hi] - s * Np
            in_valid[base : base + k] = 1.0
            in_weight[base : base + k] = w[lo:hi]

        out_degree = np.zeros(S * Np, dtype=np.float32)
        out_degree[:n] = csr.out_degree
        active = np.zeros(S * Np, dtype=np.float32)
        active[:n] = 1.0
        # padded per-vertex in-degree of THIS edge view (dense programs
        # normalize by it — GCNForwardProgram's mean aggregation)
        in_degree = np.zeros(S * Np, dtype=np.float32)
        for s in range(S):
            k = int(offsets[s + 1] - offsets[s])
            np.add.at(
                in_degree, s * Np + in_dst_loc[s * Em : s * Em + k], 1.0
            )

        self.out_degree = out_degree
        self.active = active
        self.in_degree = in_degree
        self.in_src_glob = in_src_glob
        self.in_dst_loc = in_dst_loc
        self.in_valid = in_valid
        self.in_weight = in_weight

        # retained for the lazily-built exchange plan / ELL pack — each
        # executor configuration pays only for the structures it ships
        self._src_sorted = src
        self._offsets = offsets
        self._exchange_built = False
        self._ell_built = False

    def ensure_exchange_plan(self) -> None:
        """Build the boundary all-to-all plan (send_idx / in_src_tab) once,
        on first use — the gather/segment debug path never pays for it."""
        if self._exchange_built:
            return
        self._exchange_built = True
        S, Np, Em = self.num_shards, self.shard_size, self.edges_per_shard
        src, offsets = self._src_sorted, self._offsets

        # distinct sources per (q → s) pair
        uniq: Dict[Tuple[int, int], np.ndarray] = {}
        inv_parts: List[Tuple[int, np.ndarray, int, np.ndarray]] = []
        B = 1
        for s in range(S):
            lo, hi = offsets[s], offsets[s + 1]
            ssrc = src[lo:hi]
            qof = ssrc // Np
            for q in range(S):
                if q == s:
                    continue
                m = np.nonzero(qof == q)[0]
                if len(m) == 0:
                    continue
                u, inv = np.unique(ssrc[m], return_inverse=True)
                uniq[(q, s)] = u
                inv_parts.append((s, m, q, inv))
                B = max(B, len(u))
        self.boundary_width = B

        send_idx = np.zeros((S * S, B), dtype=np.int32)
        for (q, s), u in uniq.items():
            send_idx[q * S + s, : len(u)] = u - q * Np
        self.send_idx = send_idx

        in_src_tab = np.zeros(S * Em, dtype=np.int32)
        for s in range(S):
            lo, hi = offsets[s], offsets[s + 1]
            k = hi - lo
            ssrc = src[lo:hi]
            local = (ssrc // Np) == s
            seg = in_src_tab[s * Em : s * Em + k]
            seg[local] = (ssrc[local] - s * Np).astype(np.int32)
        for s, m, q, inv in inv_parts:
            in_src_tab[s * Em + m] = (Np + q * B + inv).astype(np.int32)
        self.in_src_tab = in_src_tab
        self.msg_table_len = Np + S * B
        # per-superstep comm volume (elements/shard): a2a vs all_gather
        self.comm_a2a_elems = S * B
        self.comm_gather_elems = self.padded_n

    def ensure_ring(self) -> None:
        """Build the ring-exchange plan once: per shard, edge slots grouped
        by SOURCE OWNER into uniform blocks of Eo = max edges any (shard,
        owner) pair holds, so ring step t reduces exactly one owner's block
        (dynamic-slice by traced owner index) instead of masking the whole
        edge list every step. Arrays (leading dim S, per-shard layout
        owner-major):
          ring_src_loc (S*S*Eo,) int32 — source index LOCAL to the owner
          ring_dst_loc (S*S*Eo,) int32 — destination local to this shard
          ring_valid   (S*S*Eo,) f32
          ring_weight  (S*S*Eo,) f32
        """
        if getattr(self, "_ring_built", False):
            return
        self._ring_built = True
        S, Np, Em = self.num_shards, self.shard_size, self.edges_per_shard
        src, offsets = self._src_sorted, self._offsets

        counts = np.zeros((S, S), dtype=np.int64)
        per_shard = []
        for s in range(S):
            lo, hi = offsets[s], offsets[s + 1]
            ssrc = src[lo:hi]
            owner = (ssrc // Np).astype(np.int64)
            order = np.argsort(owner, kind="stable")
            per_shard.append((lo, order, owner[order]))
            counts[s] = np.bincount(owner, minlength=S)
        Eo = max(1, int(counts.max()))
        self.ring_block = Eo

        ring_src = np.zeros((S, S * Eo), dtype=np.int32)
        ring_dst = np.zeros((S, S * Eo), dtype=np.int32)
        ring_valid = np.zeros((S, S * Eo), dtype=np.float32)
        ring_weight = np.ones((S, S * Eo), dtype=np.float32)
        for s in range(S):
            lo, order, owner_sorted = per_shard[s]
            k = len(order)
            if not k:
                continue
            gsrc = src[lo + order]
            # position within each owner block
            block_start = np.concatenate(
                ([0], np.cumsum(np.bincount(owner_sorted, minlength=S)))
            )
            pos = np.arange(k) - block_start[owner_sorted]
            col = owner_sorted * Eo + pos
            ring_src[s, col] = (gsrc - owner_sorted * Np).astype(np.int32)
            ring_dst[s, col] = self.in_dst_loc[s * Em + order]
            ring_valid[s, col] = 1.0
            ring_weight[s, col] = self.in_weight[s * Em + order]
        self.ring_src_loc = ring_src.reshape(-1)
        self.ring_dst_loc = ring_dst.reshape(-1)
        self.ring_valid = ring_valid.reshape(-1)
        self.ring_weight = ring_weight.reshape(-1)

    def ensure_frontier_plan(self) -> None:
        """Build the frontier-compaction plan once: per shard, a CSC over
        MESSAGE-TABLE SLOTS (own Np ++ received S*B buckets) so a superstep
        can expand only the edges whose source slot is fresh, instead of
        gathering all Em local edges (the sharded analogue of
        olap/frontier.py's capped expansion; VERDICT r4 #2). Arrays
        (leading dim divisible by S, device-shardable):
          ftr_ip        (S*(T+2),) int32 — per-shard CSC indptr over table
                        slots, +1 sentinel row (slot T reads degree 0 — the
                        compaction fill target)
          ftr_dst       (S*Em,) int32 — local destination, CSC order
          ftr_w         (S*Em,) f32  — edge weight, CSC order
          ftr_deg       (S*T,) int32 — edges per table slot (planning)
          ftr_src_glob  (S*T,) int32 — global source vertex index per slot
                        (predecessor tracking); bucket pad slots alias the
                        peer's vertex 0 but carry degree 0, so they can
                        never contribute a message
        Only VALID edges enter the CSC (the dense path's in_valid pad slots
        are excluded) — a padded-edge slot must not resurrect under slot-0.
        """
        if getattr(self, "_frontier_built", False):
            return
        self.ensure_exchange_plan()
        self._frontier_built = True
        S, Np, Em = self.num_shards, self.shard_size, self.edges_per_shard
        B, T = self.boundary_width, self.msg_table_len
        offsets = self._offsets

        ftr_ip = np.zeros(S * (T + 2), dtype=np.int32)
        ftr_dst = np.zeros(S * Em, dtype=np.int32)
        ftr_w = np.ones(S * Em, dtype=np.float32)
        ftr_deg = np.zeros(S * T, dtype=np.int32)
        ftr_src_glob = np.zeros(S * T, dtype=np.int32)
        for s in range(S):
            k = int(offsets[s + 1] - offsets[s])
            base = s * Em
            tabidx = self.in_src_tab[base : base + k]
            order = np.argsort(tabidx, kind="stable")
            deg = np.bincount(tabidx[order], minlength=T)
            ip = np.zeros(T + 2, dtype=np.int64)
            np.cumsum(deg, out=ip[1 : T + 1])
            ip[T + 1] = ip[T]
            ftr_ip[s * (T + 2) : (s + 1) * (T + 2)] = ip
            ftr_dst[base : base + k] = self.in_dst_loc[base : base + k][order]
            ftr_w[base : base + k] = self.in_weight[base : base + k][order]
            ftr_deg[s * T : s * T + T] = deg
            glob = np.zeros(T, dtype=np.int64)
            glob[:Np] = s * Np + np.arange(Np)
            for q in range(S):
                if q == s:
                    continue
                glob[Np + q * B : Np + (q + 1) * B] = (
                    q * Np + self.send_idx[q * S + s]
                )
            ftr_src_glob[s * T : s * T + T] = glob
        self.ftr_ip = ftr_ip
        self.ftr_dst = ftr_dst
        self.ftr_w = ftr_w
        self.ftr_deg = ftr_deg
        self.ftr_src_glob = ftr_src_glob

    def ensure_blocked_plan(self) -> None:
        """Build the propagation-blocked (source-partitioned) halo plan
        once, on first use (parallel/halo.py): per-owner edge blocks whose
        superstep kernel bins remote-bound messages by destination shard,
        merges them locally, and exchanges pow2-tiered bins in ONE
        all_to_all — the a2a boundary table is never materialized."""
        if getattr(self, "_blocked_built", False):
            return
        self._blocked_built = True
        from janusgraph_tpu.parallel import halo

        src, dst, w = halo.edges_from_sharded(self)
        plan = halo.BlockedPlan.build(
            src, dst, w, self.num_shards, self.shard_size
        )
        self.blocked_plan = plan
        self.blk_src_loc = plan.blk_src_loc
        self._blocked_ell_built = False
        self.blk_seg = plan.blk_seg
        self.blk_bin_seg = plan.blk_bin_seg
        self.blk_valid = plan.blk_valid
        self.blk_weight = plan.blk_weight
        self.recv_dst = plan.recv_dst
        self.halo_cap = plan.halo_cap
        self.edges_per_owner = plan.edges_per_owner
        # per-superstep comm volume (elements/shard), blocked exchange
        self.comm_blocked_elems = self.num_shards * plan.halo_cap

    def ensure_frontier_plan_blocked(self) -> None:
        """Frontier CSC over the BLOCKED message table [own Np ++ received
        merged bins S*Hc]: local slots keep their intra-shard edges; each
        used (q→s, j) bin slot collapses that pair's remote edges into ONE
        edge to its destination (weight 0 — the sender already folded the
        edge weight into the merged MIN), so remote expansion work shrinks
        from per-edge to per-distinct-destination and each hop exchanges
        S*Hc merged elements instead of the S*B boundary table."""
        if getattr(self, "_frontier_blocked_built", False):
            return
        self.ensure_blocked_plan()
        self._frontier_blocked_built = True
        from janusgraph_tpu.parallel import halo

        plan = self.blocked_plan
        S, Np, Hc = self.num_shards, self.shard_size, self.halo_cap
        T = Np + S * Hc
        src, dst, w = halo.edges_from_sharded(self)
        owner = src // Np
        dshard = dst // Np

        slot_parts: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        E2 = 1
        for s in range(S):
            loc = np.nonzero((owner == s) & (dshard == s))[0]
            slots = [src[loc] - s * Np]
            dsts = [dst[loc] - s * Np]
            ws = [w[loc]]
            for q in range(S):
                u = plan.pair_lists.get((q, s))
                if u is None:
                    continue
                slots.append(Np + q * Hc + np.arange(len(u)))
                dsts.append(u - s * Np)
                ws.append(np.zeros(len(u), dtype=np.float32))
            sl = np.concatenate(slots).astype(np.int64)
            dl = np.concatenate(dsts).astype(np.int64)
            wl = np.concatenate(ws).astype(np.float32)
            order = np.argsort(sl, kind="stable")
            slot_parts.append((sl[order], dl[order], wl[order]))
            E2 = max(E2, len(sl))
        self.fblk_edges = E2
        ftr_ip = np.zeros(S * (T + 2), dtype=np.int32)
        ftr_dst = np.zeros(S * E2, dtype=np.int32)
        ftr_w = np.ones(S * E2, dtype=np.float32)
        ftr_deg = np.zeros(S * T, dtype=np.int32)
        for s in range(S):
            sl, dl, wl = slot_parts[s]
            k = len(sl)
            deg = np.bincount(sl, minlength=T)
            ip = np.zeros(T + 2, dtype=np.int64)
            np.cumsum(deg, out=ip[1 : T + 1])
            ip[T + 1] = ip[T]
            ftr_ip[s * (T + 2) : (s + 1) * (T + 2)] = ip
            ftr_dst[s * E2 : s * E2 + k] = dl
            ftr_w[s * E2 : s * E2 + k] = wl
            ftr_deg[s * T : s * T + T] = deg
        self.fblk_ip = ftr_ip
        self.fblk_dst = ftr_dst
        self.fblk_w = ftr_w
        self.fblk_deg = ftr_deg

    def ensure_blocked_ell(self) -> None:
        """Build the packed aggregation for the blocked exchange once:
        sender-side uniform ELL over [local destinations ++ outgoing
        bins] + the receiver's width-R combine rows (halo.build_ell) —
        gathers and adjacent-pair trees only, no scatter."""
        self.ensure_blocked_plan()
        if self._blocked_ell_built:
            return
        self._blocked_ell_built = True
        from janusgraph_tpu.parallel import halo

        halo.build_ell(self.blocked_plan, self.has_weight)
        plan = self.blocked_plan
        self.bell_buckets = plan.ell_buckets
        self.bell_meta = plan.ell_meta
        self.bell_unpermute = plan.ell_unpermute
        self.bell_recv_idx = plan.recv_idx
        self.bell_recv_width = plan.recv_width

    def ensure_ell(self) -> None:
        """Build the uniform ELL pack once, on first use (requires the
        exchange plan: ELL indices point into the a2a message table)."""
        if self._ell_built:
            return
        self.ensure_exchange_plan()
        self._ell_built = True
        self._build_uniform_ell(self._offsets, self.edges_per_shard)

    def _build_uniform_ell(self, offsets: np.ndarray, Em: int) -> None:
        """Per-shard degree-bucketed ELL with bucket shapes made UNIFORM
        across shards (pad each capacity's row count to the max over shards)
        so the pack can be passed through shard_map as plain sharded arrays
        (SPMD requires identical per-shard shapes)."""
        from janusgraph_tpu import native

        S, Np = self.num_shards, self.shard_size
        sentinel = self.msg_table_len

        deg = np.zeros((S, Np), dtype=np.int64)
        indptr = np.zeros((S, Np + 1), dtype=np.int64)
        for s in range(S):
            k = int(offsets[s + 1] - offsets[s])
            d = np.bincount(
                self.in_dst_loc[s * Em : s * Em + k].astype(np.int64),
                minlength=Np,
            )
            deg[s] = d
            np.cumsum(d, out=indptr[s, 1:])

        # capacity per vertex: next pow2 >= degree (min 1), clamped to the
        # max capacity — larger degrees row-split into ceil(d/cap) rows of
        # the top bucket, folded by a rows-sized segment reduce (supernodes:
        # SURVEY.md §5.7; avoids padding a jumbo bucket to the max degree)
        caps = np.maximum(
            1, 1 << np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64)
        )
        caps = np.minimum(caps, _ELL_MAX_CAPACITY)

        from janusgraph_tpu.olap.kernels import split_rows

        cap_set = sorted(set(int(c) for c in np.unique(caps)))
        self.ell_buckets: List[Tuple] = []
        # static per-bucket metadata: None (rows == slots) or the slot count
        # (+1 dead slot for padded rows) of a row-split bucket
        self.ell_meta: List[Optional[int]] = []
        unpermute = np.zeros(S * Np, dtype=np.int32)
        out_off = 0
        for c in cap_set:
            members_per_shard = [
                np.nonzero(caps[s] == c)[0] for s in range(S)
            ]
            split = c == _ELL_MAX_CAPACITY and any(
                len(m) and int(deg[s][m].max()) > c
                for s, m in enumerate(members_per_shard)
            )
            shard_rows = []
            for s in range(S):
                m = members_per_shard[s]
                if split:
                    shard_rows.append(
                        split_rows(m, deg[s][m], indptr[s][m], c)
                    )
                else:
                    shard_rows.append(
                        (indptr[s][m], deg[s][m],
                         np.arange(len(m), dtype=np.int64))
                    )
            N_rows = max(len(r[0]) for r in shard_rows)
            N_slots = max(len(m) for m in members_per_shard)
            if N_rows == 0:
                continue
            idx = np.full((S * N_rows, c), sentinel, dtype=np.int32)
            # unweighted: idx only — padded slots point at the message
            # table's identity pad slot (mirrors olap/kernels.py ELLPack)
            if self.has_weight:
                wmat = np.zeros((S * N_rows, c), dtype=np.float32)
                valid = np.zeros((S * N_rows, c), dtype=np.float32)
            else:
                wmat = valid = None
            # padded rows point at the dead slot (N_slots) and are dropped
            rowseg = np.full(S * N_rows, N_slots, dtype=np.int32)
            for s in range(S):
                members = members_per_shard[s]
                starts_r, degs_r, rseg = shard_rows[s]
                rows = len(starts_r)
                if rows == 0:
                    continue
                src32 = np.ascontiguousarray(
                    self.in_src_tab[s * Em : (s + 1) * Em], dtype=np.int32
                )
                w32 = np.ascontiguousarray(
                    self.in_weight[s * Em : (s + 1) * Em], dtype=np.float32
                )
                bidx = idx[s * N_rows : s * N_rows + rows]
                bw = (
                    wmat[s * N_rows : s * N_rows + rows]
                    if wmat is not None else None
                )
                bv = (
                    valid[s * N_rows : s * N_rows + rows]
                    if valid is not None else None
                )
                if not native.ell_fill(c, starts_r, degs_r, src32, w32, bidx, bw, bv):
                    total = int(degs_r.sum())
                    if total:
                        row_ids = np.repeat(np.arange(rows), degs_r)
                        col_ids = np.arange(total) - np.repeat(
                            np.cumsum(degs_r) - degs_r, degs_r
                        )
                        edge_pos = np.repeat(starts_r, degs_r) + col_ids
                        bidx[row_ids, col_ids] = src32[edge_pos]
                        if bv is not None:
                            bv[row_ids, col_ids] = 1.0
                        if bw is not None:
                            bw[row_ids, col_ids] = w32[edge_pos]
                rowseg[s * N_rows : s * N_rows + rows] = rseg.astype(np.int32)
                unpermute[s * Np + members] = (
                    out_off + np.arange(len(members))
                ).astype(np.int32)
            if split:
                self.ell_buckets.append((idx, wmat, valid, rowseg))
                self.ell_meta.append(N_slots)
                out_off += N_slots
            else:
                self.ell_buckets.append((idx, wmat, valid))
                self.ell_meta.append(None)
                out_off += N_rows
        self.ell_unpermute = unpermute
        self.ell_out_len = out_off


class _GlobalView:
    """Padded global view handed to program.setup (host side)."""

    def __init__(self, sharded: ShardedCSR):
        self.num_vertices = sharded.real_n
        self.local_num_vertices = sharded.padded_n
        self.global_offset = 0
        self.out_degree = sharded.out_degree
        self.active = sharded.active
        self.in_degree = sharded.in_degree


class _ShardView:
    """Per-shard view inside shard_map (traced)."""

    def __init__(
        self, num_vertices, shard_size, offset, out_degree, active,
        in_degree=None,
    ):
        self.num_vertices = num_vertices          # real global count (static)
        self.local_num_vertices = shard_size      # padded local (static)
        self.global_offset = offset               # traced scalar
        self.out_degree = out_degree
        self.active = active
        self.in_degree = in_degree


class ShardedExecutor:
    """BSP executor over a jax.sharding.Mesh (1-D axis 'p').

    exchange: "blocked" — propagation-blocked halo exchange (the default
              fast path, PAPERS.md arXiv:2011.08451): remote-bound
              messages are binned by destination shard inside the
              superstep kernel, combiner-merged locally, and the pow2-
              tiered merged bins swap in ONE lax.all_to_all — comm volume
              S*halo_cap elements (distinct remote DESTINATIONS), no
              message-table concatenation, receiver work one S*halo_cap
              scatter-combine;
              "a2a" — eager boundary-bucket lax.all_to_all (ships raw
              boundary SOURCE values, S*B elements, receiver aggregates
              its remote edges);
              "ring" — S-step lax.ppermute rotation: each step one shard's
              outgoing block streams past and its contribution is folded in
              (the ring-attention pattern applied to message aggregation —
              peak comm memory O(Np) per step instead of the S*B bucket
              table; the right shape when boundary sets approach O(n));
              "gather" — full-vector all_gather (debug/reference path);
              "auto" — olap/autotune.decide_sharded picks from the graph's
              boundary/halo widths + the device roofline, keyed by shard
              count (decision recorded in run_info["autotune"]).
    agg:      "ell" (default; a2a only) — uniform degree-bucketed ELL;
              "segment" — flat segment reduction (ring/gather use this);
              "bin" — the blocked exchange's fused bin+local segment
              reduction (implied by exchange='blocked').
    """

    def __init__(
        self,
        csr: CSRGraph,
        mesh=None,
        axis: str = "p",
        exchange: str = "a2a",
        agg: str = "ell",
        frontier_tier_growth: int = None,
        shard_measure: bool = None,
    ):
        import jax
        from jax.sharding import Mesh

        self.jax = jax
        self.axis = axis
        if mesh is None:
            devices = np.array(jax.devices())
            mesh = Mesh(devices, (axis,))
        self.mesh = mesh
        self.num_shards = mesh.devices.size
        self.csr = csr
        if exchange not in ("a2a", "ring", "gather", "blocked", "auto"):
            raise ValueError(f"unknown exchange {exchange!r}")
        if exchange in ("gather", "ring") and agg == "ell":
            # the ELL pack indexes the a2a message table, which the other
            # exchanges never build — refuse rather than silently rewiring
            raise ValueError(
                "agg='ell' requires exchange='a2a' (the ELL indices point "
                "into the all-to-all message table); use agg='segment' with "
                f"exchange={exchange!r}"
            )
        if exchange == "blocked" and agg not in ("ell", "segment"):
            raise ValueError(
                "exchange='blocked' aggregates via 'ell' (packed gather + "
                f"tree) or 'segment' (fused scatter); got agg={agg!r}"
            )
        #: "auto" defers to olap/autotune.decide_sharded at first run
        self.exchange_requested = exchange
        self.exchange = exchange
        self.agg = agg
        #: measured per-shard superstep walls (host probe) feeding the
        #: skew report; None/True = on, False = plan-derived costs only
        self.shard_measure = True if shard_measure is None else shard_measure
        #: autotune decision record for the most recent auto resolution
        self._autotune_record = None
        #: fresh compiles this run (the registry's retrace/compile-cache
        #: economics; counted at every compiled-fn cache miss)
        self._new_execs = 0
        #: bytes device_put this run (h2d_arg_bytes in the run record)
        self._h2d_bytes = 0
        from collections import OrderedDict

        self._compiled: Dict[Tuple, object] = {}
        self._sharded_cache: Dict[object, ShardedCSR] = {}
        self._channel_views: "OrderedDict" = OrderedDict()
        self._device_cache: Dict[Tuple[object, str], object] = {}
        # (cache_key, op) -> {metric_key: combiner_op}; recorded when the
        # shard body is traced (see `_metric_ops` in olap/tpu_executor.py)
        self._metric_ops: Dict[Tuple, Dict[str, str]] = {}
        self._frontier_engine = None
        # computer.frontier-tier-growth (ShardedFrontierEngine override)
        self._frontier_tier_growth = frontier_tier_growth
        #: observability for the most recent run (path + frontier tiers)
        self.last_run_info: Dict[str, object] = {}

    def comm_stats(self, undirected: bool = False) -> Dict[str, object]:
        """Per-superstep exchange volume in elements per shard. Each plan
        (a2a boundary table / blocked halo bins) is only materialized for
        executors configured to use it — ring exists precisely for the
        regime where the O(S*S*B) table is most expensive to build."""
        self._resolve_exchange(undirected)
        sc = self._sharded(undirected)
        stats: Dict[str, object] = {
            "gather_elems": sc.padded_n,
            # ring: S-1 hops x one Np block streamed per superstep (the own
            # block folds locally), peak resident comm buffer one Np block
            "ring_elems": (self.num_shards - 1) * sc.shard_size,
            "ring_peak_elems": sc.shard_size,
            "a2a_elems": None,
            "boundary_width": None,
            "blocked_elems": None,
            "halo_cap": None,
            #: collectives per superstep carrying message payload
            "batches": self.num_shards - 1 if self.exchange == "ring" else 1,
        }
        if self.exchange == "a2a":
            sc.ensure_exchange_plan()
            stats["a2a_elems"] = sc.comm_a2a_elems
            stats["boundary_width"] = sc.boundary_width
        if self.exchange == "blocked":
            sc.ensure_blocked_plan()
            stats["blocked_elems"] = sc.comm_blocked_elems
            stats["halo_cap"] = sc.halo_cap
        return stats

    def _exchange_info(self, sc: ShardedCSR) -> Dict[str, object]:
        """run_info["exchange"]: what the configured exchange actually
        ships per superstep and per shard — elements, f32 payload bytes,
        and the number of message-carrying collectives (batches)."""
        S = self.num_shards
        if self.exchange == "blocked":
            sc.ensure_blocked_plan()
            elems, width = sc.comm_blocked_elems, sc.halo_cap
        elif self.exchange == "a2a":
            sc.ensure_exchange_plan()
            elems, width = sc.comm_a2a_elems, sc.boundary_width
        elif self.exchange == "ring":
            elems, width = (S - 1) * sc.shard_size, sc.shard_size
        else:
            elems, width = sc.padded_n, sc.padded_n
        return {
            "mode": self.exchange,
            "agg": self.agg,
            "elems_per_superstep": int(elems),
            "bytes_per_superstep": int(elems) * 4,
            "batches_per_superstep": S - 1 if self.exchange == "ring" else 1,
            "width": int(width),
        }

    def _resolve_exchange(self, undirected: bool = False) -> None:
        """Resolve exchange='auto' into a concrete (exchange, agg) pair via
        the shard-count-keyed tuner (olap/autotune.decide_sharded). Pure in
        the graph + device kind, so the resolution is deterministic; the
        decision is recorded for run_info["autotune"]."""
        if self.exchange_requested != "auto" or self._autotune_record:
            return
        from janusgraph_tpu.olap import autotune
        from janusgraph_tpu.parallel import halo

        sc = self._sharded(undirected)
        src, dst, _w = halo.edges_from_sharded(sc)
        widths = halo.pair_widths(
            src, dst, self.num_shards, sc.shard_size
        )
        stats = autotune.GraphStats.from_csr(self.csr, undirected=undirected)
        decision = autotune.decide_sharded(
            stats, self._device_kind(), self.num_shards, widths,
            measured=getattr(self, "_measured_prior", None),
        )
        self.exchange = decision.exchange
        self.agg = decision.agg
        self._autotune_record = decision.as_dict()

    def _fetch(self, arr) -> np.ndarray:
        """Host copy of a mesh-sharded array. On a MULTI-PROCESS mesh each
        controller holds only its addressable shards (np.asarray raises on
        the rest), so gather across processes first — every host returns
        the identical global array (the SparkGraphComputer result-collect
        analogue)."""
        if self.jax.process_count() > 1:
            from jax.experimental import multihost_utils

            return np.asarray(
                multihost_utils.process_allgather(arr, tiled=True)
            )
        return np.asarray(arr)

    def _sharded(self, undirected: bool) -> ShardedCSR:
        sc = self._sharded_cache.get(undirected)
        if sc is None:
            sc = ShardedCSR(self.csr, self.num_shards, undirected)
            self._sharded_cache[undirected] = sc
        return sc

    #: distinct EdgeChannel views kept device-resident at once (LRU)
    CHANNEL_CACHE_SIZE = 8

    def _channel_view(self, program: VertexProgram, name: str):
        """(ShardedCSR, graph-args) for one named EdgeChannel, cached per
        channel VALUE — generic names (s0, s1, ...) recur across programs on
        a reused executor and must not alias each other's edge views.
        LRU-bounded: compiled sharded supersteps take the arrays as
        ARGUMENTS (not closures), so eviction actually frees them."""
        from janusgraph_tpu.olap.csr import channel_edges

        channel = program.edge_channels[name]
        hit = self._channel_views.get(channel)
        if hit is not None:
            self._channel_views.move_to_end(channel)
            return hit
        edges = channel_edges(self.csr, channel)
        sc = ShardedCSR(self.csr, self.num_shards, False, edges=edges)
        gargs = self._graph_args(sc, ("ch", channel), cache={})
        self._channel_views[channel] = (sc, gargs)
        while len(self._channel_views) > self.CHANNEL_CACHE_SIZE:
            evicted, _ = self._channel_views.popitem(last=False)
            # compiled supersteps close over the evicted ShardedCSR (static
            # shapes/metadata), pinning its O(E) host arrays — prune them
            # (their key layout is ("step", cache_key, op, exchange, agg,
            # ch_val))
            self._compiled = {
                k: v for k, v in self._compiled.items()
                if not (len(k) >= 6 and k[5] == evicted)
            }
        return sc, gargs

    def _dev(self, sc: ShardedCSR, view_key, name: str, cache=None):
        """Device-put a ShardedCSR array once, sharded over the mesh axis —
        re-uploading the static CSR blocks each superstep would dominate.
        view_key identifies the edge view (undirected flag or channel);
        `cache` overrides the executor-lifetime device cache (channel views
        use a private dict so LRU eviction frees their arrays)."""
        store = self._device_cache if cache is None else cache
        key = (view_key, name)
        arr = store.get(key)
        if arr is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(self.mesh, P(self.axis))
            host = getattr(sc, name)
            if name in ("ell_buckets", "bell_buckets"):
                self._h2d_bytes += sum(
                    a.nbytes for b in host for a in b
                    if a is not None and hasattr(a, "nbytes")
                )
                arr = tuple(
                    tuple(
                        self.jax.device_put(a, sharding)
                        if a is not None else None
                        for a in bucket
                    )
                    for bucket in host
                )
            else:
                self._h2d_bytes += host.nbytes
                arr = self.jax.device_put(host, sharding)
            store[key] = arr
        return arr

    def _graph_args(self, sc: ShardedCSR, view_key, cache=None) -> Dict[str, object]:
        """The static per-shard graph arrays the configured body needs."""
        g = {
            "out_degree": self._dev(sc, view_key, "out_degree", cache),
            "active": self._dev(sc, view_key, "active", cache),
            "in_degree": self._dev(sc, view_key, "in_degree", cache),
        }
        if self.exchange == "blocked":
            sc.ensure_blocked_plan()
            if self.agg == "ell":
                sc.ensure_blocked_ell()
                g["bell_buckets"] = self._dev(
                    sc, view_key, "bell_buckets", cache
                )
                g["bell_unpermute"] = self._dev(
                    sc, view_key, "bell_unpermute", cache
                )
                g["bell_recv_idx"] = self._dev(
                    sc, view_key, "bell_recv_idx", cache
                )
                return g
            g["blk_src"] = self._dev(sc, view_key, "blk_src_loc", cache)
            g["blk_seg"] = self._dev(sc, view_key, "blk_seg", cache)
            g["blk_valid"] = self._dev(sc, view_key, "blk_valid", cache)
            if sc.has_weight:
                g["blk_w"] = self._dev(sc, view_key, "blk_weight", cache)
            g["recv_dst"] = self._dev(sc, view_key, "recv_dst", cache)
            return g
        if self.exchange == "a2a":
            sc.ensure_exchange_plan()
            g["send_idx"] = self._dev(sc, view_key, "send_idx", cache)
        if self.exchange == "ring":
            sc.ensure_ring()
            g["ring_src"] = self._dev(sc, view_key, "ring_src_loc", cache)
            g["ring_dst"] = self._dev(sc, view_key, "ring_dst_loc", cache)
            g["ring_valid"] = self._dev(sc, view_key, "ring_valid", cache)
            g["ring_weight"] = self._dev(sc, view_key, "ring_weight", cache)
            return g
        if self.agg == "ell":
            sc.ensure_ell()
            g["ell_buckets"] = self._dev(sc, view_key, "ell_buckets", cache)
            g["ell_unpermute"] = self._dev(sc, view_key, "ell_unpermute", cache)
        else:
            g["dst_loc"] = self._dev(sc, view_key, "in_dst_loc", cache)
            g["valid"] = self._dev(sc, view_key, "in_valid", cache)
            g["weight"] = self._dev(sc, view_key, "in_weight", cache)
            g["src_idx"] = (
                self._dev(sc, view_key, "in_src_tab", cache)
                if self.exchange == "a2a"
                else self._dev(sc, view_key, "in_src_glob", cache)
            )
        return g

    def _shard_body(self, program: VertexProgram, op: str, sc: ShardedCSR):
        """The per-shard superstep body (traced inside shard_map)."""
        import jax
        import jax.numpy as jnp

        axis = self.axis
        S = self.num_shards
        Np = sc.shard_size
        identity = Combiner.IDENTITY[op]
        exchange, agg = self.exchange, self.agg
        B = sc.boundary_width if exchange == "a2a" else 0
        Hc = sc.halo_cap if exchange == "blocked" else 0

        # every fold below merges partial aggregates (edge blocks, halo
        # bins, shards): a monoid's, picked once; MODE is refused here
        path = "the sharded executor"
        seg_fn = Combiner.monoid(
            op, path,
            jax.ops.segment_sum, jax.ops.segment_min, jax.ops.segment_max,
        )
        reduce_fn = Combiner.monoid(op, path, jnp.sum, jnp.min, jnp.max)
        merge = Combiner.monoid(op, path, jnp.add, jnp.minimum, jnp.maximum)

        def seg_reduce_n(data, seg, n):
            return seg_fn(data, seg, num_segments=n)

        def seg_reduce(data, seg):
            return seg_reduce_n(data, seg, Np)

        def reduce_cols(m, axis_):
            return reduce_fn(m, axis=axis_)

        if exchange == "ring":
            sc.ensure_ring()
            Eo = sc.ring_block
        else:
            Eo = 0

        def ring_aggregate(g, outgoing):
            """S-step ring: rotate outgoing blocks with ppermute; step t
            reduces exactly the pre-partitioned edge block of the owner now
            passing by (dynamic-slice into the owner-major ring plan), so
            total edge work per superstep is ~Em + padding, not S*Em. The
            ring-attention streaming pattern: peak comm buffer is ONE Np
            block, not the S*B bucket table."""
            my = jax.lax.axis_index(axis)
            tail_shape = tuple(outgoing.shape[1:])
            acc0 = jnp.full((Np,) + tail_shape, identity, outgoing.dtype)
            perm = [(i, (i + 1) % S) for i in range(S)]

            def fold_owner(acc, block, owner):
                start = owner * Eo
                src = jax.lax.dynamic_slice(g["ring_src"], (start,), (Eo,))
                dst = jax.lax.dynamic_slice(g["ring_dst"], (start,), (Eo,))
                valid = jax.lax.dynamic_slice(g["ring_valid"], (start,), (Eo,))
                weight = jax.lax.dynamic_slice(g["ring_weight"], (start,), (Eo,))
                msgs = apply_edge_transform(
                    # ones-materialized pad weights must NOT transform on a
                    # weightless view — None matches every other executor
                    jnp, block[src], weight if sc.has_weight else None,
                    program.edge_transform, program.edge_transform_cols,
                )
                mask = valid[:, None] if msgs.ndim == 2 else valid
                msgs = jnp.where(mask > 0, msgs, identity)
                return merge(acc, seg_reduce(msgs, dst))

            # own block folds before any hop, so only S-1 ppermutes fire —
            # the final rotation (returning blocks home) would be dead comm
            acc0 = fold_owner(acc0, outgoing, my)

            def fold(carry, step_i):
                acc, block = carry
                block = jax.lax.ppermute(block, axis, perm)
                acc = fold_owner(acc, block, (my - step_i) % S)
                return (acc, block), None

            (acc, _), _ = jax.lax.scan(
                fold, (acc0, outgoing), jnp.arange(1, S, dtype=jnp.int32)
            )
            return acc

        def body(state, step, memory_in, g):
            offset = jax.lax.axis_index(axis) * Np
            view = _ShardView(
                sc.real_n, Np, offset, g["out_degree"], g["active"],
                g.get("in_degree"),
            )
            outgoing = program.message(state, step, view, jnp)
            tail = tuple(outgoing.shape[1:])

            if exchange == "ring":
                agg_v = ring_aggregate(g, outgoing)
                return _apply_and_reduce(state, agg_v, step, memory_in, view)

            if exchange == "blocked":
                # propagation blocking: per-edge messages bin by destination
                # shard and combiner-merge LOCALLY (local destinations
                # [0, Np) + outgoing bins [Np, Np+S*Hc)); the pow2-tiered
                # merged bins swap in ONE all_to_all and the receiver only
                # combines S*Hc merged values — no message-table concat, no
                # per-remote-edge work on the receiver. agg='ell' runs the
                # fused merge as packed gather + adjacent-pair trees over
                # the shard's own Np-row block; agg='segment' as one fused
                # scatter reduction.
                from janusgraph_tpu.olap.kernels import (
                    flat_take,
                    fp_fence,
                    tree_reduce,
                )

                pad = jnp.full((1,) + tail, identity, dtype=outgoing.dtype)
                if agg == "ell":
                    out_ext = jnp.concatenate([outgoing, pad], axis=0)
                    parts = []
                    for bucket, n_slots in zip(
                        g["bell_buckets"], sc.bell_meta
                    ):
                        idx, wm, va = bucket[0], bucket[1], bucket[2]
                        m = flat_take(jnp, out_ext, idx)
                        if wm is not None:
                            m = apply_edge_transform(
                                jnp, m, wm,
                                program.edge_transform,
                                program.edge_transform_cols,
                            )
                            va_ = va.reshape(
                                va.shape + (1,) * (m.ndim - 2)
                            )
                            m = jnp.where(va_ > 0, m, identity)
                            m = fp_fence(jnp, m)
                        r = tree_reduce(jnp, m, op)
                        if n_slots is not None:
                            r = seg_reduce_n(
                                r, bucket[3], n_slots + 1
                            )[:n_slots]
                        parts.append(r)
                    stacked = jnp.concatenate(parts + [pad], axis=0)
                    btab = stacked[g["bell_unpermute"]]
                    local_part = btab[:Np]
                    bins = btab[Np:].reshape((S, Hc) + tail)
                    recv = jax.lax.all_to_all(
                        bins, axis, split_axis=0, concat_axis=0
                    )
                    rtab = jnp.concatenate(
                        [recv.reshape((S * Hc,) + tail), local_part, pad],
                        axis=0,
                    )
                    m = flat_take(jnp, rtab, g["bell_recv_idx"])
                    agg_v = tree_reduce(jnp, m, op)
                    return _apply_and_reduce(
                        state, agg_v, step, memory_in, view
                    )
                msgs = outgoing[g["blk_src"]]
                msgs = apply_edge_transform(
                    jnp, msgs, g["blk_w"] if sc.has_weight else None,
                    program.edge_transform, program.edge_transform_cols,
                )
                valid = g["blk_valid"]
                vmask = valid.reshape((-1,) + (1,) * (msgs.ndim - 1))
                msgs = jnp.where(vmask > 0, msgs, identity)
                # the weighted product would otherwise contract into the
                # scatter-add as an FMA, breaking bitwise identity with
                # the numpy replay oracle (halo.replay_superstep)
                msgs = fp_fence(jnp, msgs)
                seg_out = seg_reduce_n(msgs, g["blk_seg"], Np + S * Hc + 1)
                local_part = seg_out[:Np]
                bins = seg_out[Np : Np + S * Hc].reshape((S, Hc) + tail)
                recv = jax.lax.all_to_all(
                    bins, axis, split_axis=0, concat_axis=0
                )
                remote = seg_reduce_n(
                    recv.reshape((S * Hc,) + tail), g["recv_dst"], Np + 1
                )[:Np]
                agg_v = merge(local_part, remote)
                return _apply_and_reduce(state, agg_v, step, memory_in, view)

            # ---- exchange: build the message table this shard reads from
            if exchange == "a2a":
                # boundary buckets only: gather the values each peer needs,
                # swap buckets with one all_to_all over ICI
                sends = outgoing[g["send_idx"]]            # (S, B, ...)
                recv = jax.lax.all_to_all(
                    sends, axis, split_axis=0, concat_axis=0
                )
                tab = jnp.concatenate(
                    [outgoing, recv.reshape((S * B,) + tail)], axis=0
                )
            else:
                tab = jax.lax.all_gather(outgoing, axis, axis=0, tiled=True)

            # ---- local aggregation by destination
            if agg == "ell":
                pad = jnp.full((1,) + tail, identity, dtype=outgoing.dtype)
                tab_ext = jnp.concatenate([tab, pad], axis=0)
                parts = []
                from janusgraph_tpu.olap.kernels import flat_take

                for bucket, n_slots in zip(g["ell_buckets"], sc.ell_meta):
                    idx, wm, va = bucket[0], bucket[1], bucket[2]
                    m = flat_take(jnp, tab_ext, idx)       # (rows, c[, k])
                    if wm is not None:
                        # weighted pack: transform, then re-assert the
                        # identity on padded slots (see kernels.py)
                        va_ = va[:, :, None] if m.ndim == 3 else va
                        m = apply_edge_transform(
                            jnp, m, wm,
                            program.edge_transform,
                            program.edge_transform_cols,
                        )
                        m = jnp.where(va_ > 0, m, identity)
                    r = reduce_cols(m, 1)
                    if n_slots is not None:
                        # fold supernode row partials (rows-sized reduce);
                        # padded rows land in the dead slot and are dropped
                        r = seg_reduce_n(r, bucket[3], n_slots + 1)[:n_slots]
                    parts.append(r)
                stacked = jnp.concatenate(parts, axis=0)
                agg_v = stacked[g["ell_unpermute"]]
            else:
                msgs = tab[g["src_idx"]]
                weight, valid = g["weight"], g["valid"]
                msgs = apply_edge_transform(
                    jnp, msgs, weight if sc.has_weight else None,
                    program.edge_transform, program.edge_transform_cols,
                )
                vmask = valid[:, None] if msgs.ndim == 2 else valid
                msgs = jnp.where(vmask > 0, msgs, identity)
                agg_v = seg_reduce(msgs, g["dst_loc"])

            return _apply_and_reduce(state, agg_v, step, memory_in, view)

        def _apply_and_reduce(state, agg_v, step, memory_in, view):
            new_state, metrics = program.apply(
                state, agg_v, step, memory_in, view, jnp
            )
            self._metric_ops[(program.cache_key(), op)] = {
                k: mop for k, (mop, _v) in metrics.items()
            }
            # barrier: global aggregator reduction over the mesh
            reduced = {}
            for k, (mop, v) in metrics.items():
                reduced[k] = Combiner.monoid(
                    mop, "the mesh barrier of global aggregators",
                    jax.lax.psum, jax.lax.pmin, jax.lax.pmax,
                )(v, axis)
            return new_state, reduced

        return body

    def _specs(self):
        from jax.sharding import PartitionSpec as P

        return P(self.axis), P()

    def _place_state(self, host_array):
        """Initial vertex state goes straight to its shards, like the
        graph arrays (`_dev`): built on the default device it would sit
        whole on device 0 until the first dispatch resharded it."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return self.jax.device_put(
            np.asarray(host_array), NamedSharding(self.mesh, P(self.axis))
        )

    def _superstep_fn(
        self, program: VertexProgram, op: str, sc: ShardedCSR, channel: str = None
    ):
        ch_val = program.edge_channels[channel] if channel is not None else None
        key = ("step", program.cache_key(), op, self.exchange, self.agg, ch_val)
        if key in self._compiled:
            return self._compiled[key]
        self._new_execs += 1

        import jax

        body = self._shard_body(program, op, sc)
        sharded_spec, rep = self._specs()
        fn = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                sharded_spec,  # state (leading dim sharded)
                rep,           # step
                rep,           # memory_in
                sharded_spec,  # graph arrays pytree (prefix: shard dim 0)
            ),
            out_specs=(sharded_spec, rep),
            check_vma=False,
        )
        fn = jax.jit(fn)
        self._compiled[key] = fn
        return fn

    def _fused_fn(self, program: VertexProgram, op: str, sc: ShardedCSR):
        """A span of the BSP run as ONE dispatch: lax.while_loop inside
        shard_map, collectives (boundary all_to_all exchange + psum barrier)
        in the loop body, `terminate_device` on the replicated aggregators as
        the on-device stop condition. steps/limit flow as traced scalars so
        one executable serves the full run and checkpoint-bounded chunks. See
        `_fused_fn` in olap/tpu_executor.py."""
        key = ("fused", program.cache_key(), op, self.exchange, self.agg)
        if key in self._compiled:
            return self._compiled[key]
        self._new_execs += 1

        import jax
        import jax.numpy as jnp

        body = self._shard_body(program, op, sc)

        def run_span(state, mem, steps_done0, limit, g):
            def cond(carry):
                _s, m, steps_done = carry
                # terminate() is consulted AFTER each superstep, never
                # before the first (at steps_done == 0 the aggregators are
                # identity-seeded placeholders) — mirrors TPUExecutor
                return jnp.logical_and(
                    steps_done < limit,
                    jnp.logical_or(
                        steps_done == 0,
                        jnp.logical_not(
                            program.terminate_device(m, steps_done, jnp)
                        ),
                    ),
                )

            def loop(carry):
                s, m, steps_done = carry
                s2, m2 = body(s, steps_done, m, g)
                return (s2, m2, steps_done + 1)

            return jax.lax.while_loop(cond, loop, (state, mem, steps_done0))

        sharded_spec, rep = self._specs()
        fn = jax.shard_map(
            run_span,
            mesh=self.mesh,
            in_specs=(sharded_spec, rep, rep, rep, sharded_spec),
            out_specs=(sharded_spec, rep, rep),
            check_vma=False,
        )
        fn = jax.jit(fn)
        self._compiled[key] = fn
        return fn

    def _run_frontier(
        self, program: VertexProgram, route, fault_hook=None
    ) -> Dict[str, np.ndarray]:
        """The program on the mesh's frontier engine, at its route's entry
        (`olap/routes.py`)."""
        from janusgraph_tpu.parallel.sharded_frontier import (
            ShardedFrontierEngine,
        )

        if self._frontier_engine is None:
            self._frontier_engine = ShardedFrontierEngine(self)
        t0 = time.perf_counter()
        out = getattr(self._frontier_engine, route.entry)(
            program, fault_hook=fault_hook
        )
        trace = self._frontier_engine.last_trace
        self.last_run_info = {
            "path": "frontier",
            "supersteps": len(trace),
            "wall_s": round(time.perf_counter() - t0, 4),
            "tiers": trace,
        }
        return out

    # ------------------------------------------------- fault/checkpoint glue
    def _bind_hook(self, fault_hook):
        """Normalize a fault hook to hook(step) -> straggler events. Mesh-
        aware hooks (FaultPlan.sharded_hook) take (step, num_shards) and
        return straggler records; single-arg hooks (FaultPlan.olap_hook,
        test lambdas) are called as-is."""
        if fault_hook is None:
            return None
        try:
            params = [
                p for p in inspect.signature(fault_hook).parameters.values()
                if p.kind in (
                    inspect.Parameter.POSITIONAL_ONLY,
                    inspect.Parameter.POSITIONAL_OR_KEYWORD,
                    inspect.Parameter.VAR_POSITIONAL,
                )
            ]
            mesh_aware = len(params) >= 2 or any(
                p.kind is inspect.Parameter.VAR_POSITIONAL for p in params
            )
        except (TypeError, ValueError):
            mesh_aware = False
        S = self.num_shards
        if mesh_aware:
            return lambda step: fault_hook(step, S)
        return fault_hook

    def _consult(self, hook, step: int) -> None:
        """One superstep-boundary fault consultation; straggler skew
        records accumulate for the run report."""
        if hook is None:
            return
        events = hook(step)
        if events:
            self._straggler_events.extend(events)

    def _save_ck(
        self, checkpoint_path, shard_dir, state_host, mem_values, steps,
        records=None,
    ) -> None:
        ck0 = time.perf_counter()
        if shard_dir:
            from janusgraph_tpu.olap.sharded_checkpoint import (
                save_sharded_checkpoint,
            )

            save_sharded_checkpoint(
                shard_dir, state_host, mem_values, steps, self.num_shards
            )
        else:
            from janusgraph_tpu.olap.checkpoint import save_checkpoint

            save_checkpoint(checkpoint_path, state_host, mem_values, steps)
        self._ck_saves += 1
        if records:
            # timeline marker (observability/timeline.py): the save's
            # wall, stamped on the superstep that paid it
            records[-1]["checkpoint_ms"] = round(
                (time.perf_counter() - ck0) * 1000.0, 3
            )

    def _load_ck(self, checkpoint_path, shard_dir):
        if shard_dir:
            from janusgraph_tpu.olap.sharded_checkpoint import (
                load_sharded_checkpoint,
            )

            ck = load_sharded_checkpoint(shard_dir)
        elif checkpoint_path:
            from janusgraph_tpu.olap.checkpoint import load_checkpoint

            ck = load_checkpoint(checkpoint_path)
        else:
            ck = None
        if ck is not None and self._resume_t_catch is not None:
            # catch -> state restored: the recovery latency an operator
            # actually pays (the replay itself is forward progress)
            self._resume_ms += (
                time.perf_counter() - self._resume_t_catch
            ) * 1000.0
            self._resume_t_catch = None
        return ck

    def _device_kind(self) -> str:
        return self.mesh.devices.flat[0].device_kind

    # -------------------------------------------------- per-shard reporting
    #: skip the measured-wall probe past this many edges — the probe runs
    #: every shard's aggregation once on the host, which must stay a
    #: negligible fraction of the run it prices
    MEASURE_MAX_EDGES = 20_000_000

    def _measured_walls(self, sc: ShardedCSR) -> Optional[List[float]]:
        """MEASURED per-shard superstep walls (ms): the SPMD barrier hides
        per-shard time inside one dispatch, so run each shard's real
        aggregation workload shard-by-shard on the host and time it
        (min of 3 repeats). Cached per edge view — the probe prices the
        layout, which does not change between runs."""
        if not self.shard_measure or self.csr.num_edges > self.MEASURE_MAX_EDGES:
            return None
        cached = getattr(sc, "_measured_walls", None)
        if cached is not None:
            return cached
        if self.exchange == "blocked":
            from janusgraph_tpu.parallel import halo

            sc.ensure_blocked_plan()
            walls = halo.measure_shard_walls(sc.blocked_plan)
        else:
            # dst-partitioned probe: gather + scatter over each shard's
            # real in-edge slice (the eager paths' per-shard work shape)
            S, Np, Em = sc.num_shards, sc.shard_size, sc.edges_per_shard
            offsets = sc._offsets
            ramp = np.arange(sc.padded_n, dtype=np.float32) % 97 + 1.0
            walls = []
            for s in range(S):
                k = max(1, int(offsets[s + 1] - offsets[s]))
                src = sc.in_src_glob[s * Em : s * Em + k]
                dst = sc.in_dst_loc[s * Em : s * Em + k]
                w = sc.in_weight[s * Em : s * Em + k]
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    msgs = ramp[src] * w
                    acc = np.zeros(Np, dtype=np.float32)
                    np.add.at(acc, dst, msgs)
                    best = min(best, time.perf_counter() - t0)
                walls.append(best * 1000.0)
        sc._measured_walls = walls
        return walls

    def _shard_report(self, sc: ShardedCSR, records: List[dict]) -> None:
        """Per-shard ledger + roofline, straggler detection, and the skew
        gauge. One SPMD dispatch runs every shard in lockstep (the barrier
        hides individual shard walls), so per-shard time comes from the
        MEASURED host probe (_measured_walls — each shard's real
        aggregation workload timed shard-by-shard, cost_source="measured")
        when available, else from the shard plan's edge counts
        (cost_source="plan"); the superstep wall is attributed by relative
        per-shard cost, and injected straggler skew (the chaos plan's
        records) adds on top. Host code only; nothing here is traced."""
        from janusgraph_tpu.observability import (
            flight_recorder,
            profiler,
            registry,
            tracer,
        )

        S = sc.num_shards
        Np = sc.shard_size
        offsets = getattr(sc, "_offsets", None)
        edges = (
            [int(offsets[s + 1] - offsets[s]) for s in range(S)]
            if offsets is not None else [0] * S
        )
        n_steps = max(1, len(records))
        mean_wall = (
            sum(r.get("wall_ms", 0.0) for r in records) / n_steps
            if records else 0.0
        )
        peaks = profiler.device_peaks(self._device_kind())
        strag: Dict[int, float] = {}
        for ev in self._straggler_events:
            strag[ev["shard"]] = strag.get(ev["shard"], 0.0) + float(ev["ms"])
        costs = []
        for s in range(S):
            verts = max(0, min(sc.real_n - s * Np, Np))
            costs.append((
                verts,
                profiler.estimate_superstep_cost(
                    max(verts, 1), max(edges[s], 1)
                ),
            ))
        max_edges = max(max(edges), 1)
        measured = self._measured_walls(sc)
        cost_source = "measured" if measured else "plan"
        max_meas = max(measured) if measured else 0.0
        per = []
        t_by_shard = []
        for s in range(S):
            verts, cost = costs[s]
            # the barrier wall is set by the busiest shard: scale the
            # measured mean superstep wall by each shard's measured share
            # of the slowest shard's probe wall (or, without the probe,
            # by relative modeled edge load)
            if measured and max_meas > 0:
                share = measured[s] / max_meas
            else:
                share = edges[s] / max_edges
            modeled_ms = mean_wall * share
            strag_ms = strag.get(s, 0.0)
            t_by_shard.append(modeled_ms + strag_ms / n_steps)
            point = profiler.roofline_point(
                cost["flops"], cost["bytes_accessed"],
                modeled_ms if modeled_ms > 0 else 0.0, peaks,
            )
            per.append({
                "shard": s,
                "vertices": verts,
                "edges": edges[s],
                "modeled_ms": round(modeled_ms, 4),
                "measured_ms": (
                    round(measured[s], 4) if measured else None
                ),
                "cost_source": cost_source,
                "straggler_ms": round(strag_ms, 3),
                "ledger": {
                    "cells_read": edges[s],
                    "bytes_read": int(cost["bytes_accessed"]),
                    "bytes_written": 8 * verts,
                },
                "roofline": {
                    "flops": cost["flops"],
                    "bytes_accessed": cost["bytes_accessed"],
                    "cost_source": cost["cost_source"],
                    **point,
                },
            })
        mean_t = sum(t_by_shard) / S if S else 0.0
        skew = (max(t_by_shard) / mean_t) if mean_t > 0 else 1.0
        slowest = int(np.argmax(t_by_shard)) if t_by_shard else 0
        block = {
            "count": S,
            "skew": round(skew, 4),
            "cost_source": cost_source,
            "slowest_shard": slowest,
            "straggler_events": len(self._straggler_events),
            "straggler_ms_total": round(sum(strag.values()), 3),
            "boundary_elems": getattr(sc, "comm_a2a_elems", None),
            "per_shard": per,
        }
        self.last_run_info["shards"] = block
        self.last_run_info["exchange"] = self._exchange_info(sc)
        if self._autotune_record is not None:
            self.last_run_info["autotune"] = self._autotune_record
        registry.gauge("olap.shard.skew").set(skew)
        # PR 8 dashboards read the skew gauge: publish whether it is now
        # measured-wall-derived (1) or still plan-derived (0)
        registry.gauge("olap.shard.skew.measured").set(
            1.0 if cost_source == "measured" else 0.0
        )
        registry.counter("olap.sharded.runs").inc()
        # ambient resource ledger: the run's plan-derived totals (one
        # message gather per edge + state write-back per vertex)
        profiler.accrue(
            cells_read=sum(edges),
            bytes_read=sum(int(c["bytes_accessed"]) for _v, c in costs),
            bytes_written=8 * sc.real_n,
        )
        # slowest-shard exemplar span: the flamegraph/trace hook for "which
        # shard sets the barrier pace" — plus a flight event when skew is
        # pathological or a straggler was injected
        with tracer.span(
            "olap.shard.slowest",
            shard=slowest,
            modeled_ms=round(t_by_shard[slowest], 4) if t_by_shard else 0.0,
            skew=round(skew, 4),
        ):
            pass
        if self._straggler_events or skew >= SKEW_FLIGHT_THRESHOLD:
            flight_recorder.record(
                "shard_skew",
                skew=round(skew, 4),
                slowest_shard=slowest,
                straggler_events=len(self._straggler_events),
                injected_ms=round(sum(strag.values()), 3),
            )

    def _persist_measured(
        self, sc: ShardedCSR, checkpoint_path, shard_dir, records
    ) -> None:
        """Measured-record persistence for the mesh: keyed by SHARD COUNT
        inside the shared .autotune.json, so an 8-chip run calibrates the
        next 8-chip run without clobbering the single-device record
        (olap/autotune.save_measured v2)."""
        if not records:
            return
        path = (
            os.path.join(shard_dir, "autotune.json") if shard_dir
            else (checkpoint_path + ".autotune.json" if checkpoint_path
                  else None)
        )
        if not path:
            return
        from janusgraph_tpu.olap import autotune

        prior = autotune.load_measured(path, shard_count=self.num_shards)
        mean_wall = sum(r.get("wall_ms", 0.0) for r in records) / max(
            1, len(records)
        )
        autotune.save_measured(
            path,
            {
                "strategy": f"sharded-{self.exchange}-{self.agg}",
                "pad_ratio": round(sc.padded_n / max(1, sc.real_n), 4),
                "superstep_ms": round(mean_wall, 3),
                "roofline_by_tier": None,
                # per-shard-layout fields (v2 records are keyed by shard
                # count; these let the next lifetime's decide_sharded
                # prefer the measured exchange layout)
                "exchange": self.exchange,
                "agg": self.agg,
                "halo_cap": getattr(sc, "halo_cap", None),
            },
            shard_count=self.num_shards,
        )
        self.last_run_info["autotune_persist"] = {
            "path": path,
            "shard_count": self.num_shards,
            "calibrated": prior is not None,
        }

    def run(
        self,
        program: VertexProgram,
        sync_every: int = 1,
        fused: bool = None,
        checkpoint_path: str = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        frontier: str = "auto",
        fault_hook=None,
        resume_attempts: int = 3,
        shard_checkpoint_dir: str = None,
    ) -> Dict[str, np.ndarray]:
        """Run to termination. `fused` (default auto): constant-combiner
        programs with terminate_device compile spans of the run into one
        dispatch (while_loop inside shard_map), optionally chunked for
        checkpointing; otherwise a host loop with `sync_every`-amortized
        aggregator fetches (see TPUExecutor.run). `frontier`:
        "auto"/"always"/"off" — the ShortestPath family runs per-shard
        frontier-compacted supersteps when eligible (checkpointing rides
        the dense path: frontier runs are short).

        `shard_checkpoint_dir` — save the SHARDED checkpoint format (per-
        shard slices + atomic manifest; olap/sharded_checkpoint.py) every
        `checkpoint_every` supersteps instead of the single-file
        `checkpoint_path` format.

        `fault_hook` (e.g. FaultPlan.sharded_hook) is consulted at every
        host-visible superstep boundary — the fused path's granularity is
        one checkpoint chunk — and may raise SuperstepPreempted (incl.
        ShardPreempted / CollectiveTimeout / HaloDropped). With
        checkpointing enabled, ALL shards roll back to the last complete
        manifest (the BSP barrier's consistency cut) and replay, up to
        `resume_attempts` times; the replay recomputes the identical SPMD
        program over exact saved arrays, so the final state is bitwise-
        identical to a fault-free run. Frontier runs carry no checkpoint
        and simply restart from scratch (they are short and deterministic).
        Mesh-aware hooks also return straggler skew records, which feed the
        run's per-shard report and the `olap.shard.skew` gauge.
        """
        from janusgraph_tpu.olap.vertex_program import (
            check_weighted_transforms,
        )

        check_weighted_transforms(program, self.csr)
        # every exchange pre-combines partial aggregates across shards
        Combiner.require_foldable(program.combiner, "the sharded executor")
        # its frontier engine scatter-mins across shards like the dense path
        program.require_dense_capable("the sharded executor")
        if frontier not in ("auto", "off", "always"):
            raise ValueError(f"unknown frontier mode: {frontier!r}")
        if not getattr(program, "sharded_compatible", True):
            # sddmm needs both endpoints' feature rows inside one kernel;
            # the halo exchange ships only source-side data — refuse with
            # the workaround instead of silently computing garbage
            raise NotImplementedError(
                "sddmm dense programs are not supported on the sharded "
                "executor (the per-edge dot needs dst features on the "
                "source side); run executor='tpu' or message_mode="
                "'copy'/'weighted'"
            )
        if self.exchange_requested == "auto" and self._autotune_record is None:
            # a persisted measured record for THIS shard count calibrates
            # the layout decision across process lifetimes (autotune v2)
            apath = (
                os.path.join(shard_checkpoint_dir, "autotune.json")
                if shard_checkpoint_dir
                else (checkpoint_path + ".autotune.json"
                      if checkpoint_path else None)
            )
            if apath:
                from janusgraph_tpu.olap import autotune

                self._measured_prior = autotune.load_measured(
                    apath, shard_count=self.num_shards
                )
        self._resolve_exchange(program.undirected)
        # the ShortestPath family runs per-shard frontier compaction
        # (parallel/sharded_frontier.py) unless its route's guards say no;
        # float32-exact vertex indices cover the PADDED index space, and
        # the mesh's CC propagates, so a superstep cap is no refusal here
        from janusgraph_tpu.olap.routes import CHECKPOINT_REFUSAL, route_of

        route = None if frontier == "off" else route_of(program)
        if route is not None:
            if checkpoint_path or shard_checkpoint_dir:
                # "always" must never silently time the dense path under a
                # frontier label (mirrors TPUExecutor.run)
                if frontier == "always":
                    raise ValueError(CHECKPOINT_REFUSAL)
                route = None
            elif not route.admits(
                program, self._sharded(program.undirected).padded_n,
                self.csr.num_edges, frontier,
            ):
                if frontier == "always":
                    raise ValueError(
                        "frontier='always' but the graph exceeds the "
                        "frontier engine's guards (|V|="
                        f"{self.csr.num_vertices}, |E|={self.csr.num_edges}; "
                        "float32 label/predecessor exactness needs padded "
                        "|V| < 2^24, int32 expansion needs |E| < 2^30) — "
                        "use frontier='auto' or 'off'"
                    )
                route = None
        sc = self._sharded(program.undirected)
        if fused is None:
            fused = program.fused_eligible()
        use_fused = (
            route is None
            and fused
            and not program.overrides("combiner_for")
        )

        from janusgraph_tpu.observability import tracer

        hook = self._bind_hook(fault_hook)
        self._straggler_events: List[dict] = []
        self._ck_saves = 0
        self._resume_ms = 0.0
        self._resume_t_catch = None
        self._new_execs = 0
        self._h2d_bytes = 0
        t_run = time.perf_counter()
        with tracer.span(
            "olap.run", executor="sharded", shards=self.num_shards,
            exchange=self.exchange,
        ) as sp:
            out = self._run_guarded(
                program, sc, sync_every, checkpoint_path, checkpoint_every,
                resume, frontier, hook, resume_attempts,
                shard_checkpoint_dir, route, use_fused,
            )
            self._publish_run(sp, program, out, time.perf_counter() - t_run)
            return out

    def _run_guarded(
        self, program, sc, sync_every, checkpoint_path, checkpoint_every,
        resume, frontier, hook, resume_attempts, shard_checkpoint_dir,
        route, use_fused,
    ):
        from janusgraph_tpu.exceptions import SuperstepPreempted
        from janusgraph_tpu.observability import flight_recorder, registry

        can_resume = bool(
            (shard_checkpoint_dir or checkpoint_path) and checkpoint_every
        )
        resumes = 0
        while True:
            try:
                if route is not None:
                    out = self._run_frontier(program, route, fault_hook=hook)
                elif use_fused:
                    out = self._run_fused(
                        program, sc, checkpoint_path, checkpoint_every,
                        resume, hook, shard_checkpoint_dir,
                    )
                else:
                    out = self._run_host_loop(
                        program, sc, sync_every, checkpoint_path,
                        checkpoint_every, resume, hook,
                        shard_checkpoint_dir,
                    )
                break
            except SuperstepPreempted as e:
                registry.counter("olap.preemptions").inc()
                # frontier runs restart from scratch (deterministic and
                # short); dense paths need a checkpoint to roll back to
                if resumes >= resume_attempts or not (
                    route is not None or can_resume
                ):
                    raise
                resumes += 1
                resume = True
                self._resume_t_catch = time.perf_counter()
                registry.counter("olap.resumes").inc()
                registry.counter("olap.sharded.resumes").inc()
                flight_recorder.record(
                    "olap_resume", executor="sharded", attempt=resumes,
                    program=program.__class__.__name__,
                    fault=type(e).__name__,
                    format="sharded" if shard_checkpoint_dir else "single",
                )
                if route is not None:
                    # nothing to reload: the restart IS the recovery
                    self._resume_ms += (
                        time.perf_counter() - self._resume_t_catch
                    ) * 1000.0
                    self._resume_t_catch = None
        if resumes:
            self.last_run_info["resumes"] = resumes
            self.last_run_info["resume_ms"] = round(self._resume_ms, 3)
        if self._ck_saves or can_resume:
            self.last_run_info["checkpoint"] = {
                "format": "sharded" if shard_checkpoint_dir else "single",
                "saves": self._ck_saves,
                "location": shard_checkpoint_dir or checkpoint_path,
            }
        return out

    def _publish_run(self, sp, program, result, wall_s) -> None:
        """Publish the finished run in the SAME record vocabulary as
        the single-device executor's — path/supersteps/superstep_records,
        transfer bytes, compile-cache economics, device memory, slowest-
        superstep exemplar, and the olap.* gauges — so dashboards and
        tests read one shape regardless of which executor a submit()
        routed to. Host code only."""
        from janusgraph_tpu.observability import registry, tracer

        from janusgraph_tpu.olap.device import describe_devices

        info = self.last_run_info
        info["executor"] = "sharded"
        info.update(describe_devices(self.mesh.devices.flat))
        info["wall_s"] = round(wall_s, 4)
        info["retraces"] = self._new_execs
        info["h2d_arg_bytes"] = int(self._h2d_bytes)
        info["d2h_bytes"] = int(
            sum(np.asarray(v).nbytes for v in result.values())
        )
        sc = self._sharded(bool(getattr(program, "undirected", False)))
        pad_ratio = round(sc.padded_n / max(1, sc.real_n), 4)
        info["pad_ratio"] = pad_ratio
        info["ell_pad_ratio"] = pad_ratio
        records = info.get("superstep_records")
        if records is None:
            # frontier path: the tier trace IS the per-superstep record
            records = [
                {
                    "step": int(t.get("hop", i)),
                    "frontier": int(t.get("frontier", 0)),
                    "edges": int(t.get("edges", 0)),
                    "e_cap": int(t.get("E_cap", 0)),
                }
                for i, t in enumerate(info.get("tiers", []))
            ]
        n = sc.real_n
        for i, r in enumerate(records):
            r.setdefault("frontier", n)
            r.setdefault("pad_ratio", pad_ratio)
            r.setdefault(
                "h2d_bytes", info["h2d_arg_bytes"] if i == 0 else 0
            )
        info["superstep_records"] = records

        dispatches = max(len(records), 1)
        misses = min(self._new_execs, dispatches)
        info["compile_cache"] = {
            "hits": dispatches - misses,
            "misses": misses,
            "compiled_total": len(self._compiled),
        }
        registry.counter("olap.compile_cache.hits").inc(dispatches - misses)
        registry.counter("olap.compile_cache.misses").inc(misses)

        stats = None
        try:
            stats = np.asarray(self.mesh.devices).flat[0].memory_stats()
        except Exception:  # noqa: BLE001 - backend-dependent API
            stats = None
        if stats and "bytes_in_use" in stats:
            info["device_memory"] = {
                "source": "device",
                "bytes_in_use": int(stats["bytes_in_use"]),
            }
        else:
            info["device_memory"] = {
                "source": "host-estimate",
                "bytes_in_use": int(info["h2d_arg_bytes"])
                + int(info["d2h_bytes"]),
            }
        registry.set_gauge(
            "olap.device.bytes_in_use",
            float(info["device_memory"]["bytes_in_use"]),
        )

        slowest = None
        for r in records[:128]:
            s = tracer.record_span(
                "superstep", float(r.get("wall_ms", 0.0)),
                **{k: v for k, v in r.items() if k != "wall_ms"},
            )
            if slowest is None or s.duration_ms > slowest.duration_ms:
                slowest = s
        if slowest is not None:
            info["slowest_superstep"] = {
                "step": slowest.attrs.get("step"),
                "wall_ms": round(slowest.duration_ms, 4),
                "span_id": f"{slowest.span_id:016x}",
                "trace_id": f"{slowest.trace_id:016x}",
            }
        sp.annotate(
            path=info.get("path"),
            supersteps=info.get("supersteps"),
            wall_s=info["wall_s"],
            retraces=self._new_execs,
            ell_pad_ratio=pad_ratio,
            h2d_arg_bytes=info["h2d_arg_bytes"],
            d2h_bytes=info["d2h_bytes"],
        )
        registry.counter("olap.runs").inc()
        registry.timer("olap.run").update(int(wall_s * 1e9))
        registry.set_gauge(
            "olap.superstep.count", float(info.get("supersteps", 0) or 0)
        )
        registry.set_gauge("olap.run.wall_ms", round(wall_s * 1000.0, 3))
        registry.set_gauge(
            "olap.transfer.h2d_bytes", float(info["h2d_arg_bytes"])
        )
        registry.set_gauge(
            "olap.transfer.d2h_bytes", float(info["d2h_bytes"])
        )
        registry.record_run("olap", info)

    def _run_host_loop(
        self,
        program: VertexProgram,
        sc: ShardedCSR,
        sync_every: int,
        checkpoint_path: str,
        checkpoint_every: int,
        resume: bool,
        hook,
        shard_checkpoint_dir: str,
    ) -> Dict[str, np.ndarray]:
        import jax.numpy as jnp

        memory = Memory()
        state = None
        start_step = 0
        if resume and (checkpoint_path or shard_checkpoint_dir):
            ck = self._load_ck(checkpoint_path, shard_checkpoint_dir)
            if ck is not None:
                ck_state, ck_mem, start_step = ck
                fresh, _m = program.setup(_GlobalView(sc), np)
                state = {}
                for k, pad in fresh.items():
                    arr = np.asarray(pad).copy()
                    arr[: sc.real_n] = np.asarray(ck_state[k])
                    state[k] = self._place_state(arr)
                memory.values = {k: float(v) for k, v in ck_mem.items()}
                memory.superstep = start_step
        if state is None:
            state, init_metrics = program.setup(_GlobalView(sc), np)
            state = {k: self._place_state(v) for k, v in state.items()}
            memory.reduce_in(init_metrics)
            memory.superstep = 0
        device_memory = {
            k: jnp.asarray(v, dtype=jnp.float32) for k, v in memory.values.items()
        }

        gargs = self._graph_args(sc, program.undirected)
        steps_done = start_step
        records: List[dict] = []
        cap = program.iteration_cap(self.csr.num_vertices)
        for step in range(start_step, cap):
            # fault boundary: the barrier between supersteps — the one
            # point where no shard holds partial superstep state
            self._consult(hook, step)
            t_step = time.perf_counter()
            op = program.combiner_for(step)
            ch = program.channel_for(step)
            if ch is not None:
                sc_step, gargs_step = self._channel_view(program, ch)
            else:
                sc_step, gargs_step = sc, gargs
            fn = self._superstep_fn(program, op, sc_step, ch)
            state, metrics = fn(
                state,
                jnp.asarray(step, dtype=jnp.int32),
                device_memory,
                gargs_step,
            )
            device_memory = {
                k: metrics.get(k, device_memory.get(k))
                for k in set(device_memory) | set(metrics)
            }
            steps_done += 1
            last = step == cap - 1
            records.append({
                "step": step,
                "wall_ms": round(
                    (time.perf_counter() - t_step) * 1000.0, 3
                ),
            })
            if steps_done % sync_every == 0 or last:
                host_vals = self.jax.device_get(metrics)
                memory.values = {k: float(v) for k, v in host_vals.items()}
                memory.superstep = steps_done
                if checkpoint_every and (
                    checkpoint_path or shard_checkpoint_dir
                ) and (steps_done % checkpoint_every == 0 or last):
                    self._save_ck(
                        checkpoint_path, shard_checkpoint_dir,
                        {
                            k: self._fetch(v)[: sc.real_n]
                            for k, v in state.items()
                        },
                        memory.values,
                        steps_done,
                        records=records,
                    )
                if program.terminate(memory):
                    break

        # strip padding
        self.last_run_info = {
            "path": "host-loop", "supersteps": steps_done,
            "superstep_records": records,
        }
        self._shard_report(sc, records)
        self._persist_measured(
            sc, checkpoint_path, shard_checkpoint_dir, records
        )
        return {
            k: self._fetch(v)[: sc.real_n] for k, v in state.items()
        }

    def _run_fused(
        self,
        program: VertexProgram,
        sc: ShardedCSR,
        checkpoint_path: str,
        checkpoint_every: int,
        resume: bool,
        hook=None,
        shard_checkpoint_dir: str = None,
    ) -> Dict[str, np.ndarray]:
        import jax.numpy as jnp

        op = program.combiner
        max_iter = program.iteration_cap(self.csr.num_vertices)
        gargs = self._graph_args(sc, program.undirected)
        steps_done = 0
        state = mem = None

        if resume and (checkpoint_path or shard_checkpoint_dir):
            ck = self._load_ck(checkpoint_path, shard_checkpoint_dir)
            if ck is not None:
                ck_state, ck_mem, steps_done = ck
                # checkpoints store the real_n rows (portable across shard
                # counts); padding rows are re-derived from a fresh setup()
                fresh, _m = program.setup(_GlobalView(sc), np)
                state = {}
                for k, pad in fresh.items():
                    arr = np.asarray(pad).copy()
                    arr[: sc.real_n] = np.asarray(ck_state[k])
                    state[k] = self._place_state(arr)
                mem = {k: jnp.asarray(v, jnp.float32) for k, v in ck_mem.items()}

        if state is None:
            state, init_metrics = program.setup(_GlobalView(sc), np)
            state = {k: self._place_state(v) for k, v in state.items()}
            mem0 = {
                k: jnp.asarray(v, dtype=jnp.float32)
                for k, (_o, v) in init_metrics.items()
            }
            if max_iter == 0:
                return {
                    k: self._fetch(v)[: sc.real_n] for k, v in state.items()
                }
            # learn apply's aggregator pytree by abstract trace (records
            # each metric's monoid op, no XLA compile), seed missing keys
            # with the monoid identity, and run superstep 0 INSIDE the
            # fused executable — one compile per program instead of two
            # (mirrors the single-device `_run_fused`)
            mkey = (program.cache_key(), op)
            if mkey not in self._metric_ops:
                step_fn = self._superstep_fn(program, op, sc)
                self.jax.eval_shape(
                    step_fn, state, jnp.asarray(0, jnp.int32), mem0, gargs
                )
            mops = self._metric_ops[mkey]
            mem = {
                k: (
                    mem0[k]
                    if k in mem0
                    else jnp.asarray(Combiner.IDENTITY[mops[k]], jnp.float32)
                )
                for k in mops
            }
            steps_done = 0

        fn = self._fused_fn(program, op, sc)
        records: List[dict] = []
        while steps_done < max_iter:
            # fault boundary: once per dispatched chunk (the while_loop
            # owns the intra-chunk superstep boundaries on device)
            self._consult(hook, steps_done)
            t_chunk = time.perf_counter()
            limit = max_iter
            if checkpoint_every:
                limit = min(steps_done + checkpoint_every, max_iter)
            state, mem, steps_dev = fn(
                state,
                mem,
                jnp.asarray(steps_done, jnp.int32),
                jnp.asarray(limit, jnp.int32),
                gargs,
            )
            new_steps = int(steps_dev)
            terminated = new_steps < limit or new_steps == steps_done
            chunk_steps = max(1, new_steps - steps_done)
            chunk_ms = (time.perf_counter() - t_chunk) * 1000.0
            for i in range(steps_done, max(new_steps, steps_done)):
                records.append({
                    "step": i,
                    "wall_ms": round(chunk_ms / chunk_steps, 3),
                })
            steps_done = max(new_steps, steps_done)
            if checkpoint_every and (checkpoint_path or shard_checkpoint_dir):
                self._save_ck(
                    checkpoint_path, shard_checkpoint_dir,
                    {
                        k: self._fetch(v)[: sc.real_n]
                        for k, v in state.items()
                    },
                    {k: float(np.asarray(v)) for k, v in mem.items()},
                    steps_done,
                    records=records,
                )
            if terminated:
                break
        self.last_run_info = {
            "path": "fused", "supersteps": steps_done,
            "superstep_records": records,
        }
        self._shard_report(sc, records)
        self._persist_measured(
            sc, checkpoint_path, shard_checkpoint_dir, records
        )
        return {k: self._fetch(v)[: sc.real_n] for k, v in state.items()}


def shard_csr(csr: CSRGraph, num_shards: int, undirected: bool = False) -> ShardedCSR:
    return ShardedCSR(csr, num_shards, undirected)
