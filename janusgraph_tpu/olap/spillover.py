"""OLTP->OLAP spillover: compile hot multi-hop traversals to frontier/
SpGEMM supersteps over a cached CSR snapshot.

The paper's OLTP engine walks ``g.V().out().out()...`` row by row through
the property layer while the OLAP engine already executes the same
adjacency math as vectorized frontier expansion over a CSR snapshot —
ALPHA-PIM and the structured-SpGEMM papers (PAPERS.md) both frame
multi-hop graph queries as sparse matrix products that are orders of
magnitude cheaper in bulk form. This module is the planner that routes
recurring expensive shapes onto the OLAP executor:

- **Recognition** (:func:`recognize`): a compilable chain is a
  ``V()``/``V(ids)`` start (optionally label-filtered), a sequence of
  ``out/in/both[E]`` hops with edge-label filters (plus mid-chain
  ``has_label`` vertex filters), terminated by ``count``/``dedup``/``id``
  -style reducers. Anything else is an unsupported step and falls back.

- **Promotion policy**: the PR 5 :class:`~janusgraph_tpu.observability.
  profiler.DigestTable` already measures per-shape mean cost; a shape is
  promoted once its measured mean wall exceeds
  ``computer.spillover-min-cost-ms`` over at least
  ``computer.spillover-min-seen`` executions. Promotion is sticky for the
  planner's lifetime (a spilled shape's now-cheap walls must not demote
  it back into the slow path — that would flap).

- **Execution**: the chain compiles to an
  :class:`~janusgraph_tpu.olap.programs.olap_traversal.
  OLAPTraversalProgram` (one typed EdgeChannel per hop, traverser-count
  state) and runs on the configured OLAP executor over a CACHED CSR
  snapshot — packed once, incrementally refreshed through the backend's
  mutation-epoch tracker while committed writes stay within
  ``computer.spillover-max-staleness``, dropped for a repack beyond it
  (counter ``olap.spillover.stale`` — the bounded-staleness groundwork
  for the streaming delta-CSR item). ``computer.sharded-auto`` routes
  multi-device processes to the sharded executor exactly like
  ``graph.compute()``. A chain from explicit ``V(ids)`` takes its first
  hop on the host (:func:`host_seed_hop`: the seeds' CSR rows, summed
  by neighbour) and starts the device program at hop 1 with the arrival
  counts as its seed mask; any other start keeps the dense hop 0.

- **One dispatch for the requests that stand at the lock**: a request
  plans in its own thread and then stands at the planner's lock; whoever
  holds the lock takes the compatible requests that stand there along
  (same fresh snapshot, no tx overlay, same remaining steps) and runs
  them with itself, their arrival vectors the columns of one
  ``(n, BATCH_WIDTH)`` start, ONE superstep and one fetch. Each member
  folds its own column in its own thread; a request that finds nobody
  runs alone as the ``(n,)`` program.

- **Tx-overlay reconciliation** (read-your-writes): the transaction's
  uncommitted adds/deletes — the existence-cell machinery already sees
  every mutation — are merged into the snapshot BEFORE the run by
  patching the edge multiset (delete tombstoned instances, append added
  edges, extend the vertex set with uncommitted vertices), so spilled
  results are set-equal to the step-by-step walk even mid-transaction.
  Overlays beyond ``computer.spillover-max-overlay`` fall back.

- **Fallback is always safe**: any unsupported step, overlay overflow,
  staleness breach, rung-2 brownout (``check_olap_admission``), count
  overflow past float32 exactness, or unexpected error returns ``None``
  to the caller — the row-by-row walk continues unchanged — with a
  ``spillover_fallback`` flight event and a per-reason counter.

Hooked from :meth:`GraphTraversal._execute` (and the ``count()``
terminal) via :func:`try_spill`; built per graph at open when
``computer.spillover`` is set (core/graph.py).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: process-wide promoted-digest set: GET /profile marks table rows whose
#: digest any live planner has promoted
_PROMOTED_LOCK = threading.Lock()
_PROMOTED_GLOBAL: set = set()


def promoted_digests() -> set:
    with _PROMOTED_LOCK:
        return set(_PROMOTED_GLOBAL)


class _SpillRefused(Exception):
    """Internal control flow: this attempt falls back (reason carried)."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


@dataclass
class SpilloverPlan:
    """One recognized compilable chain."""

    digest: str
    shape: str
    #: [(direction, edge-label names or None, [vertex-label tuples])]
    hops: List[Tuple[str, Optional[Tuple[str, ...]], List[Tuple[str, ...]]]]
    #: explicit V(ids) seeds (None = all vertices)
    seed_ids: Optional[List[int]] = None
    #: folded has_label() conditions on the seed set (AND of tuples)
    seed_labels: List[Tuple[str, ...]] = field(default_factory=list)
    distinct: bool = False
    as_ids: bool = False
    count_step: bool = False
    terminal_count: bool = False


# --------------------------------------------------------------- recognition
def traversal_digest(traversal) -> Tuple[str, str]:
    """(shape, digest) for a traversal BEFORE execution — same
    normalization as GraphTraversal._observe_digest, with the start
    access predicted (ids point-lookup vs full scan; the only accesses a
    compilable chain can resolve to, since property-filtered starts are
    unsupported and fall back before this matters)."""
    from janusgraph_tpu.observability.profiler import (
        shape_digest,
        traversal_shape,
    )

    plan = {"access": "ids" if traversal._start.ids else "full-scan"}
    shape = traversal_shape(
        [getattr(s, "_label", "step") for s in traversal._steps], plan
    )
    return shape, shape_digest(shape)


def recognize(traversal, terminal=None):
    """(SpilloverPlan, None) for a compilable chain, (None, reason)
    otherwise. Pure inspection — no store reads, no device work."""
    from janusgraph_tpu.core.codecs import Direction
    from janusgraph_tpu.core.elements import Vertex
    from janusgraph_tpu.core.predicates import Contain

    if getattr(traversal.source, "_sack_init", None) is not None:
        return None, "sack"
    start = traversal._start
    seed_ids = None
    if start.ids:
        seed_ids = [
            i.id if isinstance(i, Vertex) else i for i in start.ids
        ]
    seed_labels: List[Tuple[str, ...]] = []
    for key, p in traversal._pre_has:
        if key is not None:
            return None, f"seed-filter:{key}"
        if p.eq_value is not None:
            seed_labels.append((p.eq_value,))
        elif p.predicate is Contain.IN and all(
            isinstance(x, str) for x in (p.condition or ())
        ):
            seed_labels.append(tuple(p.condition))
        else:
            return None, "seed-label-predicate"
    cfg = getattr(traversal.source.graph, "config", None)
    if seed_ids is None and cfg is not None and cfg.get("query.force-index"):
        # the row path REFUSES an unindexed full scan under
        # query.force-index — spilling around the refusal would silently
        # change semantics
        return None, "force-index"
    dir_name = {
        Direction.OUT: "out", Direction.IN: "in", Direction.BOTH: "both",
    }
    hops: List[Tuple] = []
    tail: List[str] = []
    edge_tail = False
    for st in traversal._steps:
        em = getattr(st, "_expand_meta", None)
        sm = getattr(st, "_spill_meta", None)
        if em is not None:
            if tail or edge_tail:
                return None, "expansion-after-reducer"
            if em["sort_range"] is not None:
                return None, "sort-range"
            hops.append((
                dir_name[em["direction"]],
                tuple(em["labels"]) or None,
                [],
            ))
            if not em["to_vertex"]:
                # an edge expansion yields one traverser per edge — the
                # same count as the vertex expansion, so a TRAILING
                # outE/inE/bothE is compilable for counting terminals
                # only (edge objects/ids are not in the count state)
                edge_tail = True
        elif sm is not None:
            kind = sm[0]
            if kind == "hasLabel":
                if tail or edge_tail or not hops:
                    return None, "hasLabel-position"
                hops[-1][2].append(tuple(sm[1]))
            elif kind == "count":
                tail.append("count")
            elif kind in ("dedup", "id"):
                if edge_tail:
                    return None, f"edge-{kind}"
                tail.append(kind)
            else:
                return None, kind
        else:
            return None, getattr(st, "_label", "step")
    if edge_tail and not (
        tail == ["count"] or (not tail and terminal == "count")
    ):
        return None, "edge-expansion-without-count"
    distinct = as_ids = count_step = False
    for k in tail:
        if count_step:
            return None, "step-after-count"
        if k == "dedup":
            distinct = True
        elif k == "id":
            as_ids = True
        else:
            count_step = True
    shape, digest = traversal_digest(traversal)
    return SpilloverPlan(
        digest=digest, shape=shape, hops=hops, seed_ids=seed_ids,
        seed_labels=seed_labels, distinct=distinct, as_ids=as_ids,
        count_step=count_step, terminal_count=(terminal == "count"),
    ), None


# ------------------------------------------------------------- overlay view
def tx_overlay(tx) -> dict:
    """The transaction's uncommitted graph-structure delta, in graph-id
    space: added/deleted edge triples (src vid, dst vid, edge type id),
    uncommitted vertices ({vid: label id}), and removed vids. Property
    mutations are irrelevant to compilable chains (no property filters
    are supported) and are not collected."""
    from janusgraph_tpu.core.elements import Edge

    with tx._lock:
        added_rel = [r for rels in tx._added.values() for r in rels]
        deleted_rel = list(tx._deleted)
        removed = set(tx._removed_vertices)
        new_vertices = {
            vid: tx._new_vertex_labels.get(vid, 0)
            for vid, v in tx._vertex_cache.items()
            if v.is_new and not v.is_removed
        }
    added: List[Tuple[int, int, int]] = []
    seen: set = set()
    for r in added_rel:
        # new edges register under BOTH endpoint vids — dedupe by object
        if isinstance(r, Edge) and not r.is_removed and id(r) not in seen:
            seen.add(id(r))
            added.append((r.out_vertex.id, r.in_vertex.id, r.type_id))
    deleted: List[Tuple[int, int, int]] = []
    seen_ids: set = set()
    for r in deleted_rel:
        if isinstance(r, Edge) and r.id not in seen_ids:
            seen_ids.add(r.id)
            deleted.append((r.out_vertex.id, r.in_vertex.id, r.type_id))
    return {
        "added": added,
        "deleted": deleted,
        "new_vertices": new_vertices,
        "removed": removed,
        "size": len(added) + len(deleted) + len(new_vertices) + len(removed),
    }


def patched_csr(csr, overlay):
    """The snapshot with the tx overlay reconciled in: deleted edge
    INSTANCES removed from the multiset (one per tombstone — parallel
    edges with identical (src, dst, type) are count-equivalent), added
    edges appended, uncommitted vertices extending the vertex set. The
    committed snapshot is returned untouched for an empty overlay."""
    import numpy as np

    from janusgraph_tpu.olap.csr import csr_from_edges

    if not overlay["size"]:
        return csr
    vids = csr.vertex_ids
    if overlay["new_vertices"]:
        extra = np.setdiff1d(
            np.fromiter(
                overlay["new_vertices"].keys(), dtype=np.int64,
                count=len(overlay["new_vertices"]),
            ),
            vids,
        )
        vids2 = np.unique(np.concatenate([vids, extra]))
    else:
        vids2 = vids
    # labels aligned to the extended vertex set (seed has_label filters
    # must see uncommitted vertices' labels)
    labels2 = None
    if csr.labels is not None or overlay["new_vertices"]:
        labels2 = np.zeros(len(vids2), dtype=np.int64)
        if csr.labels is not None:
            pos = np.searchsorted(vids2, vids)
            labels2[pos] = csr.labels
        for vid, lid in overlay["new_vertices"].items():
            i = int(np.searchsorted(vids2, vid))
            if i < len(vids2) and vids2[i] == vid:
                labels2[i] = lid

    src_vid = np.repeat(vids, np.diff(csr.out_indptr)).astype(np.int64)
    dst_vid = vids[csr.out_dst].astype(np.int64)
    et = (
        csr.out_edge_type.astype(np.int64)
        if csr.out_edge_type is not None
        else np.zeros(len(src_vid), dtype=np.int64)
    )
    if overlay["deleted"]:
        # multiset subtraction: tokenize (src, dst, type) triples, then
        # drop the first `deleted count` instances of each token
        m = len(src_vid)
        trip = np.stack([src_vid, dst_vid, et], axis=1)
        dtrip = np.asarray(overlay["deleted"], dtype=np.int64).reshape(-1, 3)
        _, inv = np.unique(
            np.concatenate([trip, dtrip]), axis=0, return_inverse=True
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
    if overlay["added"]:
        a = np.asarray(overlay["added"], dtype=np.int64).reshape(-1, 3)
        src_vid = np.concatenate([src_vid, a[:, 0]])
        dst_vid = np.concatenate([dst_vid, a[:, 1]])
        et = np.concatenate([et, a[:, 2]])
    n = len(vids2)
    si = np.searchsorted(vids2, src_vid)
    di = np.searchsorted(vids2, dst_vid)
    valid = (
        (si < n) & (di < n)
        & (vids2[np.minimum(si, n - 1)] == src_vid)
        & (vids2[np.minimum(di, n - 1)] == dst_vid)
    )
    patched = csr_from_edges(
        n,
        si[valid].astype(np.int32),
        di[valid].astype(np.int32),
        edge_types=et[valid].astype(np.int32),
    )
    patched.vertex_ids = vids2
    patched.labels = labels2
    return patched


# ------------------------------------------------------------ the seed hop
#: the seeds' rows may hold this share of the view's edges and hop 0 still run on the host: numpy walks them at 11-22 ns an edge, the device gathers ALL the view's edges at 6.6-7.5 ns a slot
_SEED_HOP_HOST_MAX_SHARE = 0.125


def host_seed_hop(csr, idx, mult, step):
    """Hop 0 of a chain from explicit seeds, read off the seeds' CSR rows:
    ``(targets, weights, edges walked)``, or None where the device's dense
    hop 0 stays (per-edge types missing for a labelled hop, rows that
    cover the view, or counts a float32 could not hold exactly).

    ``idx`` are the seeds' vertex indices and ``mult`` their
    multiplicities; every neighbour over the step's (direction, labels)
    view receives its seed's multiplicity, one entry of ``targets`` /
    ``weights`` per edge, so their sum by target is the integer vector the
    dense superstep computes from the same rows: ``out`` walks the
    out-rows, ``in`` the in-rows, ``both`` the two (a self loop twice, a
    parallel edge once per copy). Nothing here is as long as the vertex
    set: in the served process a fresh page costs 3 us."""
    import numpy as np

    sides = []
    if step.direction in ("out", "both"):
        sides.append((csr.out_indptr, csr.out_dst, csr.out_edge_type))
    if step.direction in ("in", "both"):
        sides.append((csr.in_indptr, csr.in_src, csr.in_edge_type))
    if step.labels is not None and any(t is None for _, _, t in sides):
        return None
    rows = [
        (indptr[idx], indptr[idx + 1] - indptr[idx]) for indptr, _, _ in sides
    ]
    walked = int(sum(degs.sum() for _, degs in rows))
    if walked >= _SEED_HOP_HOST_MAX_SHARE * len(sides) * csr.num_edges:
        return None
    if sum(float(mult @ degs) for _, degs in rows) >= float(1 << 24):
        return None
    targets, weights = [], []
    for (_, nbr, types), (starts, degs) in zip(sides, rows):
        # the rows' slots, one after the other: each row's start, repeated,
        # plus the offset inside the row
        before = np.cumsum(degs) - degs
        pos = np.repeat(starts - before, degs) + np.arange(degs.sum())
        weight = np.repeat(mult, degs)
        if step.labels is not None:
            keep = np.isin(
                types[pos], np.asarray(step.labels, dtype=types.dtype)
            )
            pos, weight = pos[keep], weight[keep]
        targets.append(nbr[pos])
        weights.append(weight)
    return np.concatenate(targets), np.concatenate(weights), walked


# ----------------------------------------------------------------- planner
#: columns of the wide superstep, the ONE width a batch runs at (absent
#: members' columns are zero): two executables a plan shape, narrow and
#: wide. From the chip (TPU v5e, the served deployment's pack of 2,110,811
#: slots, one whole superstep; PERF.md section 6, PR 37):
#:   start      step ms   served + fetch + h2d of the start, ms
#:   (n,)       15.46     16.69 + 0.69
#:   (n, 2)     12.57     13.93 + 0.85
#:   (n, 4)     13.93     15.55 + 0.97
#:   (n, 8)     12.23     14.13 + 1.52    <- the cheapest, and carries most
#:   (n, 128)   21.16     22.65 + 8.09    (4 live columns sliced on device)
#:   (K, n)     compiles to the program of (n, K)
#: A row of 2-8 words is gathered cheaper than an element; 2 / 4 separate
#: (n,) gathers cost 30.5 / 64.9 ms.
BATCH_WIDTH = 8


@dataclass
class _Request:
    """One spilled request from its arrival to its answer: what it planned
    in its own thread (the entry that stands at the lock), then what the
    dispatch it rode left it."""

    ticket: int
    #: the tracer's clock when it first asked for the lock
    arrived_ns: int
    plan: SpilloverPlan
    #: the snapshot it was planned against, that snapshot's epoch, the
    #: transaction's overlay and the snapshot patched with it (`base`
    #: itself for an empty overlay)
    base: object = None
    epoch: int = -1
    overlay: Optional[dict] = None
    csr: object = None
    #: the (n,) program whose start is the arrival vector of hop 1 (or the
    #: seeds', where hop 0 stays on the device)
    program: object = None
    seed_hop_edges: Optional[int] = None
    #: ms of its own plan
    own_ms: float = 0.0
    #: its column, the dispatch's record (shared by the members), and
    #: whether this request held the lock and ran it
    counts: object = None
    ride: Optional[dict] = None
    led: bool = False
    #: why the dispatch that took it along failed
    refused: Optional[str] = None


def _error_reason(e: BaseException) -> str:
    return f"error:{type(e).__name__}: {e}"[:200]


class SpilloverPlanner:
    """Per-graph spillover state: cached snapshot + epoch, promotion set,
    and the cached single-device executor (compiled step executables
    survive across spilled queries of the same snapshot)."""

    def __init__(self, graph):
        self.graph = graph
        cfg = graph.config
        self.enabled = bool(cfg.get("computer.spillover"))
        self.min_cost_ms = float(cfg.get("computer.spillover-min-cost-ms"))
        self.min_seen = int(cfg.get("computer.spillover-min-seen"))
        self.min_hops = int(cfg.get("computer.spillover-min-hops"))
        self.max_overlay = int(cfg.get("computer.spillover-max-overlay"))
        self.max_staleness = int(cfg.get("computer.spillover-max-staleness"))
        #: the DEVICE's lock, held for one dispatch: the cached executor,
        #: the run, the ledger's release stamp
        self._lock = threading.RLock()
        #: the planner's short lock: the promoted set and the snapshot with
        #: its epoch. Taken alone by an arriving request (which must not
        #: wait behind a dispatch to plan), and inside the device's lock by
        #: the holder's freshness check; never the other way round
        self._state = threading.Lock()
        #: the lock's ledger (`_lock_taken`): arrival tickets, the arrival
        #: stamp of every request that stands at the lock (at either take),
        #: and the stamp the last holder left at its release
        self._tickets = itertools.count()
        self._waiting: Dict[int, int] = {}
        self._released_ns: Optional[int] = None
        #: the planned requests that stand at the lock for a dispatch, by
        #: ticket: whoever holds the lock takes the compatible ones along
        self._pending: Dict[int, "_Request"] = {}
        #: guards the per-digest tallies of `_promoted`, which requests
        #: write from their own threads
        self._tally = threading.Lock()
        #: the step chains whose wide step the cached executor has run
        self._wide_ready: set = set()
        #: the host array a batch's start is stacked in
        self._stage = None
        self._csr = None
        self._epoch = -1
        self._tpu_ex = None
        self._promoted: Dict[str, dict] = {}

    # ------------------------------------------------------------ promotion
    def _check_promotion(self, digest: str, shape: str) -> bool:
        """Sticky promotion against the digest table's measured means.
        Call under `_state`."""
        if digest in self._promoted:
            return True
        from janusgraph_tpu.observability import registry
        from janusgraph_tpu.observability.profiler import digest_table

        mean = digest_table.mean_cost_ms(digest)
        if mean is None or mean < self.min_cost_ms:
            return False
        with digest_table._lock:
            entry = digest_table._entries.get(digest)
            seen = entry["count"] if entry else 0
        if seen < self.min_seen:
            return False
        self._promoted[digest] = {
            "shape": shape, "mean_ms_at_promotion": round(mean, 3),
            "seen_at_promotion": seen, "spilled": 0, "fallbacks": 0,
        }
        with _PROMOTED_LOCK:
            _PROMOTED_GLOBAL.add(digest)
        registry.counter("olap.spillover.promotions").inc()
        # graphlint: disable=JG110 -- digest is bounded by the top-K-evicted price book (metrics.digest-top-k) that feeds promotion
        registry.set_gauge(f"olap.spillover.promoted.{digest}", 1.0)
        registry.set_gauge(
            "olap.spillover.promoted_digests", float(len(self._promoted))
        )
        from janusgraph_tpu.observability import flight_recorder

        flight_recorder.record(
            "spillover", action="promoted", digest=digest,
            mean_ms=round(mean, 3), seen=seen,
        )
        return True

    def promotion_snapshot(self) -> dict:
        with self._state, self._tally:
            return {d: dict(s) for d, s in self._promoted.items()}

    # ------------------------------------------------------------- snapshot
    def _snapshot(self):
        """The current committed-graph CSR: packed on first use, refreshed
        O(delta) from the change capture's records (zero store reads;
        olap/delta.py) while the pending overlay stays within the
        staleness bound, dropped for repack beyond it. Without a capture
        the PR 12 whole-row re-derivation (refresh_csr) remains the
        fallback. Call under `_state`. The cached executor is the
        device lock's: the next dispatch replaces one built on a snapshot
        that has gone (`_run_program`)."""
        from janusgraph_tpu.observability import registry

        backend = self.graph.backend
        if self._csr is None:
            from janusgraph_tpu.olap.csr import load_csr_snapshot

            self._csr, self._epoch = load_csr_snapshot(self.graph)
            registry.counter("olap.spillover.packs").inc()
            registry.set_gauge("olap.spillover.staleness", 0.0)
            return self._csr
        now = backend.mutation_epoch()
        if now == self._epoch:
            registry.set_gauge("olap.spillover.staleness", 0.0)
            return self._csr
        # the freshness signal the SLO engine samples over time (the
        # PR 13 spec reads this gauge unchanged): the DELTA-OVERLAY LAG —
        # pending captured records when the capture can serve, else
        # distinct touched rows. Both dedupe repeated touches of one row
        # per (tx, row) (the tracker's per-row epoch map), so a workload
        # hammering the same rows no longer inflates staleness one epoch
        # per commit and forces spurious full repacks near the bound.
        cap = getattr(self.graph, "change_capture", None)
        lag = cap.depth_since(self._epoch) if cap is not None else None
        if lag is None:
            rows = backend.touched_count_since(self._epoch)
            lag = rows if rows is not None else (now - self._epoch)
        registry.set_gauge("olap.spillover.staleness", float(lag))
        if lag > self.max_staleness:
            # beyond the bound a full repack beats an incremental
            # refresh; THIS query falls back, the next attempt repacks
            registry.counter("olap.spillover.stale").inc()
            self._csr = None
            raise _SpillRefused("stale")
        if lag == 0:
            # property-only writes bumped the epoch but changed no
            # structure; the capture append shares the epoch lock, so a
            # zero depth at `now` proves nothing is pending
            self._epoch = now
            registry.set_gauge("olap.spillover.staleness", 0.0)
            return self._csr
        refreshed = None
        if cap is not None:
            from janusgraph_tpu.olap import delta as _delta_mod

            got = _delta_mod.overlay_since(self.graph, self._epoch)
            if got is not None:
                ov, upto = got
                registry.set_gauge(
                    "olap.delta.overlay_depth", float(ov.size)
                )
                try:
                    refreshed = (
                        _delta_mod.materialize(
                            self._csr, ov, idm=self.graph.idm,
                        )
                        if ov.size else self._csr,
                        upto if ov.size else now,
                    )
                    registry.counter(
                        "olap.spillover.delta_refreshes"
                    ).inc()
                except ValueError:
                    refreshed = None  # filtered/weighted snapshot
        if refreshed is None:
            from janusgraph_tpu.olap.csr import refresh_csr

            refreshed = refresh_csr(self.graph, self._csr, self._epoch)
        self._csr, self._epoch = refreshed
        registry.counter("olap.spillover.refreshes").inc()
        registry.set_gauge("olap.spillover.staleness", 0.0)
        return self._csr

    # ------------------------------------------------------------ execution
    def maybe_execute(self, traversal, terminal=None):
        """The planner hook body: None = run the row path. For
        ``terminal="count"`` returns the int count; otherwise the final
        traverser list.

        A spilled request does its OWN work in its own thread: the plan
        (overlay, patch, the seed hop on the host) before it queues,
        against the snapshot a short take of the lock gave it; the fold of
        its own column and its records after. The lock is held per
        DISPATCH: whoever holds it takes the compatible requests that
        stand there and runs them with itself, their arrival vectors the
        columns of one superstep (`_lead`)."""
        steps = traversal._steps
        n_hops = sum(
            1 for s in steps if getattr(s, "_expand_meta", None) is not None
        )
        if n_hops < self.min_hops:
            return None  # the row path pays for no phase
        from janusgraph_tpu.observability import tracer

        with tracer.phase("spill.recognize"):
            plan, reason = recognize(traversal, terminal)
            if plan is None:
                # not compilable: only a PROMOTED shape's refusal is an
                # event
                shape, digest = traversal_digest(traversal)
                with self._state:
                    hot = digest in self._promoted
                if hot:
                    return self._fallback(digest, f"unsupported:{reason}")
                return None
        from janusgraph_tpu.exceptions import (
            DeadlineExceededError,
            QueryError,
            ServerOverloadedError,
        )

        ticket = next(self._tickets)
        try:
            request = self._ride(traversal, plan, ticket)
            if request is None:
                return None
            return self._answer(traversal, request, terminal)
        except ServerOverloadedError:
            return self._fallback(plan.digest, "brownout")
        except _SpillRefused as e:
            return self._fallback(plan.digest, e.reason)
        except (QueryError, DeadlineExceededError):
            # semantic refusals (traverser budget, expired deadline)
            # are the QUERY's errors, not planner defects — the row
            # path would raise the same way, so surface them directly
            raise
        except Exception as e:  # noqa: BLE001 - fallback IS the
            # contract: a planner defect must degrade to the row walk,
            # never fail the query (the flight event + counter keep it
            # visible)
            return self._fallback(plan.digest, _error_reason(e))
        finally:
            self._waiting.pop(ticket, None)
            self._pending.pop(ticket, None)

    def _ride(self, traversal, plan: SpilloverPlan, ticket: int):
        """From the request's arrival to its column: the `_Request` with
        `counts` and `ride` set, or None for the row path.

        First the planner's short lock, `_state`, which no dispatch holds
        (promotion, rung-2 admission, the deadline, the snapshot with its
        freshness check), then the plan against that snapshot in the
        request's own thread, beside whatever dispatch is running, then
        the stand at the device's lock until the request has its column:
        from the dispatch of whoever held the lock meanwhile, or from its
        own (`_lead`) once it holds the lock itself.

        ONE wait phase runs from before the first take until the column
        is there or the lock is held to dispatch (the plan's phase
        suspends it): a member's wait for its column is `spill.lock_wait`
        too. As a wait it is timed, never a trace event, and the lock's
        ledger keeps to the same rule: a ticket beside the stamp the phase
        has just read, the rest under the hold that dispatches
        (`_lock_taken`) and just before its release, and nothing while a
        request stands at the lock: a few microseconds between a release
        and the next take decide who wakes into it (PERF.md section 6,
        finding
        1). No timer and no sleep: a holder takes who is there."""
        from janusgraph_tpu.core import deadline as _deadline
        from janusgraph_tpu.observability import tracer
        from janusgraph_tpu.server.admission import check_olap_admission

        with contextlib.ExitStack() as waiting:
            wait = waiting.enter_context(
                tracer.phase("spill.lock_wait", wait=True))
            self._waiting[ticket] = wait.start_ns
            with self._state:
                if not self._check_promotion(plan.digest, plan.shape):
                    return None
                check_olap_admission()
                _deadline.check("spillover compile")
                base, epoch = self._snapshot(), self._epoch
            request = _Request(ticket, wait.start_ns, plan)
            self._plan(traversal, request, base, epoch)
            self._pending[ticket] = request
            with self._lock:
                if request.ride is None and request.refused is None:
                    waiting.close()  # the lock is held: the wait is over
                    try:
                        self._lead(traversal, request, wait.end_ns)
                    finally:
                        self._released_ns = tracer.now_ns()
                    return request
        # a member of somebody's dispatch
        _deadline.check("spillover run")
        if request.refused is not None:
            raise _SpillRefused(request.refused)
        return request

    def _plan(self, traversal, request: "_Request", base, epoch) -> None:
        """What depends on the request alone, against the snapshot `base`:
        the transaction's overlay, the patch, the program with hop 0 read
        off the seeds' rows (`_compile`)."""
        from janusgraph_tpu.observability import tracer

        t0 = time.perf_counter()
        with tracer.phase("spill.plan"):
            overlay = tx_overlay(traversal.tx)
            if overlay["size"] > self.max_overlay:
                raise _SpillRefused("overlay-overflow")
            request.base, request.epoch, request.overlay = base, epoch, overlay
            request.csr = patched_csr(base, overlay)
            request.program, request.seed_hop_edges = self._compile(
                request.plan, request.csr, overlay)
        request.own_ms += (time.perf_counter() - t0) * 1000.0

    def _lead(self, traversal, request: "_Request", held_ns: int) -> None:
        """One dispatch, under the lock: this request and the compatible
        ones that stand at the lock, as the columns of one program.

        Compatible is what the code can observe: built against the
        snapshot the freshness check returns NOW (so every member's answer
        is computed on a snapshot checked after its arrival), no
        transaction overlay (a patched snapshot is one transaction's), the
        same remaining steps, each a plain chain from its own arrival
        vector (`OLAPTraversalProgram.stackable`). Anything else, and a
        request that finds nobody, runs alone as the (n,) program. The
        holder takes who is there: it waits for nobody."""
        import numpy as np

        from janusgraph_tpu.core import deadline as _deadline
        from janusgraph_tpu.observability import registry, tracer
        from janusgraph_tpu.olap.programs.olap_traversal import (
            OLAPTraversalProgram,
        )

        _deadline.check("spillover run")
        self._pending.pop(request.ticket, None)
        t0 = time.perf_counter()
        with tracer.phase("spill.plan"):
            with self._state:
                base, epoch = self._snapshot(), self._epoch
            if request.base is not base:
                # a commit was refreshed in since this request planned:
                # its program is of an older snapshot than this check
                # accepts
                self._plan(traversal, request, base, epoch)
            csr, program = request.csr, request.program
            members = [request]
            if csr is base and program.stackable():
                # a copy: arrivals write the dict whoever holds the lock
                members += [
                    r for r in list(self._pending.values())
                    if r.csr is base and r.program.stackable()
                    and r.program.steps == program.steps
                ][:BATCH_WIDTH - 1]
            for r in members[1:]:
                del self._pending[r.ticket]
            ride = self._lock_taken(request, members, held_ns)
            for r in members:
                self._waiting.pop(r.ticket, None)
            try:
                if len(members) > 1:
                    # stacked in an array kept between dispatches (this
                    # dispatch is over, its start copied and its columns
                    # fetched, before the next holder stacks): a fresh 4
                    # MB array is a thousand page faults in the served
                    # process, 0.5 ms a dispatch under the lock (PERF.md
                    # section 6, PR 37). Columns nobody rides keep an old
                    # start: nobody reads them
                    if self._stage is None or len(self._stage) != (
                        csr.num_vertices
                    ):
                        self._stage = np.zeros(
                            (csr.num_vertices, BATCH_WIDTH), np.float32)
                    program = OLAPTraversalProgram.stacked(
                        [r.program for r in members], BATCH_WIDTH,
                        out=self._stage,
                    )
                with tracer.span(
                    "olap.spillover", digest=request.plan.digest,
                    hops=len(request.plan.hops), batch=len(members),
                ):
                    states = self._run_program(
                        csr, program, patched=csr is not base)
                registry.counter("olap.spillover.dispatches").inc()
                counts = states["count"]
                olap_run = registry.last_run("olap") or {}
                ride.update(
                    executor=olap_run.get("path"),
                    supersteps=olap_run.get("supersteps"),
                )
                ex, steps = self._tpu_ex, request.program.steps
                if (
                    ex is not None and ex.csr is csr
                    and request.program.stackable()
                    and steps not in self._wide_ready
                ):
                    # the first dispatch of its steps on this executor:
                    # if it ran alone, the wide step is prepared beside
                    # the narrow one now (a run, not a dispatch), so that
                    # no batch compiles in front of a waiting client
                    self._wide_ready.add(steps)
                    if len(members) == 1:
                        self._run_program(
                            csr,
                            OLAPTraversalProgram.stacked(
                                [program], BATCH_WIDTH),
                            patched=False,
                        )
            except BaseException as e:
                # the dispatch failed for every member: each falls back,
                # and is counted, in its own thread
                for r in members[1:]:
                    r.refused = _error_reason(e)
                raise
            ride["wall_ms"] = (time.perf_counter() - t0) * 1000.0
            request.led = True
            for j, r in enumerate(members):
                r.counts = counts if len(members) == 1 else counts[:, j]
                r.ride = ride

    def _lock_taken(self, request, members, held_ns: int) -> dict:
        """The ledger's entry for one hold of the lock that dispatches,
        made under it once the wait phase has closed: the lock fields of
        the run records of every request the dispatch carries.

        ``queue_depth`` is how many other requests stand at the lock (at
        either take, the members this hold takes along included),
        ``overtook`` how many of them arrived before the holder and are
        LEFT standing, ``batch`` the requests of the dispatch. Against
        the stamp the previous holder left at its release, the stretch
        from that release to this hold is a HAND-OFF when this request or
        one that arrived before it had arrived by then (the lock was
        wanted and free, the device idle), else FREE time (nobody was
        asking). Arrival and hold are the wait phases' own reads of the
        tracer's clock: nothing here reads a clock, and the ledger's times
        add up with the phases'. A member that takes the lock only to find
        its column is no hold: it leaves no stamp and no entry."""
        from janusgraph_tpu.observability import registry

        ticket = request.ticket
        riding = {r.ticket for r in members}
        # a copy: arrivals write the dict whoever holds the lock
        others = [
            (t, stamp) for t, stamp in list(self._waiting.items())
            if t != ticket
        ]
        earlier = [(t, stamp) for t, stamp in others if t < ticket]
        if self._released_ns is not None:
            # both looked up, so that a reader finds both names
            handoff = registry.timer("spill.lock_handoff")
            free = registry.timer("spill.lock_free")
            wanted = min(
                (stamp for _, stamp in earlier), default=request.arrived_ns
            ) < self._released_ns
            (handoff if wanted else free).update(
                held_ns - self._released_ns)
        return {
            "queue_depth": len(others),
            "overtook": sum(1 for t, _ in earlier if t not in riding),
            "batch": len(members),
        }

    def _answer(self, traversal, request: "_Request", terminal):
        """The request's own column folded into the chain's output, and
        its records, in its own thread."""
        import numpy as np

        from janusgraph_tpu.observability import tracer

        t0 = time.perf_counter()
        plan = request.plan
        with tracer.phase("spill.reduce"):
            counts = np.asarray(request.counts, dtype=np.float64)
            if counts.size and counts.max() >= float(1 << 24):
                # per-vertex traverser counts ride float32 on device —
                # exact only below 2^24; past it the row walk is the
                # honest answer
                raise _SpillRefused("count-overflow")
            result, total = self._reduce(
                traversal, plan, request.csr, counts, terminal
            )
        # the request's service: its own plan and fold, and the whole
        # dispatch it rode (no wait for the lock)
        wall_ms = (
            request.own_ms + request.ride["wall_ms"]
            + (time.perf_counter() - t0) * 1000.0
        )
        with tracer.phase("spill.publish"):
            self._publish(request, terminal, wall_ms, total)
        return result

    def _publish(self, request, terminal, wall_ms, total) -> None:
        """The spilled execution still feeds the digest table (the
        shape's new, cheap reality) and the ambient span, like the row
        path; then counters, the run record and the flight event. The
        lock's two counters are a DISPATCH's, written by the request that
        led it."""
        from janusgraph_tpu.observability import (
            flight_recorder,
            registry,
            tracer,
        )
        from janusgraph_tpu.observability.profiler import digest_table

        plan, overlay, ride = request.plan, request.overlay, request.ride
        seed_hop_edges = request.seed_hop_edges
        digest_table.observe(plan.digest, plan.shape, wall_ms)
        cur = tracer.current()
        if cur is not None:
            cur.annotate(digest=plan.digest, spillover=True)
        with self._tally:
            stats = self._promoted.get(plan.digest)
            if stats is not None:
                stats["spilled"] += 1
        registry.counter("olap.spillover.spilled").inc()
        if request.led:
            registry.counter("olap.spillover.lock.waiters_seen").inc(
                ride["queue_depth"])
            registry.counter("olap.spillover.lock.overtakes").inc(
                int(ride["overtook"] > 0))
        # graphlint: disable=JG110 -- digest is bounded by the top-K-evicted price book (metrics.digest-top-k) that feeds promotion
        registry.counter(f"olap.spillover.spilled.{plan.digest}").inc()
        if seed_hop_edges is not None:
            registry.counter("olap.spillover.seed_hop_host").inc()
        block = {
            "digest": plan.digest,
            "shape": plan.shape,
            "hops": len(plan.hops),
            "seed_hop": "device" if seed_hop_edges is None else "host",
            "seed_hop_edges": seed_hop_edges or 0,
            "reducer": self._reducer_name(plan, terminal),
            "overlay": {
                "added": len(overlay["added"]),
                "deleted": len(overlay["deleted"]),
                "new_vertices": len(overlay["new_vertices"]),
                "removed": len(overlay["removed"]),
            },
            "snapshot_epoch": request.epoch,
            "wall_ms": round(wall_ms, 3),
            "result_total": total,
            "fallback": None,
            "queue_depth": ride["queue_depth"],
            "overtook": ride["overtook"],
            "batch": ride["batch"],
            "led": request.led,
        }
        run_info = {
            "spillover": block,
            "executor": ride["executor"],
            "supersteps": ride["supersteps"],
        }
        registry.record_run("olap.spillover", run_info)
        flight_recorder.record(
            "spillover", action="spilled", digest=plan.digest,
            hops=len(plan.hops), overlay=overlay["size"],
            wall_ms=round(wall_ms, 3), total=total,
        )

    def _reducer_name(self, plan: SpilloverPlan, terminal) -> str:
        parts = []
        if plan.distinct:
            parts.append("dedup")
        if plan.as_ids:
            parts.append("id")
        if plan.count_step or terminal == "count":
            parts.append("count")
        return ">".join(parts) if parts else "vertices"

    def _compile(self, plan: SpilloverPlan, csr, overlay):
        """(program, edges the host walked for hop 0 or None): a chain of
        two hops or more from explicit ids has hop 0 expanded here
        (:func:`host_seed_hop`) and hands the device the hops that
        remain."""
        import numpy as np

        from janusgraph_tpu.olap.programs.olap_traversal import (
            OLAPTraversalProgram,
            steps_from_spec,
        )

        spec = [(d, list(labels) if labels else None) for d, labels, _ in plan.hops]
        try:
            steps = steps_from_spec(self.graph, spec)
        except ValueError:
            # an edge label the schema has never seen matches nothing on
            # the row path — keep that semantics there
            raise _SpillRefused("unknown-edge-label")
        n = csr.num_vertices
        seed_mask = None
        seed_rows = []
        if plan.seed_ids is not None:
            seed_mask = np.zeros(n, dtype=np.float32)
            for vid in plan.seed_ids:
                i = int(np.searchsorted(csr.vertex_ids, vid))
                if i < n and csr.vertex_ids[i] == vid and (
                    vid not in overlay["removed"]
                ):
                    # V(1, 1) seeds two traversers: the mask carries
                    # MULTIPLICITY, not membership
                    seed_mask[i] += 1.0
                    seed_rows.append(i)
        if plan.seed_labels:
            lm = self._label_mask(csr, plan.seed_labels)
            seed_mask = lm if seed_mask is None else seed_mask * lm
        if overlay["removed"]:
            rm = np.asarray(sorted(overlay["removed"]), dtype=np.int64)
            pos = np.searchsorted(csr.vertex_ids, rm)
            ok = (pos < n) & (csr.vertex_ids[np.minimum(pos, n - 1)] == rm)
            if seed_mask is None:
                seed_mask = np.ones(n, dtype=np.float32)
            seed_mask[pos[ok]] = 0.0
        step_masks = None
        if any(vlabels for _, _, vlabels in plan.hops):
            cols = [
                self._label_mask(csr, vlabels)
                if vlabels
                else np.ones(n, dtype=np.float32)
                for _, _, vlabels in plan.hops
            ]
            step_masks = np.stack(cols, axis=1)
        seed_hop_edges = None
        if plan.seed_ids is not None and len(steps) > 1:
            # a seed the label mask or the overlay zeroed walks its row
            # with weight 0
            idx = np.asarray(sorted(set(seed_rows)), dtype=np.int64)
            hop = host_seed_hop(csr, idx, seed_mask[idx], steps[0])
            if hop is not None:
                targets, weights, seed_hop_edges = hop
                # the seeds' vector becomes the arrivals' in place (every
                # partial sum an integer below 2^24: exact in float32)
                seed_mask[idx] = 0.0
                np.add.at(seed_mask, targets, weights)
                steps = steps[1:]
                if step_masks is not None:
                    seed_mask *= step_masks[:, 0]
                    step_masks = step_masks[:, 1:]
        program = OLAPTraversalProgram(
            steps, seed_mask=seed_mask, step_masks=step_masks
        )
        return program, seed_hop_edges

    def _label_mask(self, csr, label_groups):
        """AND over has_label() groups: each group is an OR of vertex
        label NAMES (unknown names match nothing, like the row filter)."""
        import numpy as np

        n = csr.num_vertices
        if csr.labels is None:
            raise _SpillRefused("no-label-column")
        mask = np.ones(n, dtype=np.float32)
        for group in label_groups:
            ids = []
            for name in group:
                el = self.graph.schema_cache.get_by_name(name)
                if el is not None:
                    ids.append(el.id)
            m = (
                np.isin(csr.labels, np.asarray(ids, dtype=np.int64))
                if ids
                else np.zeros(n, dtype=bool)
            )
            mask *= m.astype(np.float32)
        return mask

    def _run_program(self, csr, program, patched: bool):
        """Route like graph.compute(): the configured executor, with
        computer.sharded-auto sending multi-device processes to the
        sharded executor. The single-device executor is CACHED per
        snapshot so compiled step executables survive across spilled
        queries (patched-snapshot runs use a throwaway executor — the
        patch is per transaction)."""
        cfg = self.graph.config
        executor = cfg.get("computer.executor")
        if executor == "tpu" and cfg.get("computer.sharded-auto"):
            try:
                import jax

                ndev = len(jax.devices())
            except Exception:  # noqa: BLE001 - jax may be uninitialized
                ndev = 1
            from janusgraph_tpu.olap.routes import has_superstep_form

            if ndev > 1 and getattr(
                program, "sharded_compatible", True
            ) and has_superstep_form(program):
                executor = "sharded"
        if executor == "tpu":
            from janusgraph_tpu.olap.tpu_executor import TPUExecutor

            if patched:
                return TPUExecutor(csr).run(program)
            if self._tpu_ex is None or self._tpu_ex.csr is not csr:
                self._tpu_ex = TPUExecutor(csr)
                self._wide_ready = set()
            return self._tpu_ex.run(program)
        from janusgraph_tpu.olap.computer import run_on

        kwargs = {}
        if executor == "sharded":
            kwargs = {
                "exchange": cfg.get("computer.exchange"),
                "agg": cfg.get("computer.agg"),
                "frontier_tier_growth": cfg.get(
                    "computer.frontier-tier-growth"
                ),
            }
        return run_on(csr, program, executor, **kwargs)

    def _reduce(self, traversal, plan: SpilloverPlan, csr, counts, terminal):
        """Fold the per-vertex traverser counts into the chain's output:
        (result, total). ``result`` is an int for the count() terminal,
        else the final traverser list."""
        import numpy as np

        from janusgraph_tpu.core.traversal import Traverser

        if plan.distinct:
            mult = (counts > 0).astype(np.int64)
        else:
            mult = np.rint(counts).astype(np.int64)
        total = int(mult.sum())
        if plan.count_step:
            # count as a STEP yields one int traverser; the count()
            # TERMINAL over it is its len (= 1), like the row path
            if terminal == "count":
                return 1, total
            return [Traverser(total)], total
        if terminal == "count":
            return total, total
        cap = getattr(self.graph, "_max_traversers", 0)
        if cap and total > cap:
            # the row walk would have refused this frontier size — the
            # spilled path must not bypass the budget on MATERIALIZED
            # output (count terminals never materialize)
            from janusgraph_tpu.exceptions import QueryError

            raise QueryError(
                f"traverser count {total} exceeds query.max-traversers "
                f"({cap}) in spilled traversal"
            )
        idxs = np.nonzero(mult)[0]
        out: List[Traverser] = []
        if plan.as_ids:
            for i in idxs:
                vid = int(csr.vertex_ids[i])
                out.extend(Traverser(vid) for _ in range(int(mult[i])))
            return out, total
        tx = traversal.tx
        for i in idxs:
            v = _vertex_handle(tx, int(csr.vertex_ids[i]))
            if v is None:
                continue
            out.extend(Traverser(v) for _ in range(int(mult[i])))
        return out, total

    # ------------------------------------------------------------- fallback
    def _fallback(self, digest: str, reason: str):
        from janusgraph_tpu.observability import flight_recorder, registry

        registry.counter("olap.spillover.fallback").inc()
        head = reason.split(":", 1)[0]
        # graphlint: disable=JG110 -- head is the fixed refusal-reason vocabulary (unsupported/overlay/stale/brownout/overflow/error)
        registry.counter(f"olap.spillover.fallback.{head}").inc()
        with self._tally:
            stats = self._promoted.get(digest)
            if stats is not None:
                stats["fallbacks"] += 1
        flight_recorder.record(
            "spillover_fallback", digest=digest, reason=reason,
        )
        registry.record_run("olap.spillover", {
            "spillover": {"digest": digest, "fallback": reason},
        })
        return None


def _vertex_handle(tx, vid: int):
    """A Vertex handle for a vid the snapshot (or tx overlay) proved
    alive — tx.get_vertex minus the per-vid existence read, sharing the
    tx vertex cache so spilled results alias the row path's handles."""
    from janusgraph_tpu.core.elements import LifeCycle, Vertex

    with tx._lock:
        v = tx._vertex_cache.get(vid)
        if v is not None:
            return None if v.is_removed else v
        if vid in tx._removed_vertices:
            return None
        v = Vertex(vid, tx, LifeCycle.LOADED)
        tx._vertex_cache[vid] = v
    return v


# ------------------------------------------------------------------ the hook
def try_spill(traversal, terminal=None):
    """GraphTraversal's planner hook: spilled result, or None to run the
    row-by-row path. Never raises planner-internal errors (fallback is
    the contract); QueryError from budget enforcement propagates like
    the row path's own."""
    source = getattr(traversal, "source", None)
    graph = getattr(source, "graph", None) if source is not None else None
    planner = getattr(graph, "spillover_planner", None)
    if planner is None or not planner.enabled:
        return None
    start = traversal._start
    if start is None or type(start).__name__ != "_start_vertices":
        return None
    return planner.maybe_execute(traversal, terminal)
