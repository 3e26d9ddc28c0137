"""Profiler-driven autotuner: close the measurement -> pack-shape loop.

PR 5 made superstep cost visible (XLA ``cost_analysis`` flops/bytes,
%-roofline per E_cap tier, pad ratios in every run record); this module
CONSUMES it. Given a graph's degree statistics, the device kind's roofline
peaks (observability/profiler.py), the ``computer.autotune-*`` config
overrides, and optionally a prior run's measurements, it decides:

  * the hybrid pack's **hub cutoff** and **tail chunk** (olap/kernels.py
    HybridPack, the single-device executor's one aggregation structure;
    searched over pow2 candidates against a bytes/peak_bw +
    flops/peak_flops time model);
  * the frontier **tier schedules** (F_cap/E_cap ladders) for the
    ShortestPath/CC special case — sized from the degree histogram and a
    tier-count budget instead of today's fixed power-of-two growth.

Decisions are DETERMINISTIC: ``decide()`` is a pure function of
(GraphStats, device_kind, overrides, measured) — same inputs, same
AutotuneDecision, asserted by tests. The executor records the decision in
``run_info["autotune"]``. The mesh's exchange and aggregation are
``decide_sharded``'s, below.

The graph-kernel literature motivates both levers (PAPERS.md):
arXiv:2011.08451 (propagation blocking) shows format/preprocessing choice
dominates graph-kernel bandwidth; arXiv:2011.06391 (FusedMM) shows one
tuned kernel shape serves many workloads once the layout is right.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import numpy as np


def _next_pow2(v: int) -> int:
    return 1 << max(0, int(v) - 1).bit_length() if v > 1 else 1


#: pow2 hub-cutoff candidates the model searches (bounded so stats stay
#: small and the decision cheap)
CUTOFF_CANDIDATES = tuple(1 << k for k in range(3, 11))  # 8 .. 1024


@dataclass(frozen=True)
class GraphStats:
    """Degree-distribution summary the tuner decides from. Everything is
    precomputed here (one numpy pass over the degree vector) so
    ``decide()`` itself is pure integer/float arithmetic."""

    num_vertices: int
    num_edges: int          # per packed orientation (2x |E| when undirected)
    weighted: bool
    max_degree: int
    mean_degree: float
    #: log2-bucket in-degree histogram: hist[k] = #vertices with
    #: 2^(k-1) < deg <= 2^k (hist[0] = deg 0 plus deg 1)
    degree_hist: Tuple[int, ...]
    #: pure-ELL slot count (pow2 bucket rounding, supernode row-split)
    ell_slots: int
    #: candidate hub cutoff -> (cutoff, hybrid gathered slots, hub count,
    #: torso bucket count, tail chunk rows) — the closed-form HybridPack
    #: footprint per cutoff; chunk rows price the tail's partial-table
    #: scatter, the term that punishes small chunks (host XLA, s18 sweep:
    #: 132k chunks = 23.8 ms/superstep vs 6k chunks = 14.4 ms at equal pad)
    hybrid_by_cutoff: Tuple[Tuple[int, int, int, int, int], ...]

    @classmethod
    def from_degrees(
        cls, deg: np.ndarray, num_edges: int, weighted: bool,
        max_capacity: int = 1 << 14, tail_chunk: int = 256,
        hub_cutoff: int = None,
    ) -> "GraphStats":
        """`hub_cutoff`: a forced cutoff (computer.autotune-hub-cutoff)
        to price beside the pow2 candidates."""
        deg = np.asarray(deg, dtype=np.int64)
        n = len(deg)
        maxd = int(deg.max()) if n else 0
        caps = np.maximum(
            1, 1 << np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64)
        )
        capped = np.minimum(caps, max_capacity)
        ell_slots = int(capped.sum())
        over = deg > max_capacity
        if over.any():
            ell_slots += int((deg[over] - max_capacity).sum())
        hist_bins = np.zeros(36, dtype=np.int64)
        if n:
            k = np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64)
            np.add.at(hist_bins, np.minimum(k, 35), 1)
        hyb = []
        cutoffs = CUTOFF_CANDIDATES
        if hub_cutoff and hub_cutoff not in cutoffs:
            cutoffs += (int(hub_cutoff),)
        for cutoff in cutoffs:
            torso = (deg >= 1) & (deg <= cutoff)
            hub = deg > cutoff
            t = min(tail_chunk, _next_pow2(cutoff + 1), max_capacity)
            chunk_rows = int((-(-deg[hub] // t)).sum())
            slots = int(deg[torso].sum()) + chunk_rows * t
            torso_buckets = int(len(np.unique(deg[torso]))) if torso.any() else 0
            hyb.append(
                (cutoff, slots, int(hub.sum()), torso_buckets, chunk_rows)
            )
        return cls(
            num_vertices=n,
            num_edges=int(num_edges),
            weighted=bool(weighted),
            max_degree=maxd,
            mean_degree=float(num_edges) / n if n else 0.0,
            degree_hist=tuple(int(x) for x in np.trim_zeros(hist_bins, "b")),
            ell_slots=ell_slots,
            hybrid_by_cutoff=tuple(hyb),
        )

    @classmethod
    def from_csr(cls, csr, undirected: bool = False, **kw) -> "GraphStats":
        deg = np.diff(csr.in_indptr).astype(np.int64)
        edges = csr.num_edges
        if undirected:
            deg = deg + np.diff(csr.out_indptr).astype(np.int64)
            edges *= 2
        return cls.from_degrees(
            deg, edges, weighted=csr.in_edge_weight is not None, **kw
        )


@dataclass(frozen=True)
class AutotuneDecision:
    """One deterministic tuning decision. ``as_dict()`` is the record shape
    stored in ``run_info["autotune"]``."""

    hub_cutoff: int                   # the hybrid pack's two sizes
    tail_chunk: int
    pad_ratio_est: float              # the pack's modeled slots an edge
    f_schedule: Tuple[int, ...]       # frontier F_cap ladder (pow2, asc)
    e_schedule: Tuple[int, ...]       # frontier E_cap ladder (pow2, asc)
    device_kind: str
    source: str                       # model | measured+model
    #: a superstep on the pack as sized ("hybrid") and, as a comparison
    #: that decides nothing, on the pow2-bucketed ELL pack ("ell")
    modeled_ms: Dict[str, float] = field(default_factory=dict)
    #: dense-feature tier input: the program's logical/padded feature dim
    #: (0/None for scalar-message programs)
    feature_dim: int = 0
    feature_tier: Optional[int] = None

    def as_dict(self) -> dict:
        return {
            "hub_cutoff": self.hub_cutoff,
            "tail_chunk": self.tail_chunk,
            "pad_ratio_est": round(self.pad_ratio_est, 4),
            "f_schedule": list(self.f_schedule),
            "e_schedule": list(self.e_schedule),
            "device_kind": self.device_kind,
            "source": self.source,
            "feature_dim": self.feature_dim,
            "feature_tier": self.feature_tier,
            "modeled_ms": {
                k: round(v, 4) for k, v in sorted(self.modeled_ms.items())
            },
        }


#: bytes gathered per slot: idx i32; weighted packs add weight+valid f32
def _bytes_per_slot(weighted: bool) -> int:
    return 12 if weighted else 4


#: The packed layouts' price list, one column per device kind; `decide`
#: prices every pack from the SAME column. cpu: host XLA, the round-6 s18
#: sweep (CPU, before the ledger; bench_artifacts/r6_hybrid_autotune_cpu
#: .jsonl at commit 40a31da). tpu: one v5e,
#: PageRank at Graph500 s17 and s20, ELL against the single-gather hybrid
#: pack at eight (cutoff, chunk) settings (PERF.md section 6, PR 26).

#: fixed cost per distinct bucket. cpu: small, XLA fuses the per-bucket
#: work into one program. tpu: nothing measurable (16 to 374 torso buckets
#: at s20 fit 3.4 us each, 1% of a superstep and inside the fit's error;
#: at s17 the fit's sign flips), since every bucket is a slice of ONE
#: gathered vector
_BUCKET_OVERHEAD_S = {"cpu": 2e-7, "tpu": 0.0}

#: cost per hybrid tail chunk row: a partial-table scatter element and a
#: fold slot on top of its gathered slots. cpu: 126k extra chunks cost
#: ~9.4 ms. tpu: at s20 17k to 1.02M chunks fit 8.3 ns each beside 8.0 ns
#: a slot, to 0.5% on all seven packs
_TAIL_CHUNK_COST_S = {"cpu": 7.5e-8, "tpu": 8e-9}

#: cost per gathered slot — the gather unit is the binding resource, far
#: below what bytes over DRAM bandwidth predict. cpu: ~3.3 ns on both
#: layouts. tpu: 7.29 (s20) and 7.53 (s17) ns on the ELL pack, 8.05 and
#: 7.42 on the hybrid's one gather; the mean, which puts every modeled
#: superstep of the sweep within 7% of its measurement
_GATHER_COST_S = {"cpu": 3.3e-9, "tpu": 7.6e-9}

#: scatter (segment-reduce) effective-bandwidth derating vs the packed
#: gather paths, in `decide_sharded`'s flat aggregations (serialized
#: scatter-add lowering on TPU; cache-hostile on CPU)
_SEGMENT_PENALTY = {"tpu": 8.0, "cpu": 2.5}


def _modeled_seconds(
    slots: int, n: int, weighted: bool, buckets: int, peaks: dict,
    kind: str, eff_bw: Optional[float] = None,
    chunk_rows: int = 0, cols: int = 1,
) -> float:
    """Roofline time model for one superstep of a packed aggregation: the
    binding constraint is max(bytes moved at peak-or-measured bandwidth,
    slots through the gather unit) — the classic two-ceiling roof with the
    gather wall as the second ceiling — plus per-bucket kernel overhead
    and the tail's per-chunk scatter cost. ``cols`` is the message width
    (1 for scalar programs; the padded feature tier for the dense tier —
    each gathered slot moves a d-wide row and the output is (n, d))."""
    cols = max(1, int(cols))
    bw = eff_bw or peaks["peak_bytes_per_s"]
    byts = slots * _bytes_per_slot(weighted) + 4.0 * slots * cols + (
        8.0 * n * cols
    )
    t = max(byts / max(bw, 1.0), slots * _GATHER_COST_S[kind])
    t += slots * cols / max(peaks["peak_flops"], 1.0)
    t += buckets * _BUCKET_OVERHEAD_S[kind]
    # the tail's partial-table scatter moves a cols-wide row per chunk, so
    # its cost scales with the message width (CPU, before the ledger, and
    # on the tail PR 26 replaced: s16 d=32 GCN, hybrid 276.8 ms vs ELL
    # 190.9 ms per superstep; the dense tier has no chip reading)
    t += chunk_rows * cols * _TAIL_CHUNK_COST_S[kind]
    return t


def decide(
    stats: GraphStats,
    device_kind: str,
    overrides: Optional[dict] = None,
    measured: Optional[dict] = None,
    feature_dim: int = 0,
) -> AutotuneDecision:
    """Size the hybrid pack (hub cutoff, tail chunk) and the frontier tier
    schedules for one graph + device. Pure function of its arguments —
    identical inputs give an identical decision (tested), so a recorded
    decision is reproducible from its recorded inputs.

    overrides (the ``computer.autotune-*`` / ``frontier-*`` keys):
      hub_cutoff        force the hub cutoff (0/None = search)
      tail_chunk        tail chunk width (default 256)
      f_min/e_min       smallest frontier tier capacities
      max_tiers         frontier ladder length budget (default 8)
      tier_growth       max ladder growth factor (pow2, default 16)

    measured (a prior run's record — ``registry.last_run("olap")`` shape):
      ``pad_ratio`` + ``superstep_ms`` calibrate the model's effective
      bandwidth (achieved bytes/s replaces the peak table), folding real
      measurements into the next decision; ``roofline_by_tier``
      utilizations refine the frontier ladder (tiers that measured
      near-zero utilization are pruned from the schedule).

    feature_dim (the dense tier's input, 0 for scalar programs): the
      padded lane tier (features/kernels.pick_feature_tier, or the
      ``feature_dim_tier`` override) scales the modeled message traffic —
      every gathered slot moves a d-wide row — and is recorded in the
      decision as ``feature_tier``.
    """
    ov = dict(overrides or {})
    from janusgraph_tpu.observability import profiler

    peaks = profiler.device_peaks(device_kind)
    kind = "tpu" if "tpu" in (device_kind or "").lower() else "cpu"
    tail_chunk = int(ov.get("tail_chunk") or 256)
    feature_dim = int(feature_dim or 0)
    feature_tier = None
    cols = 1
    if feature_dim:
        from janusgraph_tpu.olap.features.kernels import pick_feature_tier

        feature_tier = pick_feature_tier(
            feature_dim, int(ov.get("feature_dim_tier") or 0)
        )
        cols = feature_tier

    n, m = stats.num_vertices, stats.num_edges
    bps = _bytes_per_slot(stats.weighted)

    # measured calibration: achieved bytes/s of the prior run's layout
    eff_bw = None
    source = "model"
    if measured and measured.get("superstep_ms") and measured.get("pad_ratio"):
        meas_slots = float(measured["pad_ratio"]) * m
        meas_bytes = meas_slots * bps + 4.0 * meas_slots * cols + (
            8.0 * n * cols
        )
        eff_bw = meas_bytes / (float(measured["superstep_ms"]) / 1e3)
        source = "measured+model"

    forced_cutoff = int(ov.get("hub_cutoff") or 0) or None
    best = None  # (modeled_s, cutoff, slots)
    for cutoff, slots, hubs, torso_buckets, chunk_rows in (
        stats.hybrid_by_cutoff
    ):
        if forced_cutoff is not None and cutoff != forced_cutoff:
            continue
        t = _modeled_seconds(
            slots, n, stats.weighted,
            torso_buckets + (1 if hubs else 0), peaks, kind,
            eff_bw=eff_bw, chunk_rows=chunk_rows, cols=cols,
        )
        if best is None or t < best[0]:
            best = (t, cutoff, slots)
    if best is None:
        raise ValueError(
            f"hub cutoff {forced_cutoff} is not among the statistics' "
            "candidates: build GraphStats with hub_cutoff set to it"
        )
    hyb_s, hyb_cutoff, hyb_slots = best
    ell_s = _modeled_seconds(
        stats.ell_slots, n, stats.weighted,
        max(1, len(stats.degree_hist)), peaks, kind, eff_bw=eff_bw,
        cols=cols,
    )

    f_sched, e_sched = decide_tiers(stats, ov, measured)
    return AutotuneDecision(
        hub_cutoff=hyb_cutoff,
        tail_chunk=min(tail_chunk, _next_pow2(hyb_cutoff + 1)),
        pad_ratio_est=hyb_slots / max(1, m),
        f_schedule=f_sched,
        e_schedule=e_sched,
        device_kind=device_kind or "cpu",
        source=source,
        feature_dim=feature_dim,
        feature_tier=feature_tier,
        modeled_ms={"ell": ell_s * 1e3, "hybrid": hyb_s * 1e3},
    )


#: modeled launch latency per message-carrying collective (one batch) —
#: the term that punishes the ring's S-1 ppermute batches per superstep
_COLLECTIVE_LAUNCH_S = {"cpu": 2e-5, "tpu": 5e-6}


@dataclass(frozen=True)
class ShardedDecision:
    """One deterministic per-shard-layout decision (the mesh analogue of
    AutotuneDecision), keyed by shard count. ``as_dict()`` is the record
    shape stored in ``run_info["autotune"]`` on sharded runs."""

    exchange: str                 # blocked | a2a | ring | gather
    agg: str                      # ell | segment
    halo_cap: int                 # pow2 bin tier (blocked exchange)
    boundary_width: int           # eager a2a bucket width B
    shard_count: int
    device_kind: str
    source: str                   # model | config | measured+model
    modeled_ms: Dict[str, float] = field(default_factory=dict)
    feature_tier: Optional[int] = None

    def as_dict(self) -> dict:
        return {
            "exchange": self.exchange,
            "agg": self.agg,
            "halo_cap": self.halo_cap,
            "boundary_width": self.boundary_width,
            "shard_count": self.shard_count,
            "device_kind": self.device_kind,
            "source": self.source,
            "feature_tier": self.feature_tier,
            "modeled_ms": {
                k: round(v, 4) for k, v in sorted(self.modeled_ms.items())
            },
        }


def decide_sharded(
    stats: GraphStats,
    device_kind: str,
    num_shards: int,
    widths: dict,
    overrides: Optional[dict] = None,
    measured: Optional[dict] = None,
    feature_dim: int = 0,
) -> ShardedDecision:
    """Pick the sharded executor's per-shard layout — exchange strategy +
    aggregation + pow2 halo-bin tier — for one (graph, device, SHARD
    COUNT). Pure function of its arguments (tested), so a recorded
    decision is reproducible from its recorded inputs.

    ``widths`` is halo.pair_widths' output: the eager boundary width B
    (distinct cross-shard sources any pair ships) vs the blocked halo
    width (distinct cross-shard destinations any pair merges into) plus
    the pow2 ``halo_cap`` tier.

    The per-superstep model per shard: local aggregation work (slots
    through the gather unit, ELL pays its pad ratio, blocked adds the
    S*Hc receiver scatter-combine), exchange payload at peak-or-measured
    bandwidth, and a launch cost per message-carrying collective — the
    term that charges the ring its S-1 batches. ``measured`` (the v2
    shard-count-keyed record) calibrates effective bandwidth exactly like
    ``decide()``; an explicit ``overrides["exchange"]`` forces the layout
    (source="config")."""
    ov = dict(overrides or {})
    from janusgraph_tpu.observability import profiler

    peaks = profiler.device_peaks(device_kind)
    kind = "tpu" if "tpu" in (device_kind or "").lower() else "cpu"
    S = max(1, int(num_shards))
    n, m = stats.num_vertices, stats.num_edges
    Np = -(-max(n, 1) // S)
    Em = max(1, m // S)
    cols = 1
    feature_tier = None
    if feature_dim:
        from janusgraph_tpu.olap.features.kernels import pick_feature_tier

        feature_tier = pick_feature_tier(int(feature_dim), 0)
        cols = feature_tier
    B = max(1, int(widths.get("boundary_width") or 1))
    Hc = max(1, int(widths.get("halo_cap") or 1))

    bw = peaks["peak_bytes_per_s"]
    source = "model"
    if measured and measured.get("superstep_ms"):
        # achieved bytes/s of the prior run's layout at this shard count
        meas_bytes = Em * (4.0 + 4.0 * cols) + 8.0 * Np * cols
        eff = meas_bytes / (float(measured["superstep_ms"]) / 1e3)
        bw = max(min(bw, eff), 1.0)
        source = "measured+model"

    gcost = _GATHER_COST_S[kind]
    launch = _COLLECTIVE_LAUNCH_S[kind]
    elem_bytes = 4.0 * cols

    def t_exchange(elems: int, batches: int) -> float:
        return elems * elem_bytes / max(bw, 1.0) + batches * launch

    ell_slots_per_shard = max(1, stats.ell_slots // S)
    modeled: Dict[str, float] = {
        # eager a2a + uniform ELL: padded gather slots + table concat
        "a2a-ell": (
            ell_slots_per_shard * gcost * cols
            + (Np + S * B) * elem_bytes / max(bw, 1.0)
            + t_exchange(S * B, 1)
        ),
        # eager a2a + flat segment: exact slots, scatter derating
        "a2a-segment": (
            Em * gcost * cols * _SEGMENT_PENALTY[kind] / 2.0
            + (Np + S * B) * elem_bytes / max(bw, 1.0)
            + t_exchange(S * B, 1)
        ),
        # propagation-blocked + packed merge: ELL slots gathered from the
        # shard's OWN Np-row block (no table concat, cache-resident),
        # S*Hc merged elements on the wire, one width-R receiver combine
        "blocked-ell": (
            ell_slots_per_shard * gcost * cols
            + (S * Hc) * gcost * cols
            + t_exchange(S * Hc, 1)
        ),
        # propagation-blocked + fused scatter merge: exact slots, one
        # segment reduction covering local dsts AND outgoing bins
        "blocked-segment": (
            (Em + S * Hc) * gcost * cols
            * _SEGMENT_PENALTY[kind] / 2.0
            + t_exchange(S * Hc, 1)
        ),
        # ring streaming: S-1 ppermute batches of one Np block each
        "ring-segment": (
            Em * gcost * cols * _SEGMENT_PENALTY[kind] / 2.0
            + t_exchange((S - 1) * Np, S - 1)
        ),
        # debug reference: the full padded vector every superstep
        "gather-segment": (
            Em * gcost * cols * _SEGMENT_PENALTY[kind] / 2.0
            + t_exchange(S * Np, 1)
        ),
    }

    forced = ov.get("exchange")
    if forced and forced not in ("auto",):
        agg_for = {
            "blocked": ov.get("agg") or "ell",
            "a2a": ov.get("agg") or "ell",
            "ring": "segment", "gather": "segment",
        }
        choice = f"{forced}-{agg_for.get(forced, 'segment')}"
        source = "config"
    else:
        choice = min(modeled, key=lambda k: (modeled[k], k))
    exchange, agg = choice.split("-", 1)
    return ShardedDecision(
        exchange=exchange,
        agg=agg,
        halo_cap=Hc,
        boundary_width=B,
        shard_count=S,
        device_kind=device_kind or "cpu",
        source=source,
        modeled_ms={k: v * 1e3 for k, v in modeled.items()},
        feature_tier=feature_tier,
    )


@dataclass(frozen=True)
class DeltaDecision:
    """Deterministic delta-vs-repack compaction decision for the
    incremental delta-CSR overlay (olap/delta.py): at what overlay depth
    does folding the overlay back into the base pack beat carrying the
    fused lanes through every superstep."""

    compact_threshold: int
    device_kind: str
    source: str                      # model | config
    cells: Dict[str, float] = field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "compact_threshold": self.compact_threshold,
            "device_kind": self.device_kind,
            "source": self.source,
            "cells": {
                k: round(v, 9) for k, v in sorted(self.cells.items())
            },
        }


#: per-record per-superstep cost of the fused delta lanes (one gathered
#: slot + one segment-scatter element per lane entry; the scatter side is
#: the binding one — same derating family as _SEGMENT_PENALTY)
_DELTA_LANE_COST_S = {"cpu": 8e-9, "tpu": 5.5e-8}

#: per-edge cost of the zero-scan materialize (numpy multiset merge +
#: native CSR rebuild) — measured slope of olap/delta.materialize on this
#: container (~25 ns/edge at s16-s20); the full scan+decode repack is
#: ~14x that (r05: 5.6 s at s20/16M edges => ~350 ns/edge)
_DELTA_MATERIALIZE_COST_S = 2.5e-8
_REPACK_SCAN_COST_S = 3.5e-7


def decide_delta(
    num_edges: int,
    num_vertices: int,
    device_kind: str = "cpu",
    overrides: Optional[dict] = None,
    expected_runs: int = 8,
) -> DeltaDecision:
    """Pure function of (graph size, device kind, overrides) -> the
    overlay depth at which compaction amortizes: an overlay of depth d
    costs ~d lane cells per superstep per run, while folding it costs one
    O(E) zero-scan materialize. The threshold solves
    ``expected_runs * supersteps * d * lane_cost >= materialize_cost``
    and is clamped to a pow2 in [1024, 65536] so the fused lanes' tier
    ladder stays short. ``overrides={"compact_threshold": n}`` wins
    (config computer.delta-compact-threshold)."""
    ov = overrides or {}
    if ov.get("compact_threshold"):
        return DeltaDecision(
            compact_threshold=int(ov["compact_threshold"]),
            device_kind=device_kind, source="config",
        )
    kind = "tpu" if "tpu" in str(device_kind).lower() else "cpu"
    supersteps = 20.0  # a PageRank-shaped run's typical iteration count
    lane = _DELTA_LANE_COST_S[kind]
    mat_s = num_edges * _DELTA_MATERIALIZE_COST_S
    repack_s = num_edges * _REPACK_SCAN_COST_S
    d_star = mat_s / max(expected_runs * supersteps * lane, 1e-12)
    threshold = _next_pow2(int(max(1024, min(d_star, 1 << 16))))
    threshold = min(threshold, 1 << 16)
    return DeltaDecision(
        compact_threshold=threshold,
        device_kind=device_kind,
        source="model",
        cells={
            "materialize_s": mat_s,
            "repack_s": repack_s,
            "lane_cost_per_record_per_step_s": lane,
            "d_star": d_star,
        },
    )


def decide_tiers(
    stats: GraphStats,
    overrides: Optional[dict] = None,
    measured: Optional[dict] = None,
) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """(F_cap ladder, E_cap ladder) for the frontier engine: pow2 tiers
    from the configured floors up to (n, m), with the growth factor chosen
    per graph so the ladder stays within the tier budget (each tier is one
    compiled executable) — replacing the fixed x4 growth. The E floor is
    raised to cover one mean-degree expansion of the smallest F tier, so
    the first hops of a BFS never straddle two executables.

    With ``measured`` (a prior frontier run's ``roofline_by_tier``), tiers
    whose measured roofline utilization rounds to zero are dropped from
    the MIDDLE of the ladder (floors and the dense top stay): a tier the
    hardware cannot fill is a compile with no win."""
    ov = dict(overrides or {})
    n = max(1, stats.num_vertices)
    m = max(1, stats.num_edges)
    f_min = int(ov.get("f_min") or (1 << 10))
    e_min = int(ov.get("e_min") or (1 << 13))
    max_tiers = int(ov.get("max_tiers") or 8)
    max_growth = int(ov.get("tier_growth") or 16)

    e_floor = max(e_min, _next_pow2(int(f_min * max(stats.mean_degree, 1.0))))
    e_floor = min(e_floor, _next_pow2(m))

    def ladder(lo: int, hi: int) -> Tuple[int, ...]:
        lo = _next_pow2(lo)
        top = hi  # the top tier is the dense fallback, not rounded up
        if lo >= top:
            return (top,)  # floor covers the whole graph: dense only
        growth = 2
        while growth < max_growth:
            count, c = 1, lo
            while c < top:
                c *= growth
                count += 1
            if count <= max_tiers:
                break
            growth *= 2
        tiers, c = [lo], lo
        while c < top:
            c = min(c * growth, top)
            tiers.append(c)
        return tuple(tiers)

    f_sched = ladder(f_min, n)
    e_sched = ladder(e_floor, m)

    if measured:
        by_tier = measured.get("roofline_by_tier") or {}
        dead = {
            int(k) for k, v in by_tier.items()
            if k.isdigit() and (v.get("roofline_utilization") or 0.0) < 1e-4
        }
        if dead:
            kept = tuple(
                t for i, t in enumerate(e_sched)
                if i == 0 or i == len(e_sched) - 1 or t not in dead
            )
            if len(kept) >= 2:
                e_sched = kept
    return f_sched, e_sched


def pick_tier(need: int, schedule: Tuple[int, ...], hi: int) -> int:
    """Smallest scheduled tier >= need (clamped to hi); the top tier is
    the dense fallback so nothing is ever dropped."""
    for t in schedule:
        if t >= need:
            return min(t, hi)
    return hi


# --------------------------------------------------------------------------
# Measured-record persistence (computer.autotune-persist)
# --------------------------------------------------------------------------
#
# decide() accepts a prior run's `measured` record but nothing survived an
# executor lifetime (ROADMAP #2 leftover). The executor now serializes the
# last measured record next to the checkpoint file and loads it back on
# the next run, so achieved-bandwidth calibration carries across process
# restarts the same way checkpoints carry state.
#
# v2 keys records by SHARD COUNT inside one file: a mesh superstep's
# achieved bandwidth aggregates S chips' HBM plus the collective, which is
# NOT the single-device calibration — an 8-chip run writing the same
# record the 1-chip run reads would poison the next single-device
# decide(). Each layout (shard count) now calibrates only itself; v1
# files (one flat record) are read back as the shard_count=1 entry.

_MEASURED_VERSION = 2

_RECORD_FIELDS = (
    "strategy", "pad_ratio", "superstep_ms", "roofline_by_tier",
    # per-shard-layout fields (sharded executor; absent in older records)
    "exchange", "agg", "halo_cap",
)


def _read_measured_records(path: str) -> Optional[dict]:
    """{shard_count(str): record} from a v1 or v2 file; None when missing
    or unreadable."""
    import json
    import os

    if not path or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(payload, dict):
        return None
    if payload.get("version") == 1:
        return {"1": {k: payload.get(k) for k in _RECORD_FIELDS}}
    if payload.get("version") == _MEASURED_VERSION:
        records = payload.get("records")
        return records if isinstance(records, dict) else None
    return None


def save_measured(path: str, record: dict, shard_count: int = 1) -> None:
    """Atomically persist one measured record under its shard-count key
    (tmp + rename, like the checkpoint writer), preserving every other
    layout's record in the file. Persistence must never fail a run — any
    I/O error is swallowed (the next run simply decides from the model
    alone)."""
    import json
    import os
    import tempfile

    records = _read_measured_records(path) or {}
    records[str(int(shard_count))] = {
        k: record.get(k) for k in _RECORD_FIELDS
    }
    payload = {"version": _MEASURED_VERSION, "records": records}
    try:
        d = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".json.tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
    except OSError:
        return


def load_measured(path: str, shard_count: int = 1) -> Optional[dict]:
    """Load the persisted measured record for one shard count; None when
    missing, unreadable, from an unknown version, or not carrying the
    calibration fields. v1 files answer only shard_count=1."""
    records = _read_measured_records(path)
    if records is None:
        return None
    rec = records.get(str(int(shard_count)))
    if not isinstance(rec, dict):
        return None
    if not rec.get("superstep_ms") or not rec.get("pad_ratio"):
        return None
    return rec
