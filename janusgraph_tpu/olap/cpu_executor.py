"""CPU reference executor — the correctness oracle.

Mirrors the reference's Fulgora execution semantics
(reference: FulgoraGraphComputer.java:210-230 iteration loop with terminate
check, FulgoraVertexMemory double-buffered messages, combiner application on
send): messages are combined pairwise per receiving vertex in a plain Python
loop over in-edges — deliberately unvectorized and structurally independent
of the TPU executor, so agreement between the two is meaningful evidence
(SURVEY.md §7 step 4: "the correctness oracle").
"""

from __future__ import annotations

from collections import Counter
from typing import Dict

import numpy as np

from janusgraph_tpu.olap.csr import CSRGraph
from janusgraph_tpu.olap.vertex_program import (
    Combiner,
    Memory,
    VertexProgram,
    apply_edge_transform,
)


def _combine(op: str, a, b):
    return Combiner.monoid(
        op, "the oracle's pairwise delivery", np.add, np.minimum, np.maximum
    )(a, b)


def _mode(labels) -> int:
    """Combiner.MODE of one vertex's received labels, in plain Python: the
    most frequent, the smallest on ties, NO_MESSAGE for none."""
    if not labels:
        return Combiner.NO_MESSAGE
    counts = Counter(labels)
    most = max(counts.values())
    return min(label for label, c in counts.items() if c == most)


class CPUExecutor:
    """Scalar-loop BSP executor (deliberately unvectorized).

    `strategy` (default "scalar") keeps the per-edge Python loop — the
    oracle. "ell" / "hybrid" instead run the SAME pack aggregation the
    device executors compile (olap/kernels.py is xp-generic), in numpy:
    the oracle side of the hybrid-vs-ELL bitwise-identity contract, and a
    vectorized host path when the scalar loop is too slow. Channel-switching
    supersteps always fall back to scalar delivery."""

    def __init__(self, graph: CSRGraph, strategy: str = "scalar", delta=None):
        if strategy not in ("scalar", "ell", "hybrid"):
            raise ValueError(f"unknown cpu strategy: {strategy!r}")
        self.strategy = strategy
        self._packs = {}
        self.graph = graph
        # delta-CSR overlay: consumed fused exactly like the device
        # executor (olap/delta.py is xp-generic), so cpu-fused vs
        # cpu-repacked stays inside the bitwise contract. Pack
        # strategies only — the scalar loop is the oracle for
        # MATERIALIZED snapshots instead.
        self._delta = delta if (delta is not None and delta.depth) else None
        self._fused_view = None
        if self._delta is not None:
            if strategy == "scalar":
                raise ValueError(
                    "delta-fused cpu runs require a pack strategy "
                    "('ell'/'hybrid'); the scalar oracle replays "
                    "materialized snapshots"
                )
            if graph.in_edge_weight is not None:
                raise ValueError(
                    "delta-fused runs support unfiltered weightless "
                    "snapshots only"
                )
            from janusgraph_tpu.olap.delta import FusedHostView

            self._fused_view = FusedHostView(self._delta)
        #: per-run execution record, same shape as TPUExecutor's — the
        #: CPU oracle reports the same roofline vocabulary (flops, bytes,
        #: operational intensity, utilization) so cost comparisons read
        #: uniformly; costs come from the host estimator (no XLA here)
        self.last_run_info: Dict[str, object] = {}

    def set_delta(self, delta) -> None:
        """Swap the pending-overlay view on a cached executor (the warm-
        submit executor-cache path, mirroring TPUExecutor.set_delta):
        the base graph and numpy packs survive across submits."""
        delta = delta if (delta is not None and delta.depth) else None
        if delta is None:
            self._delta = None
            self._fused_view = None
            return
        if self.strategy == "scalar":
            raise ValueError(
                "delta-fused cpu runs require a pack strategy "
                "('ell'/'hybrid'); the scalar oracle replays "
                "materialized snapshots"
            )
        if self.graph.in_edge_weight is not None:
            raise ValueError(
                "delta-fused runs support unfiltered weightless "
                "snapshots only"
            )
        if delta.csr is not self.graph:
            raise ValueError(
                "overlay view was built over a different base snapshot "
                "— a cached executor only serves overlays of ITS base "
                "CSR (the snapshot cache invalidates on compaction)"
            )
        from janusgraph_tpu.olap.delta import FusedHostView

        self._delta = delta
        self._fused_view = FusedHostView(delta)

    def run(
        self,
        program: VertexProgram,
        checkpoint_path: str = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        fault_hook=None,
        resume_attempts: int = 3,
        shard_checkpoint_dir: str = None,
        checkpoint_shards: int = 0,
    ) -> Dict[str, np.ndarray]:
        """Run to termination. Same checkpoint/auto-resume contract as
        TPUExecutor.run: save every `checkpoint_every` supersteps, and a
        SuperstepPreempted raised mid-run (the `fault_hook` consulted each
        superstep — e.g. FaultPlan.olap_hook) reloads the last checkpoint
        and replays, up to `resume_attempts` times. The replay recomputes
        the exact same numpy arithmetic from the saved arrays, so the
        final state is bitwise-identical to a fault-free run.

        `shard_checkpoint_dir` + `checkpoint_shards=S` write the SHARDED
        checkpoint format instead (per-shard slices + atomic manifest;
        olap/sharded_checkpoint.py) — the oracle side of the cross-shard
        format's executor-portability contract: a checkpoint written by
        the mesh executor restores here and vice versa."""
        from janusgraph_tpu.exceptions import SuperstepPreempted

        attempts = 0
        while True:
            try:
                return self._run(
                    program, checkpoint_path, checkpoint_every, resume,
                    fault_hook, shard_checkpoint_dir, checkpoint_shards,
                )
            except SuperstepPreempted:
                from janusgraph_tpu.observability import (
                    flight_recorder,
                    registry,
                )

                registry.counter("olap.preemptions").inc()
                if not (
                    (checkpoint_path or shard_checkpoint_dir)
                    and checkpoint_every
                ) or (attempts >= resume_attempts):
                    raise
                attempts += 1
                resume = True
                registry.counter("olap.resumes").inc()
                flight_recorder.record(
                    "olap_resume", executor="cpu", attempt=attempts,
                    program=type(program).__name__,
                    format="sharded" if shard_checkpoint_dir else "single",
                )

    def _run(
        self,
        program: VertexProgram,
        checkpoint_path: str,
        checkpoint_every: int,
        resume: bool,
        fault_hook,
        shard_checkpoint_dir: str = None,
        checkpoint_shards: int = 0,
    ) -> Dict[str, np.ndarray]:
        from janusgraph_tpu.olap.vertex_program import (
            check_weighted_transforms,
        )

        check_weighted_transforms(program, self.graph)
        program.require_dense_capable("the CPU executor")
        if getattr(program, "message_mode", None) == "sddmm" and (
            program.undirected
        ):
            # mirror TPUExecutor: the sddmm row-dst builders cover the
            # in-CSR orientation only
            raise ValueError(
                "sddmm message mode aggregates over the in-CSR only — "
                "undirected dense programs are not supported"
            )
        if self._delta is not None:
            from janusgraph_tpu.olap.delta import (
                program_delta_compatible,
            )

            Combiner.require_foldable(
                program.combiner, "the fused delta overlay"
            )
            if not program_delta_compatible(program):
                raise ValueError(
                    "delta-fused runs support default-edge-view "
                    "programs only — materialize the overlay for this "
                    "program"
                )
        g = self.graph if self._delta is None else self._fused_view
        n = getattr(g, "local_num_vertices", g.num_vertices)
        memory = Memory()
        state = None
        start_step = 0
        if resume and (checkpoint_path or shard_checkpoint_dir):
            if shard_checkpoint_dir:
                from janusgraph_tpu.olap.sharded_checkpoint import (
                    load_sharded_checkpoint,
                )

                ck = load_sharded_checkpoint(shard_checkpoint_dir)
            else:
                from janusgraph_tpu.olap.checkpoint import load_checkpoint

                ck = load_checkpoint(checkpoint_path)
            if ck is not None:
                ck_state, ck_mem, start_step = ck
                state = {k: np.asarray(v) for k, v in ck_state.items()}
                memory.values = {k: float(v) for k, v in ck_mem.items()}
                memory.superstep = start_step
        if state is None:
            state, init_metrics = program.setup(g, np)
            memory.reduce_in(init_metrics)
            memory.superstep = 0
            start_step = 0

        import time as _time

        records = []
        cap = program.iteration_cap(g.num_vertices)
        for step in range(start_step, cap):
            if fault_hook is not None:
                fault_hook(step)
            _s0 = _time.perf_counter()
            op = program.combiner_for(step)
            identity = Combiner.IDENTITY[op]
            ch_name = program.channel_for(step)
            use_pack = self.strategy != "scalar" and ch_name is None
            mode = op == Combiner.MODE
            outgoing = np.asarray(
                program.message(state, step, g, np),
                # pack paths run float32 like the device executors (the
                # bitwise-identity contract); the oracle loop keeps f64;
                # MODE's labels stay the integers they are
                dtype=np.int32 if mode
                else np.float32 if use_pack else np.float64,
            )
            if use_pack:
                # the device executors' exact aggregation arithmetic
                # replayed in numpy (the errstate guard silences the
                # documented identity*0 transform noise the validity
                # mask then repairs)
                with np.errstate(invalid="ignore"):
                    if self._delta is not None:
                        from janusgraph_tpu.olap.delta import (
                            fused_delta_aggregate,
                        )

                        nb = self.graph.num_vertices
                        base_agg = self._pack_aggregate(
                            program, op, outgoing[:nb]
                        )
                        lanes = self._delta.lanes(
                            bool(program.undirected)
                        )
                        if lanes is None:
                            raise ValueError(
                                "delta overlay lanes exceed "
                                "computer.delta-max-lane-cells"
                            )
                        aggregated = fused_delta_aggregate(
                            np,
                            {k: v for k, v in lanes.items()
                             if not k.startswith("_")},
                            lanes["_meta"], outgoing, base_agg, op,
                        )
                    else:
                        aggregated = self._pack_aggregate(
                            program, op, outgoing
                        )
            vec = outgoing.ndim == 2
            if not use_pack:
                agg_shape = (n, outgoing.shape[1]) if vec else (n,)
                aggregated = np.full(agg_shape, identity, dtype=np.float64)
            #: MODE cannot combine on delivery: every label is kept until
            #: the vertex has them all
            received = [[] for _ in range(n)] if mode and not use_pack else None

            sddmm = getattr(program, "message_mode", None) == "sddmm"

            def deliver(dst: int, src: int, weight):
                if received is not None:
                    received[dst].append(int(outgoing[src]))
                    return
                if sddmm:
                    # dense-tier dot-attention oracle: the per-edge
                    # coefficient is <h_src, h_dst> (f64 here — the scalar
                    # loop is the semantic oracle; the PACK strategies are
                    # the bitwise ones)
                    msg = outgoing[src] * float(
                        np.dot(outgoing[src], outgoing[dst])
                    )
                else:
                    msg = apply_edge_transform(
                        np, outgoing[src], weight,
                        program.edge_transform, program.edge_transform_cols,
                    )
                aggregated[dst] = _combine(op, aggregated[dst], msg)

            if use_pack:
                pass
            elif ch_name is not None:
                # typed edge view: deliver only along the channel's edges
                # (reference: per-scope slice queries,
                # VertexProgramScanJob.java:114-135)
                from janusgraph_tpu.olap.csr import channel_edges

                ch_src, ch_dst, ch_w = channel_edges(
                    g, program.edge_channels[ch_name]
                )
                for e in range(len(ch_src)):
                    w = float(ch_w[e]) if ch_w is not None else None
                    deliver(int(ch_dst[e]), int(ch_src[e]), w)
            else:
                for i in range(n):
                    for e in range(g.in_indptr[i], g.in_indptr[i + 1]):
                        w = g.in_edge_weight[e] if g.in_edge_weight is not None else None
                        deliver(i, int(g.in_src[e]), w)
                if program.undirected:
                    for i in range(n):
                        for e in range(g.out_indptr[i], g.out_indptr[i + 1]):
                            w = (
                                g.out_edge_weight[e]
                                if g.out_edge_weight is not None
                                else None
                            )
                            deliver(i, int(g.out_dst[e]), w)

            if received is not None:
                aggregated = np.asarray(
                    [_mode(labels) for labels in received], dtype=np.int32
                )
            memory_in = dict(memory.values)
            state, metrics = program.apply(
                state, aggregated, step, memory_in, g, np
            )
            memory.reduce_in(metrics)
            records.append({
                "step": step,
                "wall_ms": round((_time.perf_counter() - _s0) * 1000.0, 3),
                "combiner": op,
            })
            steps_done = step + 1
            if (checkpoint_path or shard_checkpoint_dir) and (
                checkpoint_every
            ) and (
                steps_done % checkpoint_every == 0
                or steps_done == cap
            ):
                _ck0 = _time.perf_counter()
                if shard_checkpoint_dir:
                    from janusgraph_tpu.olap.sharded_checkpoint import (
                        save_sharded_checkpoint,
                    )

                    save_sharded_checkpoint(
                        shard_checkpoint_dir,
                        {k: np.asarray(v) for k, v in state.items()},
                        memory.values,
                        steps_done,
                        max(1, checkpoint_shards),
                    )
                else:
                    from janusgraph_tpu.olap.checkpoint import (
                        save_checkpoint,
                    )

                    save_checkpoint(
                        checkpoint_path,
                        {k: np.asarray(v) for k, v in state.items()},
                        memory.values,
                        steps_done,
                    )
                # timeline marker (observability/timeline.py): the save's
                # wall, stamped on the superstep that paid it
                records[-1]["checkpoint_ms"] = round(
                    (_time.perf_counter() - _ck0) * 1000.0, 3
                )
            if program.terminate(memory):
                break
        self._publish_run(program, records)
        if self._delta is not None:
            # trim the vcap-tier padding (see TPUExecutor.run)
            return {
                k: np.asarray(v)[: self._delta.n_real]
                for k, v in state.items()
            }
        return {k: np.asarray(v) for k, v in state.items()}

    def _pack(self, undirected: bool):
        """ELL/Hybrid pack over the CPU graph's edge view (same layout the
        device executors build), cached per (strategy, orientation)."""
        key = (self.strategy, undirected)
        pack = self._packs.get(key)
        if pack is None:
            from janusgraph_tpu.olap.kernels import ELLPack, HybridPack

            g = self.graph
            n = g.num_vertices
            src = g.in_src.astype(np.int64)
            dst = np.repeat(
                np.arange(n, dtype=np.int64), np.diff(g.in_indptr)
            )
            w = g.in_edge_weight
            if undirected:
                src = np.concatenate([src, g.out_dst.astype(np.int64)])
                dst = np.concatenate([
                    dst,
                    np.repeat(
                        np.arange(n, dtype=np.int64), np.diff(g.out_indptr)
                    ),
                ])
                w = (
                    np.concatenate([w, g.out_edge_weight])
                    if w is not None
                    else None
                )
            cls = ELLPack if self.strategy == "ell" else HybridPack
            pack = cls(src, dst, w, n)
            self._packs[key] = pack
        return pack

    def _sddmm_rows(self, undirected: bool):
        """Row-destination vectors for the fused SDDMM pass, aligned with
        `_pack`'s layout — the numpy twins of TPUExecutor._row_dsts."""
        from janusgraph_tpu.olap.features import kernels as fkernels

        key = ("sddmm", self.strategy, undirected)
        rows = self._packs.get(key)
        if rows is None:
            g = self.graph
            n = g.num_vertices
            src = g.in_src.astype(np.int64)
            dst = np.repeat(
                np.arange(n, dtype=np.int64), np.diff(g.in_indptr)
            )
            if self.strategy == "ell":
                rows = fkernels.ell_row_dsts(src, dst, n)
            else:
                pack = self._pack(undirected)
                rows = fkernels.hybrid_row_dsts(
                    src, dst, n,
                    hub_cutoff=pack.hub_cutoff, tail_chunk=pack.tail_chunk,
                )
            self._packs[key] = rows
        return rows

    def _pack_aggregate(self, program: VertexProgram, op: str, outgoing):
        from janusgraph_tpu.olap.kernels import (
            ell_aggregate,
            hybrid_aggregate,
        )

        pack = self._pack(program.undirected)
        if getattr(program, "message_mode", None) == "sddmm":
            # dense tier: the same fused SDDMM+SpMM arithmetic the device
            # executor compiles, replayed in numpy (bitwise contract)
            from janusgraph_tpu.olap.features.kernels import (
                sddmm_ell_aggregate,
                sddmm_hybrid_aggregate,
            )

            rows = self._sddmm_rows(program.undirected)
            if self.strategy == "ell":
                return sddmm_ell_aggregate(np, pack, rows, outgoing, op)
            return sddmm_hybrid_aggregate(np, pack, rows, outgoing, op)
        agg_fn = ell_aggregate if self.strategy == "ell" else hybrid_aggregate
        return agg_fn(
            np, pack, outgoing, op, program.edge_transform,
            program.edge_transform_cols,
        )

    def _publish_run(self, program: VertexProgram, records) -> None:
        """Run record with the SAME roofline vocabulary as TPUExecutor
        (estimator costs: the scalar loop has no XLA to harvest). Host
        code only — nothing here is traced."""
        from janusgraph_tpu.observability import profiler, registry

        g = self.graph
        edges = g.num_edges * (2 if program.undirected else 1)
        cost = profiler.estimate_superstep_cost(
            g.num_vertices, edges,
            msg_cols=getattr(program, "d_pad", 1) or 1,
            weighted=g.in_edge_weight is not None,
        )
        peaks = profiler.device_peaks("cpu")
        tiers = profiler.attach_roofline(records, cost, peaks)
        info = {
            "path": "cpu",
            "combiner": "+".join(dict.fromkeys(
                r["combiner"] for r in records
            )) or program.combiner,
            "supersteps": len(records),
            "wall_s": round(
                sum(r["wall_ms"] for r in records) / 1000.0, 4
            ),
            "superstep_records": records,
            "roofline_by_tier": tiers,
            "roofline": {
                "peak_flops": peaks["peak_flops"],
                "peak_bytes_per_s": peaks["peak_bytes_per_s"],
                "device_kind": peaks["device_kind"],
                "peaks_source": peaks["source"],
            },
            # same cost vocabulary as the OLTP profile resources block;
            # the scalar loop moves no device bytes
            "resources": {
                "h2d_bytes": 0,
                "d2h_bytes": 0,
                "flops": sum(r.get("flops", 0.0) for r in records),
                "bytes_accessed": sum(
                    r.get("bytes_accessed", 0.0) for r in records
                ),
            },
        }
        # dense tier: same per-superstep MXU accounting as the device
        # executor, so utilization comparisons read uniformly
        if callable(getattr(program, "matmul_flops", None)):
            per_step = float(program.matmul_flops(g.num_vertices, edges))
            info["mxu"] = profiler.attach_mxu(records, per_step, peaks)
        self.last_run_info = info
        registry.record_run("olap", info)
