"""GraphComputer facade: the user-facing OLAP entry point.

Capability parity with the reference's computer API
(reference: graphdb/olap/computer/FulgoraGraphComputer.java:74 — submit()
returning a result with vertex state + memory; GraphFilter via edges()/
vertices()): `graph.compute()` bulk-loads the CSR snapshot, runs the chosen
executor, and hands back state arrays with write-back support.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence

import numpy as np

from janusgraph_tpu.olap.csr import CSRGraph, load_csr
from janusgraph_tpu.olap.vertex_program import Combiner, VertexProgram


@dataclass
class ComputerResult:
    states: Dict[str, np.ndarray]
    csr: CSRGraph
    graph: object = None
    #: map-reduce results keyed by each job's memory_key (reference:
    #: FulgoraMemory holding MapReduce side-effect keys)
    memory: Dict[str, object] = field(default_factory=dict)
    #: the executor's run record (registry.last_run("olap") shape) plus
    #: the submit() routing decision under "routing"
    run_info: Dict[str, object] = field(default_factory=dict)
    #: the program that produced `states` (path()/select() terminals)
    program: object = None
    #: name of path position 0 for select() (compute().traverse(source_as=))
    source_as: object = None

    def _path_index(self):
        """Memoized reverse-adjacency index: paths() then select() on the
        same result must not pay the O(E log E) per-step sorts twice."""
        from janusgraph_tpu.olap.programs.olap_traversal import (
            build_path_index,
        )

        idx = getattr(self, "_path_index_cache", None)
        if idx is None:
            idx = build_path_index(self.csr, self.program)
            object.__setattr__(self, "_path_index_cache", idx)
        return idx

    def paths(self, limit=None):
        """Enumerate traverser paths (tuples of vertex ids, seed first) —
        requires compute().traverse(..., paths=True). Lazy generator;
        pass `limit` on dense graphs (path counts explode — the device
        count sum prices the enumeration: states['count'].sum())."""
        from janusgraph_tpu.olap.programs.olap_traversal import (
            enumerate_paths,
        )

        if "reach" not in self.states:
            raise ValueError(
                "no reach masks recorded — run "
                "compute().traverse(..., paths=True)"
            )
        # the bound method, NOT called: the generator resolves it on first
        # iteration, so un-iterated paths() costs nothing
        return enumerate_paths(
            self.csr, self.program, self.states, limit,
            path_index=self._path_index,
        )

    def select(self, *names, limit=None):
        """Project as()-labeled path positions (TinkerPop SelectStep shape):
        yields {name: vertex_id} dicts. Label steps via 4-tuple spec items
        ('out', labels, filters, 'b'); name the source with
        traverse(source_as='a')."""
        from janusgraph_tpu.olap.programs.olap_traversal import select_paths

        if "reach" not in self.states:
            raise ValueError(
                "no reach masks recorded — run "
                "compute().traverse(..., paths=True)"
            )
        return select_paths(
            self.csr, self.program, self.states, names,
            source_as=self.source_as, limit=limit,
            path_index=self._path_index,
        )

    def value(self, key: str, vertex_id: int):
        """One vertex's value as a Python number: a float, or an int where
        the state is an integer array (CDLP's labels)."""
        return self.states[key][self.csr.index_of(vertex_id)].item()

    def by_vertex(self, key: str) -> Dict[int, float]:
        arr = np.asarray(self.states[key]).tolist()
        return {int(v): arr[i] for i, v in enumerate(self.csr.vertex_ids)}

    def write_back(self, keys: Optional[Sequence[str]] = None) -> None:
        from janusgraph_tpu.olap.tpu_executor import write_back

        cfg = getattr(self.graph, "config", None)
        batch = cfg.get("computer.write-back-batch") if cfg else 10_000
        write_back(self.graph, self.csr, self.states, keys, batch=batch)


class GraphComputer:
    """graph.compute() builder (reference: JanusGraphComputer). Executor
    kind, sync cadence and checkpointing default to
    the graph's registered config (computer.* options)."""

    def __init__(self, graph, executor: str = None):
        self.graph = graph
        cfg = getattr(graph, "config", None)
        if executor is None:
            executor = cfg.get("computer.executor") if cfg else "tpu"
        self.executor_kind = executor
        self._edge_labels: Optional[Sequence[str]] = None
        self._vertex_labels: Optional[Sequence[str]] = None
        self._property_keys: Sequence[str] = ()
        self._weight_key: Optional[str] = None
        self._program: Optional[VertexProgram] = None
        self._map_reduces: list = []

    def edges(self, *labels: str) -> "GraphComputer":
        """GraphFilter on edge labels (reference: GraphComputer.edges)."""
        self._edge_labels = labels
        return self

    def vertices(self, *labels: str) -> "GraphComputer":
        """GraphFilter on vertex labels (reference: GraphComputer.vertices)."""
        self._vertex_labels = labels
        return self

    def map_reduce(self, mr) -> "GraphComputer":
        """Add a MapReduce job over the final vertex state (reference:
        FulgoraGraphComputer.mapReduce)."""
        self._map_reduces.append(mr)
        return self

    def properties(self, *keys: str) -> "GraphComputer":
        self._property_keys = keys
        return self

    def weight(self, key: str) -> "GraphComputer":
        self._weight_key = key
        return self

    def program(self, p: VertexProgram) -> "GraphComputer":
        self._program = p
        # an explicit program supersedes any earlier traverse() shortcut —
        # submit() must not rebuild an OLAP-traversal program over it
        self._traverse_args = None
        return self

    def traverse(
        self, *spec, seed_filters=None, paths=False, source_as=None,
        sack=None, sack_init=None,
    ) -> "GraphComputer":
        """OLAP traversal shortcut (the TraversalVertexProgram analogue):
        compute().traverse(("out", ["knows"]), ("in", None)).submit() counts
        traversers per vertex; result.states["count"].sum() is the terminal
        count (reference: BASELINE config #5). Spec items may carry has()-
        filters — ("out", ["knows"], [("age", Cmp.GREATER_THAN, 30)]) — and
        `seed_filters` restricts the start set; filter masks are built from
        the CSR snapshot at submit() (build_olap_traversal).

        `paths=True` additionally records per-step reach masks device-side
        so the result supports `.paths()` / `.select()` (host traverser
        bookkeeping; olap_traversal.enumerate_paths). `source_as` names
        path position 0 for select().

        `sack="sum"|"mult"` carries a per-traverser sack folded with the
        edge weight each hop (withSack().sack(op).by(weight)); pair with
        .weight(key) so the CSR ships the weight column. result.states
        ["sack"][v] = total sack mass of the traversers at v."""
        # defer program construction to submit(): filter masks need the
        # loaded CSR's property columns
        self._traverse_args = (
            spec, seed_filters, paths, source_as, sack, sack_init,
        )
        self._program = None
        return self

    def submit(self) -> ComputerResult:
        """Load the CSR snapshot, run the program, wrap the result — the
        whole pipeline under an `olap.submit` span (children: the
        `olap.load_csr` snapshot load, the executor's `olap.run` with its
        per-superstep spans, and one `olap.map_reduce` per job)."""
        from janusgraph_tpu.observability import tracer
        from janusgraph_tpu.server import admission as _admission

        # brownout rung 2 (server/admission.py): when the serving path is
        # under sustained overload, analytical jobs — the biggest cost
        # multiplier a query can trigger — are refused so OLTP goodput
        # survives; a no-op whenever no server runs in this process
        _admission.check_olap_admission()
        with tracer.span("olap.submit", executor=self.executor_kind) as sp:
            return self._submit(sp)

    def _submit(self, sp) -> ComputerResult:
        from janusgraph_tpu.observability import tracer

        property_keys = self._property_keys
        traverse_args = getattr(self, "_traverse_args", None)
        if traverse_args is not None:
            # filters reference property names: make sure the snapshot
            # loads those columns
            from janusgraph_tpu.olap.programs.olap_traversal import (
                _parse_filters,
                steps_from_spec,
            )

            spec, seed_filters = traverse_args[0], traverse_args[1]
            fkeys = {f.key for f in _parse_filters(seed_filters)}
            for st in steps_from_spec(self.graph, spec):
                fkeys.update(f.key for f in st.filters)
            property_keys = tuple(set(property_keys or ()) | fkeys)
        assert (
            self._program is not None or traverse_args is not None
        ), "program() not set"
        cfg = getattr(self.graph, "config", None)
        with tracer.span("olap.load_csr") as ls:
            # distributed CSR loading (storage.distributed-load-workers):
            # N worker processes scan disjoint storage-partition ranges of
            # a SHARED backend and the parent merges once — the raw scan
            # carries no property/weight/filter columns, so any of those
            # falls back to the in-process loader
            workers = int(cfg.get("storage.distributed-load-workers") or 0) if cfg else 0
            plain = not (
                property_keys or self._weight_key
                or self._edge_labels or self._vertex_labels
            )
            backend = cfg.get("storage.backend") if cfg else None
            # warm delta snapshot (computer.delta; olap/delta.py): plain
            # snapshots reuse the cached base CSR — a warm submit skips
            # the store scan entirely; pending writes arrive as an
            # overlay consumed fused (small) or folded into fresh arrays
            # with zero store reads (large)
            delta_snap = delta_view = None
            if plain and cfg is not None and cfg.get("computer.delta") and (
                workers <= 1
            ):
                from janusgraph_tpu.olap import delta as _delta_mod

                delta_snap = _delta_mod.get_snapshot(self.graph)
            if delta_snap is not None:
                csr, delta_view, dinfo = delta_snap.acquire()
                ls.annotate(
                    delta_path=dinfo["path"],
                    overlay=dinfo.get("overlay", 0),
                )
            elif workers > 1 and plain and backend in ("remote", "local"):
                from janusgraph_tpu.olap.distributed_load import (
                    distributed_load_csr,
                )

                csr = distributed_load_csr(
                    dict(cfg.local), num_workers=workers,
                    timeout_s=float(
                        cfg.get("storage.distributed-load-timeout-s")
                    ),
                )
                ls.annotate(distributed_workers=workers)
            else:
                csr = load_csr(
                    self.graph,
                    edge_labels=self._edge_labels,
                    vertex_labels=self._vertex_labels,
                    property_keys=property_keys,
                    weight_key=self._weight_key,
                )
            ls.annotate(
                num_vertices=csr.num_vertices, num_edges=csr.num_edges
            )
        if traverse_args is not None:
            from janusgraph_tpu.olap.programs.olap_traversal import (
                build_olap_traversal,
            )

            spec, seed_filters, want_paths, source_as, sack, sack_init = (
                traverse_args
            )
            self._program = build_olap_traversal(
                self.graph, csr, spec, seed_filters=seed_filters,
                record_reach=want_paths, sack=sack, sack_init=sack_init,
            )
        # ---- executor routing (computer.sharded-auto, default on): with
        # more than one visible device, the default 'tpu' submit routes to
        # the sharded executor — multi-chip is the default fast path. The
        # routing decision rides run_info["routing"]; a routed run that
        # fails (e.g. collectives unavailable on this backend) falls back
        # to the single-device executor instead of failing the submit.
        executor_kind = self.executor_kind
        routing = {"requested": self.executor_kind,
                   "routed": self.executor_kind, "reason": "explicit"}
        if self.executor_kind == "tpu" and not getattr(
            self, "_no_autoroute", False
        ) and (
            cfg is None or cfg.get("computer.sharded-auto")
        ):
            try:
                import jax

                ndev = len(jax.devices())
            except Exception:
                ndev = 1
            # the mesh's exchanges pre-combine partials: a MODE program
            # stays on one device, like an sddmm one
            whole_multiset = self._program.combiner == Combiner.MODE
            if ndev > 1 and not whole_multiset and getattr(
                self._program, "sharded_compatible", True
            ):
                executor_kind = "sharded"
                routing = {
                    "requested": self.executor_kind, "routed": "sharded",
                    "reason": f"sharded-auto: mesh of {ndev} devices",
                }
            else:
                routing["reason"] = (
                    "single device" if ndev <= 1
                    else "mode combiner" if whole_multiset
                    else "sddmm program" if getattr(
                        self._program, "message_mode", None) == "sddmm"
                    else "single-device program"
                )
        run_kwargs = {}
        if cfg is not None and executor_kind == "sharded":
            run_kwargs = {
                "sync_every": cfg.get("computer.sync-every"),
                "checkpoint_every": (
                    cfg.get("computer.shard-checkpoint-every")
                    or cfg.get("computer.checkpoint-every")
                ),
                "checkpoint_path": cfg.get("computer.checkpoint-path") or None,
                "shard_checkpoint_dir": (
                    cfg.get("computer.shard-checkpoint-path") or None
                ),
                "frontier": cfg.get("computer.frontier"),
                "exchange": cfg.get("computer.exchange"),
                "agg": cfg.get("computer.agg"),
                "frontier_tier_growth": cfg.get(
                    "computer.frontier-tier-growth"
                ),
                "shard_measure": cfg.get("computer.shard-measure"),
                "features_dim_tier": cfg.get("computer.features-dim-tier"),
                "features_native_matmul": cfg.get(
                    "computer.features-native-matmul"
                ),
            }
        if cfg is not None and executor_kind == "tpu":
            run_kwargs = {
                "ell_max_capacity": cfg.get("computer.ell-max-capacity"),
                "sync_every": cfg.get("computer.sync-every"),
                "checkpoint_every": cfg.get("computer.checkpoint-every"),
                "checkpoint_path": cfg.get("computer.checkpoint-path") or None,
                "frontier": cfg.get("computer.frontier"),
                "channel_cache_size": cfg.get("computer.channel-cache-size"),
                "frontier_cc_min_edges": cfg.get(
                    "computer.frontier-cc-min-edges"
                ),
                "frontier_f_min": cfg.get("computer.frontier-f-min"),
                "frontier_e_min": cfg.get("computer.frontier-e-min"),
                "frontier_tier_growth": cfg.get(
                    "computer.frontier-tier-growth"
                ),
                "hub_cutoff": cfg.get("computer.autotune-hub-cutoff"),
                "tail_chunk": cfg.get("computer.autotune-tail-chunk"),
                "autotune_max_tiers": cfg.get("computer.autotune-max-tiers"),
                "autotune_persist": cfg.get("computer.autotune-persist"),
                "features_dim_tier": cfg.get("computer.features-dim-tier"),
                "features_native_matmul": cfg.get(
                    "computer.features-native-matmul"
                ),
            }
        if cfg is not None and executor_kind == "cpu":
            run_kwargs = {
                "checkpoint_every": cfg.get("computer.checkpoint-every"),
                "checkpoint_path": cfg.get("computer.checkpoint-path") or None,
                "shard_checkpoint_dir": (
                    cfg.get("computer.shard-checkpoint-path") or None
                ),
                "checkpoint_shards": cfg.get(
                    "computer.shard-checkpoint-shards"
                ),
                "features_dim_tier": cfg.get("computer.features-dim-tier"),
                "features_native_matmul": cfg.get(
                    "computer.features-native-matmul"
                ),
            }
            # the CPU oracle writes the sharded format only when a slice
            # count is configured — a bare shard-checkpoint-path on a
            # single-device run still means the single-file format
            if not run_kwargs["checkpoint_shards"]:
                run_kwargs["shard_checkpoint_dir"] = None
        # chaos wiring: a graph opened with storage.faults.enabled carries
        # a FaultPlan; its superstep-preemption hook rides into the
        # executors, where checkpoint auto-resume absorbs it. The sharded
        # executor gets the mesh-aware hook (shard preemption, collective
        # timeout, halo drop, straggler skew) — cross-shard auto-resume
        # rolls every shard back to the last complete manifest.
        plan = getattr(self.graph, "fault_plan", None)
        if executor_kind in ("tpu", "cpu", "sharded"):
            if plan is not None:
                run_kwargs["fault_hook"] = (
                    plan.sharded_hook
                    if executor_kind == "sharded"
                    else plan.olap_hook
                )
            if cfg is not None:
                run_kwargs["resume_attempts"] = cfg.get(
                    "computer.resume-attempts"
                )
        sp.annotate(program=type(self._program).__name__)
        # ---- pending-overlay consumption: small overlays ride into the
        # single-device executor FUSED (base pack untouched, delta lanes
        # merged in the superstep); anything else — sharded runs, typed-
        # channel programs, oversized lanes — folds into fresh arrays
        # first (zero store reads either way)
        if delta_view is not None:
            from janusgraph_tpu.olap import delta as _delta_mod

            und = bool(getattr(self._program, "undirected", False))
            fuse = (
                executor_kind == "tpu"
                and _delta_mod.program_delta_compatible(self._program)
                and csr.in_edge_weight is None
                and delta_view.lanes(und) is not None
            )
            if fuse:
                run_kwargs["delta"] = delta_view
                sp.annotate(delta="fused", overlay=delta_view.depth)
            else:
                csr = _delta_mod.materialize(
                    csr, delta_view.overlay,
                    idm=getattr(self.graph, "idm", None),
                )
                if delta_snap is not None and (
                    delta_view.upto_epoch is not None
                ):
                    delta_snap.adopt(csr, delta_view.upto_epoch)
                sp.annotate(delta="materialized", overlay=delta_view.depth)
                delta_view = None
        from janusgraph_tpu.observability import registry

        # warm-submit executor cache (the PR 14 REMAINING): when this
        # submit runs over the delta snapshot's CURRENT base pack, the
        # executor — device-resident packs and compiled executables
        # included — is cached on the snapshot and reused next submit,
        # invalidated by any compaction/adopt (generation bump)
        if delta_snap is not None and csr is delta_snap.csr:
            run_kwargs["executor_cache"] = delta_snap
        try:
            states = run_on(csr, self._program, executor_kind, **run_kwargs)
        except Exception as e:
            if routing["routed"] == executor_kind == "sharded" and (
                self.executor_kind != "sharded"
            ):
                # auto-routing must never make a working submit fail:
                # rebuild the single-device kwargs and retry there
                from janusgraph_tpu.observability import flight_recorder

                routing["routed"] = "tpu"
                routing["fallback"] = f"{type(e).__name__}: {e}"[:200]
                flight_recorder.record(
                    "sharded_auto_fallback",
                    error=f"{type(e).__name__}: {e}"[:200],
                )
                self._no_autoroute = True
                try:
                    result = self._submit(sp)
                finally:
                    self._no_autoroute = False
                # preserve the fallback story for callers and dashboards
                result.run_info["routing"] = routing
                registry.record_run("olap.routing", routing)
                return result
            raise
        if run_kwargs.get("delta") is not None:
            # fused-run results cover [base ++ new vertices] with removed
            # slots inert: compact to the surviving set so value()/
            # by_vertex()/write_back see exactly the live graph
            from janusgraph_tpu.olap import delta as _delta_mod

            states, csr = _delta_mod.compact_result(delta_view, states)
        if delta_snap is not None:
            # compaction is off the superstep path: fold the overlay into
            # the base pack AFTER the run when it crossed the threshold
            delta_snap.maybe_compact()
        routing["executor"] = executor_kind
        registry.record_run("olap.routing", routing)
        run_info = dict(registry.last_run("olap") or {})
        run_info["routing"] = routing
        memory = {}
        if self._map_reduces:
            from janusgraph_tpu.olap.mapreduce import run_map_reduce

            for mr in self._map_reduces:
                with tracer.span(
                    "olap.map_reduce", job=type(mr).__name__,
                    key=mr.memory_key,
                ):
                    memory[mr.memory_key] = run_map_reduce(mr, states, csr)
        return ComputerResult(
            states=states, csr=csr, graph=self.graph, memory=memory,
            run_info=run_info,
            program=self._program,
            source_as=(
                traverse_args[3] if traverse_args is not None else None
            ),
        )


def run_on(
    csr: CSRGraph,
    program: VertexProgram,
    executor: str = "tpu",
    ell_max_capacity: int = None,
    sync_every: int = 1,
    checkpoint_every: int = 0,
    checkpoint_path: str = None,
    frontier: str = "auto",
    channel_cache_size: int = None,
    frontier_cc_min_edges: int = None,
    frontier_f_min: int = None,
    frontier_e_min: int = None,
    frontier_tier_growth: int = None,
    exchange: str = "a2a",
    agg: str = "ell",
    shard_measure: bool = None,
    fault_hook=None,
    resume_attempts: int = 3,
    hub_cutoff: int = None,
    tail_chunk: int = None,
    autotune_max_tiers: int = None,
    autotune_persist: bool = None,
    features_dim_tier: int = None,
    features_native_matmul: bool = None,
    cpu_strategy: str = "scalar",
    shard_checkpoint_dir: str = None,
    checkpoint_shards: int = 0,
    delta=None,
    executor_cache=None,
):
    # dense-feature tier program configuration (computer.features-*):
    # applied here so EVERY executor sees the same padded lane tier and
    # matmul flavor (TPUExecutor re-applies the tier for direct callers)
    if features_dim_tier and hasattr(program, "set_dim_tier"):
        program.set_dim_tier(features_dim_tier)
    if features_native_matmul is not None and hasattr(
        program, "set_native_matmul"
    ):
        program.set_native_matmul(features_native_matmul)
    if executor == "cpu":
        from janusgraph_tpu.olap.cpu_executor import CPUExecutor

        ex = None
        cache_key = ("cpu", cpu_strategy)
        if executor_cache is not None:
            ex = executor_cache.cached_executor(cache_key)
        if ex is None:
            ex = CPUExecutor(csr, strategy=cpu_strategy, delta=delta)
            if executor_cache is not None:
                executor_cache.store_executor(cache_key, ex, csr)
        else:
            ex.set_delta(delta)
        return ex.run(
            program,
            checkpoint_path=checkpoint_path,
            checkpoint_every=checkpoint_every,
            fault_hook=fault_hook,
            resume_attempts=resume_attempts,
            shard_checkpoint_dir=shard_checkpoint_dir,
            checkpoint_shards=checkpoint_shards,
        )
    if executor == "sharded":
        if delta is not None:
            raise ValueError(
                "the sharded executor consumes MATERIALIZED delta "
                "snapshots (route_overlay + per-shard rebuild) — fold "
                "the overlay with olap/delta.materialize first"
            )
        from janusgraph_tpu.parallel import ShardedExecutor

        return ShardedExecutor(
            csr, exchange=exchange, agg=agg,
            frontier_tier_growth=frontier_tier_growth,
            shard_measure=shard_measure,
        ).run(
            program,
            sync_every=sync_every,
            checkpoint_every=checkpoint_every,
            checkpoint_path=checkpoint_path,
            frontier=frontier,
            fault_hook=fault_hook,
            resume_attempts=resume_attempts,
            shard_checkpoint_dir=shard_checkpoint_dir,
        )
    if executor == "tpu":
        from janusgraph_tpu.olap.tpu_executor import TPUExecutor

        ctor_kwargs = dict(
            ell_max_capacity=ell_max_capacity,
            frontier=frontier,
            channel_cache_size=channel_cache_size,
            frontier_cc_min_edges=frontier_cc_min_edges,
            frontier_f_min=frontier_f_min,
            frontier_e_min=frontier_e_min,
            frontier_tier_growth=frontier_tier_growth,
            hub_cutoff=hub_cutoff,
            tail_chunk=tail_chunk,
            autotune_max_tiers=autotune_max_tiers,
            autotune_persist=autotune_persist,
            features_dim_tier=features_dim_tier,
        )
        ex = None
        # the overlay is NOT part of the key: a cached executor swaps it
        # per submit (set_delta), and its compiled executables are keyed
        # by lane signature internally
        cache_key = ("tpu",) + tuple(sorted(ctor_kwargs.items()))
        if executor_cache is not None:
            ex = executor_cache.cached_executor(cache_key)
        if ex is None:
            ex = TPUExecutor(csr, delta=delta, **ctor_kwargs)
            if executor_cache is not None:
                executor_cache.store_executor(cache_key, ex, csr)
        else:
            ex.set_delta(delta)
        return ex.run(
            program,
            sync_every=sync_every,
            checkpoint_every=checkpoint_every,
            checkpoint_path=checkpoint_path,
            fault_hook=fault_hook,
            resume_attempts=resume_attempts,
        )
    raise ValueError(f"unknown executor {executor!r}")
