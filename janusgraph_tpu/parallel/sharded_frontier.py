"""Frontier-compacted supersteps on the sharded mesh executor.

Reference behavior modeled: FulgoraGraphComputer special-cases the
ShortestPath programs (FulgoraGraphComputer.java:249-253), and the
reference's storage-partition parallelism shards that work across
key ranges (IDManager.java:472-496). The single-chip TPU form of the
special case is capped frontier expansion (olap/frontier.py); this module
is its mesh form: per-shard compaction + the EXISTING boundary-bucket
all_to_all carrying only frontier messages.

Superstep anatomy (2 executables, 2 host round trips per hop — same
structure as the single-chip engine):

  plan  (one per edge view): mask the outgoing vertex values to INF off
        the frontier, swap boundary buckets with one ``lax.all_to_all``
        (fixed S*B elements — comm volume is unchanged; the win is in
        aggregation), concatenate the message table
        [own Np ++ received S*B], and count fresh slots / their edges
        (pmax for tier sizing, psum for the trace).
  step  (one per (F_cap, E_cap, mode) tier): compact fresh table slots to
        a capped index buffer, expand via the scatter+cumsum pointer
        spread over the per-shard table-slot CSC
        (ShardedCSR.ensure_frontier_plan), gather/scatter-min only the
        frontier's edges, update distances and the next-hop mask.

Per-step output is bit-identical to the dense sharded path: a
non-frontier source contributes INF (the MIN identity) to the table, so
every edge the compaction skips would have been a no-op relaxation —
the same argument as olap/frontier.py, applied per shard. The top tier
(F_cap=T, E_cap=Em) degrades to one full local edge pass: dense-
equivalent cost, nothing dropped.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from janusgraph_tpu.olap.frontier import _tier, capped_expand
from janusgraph_tpu.olap.programs.shortest_path import INF


class ShardedFrontierEngine:
    """Per-executor engine: owns the device placement of the frontier plan
    and the tier-compiled plan/step executables (cached in the executor's
    compiled-fn table)."""

    F_MIN = 1 << 10
    E_MIN = 1 << 13
    GROWTH = 4
    #: int32 telescoping-cumsum headroom (see olap/frontier.py)
    MAX_EDGES = 1 << 30

    def __init__(self, executor):
        self.ex = executor
        if getattr(executor, "_frontier_tier_growth", None):
            self.GROWTH = executor._frontier_tier_growth
        self.jax = executor.jax
        self.axis = executor.axis
        self.mesh = executor.mesh
        self.last_trace = []

    # ------------------------------------------------------------- graph args
    def _mode(self, track: bool) -> str:
        """The frontier exchange mode: 'blocked' merges remote relaxations
        sender-side (min is exactly order-insensitive, so the hop is
        bitwise-identical to the eager table) and collapses remote
        expansion to one edge per used bin; predecessor tracking needs the
        per-source identity that a merged bin discards, so track runs stay
        on the eager boundary table."""
        return (
            "blocked"
            if self.ex.exchange == "blocked" and not track
            else "a2a"
        )

    def _table_len(self, sc, mode: str) -> int:
        if mode == "blocked":
            sc.ensure_blocked_plan()
            return sc.shard_size + sc.num_shards * sc.halo_cap
        sc.ensure_exchange_plan()
        return sc.msg_table_len

    def _gargs(self, sc, view_key, weighted: bool, track: bool,
               mode: str = "a2a"):
        """Device-resident plan arrays for one edge view (reuses the
        executor's sharded device cache — the a2a send_idx / blocked bin
        maps are shared with the dense path)."""
        ex = self.ex
        if mode == "blocked":
            sc.ensure_frontier_plan_blocked()
            g = {
                "blk_src": ex._dev(sc, view_key, "blk_src_loc"),
                "blk_bin_seg": ex._dev(sc, view_key, "blk_bin_seg"),
                "blk_valid": ex._dev(sc, view_key, "blk_valid"),
                "ftr_ip": ex._dev(sc, view_key, "fblk_ip"),
                "ftr_dst": ex._dev(sc, view_key, "fblk_dst"),
                "ftr_deg": ex._dev(sc, view_key, "fblk_deg"),
            }
            if weighted:
                g["blk_w"] = ex._dev(sc, view_key, "blk_weight")
                g["ftr_w"] = ex._dev(sc, view_key, "fblk_w")
            return g
        sc.ensure_frontier_plan()
        g = {
            "send_idx": ex._dev(sc, view_key, "send_idx"),
            "ftr_ip": ex._dev(sc, view_key, "ftr_ip"),
            "ftr_dst": ex._dev(sc, view_key, "ftr_dst"),
            "ftr_deg": ex._dev(sc, view_key, "ftr_deg"),
        }
        if weighted:  # callers pass the resolved use-weights flag
            g["ftr_w"] = ex._dev(sc, view_key, "ftr_w")
        if track:
            g["ftr_src_glob"] = ex._dev(sc, view_key, "ftr_src_glob")
        return g

    # ------------------------------------------------------------------ plan
    def _plan_fn(self, sc, view_key, mode: str = "a2a", has_w: bool = False):
        """(value, mask, g) -> (tab, count_max, edge_max, count_sum,
        edge_sum): builds the frontier-masked message table (the exchange
        lives HERE, so the tier choice can follow it) and prices the
        coming expansion. mode='blocked' ships sender-merged relaxation
        bins (propagation blocking: segment-min by destination bin, ONE
        all_to_all of S*Hc merged elements) instead of the raw S*B
        boundary values."""
        key = (
            "sfrontier-plan", view_key, mode, has_w,
            self._table_len(sc, mode),
        )
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        S = sc.num_shards

        if mode == "blocked":
            Hc = sc.halo_cap

            def plan_body(value, mask, g):
                outgoing = jnp.where(mask, value, INF)
                msgs = outgoing[g["blk_src"]]
                if has_w:
                    # fold the edge weight into the merged candidate: the
                    # receiver's bin edge carries weight 0
                    msgs = msgs + g["blk_w"]
                msgs = jnp.where(g["blk_valid"] > 0, msgs, INF)
                bins = jax.ops.segment_min(
                    msgs, g["blk_bin_seg"], num_segments=S * Hc + 1
                )[: S * Hc]
                recv = jax.lax.all_to_all(
                    bins.reshape(S, Hc), axis,
                    split_axis=0, concat_axis=0,
                )
                tab = jnp.concatenate([outgoing, recv.reshape(S * Hc)])
                fresh = tab < INF
                zero = jnp.zeros((), jnp.int32)
                count = jnp.sum(fresh.astype(jnp.int32))
                edges = jnp.sum(jnp.where(fresh, g["ftr_deg"], zero))
                return (
                    tab,
                    jax.lax.pmax(count, axis),
                    jax.lax.pmax(edges, axis),
                    jax.lax.psum(count, axis),
                    jax.lax.psum(edges, axis),
                )
        else:
            B = sc.boundary_width

            def plan_body(value, mask, g):
                outgoing = jnp.where(mask, value, INF)
                sends = outgoing[g["send_idx"]]              # (S, B)
                recv = jax.lax.all_to_all(
                    sends, axis, split_axis=0, concat_axis=0
                )
                tab = jnp.concatenate([outgoing, recv.reshape(S * B)])
                fresh = tab < INF
                zero = jnp.zeros((), jnp.int32)
                count = jnp.sum(fresh.astype(jnp.int32))
                edges = jnp.sum(jnp.where(fresh, g["ftr_deg"], zero))
                return (
                    tab,
                    jax.lax.pmax(count, axis),
                    jax.lax.pmax(edges, axis),
                    jax.lax.psum(count, axis),
                    jax.lax.psum(edges, axis),
                )

        sh, rep = P(self.axis), P()
        fn = jax.jit(jax.shard_map(
            plan_body,
            mesh=self.mesh,
            in_specs=(sh, sh, sh),
            out_specs=(sh, rep, rep, rep, rep),
            check_vma=False,
        ))
        self.ex._new_execs = getattr(self.ex, "_new_execs", 0) + 1
        cache[key] = fn
        return fn

    # ------------------------------------------------------------------ step
    def _step_fn(
        self, sc, view_key, F_cap, E_cap, weighted, track, has_w, T=None,
    ):
        key = (
            "sfrontier-step", view_key, F_cap, E_cap, weighted, track,
            has_w, T,
        )
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        Np = sc.shard_size
        if T is None:
            T = sc.msg_table_len

        def step_body(value, pred, tab, t, g):
            fresh = tab < INF
            idx = jnp.nonzero(fresh, size=F_cap, fill_value=T)[0]
            idx = idx.astype(jnp.int32)
            own, pos, nbr, valid = capped_expand(
                jnp, idx, g["ftr_ip"], g["ftr_dst"], E_cap, Np
            )
            safe = jnp.clip(idx, 0, T - 1)
            if weighted:
                msg = tab[safe][own]
                if has_w:
                    msg = msg + g["ftr_w"][pos]
            elif track:
                msg = g["ftr_src_glob"][safe].astype(jnp.float32)[own]
            else:
                msg = jnp.zeros((E_cap,), jnp.float32)
            msg = jnp.where(valid, msg, INF)
            tmp = jnp.full((Np + 1,), INF, jnp.float32).at[nbr].min(msg)
            tmp = tmp[:Np]
            if weighted:
                new = jnp.minimum(value, tmp)
                changed = new < value
            else:
                changed = (value >= INF) & (tmp < INF)
                new = jnp.where(changed, t + 1.0, value)
                if track:
                    pred = jnp.where(changed, tmp, pred)
            n_changed = jax.lax.psum(
                jnp.sum(changed.astype(jnp.int32)), axis
            )
            return new, pred, changed, n_changed

        sh, rep = P(self.axis), P()
        if track:
            body = step_body
            in_specs = (sh, sh, sh, rep, sh)
        else:
            def body(value, tab, t, g):
                v, _p, m, c = step_body(value, None, tab, t, g)
                return v, m, c

            in_specs = (sh, sh, rep, sh)
        out_specs = (sh, sh, sh, rep) if track else (sh, sh, rep)
        fn = jax.jit(jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        ))
        self.ex._new_execs = getattr(self.ex, "_new_execs", 0) + 1
        cache[key] = fn
        return fn

    # ------------------------------------------------------------- host loop
    def _hop_loop(
        self, sc, view_key, value, pred, mask, weighted, track,
        max_iterations, use_weights=None, fault_hook=None,
    ):
        """`use_weights` decouples value-message semantics (weighted=True)
        from edge-weight application: CC propagates labels as value
        messages but must never add a weight (see run_cc).

        `fault_hook` is consulted once per hop (the host-visible
        boundary). Frontier runs carry no checkpoint: a raised
        SuperstepPreempted propagates to ShardedExecutor.run, whose
        auto-resume RESTARTS the frontier run from scratch — hops are
        short and the loop is deterministic, so the restart reproduces
        the identical result."""
        import jax.numpy as jnp

        jax = self.jax
        has_w = (
            weighted if use_weights is None else use_weights
        ) and sc.has_weight
        mode = self._mode(track)
        if mode == "blocked":
            sc.ensure_frontier_plan_blocked()
            T = self._table_len(sc, mode)
            Em = sc.fblk_edges
            exchange_elems = sc.num_shards * sc.halo_cap
        else:
            sc.ensure_frontier_plan()  # also builds the exchange plan
            T = sc.msg_table_len
            Em = sc.edges_per_shard
            exchange_elems = sc.num_shards * sc.boundary_width
        g = self._gargs(sc, view_key, has_w, track, mode)
        plan = self._plan_fn(sc, view_key, mode, has_w)
        trace = []
        for t in range(max_iterations):
            if fault_hook is not None:
                fault_hook(t)
            tab, cmax, emax, csum, esum = plan(value, mask, g)
            cmax, emax, csum, esum = (
                int(x) for x in jax.device_get((cmax, emax, csum, esum))
            )
            if csum == 0:
                break
            f_cap = _tier(max(cmax, 1), self.F_MIN, T, self.GROWTH)
            e_cap = _tier(max(emax, 1), self.E_MIN, Em, self.GROWTH)
            trace.append({
                "hop": t, "frontier": csum, "edges": esum,
                "shard_max_frontier": cmax, "shard_max_edges": emax,
                "F_cap": f_cap, "E_cap": e_cap,
                "exchange": mode, "exchange_elems": exchange_elems,
            })
            fn = self._step_fn(
                sc, view_key, f_cap, e_cap, weighted, track, has_w, T
            )
            tf = jnp.asarray(t, jnp.float32)
            if track:
                value, pred, mask, _c = fn(value, pred, tab, tf, g)
            else:
                value, mask, _c = fn(value, tab, tf, g)
        self.last_trace = trace
        return value, pred

    def _device_put_sharded(self, arr):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return self.jax.device_put(
            arr, NamedSharding(self.mesh, P(self.axis))
        )

    # -------------------------------------------------------------- entry
    def run(self, program, fault_hook=None) -> Dict[str, np.ndarray]:
        """SSSP/BFS (ShortestPathProgram) through the sharded hop loop."""
        sc = self.ex._sharded(program.undirected)
        view_key = program.undirected
        track = program.track_paths
        idx0 = np.arange(sc.padded_n, dtype=np.int64)
        value = self._device_put_sharded(
            np.where(idx0 == program.seed_index, 0.0, INF).astype(np.float32)
        )
        pred = None
        if track:
            pred = self._device_put_sharded(
                np.where(
                    idx0 == program.seed_index,
                    float(program.seed_index), -1.0,
                ).astype(np.float32)
            )
        mask = self._device_put_sharded(idx0 == program.seed_index)
        value, pred = self._hop_loop(
            sc, view_key, value, pred, mask, program.weighted, track,
            program.max_iterations, fault_hook=fault_hook,
        )
        out = {"distance": self.ex._fetch(value)[: sc.real_n]}
        if track:
            out["predecessor"] = self.ex._fetch(pred)[: sc.real_n]
        return out

    def run_cc(self, program, fault_hook=None) -> Dict[str, np.ndarray]:
        """Frontier-compacted connected components on the mesh: min-label
        propagation with a changed-vertex frontier, value-messages through
        the weighted step with NO weight arrays (a label must never absorb
        an edge weight). `max_iterations=None` runs until no label changes
        (`iteration_cap`); the single-device engine answers the same labels
        by Afforest (olap/frontier.py `run_cc`)."""
        sc = self.ex._sharded(True)  # symmetric closure = both orientations
        labels = self._device_put_sharded(
            np.arange(sc.padded_n, dtype=np.float32)
        )
        mask = self._device_put_sharded(sc.active > 0)
        labels, _ = self._hop_loop(
            sc, True, labels, None, mask, True, False,
            program.iteration_cap(sc.real_n), use_weights=False,
            fault_hook=fault_hook,
        )
        return {"component": self.ex._fetch(labels)[: sc.real_n]}
