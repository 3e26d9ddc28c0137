"""Frontier-compacted SSSP/BFS supersteps (push-style, capped expansion).

Reference behavior modeled: FulgoraGraphComputer special-cases the
ShortestPath programs rather than running them through the generic BSP loop
(reference: janusgraph-core .../olap/computer/FulgoraGraphComputer.java:249-253).
The TPU-native form of that special case is *frontier compaction*: a dense
superstep gathers every edge every superstep — at the measured v5e gather
wall (~140M gathered elem/s, docs/tpu_notes.md) that is ~1.9 s/superstep at
scale 23 even when the BFS frontier is a handful of vertices. Here each hop:

  1. compacts the active frontier to a capped index buffer
     (``jnp.nonzero(size=F_cap)`` — static shape, XLA-friendly),
  2. expands it to a capped edge buffer via scatter+cumsum "pointer
     spreading" (NO searchsorted: binary search is itself a gather chain
     and would re-hit the gather wall),
  3. gathers only the frontier's out-neighbors (E_frontier elements, not E),
  4. scatter-mins the relaxed distances into the state.

Tiers: one executable per (F_cap, E_cap) pair, caps growing up a ladder
whose top rung is m. A hop that picks the top rung (`E_cap == m`: it would
hold every edge of each orientation it reads) is a WIDE ROUND instead, the
dense superstep's aggregation on the executor's pack (`_wide_fn`): the
frontier's values as an n-vector, INF elsewhere, read through every slot of
the pack with ONE gather and folded with a min tree (`kernels.
hybrid_gather` / `hybrid_fold`), with no expansion and no scatter, at a
fifth of the narrow step's price of a slot. So a saturated frontier costs
one full-edge pass and nothing is ever dropped. Per-step results are
bit-identical to the dense BSP path, wide or narrow: relaxing a
non-frontier edge is a no-op (its source's distance has not changed since
it was last relaxed), so skipping it cannot change any superstep's output,
weighted or not; a message is one float32 add and `min` is exact in any
order.

Int32 throughout (the telescoping cumsum trick needs diff headroom, hence
the ``m < 2**30`` eligibility guard — beyond that the executor keeps the
dense path).

Brandes (`run_brandes`, betweenness centrality) walks the same ladder with
SUM where the programs above take MIN, K sources as the columns of `(n, K)`
state, over the executor's SIMPLE closure (parallel edges once, loops
dropped): a forward sweep sums path counts level by level, a backward sweep
sums dependencies back over the same levels in reverse, each level at the
tier its forward hop chose. Sums are float32 in the order the scatter or
the pack's tree gives: within rounding of the float64 definition, not bit
for bit.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from janusgraph_tpu.observability import tracer
from janusgraph_tpu.olap.device import await_arrays
from janusgraph_tpu.olap.kernels import (
    HybridPackView,
    brandes_scope,
    frontier_scope,
    hybrid_fold,
    hybrid_gather,
)

# the reached-ness tests below ("dist >= INF") are parity-equivalent to the
# dense program only because both use the IDENTICAL constant
from janusgraph_tpu.olap.programs.shortest_path import INF
from janusgraph_tpu.olap.vertex_program import Combiner, EdgeTransform

#: the depth of a vertex a Brandes column has not reached
UNSEEN = np.iinfo(np.int32).max


def _tier(need: int, lo: int, hi: int, growth: int = 4) -> int:
    """Smallest `growth`-power multiple of `lo`, >= need, clamped to hi
    (callers guarantee hi >= need). Growth trades executable count for
    capacity fit (computer.frontier-tier-growth)."""
    if growth < 2:
        raise ValueError(
            f"frontier tier growth must be >= 2 (got {growth})"
        )
    c = lo
    while c < need:
        c *= growth
    return min(c, hi)


# graphlint: traced -- shared by the single-chip and sharded frontier steps
def capped_expand(jnp, idx, indptr, dst, E_cap, sentinel):
    """Capped frontier expansion: frontier rows -> (owner slot, edge pos,
    neighbor, valid) buffers of static length E_cap. Shared by the
    single-chip and sharded engines (the sharded CSC is over message-table
    slots with a local-destination sentinel; here over vertices).

    own/pos come from scatter+cumsum over the *frontier-sized* start
    offsets (telescoping piecewise-constant encoding) — per-slot cost is
    two vector cumsums plus ONE m-table gather (dst), instead of a
    log(F)-deep searchsorted gather chain. Requires total edges < 2^31
    (int32 telescoping headroom; callers guard at MAX_EDGES = 2^30).
    """
    F_cap = idx.shape[0]
    starts = indptr[idx]
    degs = indptr[idx + 1] - starts
    cum = jnp.cumsum(degs)
    total = cum[-1]
    cum_ex = cum - degs
    # ownership: +1 at each row's first slot (row 0 starts at owner 0);
    # deg-0 rows collapse onto the next row's start and the scatter-adds
    # accumulate, so cumsum lands on the LAST row covering a slot
    inc = jnp.ones((F_cap,), jnp.int32).at[0].set(0)
    own = jnp.cumsum(
        jnp.zeros((E_cap,), jnp.int32).at[cum_ex].add(inc, mode="drop")
    )
    # edge position: pos[s] = s + (starts - cum_ex)[own[s]], encoded the
    # same way (scatter the base DIFFS, cumsum telescopes them)
    base = starts - cum_ex
    dbase = jnp.concatenate([base[:1], jnp.diff(base)])
    pos = jnp.arange(E_cap, dtype=jnp.int32) + jnp.cumsum(
        jnp.zeros((E_cap,), jnp.int32).at[cum_ex].add(dbase, mode="drop")
    )
    valid = jnp.arange(E_cap, dtype=jnp.int32) < total
    pos = jnp.clip(pos, 0, dst.shape[0] - 1)
    nbr = jnp.where(valid, dst[pos], jnp.int32(sentinel))
    return own, pos, nbr, valid


class FrontierEngine:
    """Per-executor engine: owns the device-resident CSR pointer arrays and
    the tier-compiled step executables for ShortestPath-family programs
    and for Brandes' sweeps (`run_brandes`)."""

    F_MIN = 1 << 10
    E_MIN = 1 << 13
    GROWTH = 4
    #: int32 telescoping headroom (see module docstring)
    MAX_EDGES = 1 << 30

    def __init__(self, executor):
        self.ex = executor
        self.jax = executor.jax
        self.jnp = executor.jnp
        # computer.frontier-f-min / frontier-e-min overrides
        if getattr(executor, "_frontier_f_min", None):
            self.F_MIN = executor._frontier_f_min
        if getattr(executor, "_frontier_e_min", None):
            self.E_MIN = executor._frontier_e_min
        if getattr(executor, "_frontier_tier_growth", None):
            self.GROWTH = executor._frontier_tier_growth
        # autotuned tier ladders (olap/autotune.decide_tiers): explicit
        # pow2 schedules sized from the degree histogram replace the fixed
        # growth-factor ladder when the executor carries a decision
        self.f_schedule = self.e_schedule = None
        # decisions are keyed (undirected, feature_dim); frontier programs
        # are scalar-message in-CSR, so the (False, 0) decision applies
        decision = getattr(executor, "_autotune_decisions", {}).get(
            (False, 0)
        )
        if decision is not None:
            self.f_schedule = decision.f_schedule
            self.e_schedule = decision.e_schedule
        csr = executor.csr
        jnp = self.jnp
        self.n = csr.num_vertices
        self.m = csr.num_edges
        if self.m >= self.MAX_EDGES:
            raise ValueError("frontier engine requires < 2^30 edges")
        self._fargs_cache = {}
        self._plans = {}

    def _orientation_args(self, prefix: str):
        """Device arrays for one orientation, built on first use — a
        directed run never transfers the in-side O(E) arrays. dst/src
        reuse the executor's lazy device copies (no 2nd transfer); the
        pointer/degree vectors are O(n). Weights are attached separately
        (`_fargs`) so unweighted runs never force the O(E) weight
        transfer."""
        csr, g, jnp = self.ex.csr, self.ex.g, self.jnp
        args = self._fargs_cache.get(prefix)
        if args is None:
            if prefix == "out":
                indptr, edges = csr.out_indptr, g.out_dst
            else:
                indptr, edges = csr.in_indptr, g.in_src
            args = {
                # indptr padded to n+2: a sentinel row (idx n) reads deg 0
                f"{prefix}_ip": jnp.asarray(
                    np.concatenate([indptr, indptr[-1:]]).astype(np.int32)
                ),
                "out_dst" if prefix == "out" else "in_src": edges,
                f"{prefix}_deg": jnp.asarray(
                    np.diff(indptr).astype(np.int32)
                ),
            }
            self._fargs_cache[prefix] = args
        return args

    def _fargs(self, undirected: bool, weighted: bool):
        g = self.ex.g
        args = dict(self._orientation_args("out"))
        if undirected:
            args.update(self._orientation_args("in"))
        if weighted:
            if g.out_edge_weight is not None:
                args["out_w"] = g.out_edge_weight
            if undirected and g.in_edge_weight is not None:
                args["in_w"] = g.in_edge_weight
        return args

    # ------------------------------------------------------------------ plan
    def _plan_fn(self, undirected: bool):
        """(mask, fargs) -> (frontier count, out-edge total, in-edge total):
        O(n) vector work, one fetch of three scalars per hop."""
        plan = self._plans.get(undirected)
        if plan is not None:
            return plan
        jnp = self.jnp

        def plan_body(mask, fargs):
            zero = jnp.zeros((), jnp.int32)
            count = jnp.sum(mask.astype(jnp.int32))
            tot_out = jnp.sum(jnp.where(mask, fargs["out_deg"], zero))
            tot_in = (
                jnp.sum(jnp.where(mask, fargs["in_deg"], zero))
                if undirected
                else zero
            )
            return count, tot_out, tot_in

        plan = self.jax.jit(plan_body)
        self._plans[undirected] = plan
        return plan

    # ------------------------------------------------------------------ step
    def _expand(self, idx, indptr, dst, E_cap):
        """See capped_expand (module level; shared with the sharded
        engine): sentinel = n, the dead scatter slot."""
        return capped_expand(self.jnp, idx, indptr, dst, E_cap, self.n)

    def _step_fn(self, F_cap, E_cap, weighted, track_paths, undirected):
        """One hop at one tier. Its device time is named by four scopes,
        none inside another (`frontier_scope`): `frontier.expand` (the
        frontier's compaction and `capped_expand`), `frontier.relax` (the
        sender's value gathered to each slot, the weight added),
        `frontier.scatter` (the scatter-min and the compare that makes the
        next mask); `frontier.parent` is `_parent_fn`'s. With `weighted`
        and `track_paths` the `pred` slot carries, per vertex, the round
        of its last strict improvement: what `_parent_fn` breaks ties by."""
        key = ("frontier-step", F_cap, E_cap, weighted, track_paths, undirected)
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        jnp = self.jnp
        n = self.n

        def one_orientation(tmp, dist, idx, indptr, dst, w):
            with frontier_scope("expand"):
                own, pos, nbr, valid = self._expand(idx, indptr, dst, E_cap)
            with frontier_scope("relax"):
                if weighted:
                    # message = sender distance (+ edge weight when
                    # present); invalid slots target the sentinel row, but
                    # mask the value anyway so a clamped gather can never
                    # leak a finite number
                    dist_f = dist[jnp.clip(idx, 0, n - 1)]
                    msg = dist_f[own]
                    if w is not None:
                        msg = msg + w[pos]
                elif track_paths:
                    # message = sender's (global) vertex index;
                    # MIN-combining yields the smallest-index frontier
                    # predecessor — the same encoding the dense program
                    # uses (programs/shortest_path.py)
                    msg = idx.astype(jnp.float32)[own]
                else:
                    # unweighted: any finite marker means "reached this hop"
                    msg = jnp.zeros((E_cap,), jnp.float32)
                msg = jnp.where(valid, msg, INF)
            with frontier_scope("scatter"):
                return tmp.at[nbr].min(msg)

        def step(dist, pred, mask, t, fargs):
            with frontier_scope("expand"):
                idx = jnp.nonzero(mask, size=F_cap, fill_value=n)[0]
                idx = idx.astype(jnp.int32)
            tmp = jnp.full((n + 1,), INF, jnp.float32)
            tmp = one_orientation(
                tmp, dist, idx, fargs["out_ip"], fargs["out_dst"],
                fargs.get("out_w") if weighted else None,
            )
            if undirected:
                tmp = one_orientation(
                    tmp, dist, idx, fargs["in_ip"], fargs["in_src"],
                    fargs.get("in_w") if weighted else None,
                )
            with frontier_scope("scatter"):
                return self._settle(
                    dist, pred, tmp[:n], t, weighted, track_paths
                )

        fn = self.jax.jit(step)
        cache[key] = fn
        return fn

    def _settle(self, dist, pred, tmp, t, weighted, track_paths):
        """The tail of a hop, narrow or wide: from the least message each
        vertex received (`tmp`, INF or more where none came from the
        frontier) to (new value, pred, next mask, its count)."""
        jnp = self.jnp
        if weighted:
            new = jnp.minimum(dist, tmp)
            changed = new < dist
            if track_paths:
                pred = jnp.where(changed, t + 1.0, pred)
            return (
                new, pred, changed,
                jnp.sum(changed.astype(jnp.int32)),
            )
        newly = (dist >= INF) & (tmp < INF)
        new = jnp.where(newly, t + 1.0, dist)
        if track_paths:
            pred = jnp.where(newly, tmp, pred)
        return new, pred, newly, jnp.sum(newly.astype(jnp.int32))

    def _wide_fn(self, weighted, track_paths, undirected, add_weight):
        """A hop at the ladder's top rung, as a wide round on the
        executor's pack of the view the hop reads: the frontier's messages
        as an n-vector (INF off the frontier, so those slots relax
        nothing), ONE gather over every slot with the weight added in
        flight where the narrow step adds it (`add_weight`; never for
        labels), a min tree, and the narrow step's tail. The call is the
        narrow step's with the pack's arrays in place of `fargs`,
        `fn(dist, pred, mask, t, pack.arrays)`: arguments, not constants
        (`TPUExecutor._graph_args`' reason). The function is named `step`
        like the narrow one, so a profile holds both under `jit_step`; the
        gather and the weight lie under `frontier.relax`, the fold and the
        tail under `frontier.scatter`."""
        key = ("frontier-wide", weighted, track_paths, undirected, add_weight)
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        pack = self.ex._hybrid_pack(undirected)  # for its static sizes
        jnp = self.jnp
        n = self.n
        transform = (
            EdgeTransform.ADD_WEIGHT if add_weight else EdgeTransform.NONE
        )

        def step(dist, pred, mask, t, hyb):
            view = HybridPackView(hyb, pack)
            with frontier_scope("relax"):
                if weighted:
                    value = dist
                elif track_paths:
                    value = jnp.arange(n, dtype=jnp.float32)
                else:
                    value = jnp.zeros((n,), jnp.float32)
                msgs = jnp.where(mask, value, INF)
                leaves = hybrid_gather(
                    jnp, view, msgs, Combiner.MIN, transform
                )
            with frontier_scope("scatter"):
                tmp = hybrid_fold(
                    jnp, view, leaves, Combiner.MIN, msgs.shape, msgs.dtype
                )
                return self._settle(dist, pred, tmp, t, weighted, track_paths)

        fn = self.jax.jit(step)
        cache[key] = fn
        return fn

    def _parent_fn(self, undirected):
        """(dist, when, fargs) -> parent array of a converged WEIGHTED
        search, in one full-width pass over the out-orientation (every
        edge once; read from both ends where `undirected`).

        `p` may be `v`'s parent if an edge between them of weight `w`
        explains `v`'s distance, `fl32(dist[p] + w) == dist[v]`, and `p`
        comes before `v` in the order (dist, when): `when` is the round of
        a vertex's last strict improvement, as the weighted step recorded
        it. Every reached vertex but the root has such a `p`: the sender
        of its last improvement, whose distance was final by then if it is
        equal (a weight of 0, or one the addition absorbs) and is smaller
        otherwise. The order strictly falls along parent pointers, so they
        form a tree that ends at the root, the one vertex with `when` 0.
        Among a vertex's candidates the smallest index is returned."""
        key = ("frontier-parent", undirected)
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        jnp = self.jnp
        n, m = self.n, self.m

        def parent_pass(dist, when, fargs):
            with frontier_scope("parent"):
                dst, w = fargs["out_dst"], fargs["out_w"]
                # the sender of slot s: +1 at each row's first slot
                # (empty rows accumulate on the next one), as capped_expand
                src = jnp.cumsum(
                    jnp.zeros((m,), jnp.int32)
                    .at[fargs["out_ip"][1:n]].add(1, mode="drop")
                )
                d_src, d_dst = dist[src], dist[dst]
                w_src, w_dst = when[src], when[dst]
                best = jnp.full((n + 1,), INF, jnp.float32)

                def offer(best, d_p, when_p, p, d_v, when_v, v):
                    ok = (
                        (d_p + w == d_v) & (d_v < INF)
                        & ((d_p < d_v) | (when_p < when_v))
                    )
                    return best.at[jnp.where(ok, v, n)].min(
                        p.astype(jnp.float32)
                    )

                best = offer(best, d_src, w_src, src, d_dst, w_dst, dst)
                if undirected:
                    best = offer(best, d_dst, w_dst, dst, d_src, w_src, src)
                best = best[:n]
                pred = jnp.where(best < INF, best, -1.0)
                return jnp.where(
                    when == 0.0, jnp.arange(n, dtype=jnp.float32), pred
                )

        fn = self.jax.jit(parent_pass)
        cache[key] = fn
        return fn

    def _caps(self, count, edges, m, schedules):
        """(F_cap, E_cap) of a hop whose frontier holds `count` vertices
        and `edges` slots of an orientation of `m`: the autotuned ladders
        `schedules` where the executor carries them, else the fixed
        growth-factor ladder. `E_cap == m` is the top rung."""
        f_schedule, e_schedule = schedules
        if f_schedule and e_schedule:
            from janusgraph_tpu.olap.autotune import pick_tier

            return (
                pick_tier(count, f_schedule, self.n),
                pick_tier(max(edges, 1), e_schedule, m),
            )
        return (
            _tier(count, self.F_MIN, self.n, self.GROWTH),
            _tier(max(edges, 1), self.E_MIN, m, self.GROWTH),
        )

    # ------------------------------------------------------------------- run
    def _hop_loop(
        self, value, pred, mask, weighted, track, und, fargs, max_iterations
    ):
        """The shared host-driven loop: plan (3 scalars) -> pick tier ->
        one compiled step, the wide round (`_wide_fn`) where the tier is
        the ladder's top. Two device round trips per hop; per-step output
        is identical to the dense BSP path's. The host's time in it is
        tiled by phases: per hop `executor.tier` (the plan's dispatch and
        the tier choice; the wait for its three scalars is the
        `executor.sync` inside it) and `executor.dispatch` (the step)."""
        jax, jnp = self.jax, self.jnp
        plan = self._plan_fn(und)
        if self.m == 0:
            mask = jnp.zeros_like(mask)
        trace = []
        for t in range(max_iterations):
            with tracer.phase("executor.tier"):
                planned = plan(mask, fargs)
                with tracer.phase("executor.sync"):
                    planned = jax.device_get(planned)
                count, tot_out, tot_in = (int(x) for x in planned)
                if count == 0:
                    break
                f_cap, e_cap = self._caps(
                    count, max(tot_out, tot_in), self.m,
                    (self.f_schedule, self.e_schedule),
                )
                # the rule is the rung: a hop that would hold every edge
                # is a wide round on the pack of the view it reads (built
                # on first use, the dense path's own)
                wide = e_cap == self.m
                pack = self.ex._hybrid_pack(und) if wide else None
                trace.append(
                    {"hop": t, "frontier": count,
                     "edges": max(tot_out, tot_in),
                     # slots the hop relaxes and slots its tier holds,
                     # over the orientations it runs (tot_in is 0
                     # directed); a wide hop holds what it gathers
                     "relaxed_slots": tot_out + tot_in,
                     "tier_slots": (
                         pack.slots if wide else e_cap * (2 if und else 1)
                     ),
                     "F_cap": f_cap, "E_cap": e_cap, "wide": wide,
                     "tier_source": (
                         "autotune" if self.e_schedule else "static"
                     )}
                )
            with tracer.phase("executor.dispatch"):
                if wide:
                    fn = self._wide_fn(
                        weighted, track, und, weighted and "out_w" in fargs
                    )
                    args = pack.arrays
                else:
                    fn = self._step_fn(f_cap, e_cap, weighted, track, und)
                    args = fargs
                value, pred, mask, _ = fn(
                    value, pred, mask, jnp.asarray(t, jnp.float32), args
                )
        with tracer.phase("executor.sync"):
            # the last step is still running: wait for it here, so that
            # the caller's fetch is the copy alone
            await_arrays((value, pred))
        # observability: which tiers each hop actually priced at — the
        # per-hop analogue of .profile() (read via executor.last_run_info)
        self.last_trace = trace
        return value, pred

    def run(self, program) -> Dict[str, np.ndarray]:
        """SSSP/BFS through the shared hop loop."""
        # the hop loop relaxes by scatter-min: partials merged in place
        Combiner.require_foldable(program.combiner, "the frontier engine")
        jnp = self.jnp
        n = self.n
        weighted = program.weighted
        track = program.track_paths
        und = program.undirected
        with tracer.phase("executor.setup"):
            idx0 = np.arange(n, dtype=np.int64)
            dist = jnp.asarray(
                np.where(idx0 == program.seed_index, 0.0, INF), jnp.float32
            )
            pred = None
            if track:
                # the root's own index; in a weighted run its round, 0
                # (`_step_fn`: the slot carries rounds until `_parent_fn`)
                pred = jnp.asarray(
                    np.where(
                        idx0 == program.seed_index,
                        0.0 if weighted else float(program.seed_index),
                        -1.0,
                    ),
                    jnp.float32,
                )
            mask = jnp.asarray(idx0 == program.seed_index)
            fargs = self._fargs(und, weighted)
        dist, pred = self._hop_loop(
            dist, pred, mask, weighted, track, und, fargs,
            program.max_iterations,
        )
        if weighted and track:
            # the loop carried rounds, not parents: read the parents off
            # the converged distances in one full-width pass
            with tracer.phase("executor.dispatch"):
                pred = self._parent_fn(und)(dist, pred, fargs)
            with tracer.phase("executor.sync"):
                await_arrays((pred,))
        with tracer.phase("executor.fetch"):
            out = {"distance": np.asarray(dist)}
            if track:
                out["predecessor"] = np.asarray(pred)
        return out

    def run_cc(self, program) -> Dict[str, np.ndarray]:
        """Frontier-compacted connected components: min-LABEL propagation
        with a changed-vertex frontier. Reuses the weighted-relaxation step
        (message = sender's value, scatter-min, changed mask) — labels
        propagate exactly like distances with zero edge weight. Late
        supersteps touch a shrinking frontier, so fixpoint convergence
        costs far less than |E| per superstep (the dense path's price).
        Per-step parity with the dense BSP path: an unchanged vertex's
        label was already absorbed by its neighbors when it last changed.
        Labels ride float32 (exact below 2^24 — eligibility-guarded)."""
        Combiner.require_foldable(program.combiner, "the frontier engine")
        jnp = self.jnp
        with tracer.phase("executor.setup"):
            labels = jnp.asarray(np.arange(self.n, dtype=np.float32))
            mask = jnp.ones((self.n,), bool)
            # both orientations, NO weight arrays: the step fn's
            # value-message branch adds w[pos] whenever weights are present
            # in fargs, and a label must never absorb an edge weight
            fargs = self._fargs(True, False)
        labels, _ = self._hop_loop(
            labels, None, mask, True, False, True, fargs,
            program.max_iterations,
        )
        with tracer.phase("executor.fetch"):
            return {"component": np.asarray(labels)}

    # --------------------------------------------------------------- brandes
    def _simple_args(self):
        """The executor's simple closure (`TPUExecutor._simple_closure`:
        both orientations of each simple edge, by source) as ONE directed
        orientation on the device: pointers padded to n + 2 as
        `_orientation_args` pads them, neighbours, degrees. Built on first
        use and kept; the plan reads `out_deg` as it reads a CSR's."""
        args = self._fargs_cache.get("simple")
        if args is None:
            jnp, n = self.jnp, self.n
            src, dst = self.ex._simple_closure()
            deg = np.bincount(src, minlength=n)
            indptr = np.zeros(n + 2, np.int64)
            np.cumsum(deg, out=indptr[1:n + 1])
            indptr[n + 1] = indptr[n]
            args = {
                "out_ip": jnp.asarray(indptr.astype(np.int32)),
                "out_dst": jnp.asarray(dst.astype(np.int32)),
                "out_deg": jnp.asarray(deg.astype(np.int32)),
            }
            self._fargs_cache["simple"] = args
        return args

    def _brandes_fn(self, sweep, F_cap, E_cap, K):
        """One level of a Brandes sweep over the simple closure, K sources
        as columns, at one tier; `E_cap == 0` is the wide round on the
        simple closure's pack. A level's senders in column k are the
        vertices at depth `level` of source k; the tier is sized from the
        UNION of the K frontiers, and a sender in the union but not in
        column k sends 0 there.

          forward  `fn(depth, sigma, mask, t, args)`: each neighbour sums
                   sigma over its senders at depth t; a vertex first
                   reached gets depth t + 1 and that sum (sigma > 0 on
                   every reached vertex, so a positive sum is "reached").
                   Returns the new (depth, sigma, union of the newly
                   reached, its count).
          backward `fn(depth, sigma, delta, d, args)`: each neighbour sums
                   (1 + delta[w]) / sigma[w] over its senders w at depth
                   d; a vertex at depth d - 1 takes delta = sigma x sum,
                   every term positive. Returns delta.

        The narrow step compacts the union (`capped_expand`) and
        scatter-adds rows of K words; the wide one reads every slot of the
        pack with ONE `hybrid_gather` of the `(n, K)` messages and folds
        with `Combiner.SUM`. Named `brandes_forward` / `brandes_backward`
        (modules `jit_brandes_*`), each under its scope `brandes.<sweep>`
        with the hop's `frontier.*` stages inside."""
        key = ("brandes", sweep, F_cap, E_cap, K)
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        jnp = self.jnp
        n = self.n
        f32 = jnp.float32
        pack = (
            self.ex._hybrid_pack(self.ex.SIMPLE_VIEW) if E_cap == 0 else None
        )

        def senders(depth, level, value):
            """Per vertex and column the value where depth is `level`."""
            return jnp.where(depth == level, value, f32(0.0))

        def relax(args, mask, sent):
            """What each vertex receives, summed: (n, K)."""
            if pack is not None:
                view = HybridPackView(args, pack)
                with frontier_scope("relax"):
                    leaves = hybrid_gather(jnp, view, sent, Combiner.SUM)
                with frontier_scope("scatter"):
                    return hybrid_fold(
                        jnp, view, leaves, Combiner.SUM, sent.shape,
                        sent.dtype,
                    )
            with frontier_scope("expand"):
                idx = jnp.nonzero(mask, size=F_cap, fill_value=n)[0]
                idx = idx.astype(jnp.int32)
                own, _, nbr, valid = capped_expand(
                    jnp, idx, args["out_ip"], args["out_dst"], E_cap, n
                )
            with frontier_scope("relax"):
                rows = sent[jnp.clip(idx, 0, n - 1)]  # (F_cap, K)
                msg = jnp.where(valid[:, None], rows[own], f32(0.0))
            with frontier_scope("scatter"):
                return jnp.zeros((n + 1, K), f32).at[nbr].add(msg)[:n]

        if sweep == "forward":
            def brandes_forward(depth, sigma, mask, t, args):
                with brandes_scope("forward"):
                    got = relax(args, mask, senders(depth, t, sigma))
                    with frontier_scope("scatter"):
                        newly = (depth == UNSEEN) & (got > 0.0)
                        depth = jnp.where(newly, t + 1, depth)
                        sigma = jnp.where(newly, got, sigma)
                        mask = jnp.any(newly, axis=1)
                        return (
                            depth, sigma, mask,
                            jnp.sum(mask.astype(jnp.int32)),
                        )

            fn = self.jax.jit(brandes_forward)
        else:
            def brandes_backward(depth, sigma, delta, d, args):
                with brandes_scope("backward"):
                    on = depth == d
                    with frontier_scope("expand"):
                        mask = jnp.any(on, axis=1)
                    with frontier_scope("relax"):
                        share = (1.0 + delta) / jnp.where(on, sigma, 1.0)
                    got = relax(args, mask, senders(depth, d, share))
                    with frontier_scope("scatter"):
                        return jnp.where(depth == d - 1, sigma * got, delta)

            fn = self.jax.jit(brandes_backward)
        cache[key] = fn
        return fn

    def _brandes_total_fn(self):
        """delta summed over the columns: the scores. Named like the
        backward levels, whose end it is (module `jit_brandes_backward`)."""
        key = ("brandes-total",)
        cache = self.ex._compiled
        if key not in cache:
            jnp = self.jnp

            def brandes_backward(delta):
                with brandes_scope("backward"):
                    return jnp.sum(delta, axis=1)

            cache[key] = self.jax.jit(brandes_backward)
        return cache[key]

    def run_brandes(self, program) -> Dict[str, np.ndarray]:
        """Brandes from `program.sources`, as the K columns of one run,
        over the executor's simple closure (ONE directed orientation of
        both orientations of each simple edge).

        Forward: per hop t the plan's three scalars price the UNION of the
        columns' frontiers (depth == t) and pick the tier as `_hop_loop`
        does, on this view's ladders; a top-rung hop is a wide round on
        the simple closure's pack. The sweep ends at the first empty
        union, so hop L (the deepest level) reaches nothing. Backward,
        for d = L ... 2, reuses hop d's tier with no plan and no host sync
        between levels: the union at depth d is hop d's frontier. Level 1
        would write delta of depth 0 alone, each source's own, which is
        left out (Brandes' definition), so it is not run.

        The host's time is tiled by phases: `executor.setup` (the start
        vectors), `executor.dispatch` (per forward hop the plan, its
        `executor.sync`, the tier choice and the step; every backward
        level), `executor.sync`, `executor.fetch`. `last_trace` lists
        every level run, forward then backward, with its sweep."""
        jax, jnp = self.jax, self.jnp
        n = self.n
        sources = np.asarray(program.sources, np.int64)
        K = len(sources)
        if ((sources < 0) | (sources >= n)).any():
            raise ValueError(
                f"BetweennessCentralityProgram: sources {program.sources} "
                f"are not all vertex indices of a graph of {n} vertices"
            )
        columns = np.arange(K)
        with tracer.phase("executor.setup"):
            depth = np.full((n, K), UNSEEN, np.int32)
            depth[sources, columns] = 0
            sigma = np.zeros((n, K), np.float32)
            sigma[sources, columns] = 1.0
            mask = np.zeros(n, bool)
            mask[sources] = True
            depth, sigma, mask = (
                jnp.asarray(depth), jnp.asarray(sigma), jnp.asarray(mask)
            )
            args = self._simple_args()
            m = int(args["out_dst"].shape[0])
            decision = self.ex._autotune(self.ex.SIMPLE_VIEW)
            schedules = (decision.f_schedule, decision.e_schedule)
        plan = self._plan_fn(False)
        trace = []
        for t in range(n if m else 0):
            with tracer.phase("executor.dispatch"):
                planned = plan(mask, args)
                with tracer.phase("executor.sync"):
                    planned = jax.device_get(planned)
                count, edges, _ = (int(x) for x in planned)
                if count == 0:
                    break
                f_cap, e_cap = self._caps(count, edges, m, schedules)
                wide = e_cap == m
                pack = (
                    self.ex._hybrid_pack(self.ex.SIMPLE_VIEW) if wide else None
                )
                trace.append(
                    {"sweep": "forward", "hop": t, "frontier": count,
                     "edges": edges, "relaxed_slots": edges,
                     "tier_slots": pack.slots if wide else e_cap,
                     "F_cap": f_cap, "E_cap": e_cap, "wide": wide}
                )
                fn = self._brandes_fn(
                    "forward", f_cap, 0 if wide else e_cap, K
                )
                depth, sigma, mask, _ = fn(
                    depth, sigma, mask, jnp.asarray(t, jnp.int32),
                    pack.arrays if wide else args,
                )
        delta = jnp.zeros((n, K), jnp.float32)
        with tracer.phase("executor.dispatch"):
            for hop in trace[:1:-1]:  # levels L ... 2
                fn = self._brandes_fn(
                    "backward", hop["F_cap"],
                    0 if hop["wide"] else hop["E_cap"], K,
                )
                delta = fn(
                    depth, sigma, delta, jnp.asarray(hop["hop"], jnp.int32),
                    self.ex._hybrid_pack(self.ex.SIMPLE_VIEW).arrays
                    if hop["wide"] else args,
                )
                trace.append(dict(hop, sweep="backward"))
            scores = self._brandes_total_fn()(delta)
        with tracer.phase("executor.sync"):
            await_arrays((scores,))
        self.last_trace = trace
        with tracer.phase("executor.fetch"):
            return {"betweenness": np.asarray(scores)}
