"""Frontier-compacted SSSP/BFS supersteps: top-down hops (push-style,
capped expansion, or a wide round on the pack) and, for unweighted
searches, bottom-up hops (pull-style, direction-optimising).

Reference behavior modeled: FulgoraGraphComputer special-cases the
ShortestPath programs rather than running them through the generic BSP loop
(reference: janusgraph-core .../olap/computer/FulgoraGraphComputer.java:249-253).
The TPU-native form of that special case is *frontier compaction*: a dense
superstep gathers every edge every superstep — at the measured v5e gather
wall (~140M gathered elem/s, docs/tpu_notes.md) that is ~1.9 s/superstep at
scale 23 even when the BFS frontier is a handful of vertices. Here each
top-down hop:

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

A BOTTOM-UP hop (Beamer, Asanović, Patterson, SC12; GAP's `bfs.cc`) runs
where an unweighted search's frontier holds more slots than the unreached
vertices' rows / ALPHA, and until the frontier holds fewer than n / BETA
vertices and shrinks (`_direction`). It reads the rows of the unreached
vertices instead of the frontier's (the simple closure's rows for an
undirected search, in-rows for a directed one), compacted on the tier
ladder, in blocks of row positions: a row stops at its first neighbour in
the frontier, which becomes its parent, and leaves the next block
(`_bottom_up_fn`). A vertex joins the next frontier exactly when some
neighbour is on this one, so levels are the top-down hop's bit for bit;
parents differ (first by row position, where a top-down hop takes the
smallest-index sender), each a frontier neighbour one level up.

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

Connected components (`run_cc`) are GAP's Afforest (Sutton, Ben-Nun,
Barak, IPDPS 2018) over the same simple closure, in two executables: the
SAMPLE links every vertex with its first two neighbours, takes the most
frequent root among 1,024 vertices as the giant component's, and marks the
vertices outside it; the FINISH links only those vertices' rows, compacted
on the closure's tier ladder, and never reads the rest of the giant's
edges. A link hangs the larger of an edge's two roots under the smaller
and pointer jumping compresses the forest, so every label only falls and
each component's root is its smallest index: the labels of min-label
propagation, which the dense path, `CPUExecutor` and the mesh still run.
Both run over the vertices that have an edge alone, renumbered in their own
order (`_cc_args`): a vertex without one is its own root throughout.
"""

from __future__ import annotations

import functools

import numpy as np

from janusgraph_tpu.observability import registry, tracer
from janusgraph_tpu.olap.device import await_arrays
from janusgraph_tpu.olap.kernels import (
    HybridPackView,
    afforest_scope,
    brandes_scope,
    flat_take,
    frontier_scope,
    hybrid_fold,
    hybrid_gather,
    row_take,
)

# the reached-ness tests below ("dist >= INF") are parity-equivalent to the
# dense program only because both use the IDENTICAL constant
from janusgraph_tpu.olap.programs.shortest_path import INF
from janusgraph_tpu.olap.vertex_program import Combiner, EdgeTransform

#: the depth of a vertex a Brandes column has not reached
UNSEEN = np.iinfo(np.int32).max

#: Beamer's direction rule with GAP's constants (`bfs.cc`, alpha 15, beta
#: 18): an unweighted search goes bottom-up when its frontier's slots
#: exceed the unreached vertices' slots / ALPHA, and returns top-down when
#: its frontier holds fewer than n / BETA vertices and is shrinking
ALPHA = 15
BETA = 18


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


# graphlint: traced -- Afforest's pointer jumping, inside both executables
def _compress(jnp, lax, comp):
    """Pointer jumping to the fixpoint, `comp = comp[comp]` until nothing
    moves, in one `lax.while_loop`: every vertex then points at its root.
    Returns (comp, jumps); the last jump is the one that moved nothing."""

    def jump(carry):
        comp, _, jumps = carry
        nxt = row_take(jnp, comp, comp)
        return nxt, jnp.any(nxt != comp), jumps + 1

    comp, _, jumps = lax.while_loop(
        lambda carry: carry[1], jump, (comp, jnp.bool_(True), jnp.int32(0))
    )
    return comp, jumps


# graphlint: traced -- Afforest's link, inside both executables
def _link(jnp, lax, comp, u, v, valid):
    """Afforest's link of the edges (u, v) where `valid`, over a forest in
    which every vertex points at its root (`comp`, int32; `u` None means
    each vertex its own end, `comp[u] == comp`). Bulk-synchronous where
    GAP loops a compare-and-swap per edge: a pass reads both ends' roots,
    scatter-mins the smaller onto the larger (a root only ever falls, so
    the forest stays acyclic and a component's root is its smallest
    index) and compresses; passes repeat until every edge's ends share a
    root. Returns (comp, passes, jumps): passes that hooked, jumps of
    their compressions."""
    n = comp.shape[0]

    def roots(comp):
        ru = comp if u is None else row_take(jnp, comp, u)
        return ru, row_take(jnp, comp, v)

    def hook(carry):
        comp, ru, rv, _, passes, jumps = carry
        live = valid & (ru != rv)
        comp = comp.at[jnp.where(live, jnp.maximum(ru, rv), n)].min(
            jnp.minimum(ru, rv), mode="drop"
        )
        comp, j = _compress(jnp, lax, comp)
        ru, rv = roots(comp)
        return (comp, ru, rv, jnp.any(valid & (ru != rv)), passes + 1,
                jumps + j)

    ru, rv = roots(comp)
    zero = jnp.int32(0)
    comp, _, _, _, passes, jumps = lax.while_loop(
        lambda carry: carry[3], hook,
        (comp, ru, rv, jnp.any(valid & (ru != rv)), zero, zero),
    )
    return comp, passes, jumps


# graphlint: traced -- Afforest's answer, inside both executables
def _expand_labels(jnp, comp, rank, ids):
    """Afforest's float32 labels of all n vertices from the forest `comp`
    over a renumbered CC view's rows (`FrontierEngine._cc_args`): each
    row's root row back to its vertex index (`ids`, n' rows), then each
    vertex its row's label (`rank`, n rows), a vertex without an edge its
    own index."""
    root = row_take(jnp, ids, comp)
    got = row_take(jnp, root, jnp.maximum(rank, 0))
    own = jnp.arange(rank.shape[0], dtype=jnp.int32)
    return jnp.where(rank >= 0, got, own).astype(jnp.float32)


# graphlint: traced -- the bottom-up hop's compactions
def _first_true(jnp, flags, size, fill):
    """`jnp.nonzero(flags, size=size, fill_value=fill)[0]` as int32. Where
    `size` is small against the flags, by binary search over their running
    count (log2(len) gathers of `size` indices) in place of nonzero's
    scatter-add of every flag into `size` buckets."""
    if 32 * size > flags.shape[0]:
        return jnp.nonzero(flags, size=size, fill_value=fill)[0].astype(
            jnp.int32)
    count = jnp.cumsum(flags.astype(jnp.int32))
    which = jnp.arange(1, size + 1, dtype=jnp.int32)
    found = jnp.searchsorted(count, which, side="left").astype(jnp.int32)
    return jnp.where(which <= count[-1], found, fill)


class FrontierEngine:
    """Per-executor engine: owns the device-resident CSR pointer arrays and
    the tier-compiled step executables for ShortestPath-family programs,
    for Brandes' sweeps (`run_brandes`) and for Afforest (`run_cc`)."""

    F_MIN = 1 << 10
    E_MIN = 1 << 13
    GROWTH = 4
    #: int32 telescoping headroom (see module docstring)
    MAX_EDGES = 1 << 30
    #: Afforest's sample (GAP's `neighbor_rounds`): each vertex links the
    #: neighbours at these positions of its closure row, which holds its
    #: higher neighbours and then its lower ones (GAP's rows are sorted)
    SAMPLE_ROUNDS = 2
    #: vertices the giant's root is read from (GAP's `num_samples`), at a
    #: fixed stride where GAP draws them
    SAMPLE_SIZE = 1024
    #: a bottom-up hop's stages (`_bottom_up_stages`): each holds this
    #: many times fewer rows than the one before and reads twice as many
    #: positions of each a block
    BOTTOM_UP_SHRINK = 4
    #: None: an unweighted search picks each hop's direction by the rule
    #: (`_direction`); "top_down" or "bottom_up" forces every hop after
    #: hop 0 one way (the engine's own switch, for tests; no option)
    direction = None

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
    def _plan_fn(self, undirected: bool, bottom_up: bool = False):
        """(mask, fargs) -> (frontier count, out-edge total, in-edge total):
        O(n) vector work, one fetch of three scalars per hop. With
        `bottom_up` (an unweighted search) the call is `plan(mask, fargs,
        dist, deg)` and two scalars follow in the same fetch: the slots of
        the unreached vertices' rows in the bottom-up view (`deg`, its
        degrees) and how many of those rows hold an edge."""
        plan = self._plans.get((undirected, bottom_up))
        if plan is not None:
            return plan
        jnp = self.jnp

        def frontier_sums(mask, fargs):
            zero = jnp.zeros((), jnp.int32)
            count = jnp.sum(mask.astype(jnp.int32))
            tot_out = jnp.sum(jnp.where(mask, fargs["out_deg"], zero))
            tot_in = (
                jnp.sum(jnp.where(mask, fargs["in_deg"], zero))
                if undirected
                else zero
            )
            return count, tot_out, tot_in

        if bottom_up:
            def plan_body(mask, fargs, dist, deg):
                open_rows = (dist >= INF) & (deg > 0)
                return (
                    *frontier_sums(mask, fargs),
                    jnp.sum(jnp.where(open_rows, deg, 0)),
                    jnp.sum(open_rows.astype(jnp.int32)),
                )
        else:
            def plan_body(mask, fargs):
                return frontier_sums(mask, fargs)

        plan = self.jax.jit(plan_body)
        self._plans[(undirected, bottom_up)] = plan
        return plan

    def _direction(self, t, bottom_up, count, last_count, slots, unreached):
        """Whether hop `t` of an unweighted search runs bottom-up (Beamer's
        rule, ALPHA and BETA), given the last hop's direction, the
        frontier's vertices now and a hop ago, its slots (what a top-down
        hop would relax) and the unreached vertices' slots (what a
        bottom-up hop reads at most). Hop 0 is always top-down: its
        frontier is the root. `direction` forces the rest one way."""
        if t == 0:
            return False
        if self.direction is not None:
            return self.direction == "bottom_up"
        if not bottom_up:
            return slots * ALPHA > unreached
        return not (count < last_count and count * BETA < self.n)

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
        flight where the narrow step adds it (`add_weight`: a weighted
        search on a graph that has weights), a min tree, and the narrow step's tail. The call is the
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

    def _bottom_up_args(self, undirected: bool):
        """The rows a bottom-up hop reads, as `{ip, nbr, deg}` (pointers
        padded to n + 2, neighbours, degrees): an undirected search's the
        simple closure's (`_simple_args`; a parallel edge or a loop changes
        no level and no valid parent), a directed one's the in-rows."""
        if undirected:
            args = self._simple_args()
            return {"ip": args["out_ip"], "nbr": args["out_dst"],
                    "deg": args["out_deg"]}
        args = self._orientation_args("in")
        return {"ip": args["in_ip"], "nbr": args["in_src"],
                "deg": args["in_deg"]}

    def _bottom_up_stages(self, F_cap):
        """(rows, positions) of each stage of a bottom-up hop at tier
        F_cap: the rows held, and the positions of each a block reads.
        Each stage holds BOTTOM_UP_SHRINK times fewer rows than the last,
        down to the ladder's first rung, and reads twice as many positions
        a block, so a block's size falls by half a stage while the rows
        that are left open run longer."""
        stages = [(F_cap, 1)]
        while stages[-1][0] > self.F_MIN:
            rows, width = stages[-1]
            stages.append((
                max(-(-rows // self.BOTTOM_UP_SHRINK), self.F_MIN), 2 * width
            ))
        return stages

    def _bottom_up_fn(self, F_cap, track_paths):
        """A bottom-up hop at tier F_cap (module `jit_bottom_up`, all of it
        under `frontier.bottom_up`): `fn(dist, pred, mask, t, args)` over
        the rows `_bottom_up_args` gives, returning the narrow step's
        (value, pred, next mask, its count) and two counts: the row
        positions read (within their rows) and the positions the stages'
        blocks held.

        The unreached vertices with an edge are compacted to F_cap rows
        (`_first_true`; at the ladder's top, F_cap == n, every vertex is
        its own row and nothing is compacted). Every open row then reads
        the same positions at once, block after block
        (`_bottom_up_stages`): each position's neighbour, and whether it
        is on the frontier. A row that found one is closed: its first
        such neighbour by position is its parent. A row whose positions
        are all read is closed too. A stage runs blocks until its open
        rows fit the next stage, whose buffer they are compacted into; the
        last runs until none is open. So the levels are the top-down
        hop's: a vertex is reached exactly when some neighbour is on the
        frontier."""
        key = ("frontier-bottom-up", F_cap, track_paths)
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        jnp, lax = self.jnp, self.jax.lax
        n = self.n
        i32 = jnp.int32
        stages = self._bottom_up_stages(F_cap)

        def block(carry, nbr, front, start, end, width, size):
            """The next `width` positions of every open row."""
            open_, got, _, done, read, held = carry
            steps = jnp.arange(width, dtype=i32)
            pos = start[:, None] + done + steps
            valid = open_[:, None] & (pos < end[:, None])
            u = jnp.where(
                valid, nbr[jnp.clip(pos, 0, nbr.shape[0] - 1)], n)
            hit = front[u]
            found = jnp.any(hit, axis=1)
            # the first hit by position, picked without a gather
            at = jnp.argmax(hit, axis=1)
            first = jnp.sum(jnp.where(steps == at[:, None], u, 0), axis=1)
            got = jnp.where(found, first, got)
            done = done + width
            open_ = open_ & ~found & (start + done < end)
            return (
                open_, got, jnp.sum(open_.astype(i32)), done,
                read + jnp.sum(valid.astype(i32)), held + size * width,
            )

        def bottom_up(dist, pred, mask, t, args):
            with frontier_scope("bottom_up"):
                ip, nbr = args["ip"], args["nbr"]
                # the sentinel row n is never on the frontier
                front = jnp.concatenate([mask, jnp.zeros((1,), bool)])
                unreached = dist >= INF
                if F_cap == n:
                    rows = jnp.arange(n, dtype=i32)
                    start, end = ip[:n], ip[1:n + 1]
                    open_ = unreached & (start < end)
                else:
                    rows = _first_true(
                        jnp, unreached & (args["deg"] > 0), F_cap, n)
                    # row n reads degree 0: padding is never open
                    start, end = ip[rows], ip[rows + 1]
                    open_ = start < end
                parent = jnp.full((n,), -1, i32)
                done = read = held = i32(0)
                for s, (size, width) in enumerate(stages):
                    if s:
                        # the open rows into this stage's smaller buffer
                        pick = _first_true(
                            jnp, open_, size, open_.shape[0])
                        rows, start, end = (
                            jnp.concatenate([a, jnp.array([fill], i32)])[pick]
                            for a, fill in ((rows, n), (start, 0), (end, 0))
                        )
                        open_ = start + done < end
                    floor = stages[s + 1][0] if s + 1 < len(stages) else 0
                    open_, got, _, done, read, held = lax.while_loop(
                        lambda carry, floor=floor: carry[2] > floor,
                        functools.partial(
                            block, nbr=nbr, front=front, start=start,
                            end=end, width=width, size=size),
                        (open_, jnp.full((size,), -1, i32),
                         jnp.sum(open_.astype(i32)), done, read, held),
                    )
                    if size == n:  # every vertex its own row, in order
                        parent = jnp.maximum(parent, got)
                    else:
                        parent = parent.at[
                            jnp.where(got >= 0, rows, n)
                        ].set(got, mode="drop")
                tmp = jnp.where(
                    parent >= 0, parent.astype(jnp.float32), INF
                )
                return (
                    *self._settle(dist, pred, tmp, t, False, track_paths),
                    read, held,
                )

        fn = self.jax.jit(bottom_up)
        cache[key] = fn
        return fn

    def _parent_fn(self, undirected):
        """`parent_pass(dist, when, pack.arrays, row_dsts)` -> parent
        array of a converged WEIGHTED search, read off the executor's
        pack of the view the search reads (the wide round's, built on
        first use) by destination: ONE gather of every slot's sender as a
        row `(dist[p] + w, when[p], dist[p], p)`, the weight added in
        flight as the wide round adds it; each row of the pack's
        `(dist[v], when[v])` (`row_dsts`: its destination, per row); the
        test below per slot in `hybrid_fold`'s `leaf_fn`; the min tree.

        `p` may be `v`'s parent if an edge between them of weight `w`
        explains `v`'s distance, `fl32(dist[p] + w) == dist[v]`, and `p`
        comes before `v` in the order (dist, when): `when` is the round of
        a vertex's last strict improvement, as the weighted step recorded
        it. Every reached vertex but the root has such a `p`: the sender
        of its last improvement, whose distance was final by then if it is
        equal (a weight of 0, or one the addition absorbs) and is smaller
        otherwise. The order strictly falls along parent pointers, so they
        form a tree that ends at the root, the one vertex with `when` 0.
        Among a vertex's candidates the smallest index is returned: the
        pack holds the edges into `v` (both orientations where
        `undirected`), each once, so the MIN over the candidates' float32
        indices (exact below 2^24) is that index whatever the fold's
        order, on every graph."""
        key = ("frontier-parent", undirected)
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        pack = self.ex._hybrid_pack(undirected)  # for its static sizes
        jnp = self.jnp
        n = self.n
        f32 = jnp.float32
        # the weight rides on the sender's distance alone
        cols = (EdgeTransform.ADD_WEIGHT,) + (EdgeTransform.NONE,) * 3

        def parent_pass(dist, when, hyb, row_dsts):
            view = HybridPackView(hyb, pack)
            with frontier_scope("parent"):
                index = jnp.arange(n, dtype=f32)
                leaves = hybrid_gather(
                    jnp, view, jnp.stack([dist, when, dist, index], axis=1),
                    Combiner.MIN, edge_transform_cols=cols,
                )
                mine = flat_take(jnp, jnp.stack([dist, when], axis=1),
                                 row_dsts)

                def candidates(block, lo, hi):
                    d_v, when_v = mine[lo:hi, 0], mine[lo:hi, 1]
                    ok = (
                        (block[..., 0] == d_v) & (d_v < INF)
                        & ((block[..., 2] < d_v) | (block[..., 1] < when_v))
                    )
                    return jnp.where(ok, block[..., 3], INF)

                best = hybrid_fold(
                    jnp, view, leaves, Combiner.MIN, (n,), f32,
                    leaf_fn=candidates, leaf_cols=(),
                )
                pred = jnp.where(best < INF, best, -1.0)
                return jnp.where(when == 0.0, index, pred)

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
        """The shared host-driven loop: plan -> pick direction and tier ->
        one compiled step: top-down narrow, the wide round (`_wide_fn`)
        where the tier is the ladder's top, or, in an unweighted search
        where the rule says so (`_direction`), bottom-up
        (`_bottom_up_fn`). Two device round trips per hop; per-step
        output is identical to the dense BSP path's (a bottom-up hop's
        parents excepted: the module docstring). The host's time in it is
        tiled by phases: per hop `executor.tier` (the plan's dispatch and
        the choices; the wait for its scalars is the `executor.sync`
        inside it) and `executor.dispatch` (the step). Returns the state
        and the hops' `tiers`; a bottom-up hop's two counts are left in
        its entry as device scalars, for the caller's fetch."""
        jax, jnp = self.jax, self.jnp
        rows = None if weighted else self._bottom_up_args(und)
        plan = self._plan_fn(und, rows is not None)
        if self.m == 0:
            mask = jnp.zeros_like(mask)
        trace = []
        bottom_up, last_count = False, 0
        for t in range(max_iterations):
            with tracer.phase("executor.tier"):
                planned = (
                    plan(mask, fargs) if rows is None
                    else plan(mask, fargs, value, rows["deg"])
                )
                with tracer.phase("executor.sync"):
                    planned = jax.device_get(planned)
                count, tot_out, tot_in, *unreached = (int(x) for x in planned)
                if count == 0:
                    break
                f_cap, e_cap = self._caps(
                    count, max(tot_out, tot_in), self.m,
                    (self.f_schedule, self.e_schedule),
                )
                if rows is not None:
                    bottom_up = self._direction(
                        t, bottom_up, count, last_count, tot_out + tot_in,
                        unreached[0],
                    )
                    last_count = count
                # the rule is the rung: a top-down hop that would hold
                # every edge is a wide round on the pack of the view it
                # reads (built on first use, the dense path's own)
                wide = e_cap == self.m and not bottom_up
                pack = self.ex._hybrid_pack(und) if wide else None
                hop = {"hop": t, "frontier": count,
                       "edges": max(tot_out, tot_in),
                       "direction": (
                           "bottom_up" if bottom_up
                           else "wide" if wide else "top_down"),
                       "tier_source": (
                           "autotune" if self.e_schedule else "static")}
                if bottom_up:
                    # the unreached rows with an edge, on the F ladder
                    f_cap = self._caps(
                        unreached[1], 1, self.m,
                        (self.f_schedule, self.e_schedule),
                    )[0]
                    hop.update(relaxed_slots=0, tier_slots=0, F_cap=f_cap,
                               E_cap=0, wide=False,
                               unreached_slots=unreached[0])
                else:
                    # slots the hop relaxes and slots its tier holds, over
                    # the orientations it runs (tot_in is 0 directed); a
                    # wide hop holds what it gathers
                    hop.update(
                        relaxed_slots=tot_out + tot_in,
                        tier_slots=(
                            pack.slots if wide
                            else e_cap * (2 if und else 1)
                        ),
                        F_cap=f_cap, E_cap=e_cap, wide=wide,
                    )
                trace.append(hop)
            with tracer.phase("executor.dispatch"):
                t_dev = jnp.asarray(t, jnp.float32)
                if bottom_up:
                    fn = self._bottom_up_fn(f_cap, track)
                    (value, pred, mask, _, hop["bottom_up_slots"],
                     hop["bottom_up_tier_slots"]) = fn(
                        value, pred, mask, t_dev, rows)
                    continue
                if wide:
                    fn = self._wide_fn(
                        weighted, track, und, weighted and "out_w" in fargs
                    )
                    args = pack.arrays
                else:
                    fn = self._step_fn(f_cap, e_cap, weighted, track, und)
                    args = fargs
                value, pred, mask, _ = fn(value, pred, mask, t_dev, args)
        with tracer.phase("executor.sync"):
            # the last step is still running: wait for it here, so that
            # the caller's fetch is the copy alone
            await_arrays((value, pred))
        # which tiers each hop actually priced at: the per-hop analogue of
        # .profile(), the run record's `tiers`
        return value, pred, trace

    def _start_fn(self, track_paths):
        """`start(root) -> (dist, pred, mask)`, an unweighted search's
        start vectors made on the device from the root's index (module
        `jit_start`, one execution a search): 0 at the root and INF
        elsewhere; with `track_paths` the root's own index and -1
        elsewhere, else None; the mask of the root alone. Nothing of n
        rows is made on the host."""
        key = ("frontier-start", track_paths)
        cache = self.ex._compiled
        if key not in cache:
            jnp = self.jnp
            f32 = jnp.float32

            def start(root):
                here = jnp.arange(self.n, dtype=jnp.int32) == root
                pred = None
                if track_paths:
                    pred = jnp.where(here, root.astype(f32), f32(-1.0))
                return jnp.where(here, f32(0.0), f32(INF)), pred, here

            cache[key] = self.jax.jit(start)
        return cache[key]

    def _host_start(self, seed, track_paths):
        """A weighted search's start vectors, made on the host and copied:
        0 at the root and INF elsewhere; with `track_paths` the root's
        round, 0 (`_step_fn`'s slot carries rounds until `_parent_fn`), and
        -1 elsewhere, else None; the mask of the root alone. Device-made
        ones (`_start_fn`) ran kernel 3 at scale 18 4.6-4.7% slower a search
        on a TPU v5e, every key alike, for a cause not found."""
        jnp = self.jnp
        idx0 = np.arange(self.n, dtype=np.int64)
        dist = jnp.asarray(np.where(idx0 == seed, 0.0, INF), jnp.float32)
        pred = None
        if track_paths:
            pred = jnp.asarray(np.where(idx0 == seed, 0.0, -1.0), jnp.float32)
        return dist, pred, jnp.asarray(idx0 == seed)

    def _record(self, path, trace, view, cols=(), **fields):
        """The run record of `trace`'s hops over `view` (its pack and
        ladder), and the engine's counters: totals a layer metric can read
        (hops, slots relaxed, slots their tiers held, hops run as wide
        rounds on the pack), one superstep record a hop. A wide round
        gathers pieces `cols` wide (`()`: a 1-D state as rows of two
        words, `kernels.row_take`, counted by `olap.pack.row_gathers`)."""
        relaxed = sum(t["relaxed_slots"] for t in trace)
        held = sum(t["tier_slots"] for t in trace)
        wide = sum(t["wide"] for t in trace)
        registry.counter("olap.frontier.rounds").inc(len(trace))
        registry.counter("olap.frontier.relaxed_slots").inc(relaxed)
        registry.counter("olap.frontier.tier_slots").inc(held)
        registry.counter("olap.frontier.wide_rounds").inc(wide)
        registry.counter("olap.pack.row_gathers").inc(0 if cols else wide)
        pack = self.ex._hybrid_packs.get(view)
        record = {
            "path": path,
            "supersteps": len(trace),
            "tiers": trace,
            "rounds": len(trace),
            "relaxed_slots": relaxed,
            "tier_slots": held,
            "wide_rounds": wide,
            "pad_ratio": None if pack is None else round(pack.pad_ratio, 4),
            "autotune": self.ex._autotune(view).as_dict(),
            "superstep_records": [
                {"step": int(t.get("hop", i)),
                 "frontier": int(t.get("frontier", 0)),
                 "edges": int(t.get("edges", 0)),
                 "e_cap": int(t.get("E_cap", 0))}
                for i, t in enumerate(trace)
            ],
        }
        if wide:
            record["gather_words"] = cols[0] if cols else 2
        record.update(fields)
        return record

    def run(self, program):
        """SSSP/BFS through the shared hop loop: (states, run record)."""
        # the hop loop relaxes by scatter-min: partials merged in place
        Combiner.require_foldable(program.combiner, "the frontier engine")
        jnp = self.jnp
        n = self.n
        weighted = program.weighted
        track = program.track_paths
        und = program.undirected
        with tracer.phase("executor.setup"):
            if weighted:
                dist, pred, mask = self._host_start(program.seed_index, track)
            else:
                dist, pred, mask = self._start_fn(track)(
                    jnp.asarray(program.seed_index, jnp.int32))
            fargs = self._fargs(und, weighted)
        dist, pred, trace = self._hop_loop(
            dist, pred, mask, weighted, track, und, fargs,
            program.max_iterations,
        )
        # the slots the weighted parent pass gathered, 0 where none ran
        # (its one gather of (n, 4) rows is neither `gather_words` nor a
        # row gather)
        parent_slots = 0
        if weighted and track:
            # the loop carried rounds, not parents: read the parents off
            # the converged distances in one pass over the pack
            with tracer.phase("executor.dispatch"):
                if self.m == 0:
                    # no pack: every vertex is unreached but the root
                    pred = jnp.where(
                        pred == 0.0, jnp.arange(n, dtype=jnp.float32), -1.0
                    )
                else:
                    pack = self.ex._hybrid_pack(und)
                    pred = self._parent_fn(und)(
                        dist, pred, pack.arrays, self.ex._row_dsts(und)
                    )
                    parent_slots = pack.slots
            with tracer.phase("executor.sync"):
                await_arrays((pred,))
        bottom_up = [t for t in trace if t["direction"] == "bottom_up"]
        with tracer.phase("executor.fetch"):
            out = {"distance": np.asarray(dist)}
            if track:
                out["predecessor"] = np.asarray(pred)
            counted = self.jax.device_get([
                (t["bottom_up_slots"], t["bottom_up_tier_slots"])
                for t in bottom_up
            ])
        for t, (read, held) in zip(bottom_up, counted):
            t["bottom_up_slots"], t["bottom_up_tier_slots"] = (
                int(read), int(held))
        read = sum(t["bottom_up_slots"] for t in bottom_up)
        registry.counter("olap.frontier.bottom_up_rounds").inc(len(bottom_up))
        registry.counter("olap.frontier.bottom_up_slots").inc(read)
        record = self._record(
            "frontier", trace, und, parent_slots=parent_slots,
            bottom_up_rounds=len(bottom_up),
            # row positions the bottom-up hops read, and their blocks held
            bottom_up_slots=read,
            bottom_up_tier_slots=sum(
                t["bottom_up_tier_slots"] for t in bottom_up),
        )
        # Beamer's "edges examined": relaxed top-down plus read bottom-up
        record["examined_slots"] = record["relaxed_slots"] + read
        return out, record

    # -------------------------------------------------------------- afforest
    def _afforest_sample_fn(self):
        """`afforest_sample(args) -> (comp, labels, mask, count, slots,
        passes, jumps, giant)` over the rows of the CC view (`_cc_args`;
        module `jit_afforest_sample`, scope `afforest.sample`). For r <
        SAMPLE_ROUNDS every row of degree above r links the neighbour at
        position r of its row, to the fixpoint; then the most frequent
        root among the view's `picks` is the giant's (the first of
        equals; a pick of a vertex v without an edge votes `-1 - v`, its
        own component). `mask` marks
        the rows with an edge outside the giant, `count` and `slots` price
        them as `_plan_fn` prices a frontier, `giant` counts the vertices
        under its root. `labels` is the answer when no finish runs: on a
        renumbered view it is expanded to n rows (`_expand_labels`) only
        then, under a `lax.cond` whose other branch writes zeros, so a
        submit expands once, in the finish where one runs."""
        key = ("afforest-sample",)
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        jnp, lax = self.jnp, self.jax.lax
        n = self.n
        renumbered = "rank" in self._cc_args()

        def afforest_sample(args):
            with afforest_scope("sample"):
                m = args["out_dst"].shape[0]
                rows = args["out_deg"].shape[0]
                starts, deg = args["out_ip"][:rows], args["out_deg"]
                me = jnp.arange(rows, dtype=jnp.int32)
                comp, passes, jumps = me, jnp.int32(0), jnp.int32(0)
                for r in range(self.SAMPLE_ROUNDS):
                    has = deg > r
                    at = jnp.clip(starts + r, 0, m - 1)
                    nbr = jnp.where(has, args["out_dst"][at], me)
                    comp, p, j = _link(jnp, lax, comp, None, nbr, has)
                    passes, jumps = passes + p, jumps + j
                picks = args["picks"]
                seen = jnp.where(
                    picks >= 0, comp[jnp.maximum(picks, 0)], picks)
                votes = jnp.sum(
                    (seen[:, None] == seen[None, :]).astype(jnp.int32), axis=1
                )
                giant = seen[jnp.argmax(votes)]
                mask = (comp != giant) & (deg > 0)
                slots = jnp.sum(jnp.where(mask, deg, 0))
                if renumbered:
                    labels = lax.cond(
                        slots == 0,
                        lambda c: _expand_labels(
                            jnp, c, args["rank"], args["ids"]),
                        lambda c: jnp.zeros((n,), jnp.float32),
                        comp,
                    )
                else:
                    labels = comp.astype(jnp.float32)
                return (
                    comp, labels, mask, jnp.sum(mask.astype(jnp.int32)),
                    slots, passes, jumps,
                    # a giant without an edge is its one vertex
                    jnp.where(giant < 0, 1,
                              jnp.sum((comp == giant).astype(jnp.int32))),
                )

        fn = self.jax.jit(afforest_sample)
        cache[key] = fn
        return fn

    def _afforest_finish_fn(self, F_cap, E_cap):
        """`afforest_finish(comp, mask, args) -> (labels, passes, jumps)`
        (module `jit_afforest_finish`, scope `afforest.finish`): the rows
        in `mask` linked to the fixpoint over the CC view (`_cc_args`),
        then the labels of all n vertices as float32 (on a renumbered
        view the expansion to n rows, `_expand_labels`, runs here).
        Narrow: the rows compacted to F_cap rows and E_cap slots
        (`capped_expand`); a row is read whole, its positions 0 and 1
        again too, whose ends the sample gave one root (GAP skips them: no
        pass differs). `E_cap == 0` is the ladder's top rung: every slot
        of the closure, its sender read off the pointers (ones
        scatter-added at each row's first slot, then a cumsum). An edge
        there is linked from both ends, or from neither where both lie in
        the giant, whose ends share a root already: the same passes as the
        narrow form's, and no pack, since a link is no fold by
        destination."""
        key = ("afforest-finish", F_cap, E_cap)
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        jnp, lax = self.jnp, self.jax.lax
        renumbered = "rank" in self._cc_args()

        def afforest_finish(comp, mask, args):
            with afforest_scope("finish"):
                ip, dst = args["out_ip"], args["out_dst"]
                m, rows = dst.shape[0], comp.shape[0]
                if E_cap == 0:
                    u = jnp.cumsum(
                        jnp.zeros((m,), jnp.int32).at[ip[1:rows]].add(
                            1, mode="drop")
                    )
                    v, valid = dst, jnp.ones((m,), bool)
                else:
                    idx = jnp.nonzero(mask, size=F_cap, fill_value=rows)[0]
                    idx = idx.astype(jnp.int32)
                    own, _, v, valid = capped_expand(
                        jnp, idx, ip, dst, E_cap, rows
                    )
                    u = jnp.minimum(idx[own], rows - 1)
                    v = jnp.minimum(v, rows - 1)
                comp, passes, jumps = _link(jnp, lax, comp, u, v, valid)
                if renumbered:
                    labels = _expand_labels(
                        jnp, comp, args["rank"], args["ids"])
                else:
                    labels = comp.astype(jnp.float32)
                return labels, passes, jumps

        fn = self.jax.jit(afforest_finish)
        cache[key] = fn
        return fn

    def run_cc(self, program):
        """Connected components to the fixpoint by Afforest over the
        executor's simple closure, in its CC view (`_cc_args`: the
        vertices that have an edge alone, renumbered in their own order,
        where some vertex has none): the sample (one executable, then ONE
        host sync for its five scalars), the finish's tier from its
        vertices and slots on the closure's ladder (`_caps`), the finish
        (none where no vertex outside the giant has an edge), one fetch
        of the labels and the finish's counters. The labels come back
        expanded to n rows by whichever of the two executables ran last.
        Labels are float32 vertex indices (exact below 2^24:
        eligibility-guarded), each component's smallest. The host's time
        is tiled by `executor.setup` / `.dispatch` (with the sample's
        `executor.sync` inside) / `.sync` / `.fetch`. Returns (states, run
        record): the record's `tiers` hold the finish's tier, if one ran,
        and a superstep of Afforest is a link pass."""
        Combiner.require_foldable(program.combiner, "the frontier engine")
        jax = self.jax
        n = self.n
        with tracer.phase("executor.setup"):
            args = self._cc_args()
            m = int(args["out_dst"].shape[0])
            decision = self.ex._autotune(self.ex.SIMPLE_VIEW)
            schedules = (decision.f_schedule, decision.e_schedule)
        trace = []
        passes, jumps = [0, 0], [0, 0]
        count = slots = 0
        giant = 1
        finished = ()
        if m == 0:
            # no edge that is no loop: every vertex is its own component
            labels = np.arange(n, dtype=np.float32)
        else:
            with tracer.phase("executor.dispatch"):
                comp, labels, mask, *plan = self._afforest_sample_fn()(args)
                with tracer.phase("executor.sync"):
                    plan = jax.device_get(plan)
                count, slots, passes[0], jumps[0], giant = (
                    int(x) for x in plan
                )
                if slots:
                    f_cap, e_cap = self._caps(count, slots, m, schedules)
                    top = e_cap == m
                    trace.append(
                        {"phase": "finish", "hop": 0, "frontier": count,
                         "edges": slots, "relaxed_slots": slots,
                         "tier_slots": e_cap, "F_cap": f_cap,
                         "E_cap": e_cap, "wide": False, "top_rung": top,
                         "tier_source": (
                             "autotune" if schedules[1] else "static"
                         )}
                    )
                    labels, *finished = self._afforest_finish_fn(
                        f_cap, 0 if top else e_cap
                    )(comp, mask, args)
            with tracer.phase("executor.sync"):
                await_arrays((labels,))
        with tracer.phase("executor.fetch"):
            labels, finished = jax.device_get((labels, finished))
            out = {"component": np.asarray(labels)}
        if finished:
            passes[1], jumps[1] = (int(x) for x in finished)
        return out, self._record(
            "frontier", trace, self.ex.SIMPLE_VIEW,
            supersteps=sum(passes),
            algorithm="afforest",
            link_passes={"sample": passes[0], "finish": passes[1]},
            compress_jumps={"sample": jumps[0], "finish": jumps[1]},
            # what the finish read, its tier's padding excluded
            finish_vertices=count,
            finish_slots=slots,
            closure_slots=m,
            # the rows the sample's and the finish's vectors hold, and n
            sample_vertices=int(args["out_deg"].shape[0]),
            vertices=n,
            giant_share=giant / max(n, 1),
        )

    def _cc_args(self):
        """Afforest's view of the simple closure (`run_cc`): where some
        vertex has no edge in it, `_simple_args`' arrays over the n'
        vertices that have one, renumbered in rising order of their own
        index, with `rank` (each vertex's row, -1 without an edge) and
        `ids` (each row's vertex). Renaming in order keeps every min and
        max, so every hook, jump and pass is the one over all n vertices,
        on 38% fewer rows at GAP's kron. `picks` are the rows of the
        SAMPLE_SIZE vertices at the stride i x n / SAMPLE_SIZE of the
        vertices' own numbering; a pick without an edge is `-1 - v`, a
        vote no root can equal and that only another pick of v shares.
        Where every vertex has an edge the view is `_simple_args`' own
        arrays and `picks`: nothing is renumbered or expanded. Built on
        the host on first use and kept, an index of the graph like the
        CSR; `_simple_args` is built only where something reads it, so a
        process that runs both Brandes and CC holds the neighbours twice."""
        args = self._fargs_cache.get("cc")
        if args is None:
            jnp, n = self.jnp, self.n
            src, dst = self.ex._simple_closure()
            deg = np.bincount(src, minlength=n)
            picks = (
                np.arange(self.SAMPLE_SIZE, dtype=np.int64) * n
                // self.SAMPLE_SIZE
            )
            ids = np.flatnonzero(deg)
            if len(ids) == n:
                args = dict(self._simple_args())
            else:
                rank = np.full(n, -1, np.int32)
                rank[ids] = np.arange(len(ids), dtype=np.int32)
                indptr = np.zeros(len(ids) + 2, np.int64)
                np.cumsum(deg[ids], out=indptr[1:len(ids) + 1])
                indptr[-1] = indptr[-2]
                picks = np.where(rank[picks] >= 0, rank[picks], -1 - picks)
                args = {
                    "out_ip": jnp.asarray(indptr.astype(np.int32)),
                    "out_dst": jnp.asarray(rank[dst]),
                    "out_deg": jnp.asarray(deg[ids].astype(np.int32)),
                    "rank": jnp.asarray(rank),
                    "ids": jnp.asarray(ids.astype(np.int32)),
                }
            args["picks"] = jnp.asarray(picks.astype(np.int32))
            self._fargs_cache["cc"] = args
        return args

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

    def run_brandes(self, program):
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
        level), `executor.sync`, `executor.fetch`. Returns (states, run
        record): its `tiers` list every level run, forward then backward,
        with its sweep."""
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
        with tracer.phase("executor.fetch"):
            out = {"betweenness": np.asarray(scores)}
        forward = sum(t["sweep"] == "forward" for t in trace)
        return out, self._record(
            "brandes", trace, self.ex.SIMPLE_VIEW, cols=(K,),
            sources=list(program.sources),
            # the deepest level any column reached (the last forward hop
            # reaches nothing); the backward sweep runs levels L ... 2
            levels=max(forward - 1, 0),
            forward_rounds=forward,
            backward_rounds=len(trace) - forward,
            closure_slots=m,
        )
