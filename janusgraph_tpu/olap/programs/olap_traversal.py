"""OLAP traversal execution: the TraversalVertexProgram analogue.

The reference runs Gremlin traversals OLAP-side by shipping TinkerPop's
TraversalVertexProgram through Fulgora (reference: BASELINE config #5 "3-hop
via TraversalVertexProgram"; FulgoraGraphComputer.submit on a traversal;
SURVEY.md §7 hard part (a) "arbitrary traversers as device state"). The
TPU-native form: a RESTRICTED traversal — a chain of expansion steps, each
with its own direction + edge labels — compiles into one BSP run where
superstep k applies step k's typed EdgeChannel, and per-vertex state is the
dense TRAVERSER COUNT vector (the device representation of "how many
traversers sit here"), exactly what count()/group-count terminals need.
Arbitrary per-traverser state (paths, arbitrary objects) stays an OLTP
concern — the restriction that makes the hot path one gather/segment-reduce
per step.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

from janusgraph_tpu.olap.vertex_program import (
    Combiner,
    EdgeChannel,
    EdgeTransform,
    VertexProgram,
)


from dataclasses import dataclass


@dataclass(frozen=True)
class PropertyFilter:
    """A mid-chain has()-filter: keep traversers only on vertices whose
    property satisfies the predicate (reference: TraversalVertexProgram
    executes arbitrary Gremlin OLAP-side incl. HasStep —
    FulgoraGraphComputer.java:249-253 submits the full traversal).

    Evaluation is HOST-side over the CSR's property arrays, producing an
    (n,) {0,1} mask shipped to device once (rationale: every predicate —
    Cmp, Text, Geo — works unchanged on any property type; the per-superstep
    device cost is one elementwise multiply, and the mask IS the
    device-resident form of the property column)."""

    key: str
    predicate: object  # a core.predicates.Predicate singleton
    value: object


@dataclass(frozen=True)
class TraversalStep:
    """One expansion: direction out/in/both, optional edge-label ids, and
    optional post-expansion property filters (the `.out().has(...)` shape).
    Frozen/value-comparable so program cache keys (and the executors'
    channel caches) hit across instances built from the same spec."""

    direction: str = "out"
    labels: Optional[Tuple[int, ...]] = None
    filters: Tuple[PropertyFilter, ...] = ()
    #: step label for select() over enumerated paths (the as() tag of
    #: TinkerPop; reference: TraversalVertexProgram carrying path labels)
    as_label: Optional[str] = None

    def __post_init__(self):
        if self.direction not in ("out", "in", "both"):
            raise ValueError(f"unknown step direction {self.direction!r}")
        if self.labels is not None:
            object.__setattr__(self, "labels", tuple(self.labels))
        object.__setattr__(self, "filters", tuple(self.filters))


def _parse_filters(filters) -> Tuple[PropertyFilter, ...]:
    out = []
    for f in filters or ():
        if isinstance(f, PropertyFilter):
            out.append(f)
        else:
            key, pred, value = f
            out.append(PropertyFilter(key, pred, value))
    return tuple(out)


def steps_from_spec(graph, spec: Sequence) -> Tuple[TraversalStep, ...]:
    """Build steps from spec items, resolving label NAMES to schema ids via
    the graph (None/empty labels = all). Item shapes:
      'out'                                  — expand, all labels
      ('out', ['knows'])                     — expand along labels
      ('out', ['knows'], [(key, pred, v)])   — expand, then has()-filter
      ('out', ['knows'], [...], 'b')         — ... and as('b')-tag the step
    """
    out = []
    for item in spec:
        filters = ()
        as_label = None
        if isinstance(item, str):
            direction, labels = item, None
        elif len(item) == 2:
            direction, labels = item
        elif len(item) == 3:
            direction, labels, filters = item
        else:
            direction, labels, filters, as_label = item
        ids = None
        if labels:
            ids = []
            for name in labels:
                el = graph.schema_cache.get_by_name(name)
                if el is None:
                    # a typo'd label silently matching nothing would return
                    # a wrong-but-plausible count — fail loudly instead
                    raise ValueError(f"unknown edge label {name!r}")
                ids.append(el.id)
            ids = tuple(ids)
        out.append(
            TraversalStep(direction, ids, _parse_filters(filters), as_label)
        )
    return tuple(out)


def evaluate_filter_mask(csr, filters: Sequence[PropertyFilter]):
    """AND-combined (n,) float32 {0,1} mask over the CSR's host property
    arrays. Cmp predicates on numeric columns vectorize through numpy; every
    other predicate falls back to the scalar evaluate() loop (correct for
    text/geo/object types)."""
    import numpy as np

    n = csr.num_vertices
    mask = np.ones(n, dtype=np.float32)
    for f in filters:
        col = csr.properties.get(f.key)
        if col is None:
            raise ValueError(
                f"property {f.key!r} not loaded in this CSR snapshot — "
                f"pass property_keys={f.key!r} to load_csr"
            )
        from janusgraph_tpu.core.predicates import _CmpPredicate

        m = None
        if isinstance(f.predicate, _CmpPredicate) and np.issubdtype(
            np.asarray(col).dtype, np.number
        ):
            try:
                with np.errstate(invalid="ignore"):
                    m = f.predicate._fn(np.asarray(col), f.value)
            except TypeError:
                m = None  # mistyped condition: scalar evaluate() decides
        if m is None:
            m = np.fromiter(
                (f.predicate.evaluate(v, f.value) for v in col),
                dtype=bool, count=n,
            )
        mask *= m.astype(np.float32)
    return mask


class OLAPTraversalProgram(VertexProgram):
    """Traverser-count BSP over a step chain.

    state["count"][v] = number of traversers at v after the steps so far
    (float64-safe in f32 up to 2^24 per vertex; overflow means the query
    wants group-counting, not exact enumeration). Starts from all vertices
    (g.V() semantics) or a seed set.

    Terminals on the result:
      total = result["count"].sum()            — g.V().out()...count()
      per-vertex counts                         — group-count by destination
    """

    compute_keys = ("count",)
    combiner = Combiner.SUM
    setup_only_params = ("seed_indices",)

    def __init__(
        self,
        steps: Sequence[TraversalStep],
        seed_indices=None,
        seed_mask=None,
        step_masks=None,
        record_reach: bool = False,
        sack: Optional[str] = None,
        sack_init: Optional[float] = None,
    ):
        """`seed_mask`: (n,) {0,1} array filtering the start set (the
        g.V().has(...) head). `step_masks`: (n, S) array, column k the
        post-expansion filter mask of step k (ones where unfiltered) —
        both prebuilt by `build_olap_traversal` from the steps' filters.
        Masks travel through STATE (not closures) so they ride the jit
        argument path like every other device array (_graph_args lesson:
        big closure constants break remote compile).

        An (n, K) `seed_mask` is K STARTS run as the columns of one
        chain (the spillover planner's batch: K requests' arrival
        vectors, one dispatch): `count` is (n, K) throughout, column j
        bit for bit the (n,) run of column j (the pack's aggregation
        folds each column through the same tree). Such a start carries no
        sack, no step masks and no reach record."""
        self.steps = tuple(steps)
        if not self.steps:
            raise ValueError("at least one traversal step required")
        if step_masks is None and any(st.filters for st in self.steps):
            # running a filter-bearing chain without masks would silently
            # return unfiltered counts — demand the builder
            raise ValueError(
                "steps carry property filters but no step_masks were "
                "built — construct via build_olap_traversal(graph, csr, "
                "spec) so masks are evaluated against the CSR snapshot"
            )
        self.seed_indices = (
            tuple(int(i) for i in seed_indices)
            if seed_indices is not None
            else None
        )
        #: columns of an (n, K) start, 0 for the (n,) start: an int, so it
        #: tells the two compiled steps apart (`cache_key`)
        self.width = 0
        if seed_mask is not None and getattr(seed_mask, "ndim", 1) == 2:
            for given, name in (
                (sack, "sack"), (step_masks, "step_masks"),
                (record_reach, "record_reach"),
                (seed_indices, "seed_indices"),
            ):
                if given is not None and given is not False:
                    raise ValueError(
                        f"an (n, K) seed_mask runs K plain chains: {name} "
                        "is not supported with it"
                    )
            self.width = int(seed_mask.shape[1])
        self._seed_mask = seed_mask
        self._step_masks = step_masks
        self.has_step_masks = step_masks is not None
        #: device-side half of path()/select(): record, per superstep, the
        #: {0,1} mask of vertices holding >=1 traverser — the per-level
        #: reachability host enumeration walks backward over
        #: (enumerate_paths; SURVEY §7 hard part (a)'s hybrid design)
        self.record_reach = record_reach
        #: OLAP-side sack (TinkerPop withSack().sack(op).by('weight')):
        #: state["sack"][v] = total sack mass of the traversers at v.
        #:   "sum"  — each hop adds the edge weight per traverser:
        #:            S'[v] = Σ_{u→v} (S[u] + w·c[u]); message columns
        #:            [count, sack, count] ride per-column transforms
        #:            (NONE, NONE, MUL_WEIGHT) — the third column carries
        #:            the cross-term Σ w·c (apply_edge_transform)
        #:   "mult" — each hop multiplies by the edge weight:
        #:            S'[v] = Σ S[u]·w; columns [count, sack] with
        #:            (NONE, MUL_WEIGHT)
        if sack not in (None, "sum", "mult"):
            raise ValueError(f"unknown sack op {sack!r} (sum|mult)")
        self.sack = sack
        self.sack_init = (
            sack_init if sack_init is not None
            else (0.0 if sack == "sum" else 1.0)
        )
        if sack == "sum":
            self.edge_transform_cols = (
                EdgeTransform.NONE, EdgeTransform.NONE,
                EdgeTransform.MUL_WEIGHT,
            )
        elif sack == "mult":
            self.edge_transform_cols = (
                EdgeTransform.NONE, EdgeTransform.MUL_WEIGHT,
            )
        self.max_iterations = len(self.steps)
        # one named channel per step; labels=None channels still express
        # per-step direction through the same machinery
        self.edge_channels = {
            f"s{i}": EdgeChannel(st.direction, st.labels)
            for i, st in enumerate(self.steps)
        }

    def channel_for(self, superstep: int) -> str:
        return f"s{min(superstep, len(self.steps) - 1)}"

    def stackable(self) -> bool:
        """Whether this chain can ride as ONE COLUMN of an (n, K) start:
        a plain chain (no sack, step masks or reach record) from a host
        vector of its own."""
        return (
            self._seed_mask is not None and not self.width
            and self.seed_indices is None and not self.has_step_masks
            and self.sack is None and not self.record_reach
        )

    @classmethod
    def stacked(cls, programs, width: int, out=None) -> "OLAPTraversalProgram":
        """Up to `width` stackable chains of the SAME steps as one chain
        whose start is (n, width): column j is programs[j]'s start, the
        columns past the last are zero (and stay zero). Given `out`, an
        (n, width) float32 array, the columns are written there and it is
        the start: a caller that stacks again and again keeps its pages,
        and the columns past the last keep what they held (each column is
        a chain of its own, so they disturb nobody: the caller reads the
        columns it wrote)."""
        import numpy as np

        first = programs[0]
        if len(programs) > width or not all(
            p.stackable() and p.steps == first.steps for p in programs
        ):
            raise ValueError(
                "stacked() takes at most `width` stackable chains of the "
                "same steps"
            )
        start = out if out is not None else np.zeros(
            (len(first._seed_mask), width), dtype=np.float32)
        for j, p in enumerate(programs):
            start[:, j] = p._seed_mask
        return cls(first.steps, seed_mask=start)

    def setup(self, graph, xp):
        n = graph.local_num_vertices
        mask = self._seed_mask
        if (
            self.seed_indices is None
            and mask is not None
            and getattr(graph, "all_active", False)
            and graph.global_offset == 0
            and len(mask) == n
        ):
            # the start is a host-resident vector, and the view says that
            # its `active` is all ones and its local slice the whole: the
            # product below is then the mask itself, so it reaches the
            # device in one copy and no device op (a spilled request's
            # whole set-up)
            import numpy as np

            count = xp.asarray(np.asarray(mask, dtype=xp.result_type(float)))
        else:
            if self.seed_indices is None:
                # `active` masks SPMD padding slots on sharded views (all
                # graph views define it)
                count = xp.ones(n) * graph.active
            else:
                idx = xp.arange(n) + graph.global_offset
                count = xp.isin(
                    idx, xp.asarray(self.seed_indices)
                ).astype(float)
            if mask is not None:
                local = self._slice_local(mask, graph, xp)
                count = count[:, None] * local if self.width else count * local
        state = {"count": count}
        if self.sack is not None:
            state["sack"] = count * self.sack_init
        if self.has_step_masks:
            state["step_masks"] = self._slice_local(
                self._step_masks, graph, xp
            )
        if self.record_reach:
            # column k = mask after step k (column 0: the seed set)
            ncols = len(self.steps) + 1
            reach = xp.zeros((n, ncols), dtype=count.dtype)
            onehot = (xp.arange(ncols) == 0).astype(count.dtype)
            reach = reach + (count > 0).astype(count.dtype)[:, None] * onehot
            state["reach"] = reach
        return state, {}

    @staticmethod
    def _slice_local(arr, graph, xp):
        """A mask's shard-local rows: [global_offset, +local_n), zero-padded
        where a sharded view pads past the global vertex count (padding
        slots never hold traversers — `active` already zeroes them)."""
        off = graph.global_offset
        n = graph.local_num_vertices
        a = xp.asarray(arr)
        s = a[off:off + n]
        short = n - s.shape[0]
        if short > 0:
            pad = [(0, short)] + [(0, 0)] * (a.ndim - 1)
            s = xp.pad(s, pad)
        return s

    def message(self, state, superstep, graph, xp):
        if self.sack == "sum":
            # [count, sack, count]: the 3rd column rides MUL_WEIGHT and
            # aggregates to the cross-term Σ w·c (see __init__)
            return xp.stack(
                [state["count"], state["sack"], state["count"]], axis=1
            )
        if self.sack == "mult":
            return xp.stack([state["count"], state["sack"]], axis=1)
        return state["count"]

    def apply(self, state, aggregated, superstep, memory_in, graph, xp):
        # traversers MOVE: the new count is exactly what arrived — then the
        # step's has()-filter mask zeroes the vertices it rejects. Column
        # select by the (traced) superstep index keeps ONE executable per
        # channel; leading axis stays n so shard-by-vertex layouts hold.
        if self.sack == "sum":
            new = {
                "count": aggregated[:, 0],
                # S' = Σ S[u] + Σ w·c[u]
                "sack": aggregated[:, 1] + aggregated[:, 2],
            }
        elif self.sack == "mult":
            new = {"count": aggregated[:, 0], "sack": aggregated[:, 1]}
        else:
            new = {"count": aggregated}
        if self.has_step_masks:
            masks = state["step_masks"]
            col = xp.clip(superstep, 0, masks.shape[1] - 1)
            new["count"] = new["count"] * masks[:, col]
            if self.sack is not None:
                # rejected traversers take their sack mass with them
                new["sack"] = new["sack"] * masks[:, col]
            new["step_masks"] = masks
        if self.record_reach:
            # one-hot column write (xp-agnostic: no .at[] in numpy) —
            # column superstep+1 becomes this step's arrival mask
            reach = state["reach"]
            ncols = reach.shape[1]
            col1 = xp.clip(superstep, 0, ncols - 2) + 1
            onehot = (xp.arange(ncols) == col1).astype(reach.dtype)
            arrived = (new["count"] > 0).astype(reach.dtype)
            new["reach"] = (
                reach * (1.0 - onehot)[None, :]
                + arrived[:, None] * onehot[None, :]
            )
        return new, {}

    def terminate(self, memory):
        return False  # fixed-length chain; max_iterations bounds the run


def build_olap_traversal(
    graph,
    csr,
    spec: Sequence,
    seeds=None,
    seed_filters=None,
    record_reach: bool = False,
    sack: Optional[str] = None,
    sack_init: Optional[float] = None,
) -> "OLAPTraversalProgram":
    """Compile a filtered traversal spec against a CSR snapshot:
    `g.V().has(seed_filters...).out(...).has(...)...` as one BSP program
    (reference: FulgoraGraphComputer.submit(traversal),
    FulgoraGraphComputer.java:155). Filter predicates evaluate host-side
    over csr.properties into device masks (see PropertyFilter)."""
    import numpy as np

    steps = steps_from_spec(graph, spec)
    seed_mask = None
    if seed_filters:
        seed_mask = evaluate_filter_mask(csr, _parse_filters(seed_filters))
    step_masks = None
    if any(st.filters for st in steps):
        cols = [
            evaluate_filter_mask(csr, st.filters)
            if st.filters
            else np.ones(csr.num_vertices, dtype=np.float32)
            for st in steps
        ]
        step_masks = np.stack(cols, axis=1)  # (n, S): shard-by-vertex axis
    seed_indices = None
    if seeds is not None:
        seed_indices = [csr.index_of(v) for v in seeds]
    if sack is not None and (
        csr.in_edge_weight is None and csr.out_edge_weight is None
    ):
        # fail fast like TinkerPop's .by('weight') on a missing key —
        # silently folding w=1 would produce plausible wrong numbers
        raise ValueError(
            f"sack={sack!r} folds edge weights but the CSR snapshot "
            "carries none — load with compute().weight(<property key>)"
        )
    return OLAPTraversalProgram(
        steps,
        seed_indices=seed_indices,
        seed_mask=seed_mask,
        step_masks=step_masks,
        record_reach=record_reach,
        sack=sack,
        sack_init=sack_init,
    )


def build_path_index(csr, program):
    """The per-step reverse adjacency enumerate_paths walks: one
    O(E log E) sort per step. Build ONCE per (csr, program) and reuse —
    ComputerResult memoizes it so paths() + select() on the same result
    don't pay it twice."""
    import numpy as np

    from janusgraph_tpu.olap.csr import channel_edges

    n = csr.num_vertices
    rev = []
    for k in range(len(program.steps)):
        src, dst, _w = channel_edges(csr, program.edge_channels[f"s{k}"])
        order = np.argsort(dst, kind="stable")
        srcs = src[order]
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(np.bincount(dst, minlength=n), out=indptr[1:])
        rev.append((indptr, srcs))
    return rev


def enumerate_paths(csr, program, states, limit=None, path_index=None):
    """Host half of OLAP path(): lazily enumerate the traverser paths of a
    `record_reach` run, as tuples of GRAPH vertex ids (seed first).

    Hybrid design (SURVEY §7 hard part (a); reference:
    FulgoraGraphComputer.java:155 shipping TraversalVertexProgram with
    per-traverser path objects): the DEVICE ran the frontier expansion and
    recorded per-step reach masks — exact reachability, counts > 0 — and
    the HOST walks them backward over each step's edge view. A backward
    neighbor u of v at level k-1 with reach[u, k-1] set lies on a real
    seed-to-v path, so the DFS emits exactly the OLTP traverser paths
    (parallel edges yield one path per edge instance, like OLTP
    traversers). Cost is O(paths emitted) adjacency probes after an
    O(E log E) per-step reverse-sort — independent of |V| once built.

    Generator: bound it with `limit` (3-hop path counts explode on dense
    graphs; the device-side `count` sum prices the enumeration first).
    """
    import numpy as np

    reach = np.asarray(states["reach"]) > 0          # (n, S+1)
    S = len(program.steps)
    # path_index may be a zero-arg callable (memoized builder): the
    # O(E log E) build then happens on FIRST ITERATION, after cheap
    # validation (unknown select() names must not pay for the sorts)
    if callable(path_index):
        path_index = path_index()
    rev = path_index if path_index is not None else build_path_index(
        csr, program
    )
    vids = csr.vertex_ids

    def back(v, k):
        if k == 0:
            yield (v,)
            return
        indptr, srcs = rev[k - 1]
        for u in srcs[indptr[v]: indptr[v + 1]]:
            if reach[u, k - 1]:
                for prefix in back(int(u), k - 1):
                    yield prefix + (v,)

    emitted = 0
    if limit is not None and limit <= 0:
        return
    for v in np.nonzero(reach[:, S])[0]:
        for p in back(int(v), S):
            yield tuple(int(vids[i]) for i in p)
            emitted += 1
            if limit is not None and emitted >= limit:
                return


def select_paths(
    csr, program, states, names, source_as=None, limit=None, path_index=None,
):
    """select() over enumerated paths: project the as()-labeled positions
    of each path into a dict (reference: TinkerPop SelectStep consuming
    step labels). `source_as` names path position 0 (the g.V() head)."""
    positions = {}
    if source_as is not None:
        positions[source_as] = 0
    for i, st in enumerate(program.steps):
        if st.as_label is not None:
            if st.as_label in positions:
                # TinkerPop collects duplicated labels into lists; this
                # projection is single-valued — refuse rather than
                # silently dropping the earlier binding
                raise ValueError(
                    f"duplicate as()-label {st.as_label!r} — give each "
                    "selected step a distinct label"
                )
            positions[st.as_label] = i + 1
    missing = [nm for nm in names if nm not in positions]
    if missing:
        raise ValueError(
            f"select() names {missing} match no as()-labeled step "
            f"(labeled: {sorted(positions)})"
        )
    for p in enumerate_paths(
        csr, program, states, limit=limit, path_index=path_index,
    ):
        yield {nm: p[positions[nm]] for nm in names}


def group_count_by_label(graph, csr, counts) -> Dict[str, float]:
    """Group-count terminal: traverser totals per vertex LABEL — the
    g.V()...groupCount().by(label) shape (reference: TinkerPop
    GroupCountStep run OLAP-side through TraversalVertexProgram). Host-side
    bincount over the CSR's label column; O(n)."""
    import numpy as np

    if csr.labels is None:
        raise ValueError(
            "CSR snapshot has no vertex-label column — reload with load_csr"
        )
    counts = np.asarray(counts, dtype=np.float64)
    labels = np.asarray(csr.labels)
    out: Dict[str, float] = {}
    for lbl in np.unique(labels):
        total = float(counts[labels == lbl].sum())
        if total == 0.0:
            continue
        el = graph.schema_cache.get_by_id(int(lbl))
        out[el.name if el is not None else str(int(lbl))] = total
    return out
