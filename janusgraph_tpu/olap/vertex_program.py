"""VertexProgram SPI — the BSP contract both executors implement.

Capability parity with the reference's vertex-program machinery
(reference: TinkerPop VertexProgram via graphdb/olap/computer/
VertexProgramScanJob.java:82-111 per-vertex execute + FulgoraVertexMemory
double-buffered message slots + message combiners :91-95 + FulgoraMemory
global aggregators), re-designed as an **array-BSP** model: a superstep is

    aggregated[i] = combine({ transform(message(src), w_e) for e=(src, i) })
    state', metrics = apply(state, aggregated, superstep, memory)

with `combine` a `Combiner` and per-vertex state a dict of dense arrays.
SUM, MIN and MAX are segment-reduction monoids: an executor may fold a
destination's messages in any grouping (pack chunks, split rows, shards)
and combine the partial aggregates. MODE is not: it needs a destination's
WHOLE multiset at once (see `Combiner`). This restriction (fixed-width
numeric messages — SURVEY.md §7 hard part (b)) makes message passing one
segment-reduce / SpMV, or one sort per destination, instead of the
reference's NonBlockingHashMapLong churn; every BASELINE workload fits it.

jit/psum-compatible by construction:
- programs never mutate host state inside the superstep; global aggregators
  flow as `metrics` return values (op, scalar) that the executor reduces at
  the barrier — locally on one chip, with psum/pmin/pmax across a mesh
  (the reference's FulgoraMemory sub-round barrier);
- the previous superstep's reduced aggregators are passed back in as traced
  scalars (`memory_in`), so values like PageRank's dangling-rank mass are
  globally consistent without a second pass;
- `superstep` arrives as a traced scalar: one compiled superstep function
  serves all iterations.

Programs are written against the `xp` array namespace (numpy or jax.numpy),
so one definition runs on the CPU oracle executor and the TPU executor.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from types import MappingProxyType
from functools import lru_cache
from typing import Dict, Mapping, Optional, Tuple


class Combiner:
    """How the messages a vertex receives in one superstep become its
    aggregate (reference: MessageCombiner).

    SUM / MIN / MAX are monoids over float messages: partial aggregates
    combine, and `IDENTITY[op]` is what a vertex without messages reads.

    MODE is the most frequent label among ALL the messages a destination
    receives in the superstep, the smallest such label on ties. Labels are
    int32 in [0, NO_MESSAGE) end to end (no float cast anywhere); a vertex
    that receives nothing reads `NO_MESSAGE`, which also pads packs (a
    padded slot carries it and never wins). It is the one combiner that
    CANNOT be folded from partial aggregates: two partial modes do not
    give the mode of the union. Every executor path either folds each
    destination's whole multiset or raises through `monoid` /
    `require_foldable`, naming the combiner and the path."""

    SUM = "sum"
    MIN = "min"
    MAX = "max"
    MODE = "mode"

    #: MODE's "no message" and padding value: the largest int32
    NO_MESSAGE = 2**31 - 1

    IDENTITY = {
        "sum": 0.0, "min": float("inf"), "max": float("-inf"),
        "mode": NO_MESSAGE,
    }

    @staticmethod
    def monoid(op: str, path: str, sum_, min_, max_):
        """Pick a monoid's implementation by name — the one place an
        `if SUM / if MIN / else` chain ends: an op that is none of the
        three raises instead of being computed as a maximum."""
        if op == Combiner.SUM:
            return sum_
        if op == Combiner.MIN:
            return min_
        if op == Combiner.MAX:
            return max_
        Combiner.require_foldable(op, path)
        raise ValueError(f"unknown combiner {op!r} in {path}")

    @staticmethod
    def require_foldable(op: str, path: str) -> None:
        """Raise for a combiner that `path` would fold from partials."""
        if op == Combiner.MODE:
            raise ValueError(
                f"Combiner.MODE ({op!r}) needs each destination's whole "
                f"multiset of messages and cannot be folded from partial "
                f"aggregates: {path} combines partials — run it on the "
                "single-device executor (executor='tpu' or 'cpu', strategy "
                "auto/hybrid/ell/segment)"
            )


class EdgeTransform:
    """How an edge modifies the message it carries."""

    NONE = "none"
    MUL_WEIGHT = "mul"   # msg * w  (e.g. weighted pagerank)
    ADD_WEIGHT = "add"   # msg + w  (e.g. shortest path)


def check_weighted_transforms(program, csr) -> None:
    """Executors call this at run() entry: a program declaring weight
    transforms (scalar edge_transform OR per-column cols) over a
    weightless CSR would otherwise silently compute as if no transform
    existed (every executor skips transforms when weights are absent) —
    plausible wrong numbers, not an error. E.g. weighted SSSP on a
    weightless snapshot would relax every distance to 0."""
    cols = getattr(program, "edge_transform_cols", None)
    wants_weights = bool(
        cols and any(t != EdgeTransform.NONE for t in cols)
    ) or getattr(
        program, "edge_transform", EdgeTransform.NONE
    ) != EdgeTransform.NONE
    if wants_weights:
        if csr.in_edge_weight is None and csr.out_edge_weight is None:
            raise ValueError(
                f"{type(program).__name__} declares weight-dependent edge "
                "transforms but the CSR snapshot carries no edge weights "
                "— load with a weight key (compute().weight(key) / "
                "load_csr(weight_key=...))"
            )


@lru_cache(maxsize=64)
# graphlint: host -- cached NUMPY constants by design; caching xp arrays would leak tracers
def _col_masks(cols):
    """Per-column {0,1} transform masks, cached as NUMPY — the CPU oracle
    calls the transform once per edge delivery, and caching xp arrays
    would leak tracers out of jit scopes."""
    import numpy as _np

    mul = _np.asarray(
        [1.0 if t == EdgeTransform.MUL_WEIGHT else 0.0 for t in cols],
        dtype=_np.float32,
    )
    add = _np.asarray(
        [1.0 if t == EdgeTransform.ADD_WEIGHT else 0.0 for t in cols],
        dtype=_np.float32,
    )
    return mul, add


# graphlint: traced -- routed into every executor's compiled body (xp=jnp)
def apply_edge_transform(xp, msgs, w, transform, cols=None):
    """Apply a program's in-flight edge transform — THE one shared
    implementation (cpu/tpu-segment/ELL/sharded bodies all route here so
    per-column semantics can never drift between executors).

    `msgs`: (..., k) message columns or (...) scalars, `w`: per-edge
    weights broadcastable to msgs minus its column axis (None = pass).
    With `cols` (= program.edge_transform_cols) set and k-column
    messages, column j rides its own transform: masked as
      msgs * (1 + (w-1)*mul_j) + w*add_j
    (branch-free — compiles to two broadcasts under jit).
    """
    if w is None:
        return msgs
    w = xp.asarray(w)
    if cols is not None:
        # the program contract: with per-column transforms, messages ARE
        # k-column and the LAST axis is the column axis in every layout
        # (flat (E,k), ELL (rows,c,k), oracle row (k,))
        k = msgs.shape[-1]
        if len(cols) != k:
            raise ValueError(
                f"edge_transform_cols has {len(cols)} entries for "
                f"{k}-column messages"
            )
        mul_np, add_np = _col_masks(cols)
        mul = xp.asarray(mul_np, dtype=msgs.dtype)
        add = xp.asarray(add_np, dtype=msgs.dtype)
        shape = (1,) * (msgs.ndim - 1) + (k,)
        wb = w[..., None]
        # where-select, NOT msgs*(1+(w-1)*mul): the algebraic form absorbs
        # |w-1| below float32 eps and mis-scales tiny weights 100%
        return xp.where(
            mul.reshape(shape) > 0, msgs * wb, msgs
        ) + wb * add.reshape(shape)
    if transform == EdgeTransform.MUL_WEIGHT:
        return msgs * (w[..., None] if msgs.ndim > w.ndim else w)
    if transform == EdgeTransform.ADD_WEIGHT:
        return msgs + (w[..., None] if msgs.ndim > w.ndim else w)
    return msgs


@dataclass(frozen=True)
class EdgeChannel:
    """A typed edge view for one message round (reference: TinkerPop
    MessageScope.Local carrying a per-step traversal like __.out('knows'),
    compiled to reversed slice queries at VertexProgramScanJob.java:114-135).

    direction: traverser movement along the edge —
        "out"  src -> dst  (aggregate at dst over in-edges; the default)
        "in"   dst -> src  (aggregate at src over out-edges)
        "both" both orientations
    labels: edge type ids to include (None = all). Requires the CSR to carry
        per-edge type arrays (in_edge_type/out_edge_type).
    """

    direction: str = "out"
    labels: Optional[Tuple[int, ...]] = None


@dataclass
class Memory:
    """Host-side view of the global aggregators, updated at each superstep
    barrier from the reduced metrics (reference: FulgoraMemory.java:45)."""

    values: Dict[str, float] = field(default_factory=dict)
    superstep: int = 0

    def get(self, key: str, default: float = 0.0) -> float:
        return self.values.get(key, default)

    def reduce_in(self, metrics: Dict[str, Tuple[str, float]]) -> None:
        for k, (_op, v) in metrics.items():
            self.values[k] = float(v)
        self.superstep += 1


class VertexProgram:
    """Array-BSP vertex program. Subclasses define the hooks below.

    Class attributes:
      compute_keys    — state entries that write-back persists as properties
      combiner        — Combiner (or override combiner_for per phase)
      edge_transform  — EdgeTransform applied to messages in flight
      edge_transform_cols — per-COLUMN EdgeTransforms for 2-D messages
                        (overrides edge_transform; the substrate for
                        OLAP-side sack: one message column can ride
                        MUL_WEIGHT while the traverser-count column
                        passes untransformed). SUM combiner only — the
                        post-transform identity masking is uniform.
      undirected      — aggregate over both edge orientations
      max_iterations  — hard superstep cap
    """

    compute_keys: Tuple[str, ...] = ()
    combiner: str = Combiner.SUM
    edge_transform: str = EdgeTransform.NONE
    edge_transform_cols: Optional[Tuple[str, ...]] = None
    undirected: bool = False
    max_iterations: int = 100

    #: named typed edge views; programs with per-superstep edge scopes
    #: (the TraversalVertexProgram analogue) SHADOW this with their own dict
    #: and pick one per superstep via channel_for (the immutable default
    #: cannot be mutated in place, so per-class declarations can't leak
    #: across programs)
    edge_channels: Mapping[str, EdgeChannel] = MappingProxyType({})

    def combiner_for(self, superstep: int) -> str:
        """Monoid for a given superstep — overridable for phase-alternating
        programs (e.g. peer pressure's count-then-resolve phases)."""
        return self.combiner

    def channel_for(self, superstep: int) -> Optional[str]:
        """Edge channel for a given superstep. None = the program's default
        edge view (in-CSR, or the symmetric closure when `undirected`)."""
        return None

    def setup(self, graph, xp) -> Tuple[Dict[str, object], Dict[str, Tuple[str, object]]]:
        """Return (initial state, initial metrics). Metrics are (op, scalar)
        pairs reduced across shards before superstep 0 reads them."""
        raise NotImplementedError

    def message(self, state: Dict[str, object], superstep, graph, xp):
        """Per-vertex outgoing message array (n,) or (n, k)."""
        raise NotImplementedError

    def apply(
        self,
        state: Dict[str, object],
        aggregated,
        superstep,
        memory_in: Dict[str, object],
        graph,
        xp,
    ) -> Tuple[Dict[str, object], Dict[str, Tuple[str, object]]]:
        """Fold aggregated messages into new state; emit metrics."""
        raise NotImplementedError

    def terminate(self, memory: Memory) -> bool:
        raise NotImplementedError

    def terminate_device(self, values: Dict[str, object], steps_done, xp):
        """Traceable termination predicate for the fused on-device run loop
        (the whole BSP iteration compiles into ONE lax.while_loop dispatch;
        host-loop executors use `terminate` instead). `values` are the
        barrier-reduced aggregators, `steps_done` a traced step count.
        Default: rely on the loop's max_iterations bound only."""
        return xp.asarray(False)

    #: parameters consumed only by setup() (host-side initial state), not
    #: baked into the traced superstep — excluded from cache_key so varying
    #: them (e.g. BFS seeds) reuses the compiled executable
    setup_only_params: Tuple[str, ...] = ()

    def cache_key(self) -> Tuple:
        """Identity of this program's compiled computation (parameters that
        are baked into the traced superstep)."""
        return (
            type(self).__module__,
            type(self).__qualname__,
            tuple(sorted(
                (k, v) for k, v in self.__dict__.items()
                if isinstance(v, (int, float, bool, str, tuple))
                and k not in self.setup_only_params
            )),
        )

    def require_dense_capable(self, path: str) -> None:
        """Raise, naming `path`, if this program's result cannot come from
        message / fold / apply supersteps. Every executor whose run is such
        supersteps calls it at run() entry; the default refuses nothing."""

    def fused_eligible(self) -> bool:
        """Whether run() may compile the whole iteration into one on-device
        while_loop: requires a constant combiner monoid, a constant edge
        channel, AND an overridden terminate_device (the default never stops
        early, which would change semantics for programs relying on host
        terminate())."""
        return (
            type(self).combiner_for is VertexProgram.combiner_for
            and type(self).channel_for is VertexProgram.channel_for
            and type(self).terminate_device is not VertexProgram.terminate_device
        )
