"""Neighbourhood intersection: per-vertex triangle counts and the local
clustering coefficient in one compiled pass (LDBC Graphalytics LCC;
ROADMAP M4). The frontier engine's sibling: an engine with no numpy
superstep, `LCCProgram`'s route (`olap/routes.py`).

Every other path of the executor moves ONE value per edge from a sender to
a receiver and folds it. A common-neighbour count reads two adjacency ROWS
against each other, which no monoid fold gives. The pass counts each
triangle {a, b, c} of the SIMPLE undirected closure once, at its lowest
edge in the order (degree, id), and credits its three corners:

  rows    vertices with an edge, renumbered by (degree, id) rising; `u -> v`
          is an edge read from its lower end, N+(u) the higher neighbours.
  hubs    the vertices of degree >= D, the top rows. N+(u) restricted to
          them is a BIT ROW of K bits (`W = K / 32` words). For every edge
          u -> v: `popcount(bits[u] & bits[v])` triangles whose third corner
          w is a hub, 32 candidates a word; the set bits, summed by column,
          are the credit of each w.
  classes an edge reads only the words that can close it. `bits[v]` holds
          the neighbours ABOVE v, so every word below the first row of v's
          DEGREE is zero in it, and so in the AND. Edge slots are classed
          by the width of the suffix their edge can need (W, W / 2, W / 4,
          ... down to `CLASS_FLOOR` words) and each class is scanned at its
          own width. The table is kept ONCE, in column blocks cut where the
          classes begin (words [0, W / 2), [W / 2, 3 W / 4), ...): a class
          gathers whole rows of the blocks of its suffix and of no other.
          (A gather with the column offset in its start index reads one row
          a microsecond on the v5e, and a sliced table is a copy.)
  tail    a third corner below D is found the narrow way: the edge's end
          with the shorter list of tail neighbours of at least its own
          DEGREE expands it (`frontier.capped_expand`), each candidate w
          above both ends is binary-searched in the other end's list.

D is chosen from the degree sequence alone (`_choose_split`): the bit rows
cost words an edge, the tail gathered elements a candidate, at the two
prices read on the chip. Every shape is a function of the degree SEQUENCE,
never of the ids: the hub set is cut at a degree (no tie to break), a tail
list holds the neighbours of at least the owner's degree (ties included,
the lower-ranked masked out when tested), an edge's class is cut at the
first row of its higher end's degree, so a relabelled graph compiles
nothing anew (ROADMAP S3).

Counts are int32 to the last step: `lcc = float32(T) / float32(d (d - 1) /
2)`. A vertex of degree 65,536 or more could overflow `d (d - 1) / 2` and
the count: such a graph is refused by name at submit.
"""

from __future__ import annotations

import time

import numpy as np

from janusgraph_tpu.observability import registry, tracer
from janusgraph_tpu.olap.csr import simple_closure
from janusgraph_tpu.olap.device import await_arrays
from janusgraph_tpu.olap.frontier import capped_expand
from janusgraph_tpu.olap.kernels import intersect_scope

#: a vertex of this degree could count 2^31 pairs of neighbours
MAX_DEGREE = 1 << 16


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def _tail_lists(first, u, v, base):
    """The tail below row `base`: its edges (tu, tv) and, for the row of
    each tail vertex, the length of its list (its tail neighbours of at
    least its own DEGREE, ties included: a number no labelling moves).
    `first` is for each row the first row of its degree."""
    tail = v < base
    tu, tv = u[tail], v[tail]
    # tv lies above tu, so it is always of at least tu's degree
    length = np.bincount(tu, minlength=base) + np.bincount(
        tv[tu >= first[tv]], minlength=base)
    return tu, tv, length


def _choose_split(row_degree, first, u, v, widths, word_ns, candidate_ns,
                  table_bytes_limit):
    """Where the hubs begin, from the degree sequence: for each width W of
    the ladder, the hubs are the vertices of degree >= D with D the least
    degree that leaves at most 32 W of them; the pass then costs at most
    `edges x 2 W` gathered words and, per tail candidate, a binary search.
    The word term is an UPPER BOUND since the edge slots are classed
    (`_edge_classes`: at scale 20 two fifths of it are read); the split is
    not re-priced by classes (PERF.md section 7). The cheapest width whose
    table fits wins. Every quantity here is the same for every labelling
    of one structure."""
    active = len(row_degree)
    best, costs = None, {}
    for W in widths:
        K = 32 * W
        if K >= active:
            base = 0
        else:
            # rows rise by degree: cut below the first row whose whole
            # degree class fits among the top K
            base = int(np.searchsorted(
                row_degree, row_degree[active - K - 1], side="right"))
        tu, tv, length = _tail_lists(first, u, v, base)
        candidates = int(np.minimum(length[tu], length[tv]).sum())
        steps = int(length.max()).bit_length() if base else 0
        cost = (
            len(u) * 2 * W * word_ns + candidates * (steps + 4) * candidate_ns
        )
        costs[W] = round(cost / 1e6, 3)
        fits = (active + K) * 4 * W <= table_bytes_limit
        if best is None or (fits and cost < best["cost"]):
            best = {"words": W, "hub_base": base, "cost": cost}
        if base == 0:
            break  # every vertex is a hub: a wider row buys nothing
    best["costs_ms"] = costs
    return best


def _edge_classes(first, v, base, W, floor):
    """The class ladder and each edge's place on it. An edge u -> v is
    closed by a hub above v; `bits[v]` holds only rows above v, so its words
    below the first row of v's DEGREE (`first[v]`, a cut no labelling
    moves) are zero and the edge needs the words `[first_word, W)` alone.
    The ladder is the widths W, W / 2, W / 4, ... of at least `floor`
    words; an edge joins the narrowest class that holds its suffix. A tail
    edge (v below the hubs) can be closed by any hub and reads whole
    rows."""
    # the hubs are cut at a degree, so first[v] >= base wherever v >= base
    first_word = np.where(v >= base, (first[v] - base) // 32, 0)
    ladder = [W]
    while ladder[-1] % 2 == 0 and ladder[-1] // 2 >= floor:
        ladder.append(ladder[-1] // 2)
    # the narrowest width >= the words needed (the ladder falls)
    rising = np.array(ladder[::-1])
    return ladder, len(ladder) - 1 - np.searchsorted(rising, W - first_word)


class IntersectView:
    """One snapshot's intersection tables, built on the host: the simple
    closure by rows, the hubs' bit rows in column blocks (`bits.<first
    word>`), the edge slots by class (each class's ends as `edge_u.<width>`
    / `edge_v.<width>`, scanned over the blocks of the last `width` words),
    the tail's lists. `tables` holds the arrays until the engine has shipped
    them; the sizes and counts stay. Shapes by structure alone."""

    def __init__(self, n, src, dst, widths, word_ns, candidate_ns,
                 table_bytes_limit, class_floor):
        lo, hi = simple_closure(n, src, dst)  # loops out, parallels once
        self.simple_edges = int(len(lo))
        degree = np.bincount(lo, minlength=n) + np.bincount(hi, minlength=n)
        if len(degree) and degree.max() >= MAX_DEGREE:
            raise ValueError(
                f"LCCProgram: a vertex of degree {int(degree.max())} has "
                f"2^31 or more pairs of neighbours (degree >= {MAX_DEGREE}); "
                "the intersection engine of the single-device executor "
                "counts in int32 and refuses the graph"
            )
        # rows: the vertices with an edge by (degree, id); the rest read
        # the zero row `active`
        order = np.lexsort((np.arange(n), degree))
        active = int(np.count_nonzero(degree))
        row_of = np.full(n, active, np.int64)
        row_of[order[n - active:]] = np.arange(active)
        row_degree = degree[order[n - active:]]
        a, b = row_of[lo], row_of[hi]
        u, v = np.minimum(a, b), np.maximum(a, b)
        by_u = np.lexsort((v, u))
        u, v = u[by_u], v[by_u]

        # for each row the first row of its degree: where a cut must fall
        # for no labelling to move it
        first = np.searchsorted(row_degree, row_degree)

        split = _choose_split(
            row_degree, first, u, v, widths, word_ns, candidate_ns,
            table_bytes_limit,
        )
        W = self.words = split["words"]
        base = self.hub_base = split["hub_base"]
        # rows of the tables: every row, the zero row, and room for the
        # whole width of columns above the first hub
        self.rows = _round_up(max(active + 1, base + 32 * W), 8)

        # ---- edge slots by class, each in whole chunks of its own scan (a
        # chunk's operands stay 16.8 MB); padding reads the zero row; a
        # class with no edge has no arrays. A chunk takes every n-th edge of
        # its class in (u, v) order (n chunks): side by side, the edges of
        # one u gather ONE row over and over, which the v5e serves 5 ns a
        # row slower than rows apart (PERF.md section 6, PR 35)
        ladder, class_of = _edge_classes(first, v, base, W, class_floor)
        self.classes, edge_tables = [], {}
        for k, width in enumerate(ladder):
            mine = class_of == k
            count = int(np.count_nonzero(mine))
            if not count:
                continue
            chunk = min(1 << 15, max(8, (1 << 22) // width))
            ends = np.full((2, _round_up(count, chunk)), active, np.int32)
            ends[0, :count], ends[1, :count] = u[mine], v[mine]
            for name, end in zip(("edge_u", "edge_v"), ends):
                edge_tables[f"{name}.{width}"] = np.ascontiguousarray(
                    end.reshape(chunk, -1).T)
            self.classes.append({
                "words": width, "chunk": chunk, "edge_slots": ends.shape[1],
            })

        # ---- bit rows: bit (v - base) of row u for every edge u -> hub v,
        # the table kept once, in column blocks cut where the rungs begin: a
        # class reads the blocks from its own start on, as whole rows
        starts = [W - width for width in ladder]
        #: (first word, words) of each column block, rising
        self.blocks = [
            (a, b - a) for a, b in zip(starts, starts[1:] + [W])]
        to_hub = v >= base
        bit = v[to_hub] - base
        hub_u, column = u[to_hub], bit // 32
        mask = np.uint32(1) << (bit % 32).astype(np.uint32)
        bit_tables = {}
        for a, words in self.blocks:
            here = (column >= a) & (column < a + words)
            # edges lie by (u, v), so the words they set rise
            word = hub_u[here] * words + (column[here] - a)
            cut = np.flatnonzero(np.r_[True, word[1:] != word[:-1]])
            block = np.zeros(self.rows * words, np.uint32)
            if len(word):
                # distinct bits of one word: their sum is their union
                block[word[cut]] = np.add.reduceat(mask[here], cut)
            bit_tables[f"bits.{a}"] = block.reshape(self.rows, words)

        # ---- the tail: both ends below the first hub. The list of x is
        # its tail neighbours of at least its own degree, rising
        tu, tv, length = _tail_lists(first, u, v, base)
        back = tu >= first[tv]
        x, y = np.r_[tu, tv[back]], np.r_[tv, tu[back]]
        tail_nbr = y[np.lexsort((y, x))].astype(np.int32)
        indptr = np.zeros(base + 2, np.int64)
        np.cumsum(length, out=indptr[1:base + 1])
        indptr[base + 1] = indptr[base]
        # the end that expands (x) is the one with the shorter list; one
        # row a tail edge, read whole by each of its candidates: x, the
        # other end y, the higher end, y's list as [from, to)
        swap = length[tv] < length[tu]
        x, y = np.where(swap, tv, tu), np.where(swap, tu, tv)
        tail_edge = np.zeros((len(tu), 8), np.int32)
        for column, values in enumerate(
                (x, y, tv, indptr[y], indptr[y + 1])):
            tail_edge[:, column] = values
        self.tail_candidates = int(length[x].sum())
        self.tail_slots = len(tail_nbr)
        #: halvings that empty the longest list; then the equality probe
        self.search_steps = (
            int(length.max()).bit_length() if base else 0)

        # ---- what the run record says of a pass
        # candidates as a forward count would list them: per edge the
        # shorter of its ends' hub lists, and the tail's
        hub_len = np.bincount(u[to_hub], minlength=self.rows)
        self.candidates = int(
            np.minimum(hub_len[u], hub_len[v]).sum()) + self.tail_candidates
        # the words a pass really gathers: each class at its own width
        self.probe_slots = int(
            sum(2 * c["edge_slots"] * c["words"] for c in self.classes)
            + self.tail_candidates * (self.search_steps + 1)
        )
        self.sizes = {
            "rows": self.rows, "words": W,
            "block_words": [words for _, words in self.blocks],
            "classes": [dict(c) for c in self.classes],
            "edge_slots": sum(c["edge_slots"] for c in self.classes),
            "hubs": active - base,
            "hub_threshold": int(row_degree[base]) if base < active else 0,
            "tail_edges": int(len(tu)),
            "tail_candidates": self.tail_candidates,
            "search_steps": self.search_steps,
            "split_costs_ms": split["costs_ms"],
        }
        self.tables = {
            **bit_tables,
            **edge_tables,
            "tail_indptr": indptr.astype(np.int32),
            "tail_nbr": tail_nbr,
            "tail_edge": tail_edge,
            "row_of": row_of.astype(np.int32),
            # d (d - 1) / 2 per vertex, below 2^31 under the guard
            "pairs": (degree * (degree - 1) // 2).astype(np.int32),
        }


class IntersectEngine:
    """Per-executor engine: the snapshot's intersection tables on the
    device and the one compiled pass over them."""

    #: the ladder of bit-row widths, in 32-bit words (128 lanes a step)
    WIDTHS = (128, 256, 512, 1024, 2048)
    #: the narrowest class of edge slots reads this many words of a row.
    #: A class pays per gathered PIECE (about 7.3 + 0.0107 x words ns on
    #: the v5e: a row of 128 words costs an element's 8.7 ns), so a lower
    #: floor cuts the wide classes' rows into more pieces than it saves
    #: the narrow ones words (PERF.md section 6, PR 35: floors of 512 /
    #: 256 / 128 read on the chip)
    CLASS_FLOOR = 256
    #: prices of the split's two sides, read on the v5e (PERF.md section
    #: 6, PR 34): a gathered word of a bit row with its share of the AND,
    #: the popcount and the column sums; a gathered element of the tail's
    #: expansion, search and credit
    WORD_NS = 0.05
    CANDIDATE_NS = 8.0
    #: the bit rows may take this much of the device
    TABLE_BYTES_LIMIT = 6 << 30

    def __init__(self, executor):
        self.ex = executor
        self.jax, self.jnp = executor.jax, executor.jnp
        # from the executor's directed view (each stored edge once, int64)
        src, dst, _ = executor._edge_view(False)
        self.view = IntersectView(
            executor.csr.num_vertices, src, dst, self.WIDTHS, self.WORD_NS,
            self.CANDIDATE_NS, self.TABLE_BYTES_LIMIT, self.CLASS_FLOOR,
        )
        # the tables go to the device once; the host keeps none of them
        self.args = {
            name: self.jnp.asarray(table)
            for name, table in self.view.tables.items()
        }
        self.view.tables = None

    def _pass_fn(self):
        key = ("intersect-pass",)
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        jax, jnp = self.jax, self.jnp
        lax = jax.lax
        view = self.view
        W, rows, base = view.words, view.rows, view.hub_base
        shifts = jnp.arange(32, dtype=jnp.uint32)[:, None, None]

        def gather_rows(table, idx):
            return table.at[idx].get(mode="promise_in_bounds")

        def chunk_step(columns, ends, tables):
            """One chunk of one class: `tables` are the column blocks of
            its suffix, `columns` their set bits by column so far."""
            u, v = ends
            count, out = 0, []
            for column, table in zip(columns, tables):
                with intersect_scope("intersect"):
                    both = gather_rows(table, u) & gather_rows(table, v)
                    count = count + jnp.sum(
                        lax.population_count(both).astype(jnp.int32), axis=1)
                with intersect_scope("credit"):
                    # the set bits by column: bit b of every word, over
                    # the chunk's rows (one fused reduce; a byte-sliced form
                    # with a quarter of the shifts read three times slower
                    # on the v5e)
                    out.append(column + jnp.sum(
                        ((both[None] >> shifts) & jnp.uint32(1)).astype(
                            jnp.int32),
                        axis=1,
                    ))
            return tuple(out), count

        def tail(credit, a):
            with intersect_scope("expand"):
                own, _, w, valid = capped_expand(
                    jnp, a["tail_edge"][:, 0], a["tail_indptr"],
                    a["tail_nbr"], view.tail_candidates, rows - 1,
                )
            with intersect_scope("intersect"):
                # a candidate reads its edge's row whole: a gathered row
                # costs what a gathered element costs
                edge = gather_rows(a["tail_edge"], own)
                x, y, above = edge[:, 0], edge[:, 1], edge[:, 2]
                lo, end = edge[:, 3], edge[:, 4]
                hi = end
                last = view.tail_slots - 1
                for _ in range(view.search_steps):
                    mid = (lo + hi) // 2
                    right = (lo < hi) & (
                        gather_rows(a["tail_nbr"], jnp.minimum(mid, last))
                        < w)
                    lo, hi = (
                        jnp.where(right, mid + 1, lo),
                        jnp.where(right | (lo >= hi), hi, mid),
                    )
                found = (
                    valid & (w > above) & (lo < end)
                    & (gather_rows(a["tail_nbr"], jnp.minimum(lo, last))
                       == w)
                ).astype(jnp.int32)
            with intersect_scope("credit"):
                corners = jnp.concatenate([x, y, w])
                return credit.at[corners].add(jnp.tile(found, 3))

        def lcc_pass(a):
            with intersect_scope("credit"):
                columns = [
                    jnp.zeros((32, words), jnp.int32)
                    for _, words in view.blocks]
                credit = jnp.zeros((rows,), jnp.int32)
            starts = [start for start, _ in view.blocks]
            # one scan a class, over the column blocks of its suffix
            for width in (c["words"] for c in view.classes):
                edge_u, edge_v = a[f"edge_u.{width}"], a[f"edge_v.{width}"]
                k = starts.index(W - width)
                tables = tuple(a[f"bits.{start}"] for start in starts[k:])
                columns[k:], counts = lax.scan(
                    lambda c, ends: chunk_step(c, ends, tables),
                    tuple(columns[k:]), (edge_u, edge_v),
                )
                with intersect_scope("credit"):
                    counts = counts.reshape(-1)
                    credit = (
                        credit.at[edge_u.reshape(-1)].add(counts)
                        .at[edge_v.reshape(-1)].add(counts)
                    )
            with intersect_scope("credit"):
                # column (bit, word) is row base + 32 word + bit
                by_bit = jnp.concatenate(columns, axis=1).T.reshape(32 * W)
                credit = credit.at[base:base + 32 * W].add(by_bit)
            if view.tail_candidates:
                credit = tail(credit, a)
            with intersect_scope("credit"):
                triangles = credit[a["row_of"]]
                pairs = a["pairs"]
                lcc = jnp.where(
                    pairs > 0,
                    triangles.astype(jnp.float32)
                    / jnp.maximum(pairs, 1).astype(jnp.float32),
                    jnp.float32(0.0),
                )
            return triangles, lcc

        fn = jax.jit(lcc_pass)
        cache[key] = fn
        return fn

    def run(self, program):
        """One compiled pass: (states, run record), and the engine's
        counters."""
        t0 = time.perf_counter()
        with tracer.phase("executor.setup"):
            fn = self._pass_fn()
        with tracer.phase("executor.dispatch"):
            triangles, lcc = fn(self.args)
        with tracer.phase("executor.sync"):
            await_arrays((triangles, lcc))
        with tracer.phase("executor.fetch"):
            out = {
                "triangles": np.asarray(triangles), "lcc": np.asarray(lcc),
            }
        wall_ms = round((time.perf_counter() - t0) * 1000.0, 4)
        view = self.view
        registry.counter("olap.intersect.runs").inc()
        registry.counter("olap.intersect.candidates").inc(view.candidates)
        registry.counter("olap.intersect.probe_slots").inc(view.probe_slots)
        return out, {
            "path": "intersect",
            "supersteps": 1,
            "dispatches": 1,
            # totals a layer metric can read: the closure's edges, the
            # pairs the pass decides (as a forward count lists them), the
            # words and elements it gathers to decide them (padding
            # included), the triangles of the graph
            "simple_edges": view.simple_edges,
            "candidates": view.candidates,
            "probe_slots": view.probe_slots,
            "triangles_total": int(
                out["triangles"].sum(dtype=np.int64) // 3
            ),
            "intersect": dict(view.sizes),
            "superstep_records": [{
                "step": 0, "wall_ms": wall_ms, "edges": view.simple_edges,
            }],
        }
