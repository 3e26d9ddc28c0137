"""Plain reference for Graph500 v3 kernel 2, breadth-first search
(graph500.org, specification v3: kernel 1's Kronecker edge list, read as
undirected with duplicates and loops kept, searched from one key at a time,
each search returning a parent array that passes the specification's
validation). int64 / float32 numpy; nothing imported from the package.

Levels come from a level-synchronous search over the closure, both ends of
every generated edge (the sender's end alone where directed); a level is a
float32 hop count, infinite where a vertex is unreached. Parents are held
to Graph500's five rules by `sssp.validate` with every weight 1: a BFS is
the shortest-path search of unit weights, and its levels the distances."""

from __future__ import annotations

import hashlib
import importlib.util
import os

import numpy as np


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


sssp = _sibling("sssp")
#: the program's "unreached" (ShortestPathProgram's INF)
UNREACHED = sssp.UNREACHED


def by_sender(n, sender, receiver):
    """(indptr, receivers) of the closure's slots grouped by sender."""
    order = np.argsort(sender, kind="stable")
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(sender, minlength=n), out=indptr[1:])
    return indptr, receiver[order]


def levels(n, indptr, receivers, root):
    """Hop counts from `root` as float32, infinite where unreached: every
    receiver of a level's slots that has no level yet takes the next."""
    level = np.full(n, np.inf, np.float32)
    level[root] = 0
    frontier = np.array([root], np.int64)
    hop = 0
    while len(frontier):
        hop += 1
        starts, ends = indptr[frontier], indptr[frontier + 1]
        sizes = ends - starts
        total = int(sizes.sum())
        if total == 0:
            break
        # every slot of the frontier's rows, by slices of `receivers`
        offsets = np.repeat(starts - np.cumsum(sizes) + sizes, sizes)
        reached = np.unique(receivers[offsets + np.arange(total)])
        frontier = reached[np.isinf(level[reached])]
        level[frontier] = hop
    return level


class KernelTwo:
    """`expect` gives a key's reference levels and the closure the parents
    are validated over; `agrees` holds the system's (distance, parent) to
    them."""

    @staticmethod
    def expect(data, seed_index, undirected=True, **_):
        # the closure and its grouping are the data's, not the key's: kept
        # on the data for the next key
        kept = getattr(data, "_kernel2_closure", None)
        if kept is None or kept[0] != undirected:
            sender, receiver, w = sssp.closure(
                data.src, data.dst, np.ones(data.m, np.float32), undirected)
            kept = data._kernel2_closure = (
                undirected, (sender, receiver, w),
                by_sender(data.n, sender, receiver))
        _, closure, grouped = kept
        return {
            "n": data.n, "root": seed_index, "undirected": undirected,
            "closure": closure,
            "levels": levels(data.n, *grouped, seed_index),
            # verdicts by the answer's digest: a search answers a key the
            # same way every time, and is validated once
            "verdicts": {},
        }

    @staticmethod
    def agrees(got, want) -> bool:
        return not KernelTwo.disagreements(got, want)

    @staticmethod
    def disagreements(got, want) -> list:
        """What `agrees` found wrong, by name (empty: agrees). `got` is
        {"distance", "predecessor"} as the program returned them."""
        n = want["n"]
        distance = np.asarray(got["distance"])
        parent = np.asarray(got["predecessor"])
        if distance.shape != (n,) or parent.shape != (n,):
            return ["shape"]
        if distance.dtype != np.float32:
            return ["distance-not-float32"]
        digest = hashlib.sha256(distance.tobytes())
        digest.update(parent.dtype.str.encode() + parent.tobytes())
        digest = digest.hexdigest()
        if digest not in want["verdicts"]:
            want["verdicts"][digest] = KernelTwo._judge(
                distance, parent, want)
        return list(want["verdicts"][digest])

    @staticmethod
    def _judge(distance, parent, want) -> list:
        if parent.dtype.kind == "f" and not bool(
                np.all(np.isfinite(parent) & (parent == np.floor(parent)))):
            return ["parent-not-integral"]
        level = np.where(distance >= np.float32(UNREACHED),
                         np.float32(np.inf), distance)
        wrong = []
        # tolerance 0: hop counts are small integers, exact in float32
        if not np.array_equal(level.view(np.uint32),
                              want["levels"].view(np.uint32)):
            wrong.append("level")
        wrong += sssp.validate(want["n"], *want["closure"], want["root"],
                               parent.astype(np.int64), level,
                               want["undirected"])
        return wrong


REFERENCES = {"graph500-kernel2": KernelTwo}
