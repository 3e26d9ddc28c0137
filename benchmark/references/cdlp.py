"""Plain reference for LDBC Graphalytics CDLP (specification v1.0,
arXiv:2011.15028) over the generated edge list: int64 numpy, independent of
the package's executors. Synchronous label propagation under the directed
rule: a vertex takes the most frequent label among its in- and
out-neighbours, a neighbour reached in both directions (or by a parallel
edge) counted once per edge, a self loop twice; the smallest label on
ties; a vertex without neighbours keeps its label."""

from __future__ import annotations

import numpy as np


class CdlpLabels:
    @staticmethod
    def expect(data, max_iterations=10, **_):
        n = data.n
        src = np.asarray(data.src, np.int64)
        dst = np.asarray(data.dst, np.int64)
        # every edge delivers twice: src's label to dst, dst's to src
        receiver = np.concatenate([dst, src])
        sender = np.concatenate([src, dst])
        label = np.arange(n, dtype=np.int64)
        for _ in range(max_iterations):
            # one sort of the 2m (receiver, label) pairs, as one int64 key
            pair = np.sort(receiver * n + label[sender])
            first = np.flatnonzero(np.r_[True, pair[1:] != pair[:-1]])
            count = np.diff(np.r_[first, len(pair)])
            who, what = pair[first] // n, pair[first] % n
            # per receiver its runs by falling count, then rising label
            order = np.lexsort((what, -count, who))
            who, what = who[order], what[order]
            best = np.r_[True, who[1:] != who[:-1]]
            label = label.copy()
            label[who[best]] = what[best]
        return label

    @staticmethod
    def agrees(got, want) -> bool:
        """Graphalytics validates by exact match: no tolerance."""
        got = np.asarray(got)
        if got.shape != want.shape or got.dtype.kind not in "iuf":
            return False
        if got.dtype.kind == "f" and not bool(
                np.all(np.isfinite(got) & (got == np.floor(got)))):
            return False
        return bool(np.array_equal(got.astype(np.int64), want))


REFERENCES = {"cdlp-labels": CdlpLabels}
