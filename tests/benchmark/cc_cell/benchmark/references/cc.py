"""Plain reference for weakly connected components over the generated edge
list (LDBC Graphalytics WCC's answer in this repo's labelling): per vertex
the smallest vertex index of its component, edges read without direction.
int64 numpy, independent of the package's executors: min-label hooking
with pointer jumping, every round a `np.minimum.at` over the edge list and
a halving of the label forest."""

from __future__ import annotations

import numpy as np


class MinLabelComponents:
    @staticmethod
    def expect(data, **_):
        src = np.asarray(data.src, np.int64)
        dst = np.asarray(data.dst, np.int64)
        label = np.arange(data.n, dtype=np.int64)
        while True:
            # hook: the larger of an edge's two roots under the smaller
            a, b = label[src], label[dst]
            low, high = np.minimum(a, b), np.maximum(a, b)
            hooked = label.copy()
            np.minimum.at(hooked, high, low)
            # jump: every vertex to its root (labels only ever fall, and a
            # root points at itself, so this ends)
            while True:
                jumped = hooked[hooked]
                if np.array_equal(jumped, hooked):
                    break
                hooked = jumped
            if np.array_equal(hooked, label):
                return label
            label = hooked

    @staticmethod
    def agrees(got, want) -> bool:
        """Exact integer match; a float state must hold whole numbers."""
        got = np.asarray(got)
        if got.shape != want.shape or got.dtype.kind not in "iuf":
            return False
        if got.dtype.kind == "f" and not bool(
                np.all(np.isfinite(got) & (got == np.floor(got)))):
            return False
        return bool(np.array_equal(got.astype(np.int64), want))


REFERENCES = {"cc-min-label": MinLabelComponents}
