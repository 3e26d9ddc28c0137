"""A plain reference added with a cell: numpy over the generated edge list,
independent of the package's executors."""

import numpy as np


class InDegree:
    """In-edges of every vertex, duplicates counted."""

    @staticmethod
    def expect(data, **_):
        return np.bincount(data.dst, minlength=data.n)

    @staticmethod
    def agrees(got, want) -> bool:
        got = np.asarray(got)
        return got.shape == want.shape and bool(np.array_equal(got, want))


REFERENCES = {"in-degree": InDegree}
