"""The bytes a Graph500 kernel 2 search must move, for the cell
`graph500-bfs.kernel2`'s share of the memory roofline (read by
`device_trace.roofline`, per search: one execution of `jit_start`)."""

from __future__ import annotations


def graph500_bfs_bytes(shapes):
    """What ANY implementation of kernel 2 moves in a search: the closure's
    4-byte neighbour index read once (8 B a generated edge), and per vertex
    a 4-byte level and a 4-byte parent written (8 B). Directions, levels,
    tiers, padding and the layout are the implementation's and are not
    counted, so no design can make the count stale or pass 100%."""
    return 8 * shapes["edges"] + 8 * shapes["vertices"]


BYTES = {"graph500-bfs": graph500_bfs_bytes}
