"""The bytes one GAP BC trial must move, for the roofline share of the cell
`gap-kron-bc.bc` (the reader is `device_trace.py`'s `roofline`). The shapes
are the driver's: `closure_slots` (both orientations of each simple edge),
`vertices`, `sources` (a trial's, the columns of one run)."""


def brandes_trial_bytes(shapes):
    """What ANY implementation of Brandes moves in a trial: the closure's
    4-byte neighbour index read once in each sweep (8 B a slot), and per
    vertex and source a 4-byte path count written forward and read back
    and a 4-byte dependency written (12 B). Levels, frontiers, tiers,
    padding and the pack's layout are the implementation's and are not
    counted, so no design can make the count stale or pass 100%."""
    return 8 * shapes["closure_slots"] + 12 * shapes["vertices"] * shapes[
        "sources"]


BYTES = {"brandes-trial": brandes_trial_bytes}
