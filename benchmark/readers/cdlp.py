"""Bytes a CDLP round must move, for the roofline shares of the cell
`graphalytics-g500.cdlp` (the readers are `device_trace.py`'s). `edges` is
the directed edge list's length; the program reads each edge from both
ends."""


def mode_fold_bytes(shapes):
    """The mode fold alone: a 4-byte read of each of the 2 x edges gathered
    labels and a 4-byte write of each vertex's winner, whatever sorts or
    counts in between."""
    return 8 * shapes["edges"] + 4 * shapes["vertices"]


def cdlp_round_bytes(shapes):
    """A whole round: per closure slot (2 x edges) a 4-byte index and a
    4-byte label; per vertex its old label read and its new one written."""
    return 16 * shapes["edges"] + 8 * shapes["vertices"]


BYTES = {"mode-fold": mode_fold_bytes, "cdlp-round": cdlp_round_bytes}
