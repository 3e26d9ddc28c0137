"""The bytes a local-clustering-coefficient pass must move, for the
roofline share of the cell `graphalytics-lcc.lcc` (the readers are
`device_trace.py`'s and `frontier.py`'s). `edges` is the generated edge
list's length, `vertices` the vertex count."""


def lcc_pass_bytes(shapes):
    """What ANY implementation moves: the generated edge list read once
    (two 4-byte ends an edge) and per vertex a 4-byte degree read and a
    4-byte coefficient written. Rows read against each other, bit rows,
    candidate lists and searches are the implementation's and are not
    counted, so no design can make the count stale or pass 100%."""
    return 8 * shapes["edges"] + 8 * shapes["vertices"]


BYTES = {"lcc-pass": lcc_pass_bytes}
