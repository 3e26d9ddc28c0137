"""The bytes a connected-components pass must move, for the roofline share
of the cell `gap-kron-cc.cc` (the reader is `device_trace.py`'s
`roofline`). `edges` is the generated edge list's length, `vertices` the
vertex count."""


def cc_pass_bytes(shapes):
    """What ANY implementation moves: the generated edge list read once
    (two 4-byte ends an edge) and per vertex a 4-byte label read and
    written. Samples, passes, jumps, tiers and the closure's layout are the
    implementation's and are not counted, so no design can make the count
    stale or pass 100%."""
    return 8 * shapes["edges"] + 8 * shapes["vertices"]


BYTES = {"cc-pass": cc_pass_bytes}
