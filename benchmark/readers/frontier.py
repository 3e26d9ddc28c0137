"""Readers of the frontier engine's run record (`rounds`, `relaxed_slots`,
`tier_slots`: the executor's record of kind `olap` for a frontier run) and
the bytes a weighted relaxation step must move. A record without the
fields (a dense run, a program from before they were added) gives a reader
nothing to read: None, and the metric is left out of the line."""

from __future__ import annotations


def record_ratio(run, kind, numerator, denominator=None, shape=None,
                 shape_factor=1.0):
    """Sum of a field over the run records of `kind` the registry still
    holds (a ring of the newest 32), over the sum of another field of the
    same records, or over `shape_factor` x one of the run's shapes a
    record (e.g. 2 x edges: the closure's slots)."""
    from janusgraph_tpu.observability import registry

    top = bottom = 0.0
    for record in registry.runs(kind):
        if not isinstance(record.get(numerator), (int, float)):
            continue
        if denominator is not None:
            if not record.get(denominator):
                continue
            bottom += record[denominator]
        else:
            bottom += shape_factor * run.shapes.get(shape, 0)
        top += record[numerator]
    return top / bottom if bottom else None


def sssp_relax_bytes(shapes):
    """What one round of a weighted label-correcting search must move,
    however it is tiered: per slot it relaxes 16 B (the neighbour's index,
    the weight, the sender's distance, the receiver's distance
    read-modified) and per vertex 8 B (its mask and its distance), as a
    mean over the rounds of the traced searches (the driver's counts).
    Padding of a tier and the parent pass are not counted: padding lowers
    the share."""
    rounds = shapes.get("rounds_traced")
    if not rounds:
        return 0.0
    return (16.0 * shapes["relaxed_slots_traced"] / rounds
            + 8.0 * shapes["vertices"])


READERS = {"record-ratio": record_ratio}
BYTES = {"sssp-relax": sssp_relax_bytes}
