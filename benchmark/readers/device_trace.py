"""Readers of the traced stretch, as `trace_reduce.reduce` summed it up, and
the functions that compute the bytes an algorithm must move."""

from __future__ import annotations

import device


def trace_ops(run, modules, per):
    """Device time of the named executables (XLA modules, as the trace
    prints them) in ms, per unit: `{"count": <harness count>}`, e.g. the
    supersteps of the traced submits, or `{"executions_of": [modules]}`,
    where one execution of those is one unit."""
    summary = run.trace_summary
    if not summary:
        return None
    seconds = sum(summary["modules"].get(m, (0, 0.0))[1] for m in modules)
    if "count" in per:
        units = run.counts.get(per["count"], 0)
    else:
        units = sum(
            summary["modules"].get(m, (0, 0.0))[0]
            for m in per["executions_of"]
        )
    return 1000.0 * seconds / units if units and seconds else None


def trace_idle(run):
    """Share of the traced stretch in which no operation ran on the
    device, in %."""
    summary = run.trace_summary
    if not summary:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


def dense_superstep_bytes(shapes):
    """What one dense superstep (PageRank, a spilled traversal hop) must
    move however it is packed: per edge the 4-byte index of its source and
    a 4-byte read of that source's value; per vertex a 4-byte read of its
    scale (degree or mask), of its old value and a 4-byte write of its new
    one. Padding, gathers done twice and intermediates are the
    implementation's, and are not counted."""
    return 8 * shapes["edges"] + 12 * shapes["vertices"]


BYTES = {"dense-superstep": dense_superstep_bytes}


def roofline(run, bytes_function, paths, modules, per):
    """Share of the memory roofline: the least time the chip could take to
    move the algorithm's bytes, over the device time measured, in %. Only
    for the executor paths the bytes function describes (`paths`, as
    run_info names them); bandwidth-bound, so the FLOP peak is not read."""
    if run.notes.get("run_info", {}).get("path") not in paths:
        return None
    ms = trace_ops(run, modules, per)
    if ms is None:
        return None
    peak = device.peaks(device.describe(run.devices)["kind"])["bytes_per_s"]
    least_ms = 1000.0 * BYTES[bytes_function](run.shapes) / peak
    return 100.0 * least_ms / ms


READERS = {
    "trace-ops": trace_ops,
    "trace-idle": trace_idle,
    "roofline": roofline,
}
