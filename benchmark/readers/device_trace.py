"""Readers of the traced stretch, as `trace_reduce.reduce` summed it up, and
the functions that compute the bytes an algorithm must move."""

from __future__ import annotations

import device


def _units(run, per) -> float:
    """What a device time is divided by: `{"count": <harness count>}`, e.g.
    the supersteps of the traced submits, or `{"executions_of": [modules]}`,
    where one execution of those executables is one unit."""
    if "count" in per:
        return run.counts.get(per["count"], 0)
    modules = run.trace_summary["modules"]
    return sum(modules.get(m, (0, 0.0))[0] for m in per["executions_of"])


def trace_ops(run, modules, per):
    """Device time of the named executables (XLA modules, as the trace
    prints them) in ms, per unit (`_units`)."""
    summary = run.trace_summary
    if not summary:
        return None
    seconds = sum(summary["modules"].get(m, (0, 0.0))[1] for m in modules)
    units = _units(run, per)
    return 1000.0 * seconds / units if units and seconds else None


def trace_scope(run, scopes, per):
    """Device time under the named scopes (`jax.named_scope` in the
    program, read from each device operation's name stack:
    `trace_reduce.scope_times`) in ms, per unit (`_units`); None where the
    trace holds none of them. A scope's time holds the scopes inside it, so
    name scopes of which none encloses another."""
    summary = run.trace_summary
    if not summary:
        return None
    by_scope = summary.get("scopes", {})
    seconds = sum(by_scope.get(scope, 0.0) for scope in scopes)
    units = _units(run, per)
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


#: bytes functions by name, `shapes -> bytes`; every `readers/*.py` may offer
#: such a table, so a kernel's bytes come with the metric that reads them
BYTES = {"dense-superstep": dense_superstep_bytes}


def roofline(run, bytes_function, per, paths=None, modules=None,
             scopes=None):
    """Share of the memory roofline: the least time the chip could take to
    move the algorithm's bytes, over the device time measured, in %. The
    time is that of whole executables (`modules`, as `trace-ops`) or that
    under named scopes inside them (`scopes`, as `trace-scope`); with
    `paths`, only for the executor paths the bytes function describes (as
    run_info names them). Bandwidth-bound, so the FLOP peak is not read."""
    if paths is not None and (
            run.notes.get("run_info", {}).get("path") not in paths):
        return None
    if scopes is not None:
        ms = trace_scope(run, scopes, per)
    else:
        ms = trace_ops(run, modules, per)
    if ms is None:
        return None
    peak = device.peaks(device.describe(run.devices)["kind"])["bytes_per_s"]
    count_bytes = run.catalog.plugins("readers", "BYTES")[bytes_function]
    least_ms = 1000.0 * count_bytes(run.shapes) / peak
    return 100.0 * least_ms / ms


READERS = {
    "trace-ops": trace_ops,
    "trace-scope": trace_scope,
    "trace-idle": trace_idle,
    "roofline": roofline,
}
