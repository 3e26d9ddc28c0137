"""Readers of what the program measures about itself: its phases (the
registry timers `phase.<name>`, each the SELF time of a named stretch of
host code, so that the phases of a request or a submit tile it), its own
compile timer, and how much of the device's idle time the trace reducer
could put no program phase on. A program without phases (one from before
they were added) gives every reader here nothing to read: None, and the
metric is left out of the line."""

from __future__ import annotations

PHASE_PREFIX = "phase."


def _note_phases(run) -> None:
    """Every phase timer that moved in the window, into the notes file:
    the per-phase table behind the summed metrics (PERF.md section 5)."""
    if "phases" in run.notes:
        return  # several metrics share this reader: one table a run
    per = run.counts.get("requests") or 0
    table = {}
    for name in sorted(run.registry_after or {}):
        if name.startswith(PHASE_PREFIX) and run.moved(name):
            total_ms = run.moved(name, "total_ms")
            table[name[len(PHASE_PREFIX):]] = {
                "count": run.moved(name),
                "total_ms": total_ms,
                "ms_per_request": total_ms / per if per else None,
            }
    run.notes["phases"] = table


def registry_timers_per(run, timers, per):
    """Time under a list of registry timers during the window, summed, in
    ms per unit of a count of the harness (`per`, e.g. the requests or
    submits of the window)."""
    after = run.registry_after or {}
    if not any(t in after for t in timers):
        return None
    _note_phases(run)
    base = run.counts.get(per)
    if not base:
        return None
    return sum(run.moved(t, "total_ms") for t in timers) / base


def registry_timer_setup(run, timer):
    """Seconds a registry timer had gathered when the window opened: what
    set-up spent under it."""
    at_open = (run.registry_before or {}).get(timer)
    return at_open["total_ms"] / 1000.0 if at_open else None


def idle_unnamed_share(run):
    """Of the device's idle time that the breakdown lists (its ten longest
    labels), the share under labels that name no part of the program:
    `<span>/python` (interpreter code nothing annotated) and `no_span/...`
    (outside every span of the benchmark), in %."""
    summary = run.trace_summary
    if not summary or not summary.get("idle_gaps"):
        return None
    listed = sum(seconds for _, seconds in summary["idle_gaps"])
    unnamed = sum(
        seconds for label, seconds in summary["idle_gaps"]
        if label.endswith("/python") or label.startswith("no_span/")
    )
    return 100.0 * unnamed / listed if listed else None


READERS = {
    "registry-timers-per": registry_timers_per,
    "registry-timer-setup": registry_timer_setup,
    "idle-unnamed-share": idle_unnamed_share,
}
