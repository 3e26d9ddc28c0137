"""A reader of registry counters held against each other: what the
spillover planner's lock counts about its queue, per request it served. A
program without the counter (one from before the lock kept a ledger) gives
it nothing to read: None, and the metric is left out of the line."""

from __future__ import annotations


def registry_counter_per(run, counter, per_counter, scale=1.0):
    """How far a registry counter moved during the window, per unit another
    registry counter moved (times `scale`: 100 for a share in %)."""
    if counter not in (run.registry_after or {}):
        return None
    base = run.moved(per_counter)
    return scale * run.moved(counter) / base if base else None


READERS = {
    "registry-counter-per": registry_counter_per,
}
