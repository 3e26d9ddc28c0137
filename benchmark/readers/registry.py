"""Readers of the program's registry (timers, counters, run records) and
of the harness's own spans and counts. A reader is
`reader(run, **args) -> number or None`; None means there was nothing to
read and the metric is left out of the line."""

from __future__ import annotations


def registry_timer(run, timer):
    """Mean of a registry timer over the window, in ms."""
    count = run.moved(timer)
    return run.moved(timer, "total_ms") / count if count else None


def registry_counter_ratio(run, counter, per):
    """How far a registry counter moved during the window, as a percentage
    of a count of the harness (`per`, e.g. the requests in the window)."""
    base = run.counts.get(per)
    return 100.0 * run.moved(counter) / base if base else None


def last_run(run, kind, path, require_none=None):
    """Mean of a field of the run records of `kind` the registry still
    holds (a ring of the newest 32), e.g. ["spillover", "wall_ms"]."""
    from janusgraph_tpu.observability import registry

    values = []
    for record in registry.runs(kind):
        if require_none and _dig(record, require_none) is not None:
            continue
        value = _dig(record, path)
        if value is not None:
            values.append(float(value))
    return sum(values) / len(values) if values else None


def _dig(record, path):
    for key in path:
        if not isinstance(record, dict) or key not in record:
            return None
        record = record[key]
    return record


def span(run, span):  # noqa: A002 - the argument is the span's name
    """Seconds of a span the harness or the driver recorded."""
    return run.spans.get(span)


def harness_count(run, count):
    return run.counts.get(count)


def window_statistic(run, name):
    """A statistic the driver took over the window's requests, read in the
    traced run (so under the tracer's overhead)."""
    return run.end_to_end.get(name)


READERS = {
    "registry-timer": registry_timer,
    "registry-counter-ratio": registry_counter_ratio,
    "last-run": last_run,
    "span": span,
    "harness-count": harness_count,
    "window-statistic": window_statistic,
}
