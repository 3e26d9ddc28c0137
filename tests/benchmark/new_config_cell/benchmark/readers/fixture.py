"""A reader added with a cell: found under the root by its table, like the
checkout's `benchmark/readers/*.py`."""


def span_ms(run, span):  # noqa: A002 - the argument is the span's name
    """Milliseconds of a span the harness or the driver recorded, or None
    where there is no such span."""
    seconds = run.spans.get(span)
    return None if seconds is None else 1000.0 * seconds


READERS = {"span-ms": span_ms}
