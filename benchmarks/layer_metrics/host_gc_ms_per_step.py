"""Milliseconds per engine step of the traced window spent in Python's
garbage collector: the program's ``gc`` spans on the ``host`` track
over the window's ``step`` spans."""

from benchmarks import request_spans


def read(ctx):
    seconds = request_spans.gc_seconds_per_step(ctx)
    return None if seconds is None else 1e3 * seconds
