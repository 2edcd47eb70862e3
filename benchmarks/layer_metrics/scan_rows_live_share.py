"""Rows that carried a token over the rows the mixed program's
rectangle handed to the recurrent layers inside the traced window, in
percent: the program's counters ``scan_rows_live`` and
``scan_rows_dispatched`` (per mixed dispatch, ``max_slots x
prefill_chunk`` rows dispatched, times the layers that keep a state)."""

from benchmarks import program_spans


def read(ctx):
    rows = program_spans.counter_growth(ctx, "scan_rows_dispatched")
    if not rows:
        return None
    return 100.0 * program_spans.counter_growth(ctx, "scan_rows_live") / rows
