"""Rows that carried a token over the rows the mixed program's dense
layers computed inside the traced window, in percent: the program's
counters ``mixed_rows_live`` and ``mixed_rows_computed`` (per mixed
dispatch, the sum of ``n_live`` against ``max_slots x prefill_chunk``,
or against the packed width T where the model takes its rows packed).
A program without the counters reads nothing."""

from benchmarks import program_spans


def read(ctx):
    rows = program_spans.counter_growth(ctx, "mixed_rows_computed")
    if not rows:
        return None
    return 100.0 * program_spans.counter_growth(ctx, "mixed_rows_live") / rows
