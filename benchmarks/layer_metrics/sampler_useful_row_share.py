"""Tokens emitted over rows handed to the sampler inside the traced
window, in percent: the program's counters ``tokens`` and
``rows_sampled`` (``max_slots`` rows a decode-program step, ``max_slots x
chunk`` a mixed one)."""

from benchmarks import program_spans


def read(ctx):
    rows = program_spans.counter_growth(ctx, "rows_sampled")
    if not rows:
        return None
    return 100.0 * program_spans.counter_growth(ctx, "tokens") / rows
