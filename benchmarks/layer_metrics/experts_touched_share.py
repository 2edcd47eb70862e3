"""Held experts that had at least one row, over held experts x expert
layers x steps of the traced window: the program's counter
``experts_touched`` (per step, summed over its expert layers) against
the counters ``decode_steps`` + ``mixed_steps``. What a step must read
of the experts' weights follows it."""

from benchmarks import counts_nemotron_h as counts
from benchmarks import program_spans


def read(ctx):
    cfg = ctx["cfg"]
    steps = (program_spans.counter_growth(ctx, "decode_steps")
             + program_spans.counter_growth(ctx, "mixed_steps"))
    touched = program_spans.counter_growth(ctx, "experts_touched")
    if not steps or not touched:
        return None
    return 100.0 * touched / (cfg["experts_held"][1]
                              * counts.kinds(cfg)[counts.MOE] * steps)
