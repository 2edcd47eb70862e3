"""Mean milliseconds per engine step in ``build_inputs`` (the numpy lanes
and their transfer) and in the dispatch of the compiled program
(``decode_dispatch`` or ``mixed_dispatch``: the call and nothing else)."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.phase_group_ms(ctx, program_spans.DISPATCH)
