"""Mean milliseconds per engine step after the device has answered:
``sample_emit`` (tokens to requests, finishes, prefix registration) and
``bookkeeping`` (metrics, the retrace sentinel, the stall check)."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.phase_group_ms(ctx, program_spans.EMIT)
