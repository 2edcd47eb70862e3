"""Mean milliseconds per engine step in the scheduling phases, before any
input is built: ``deadline_sweep``, ``brownout``, ``admission``,
``draft``, ``ensure_pages`` and ``plan`` (the program's spans)."""

from benchmarks import program_spans


def read(ctx):
    return program_spans.phase_group_ms(ctx, program_spans.SCHEDULE)
