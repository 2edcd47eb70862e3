"""Of the expert assignments the program routed inside the traced
window (live rows x experts per token), the share that landed on an
expert this chip holds: the program's counters ``expert_rows_held`` over
``expert_rows_routed``. Experts held over experts routed (25% in
``nemotron3_super_serve``) when the share is honoured and no row is
dropped."""

from benchmarks import program_spans


def read(ctx):
    routed = program_spans.counter_growth(ctx, "expert_rows_routed")
    if not routed:
        return None
    return 100.0 * program_spans.counter_growth(ctx, "expert_rows_held") / routed
