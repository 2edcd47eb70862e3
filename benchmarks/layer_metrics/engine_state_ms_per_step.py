"""Mean milliseconds per engine step of the traced window spent on the
per-slot recurrent state's bookkeeping: the program's spans
``state_admit`` and ``state_release`` (wherever in a step they fall)
over the window's ``step`` spans."""

from benchmarks import program_spans


def read(ctx):
    events = program_spans.window_events(ctx)
    steps = program_spans.spans(events, "step", "engine")
    state = [e for name in ("state_admit", "state_release")
             for e in program_spans.spans(events, name, "engine")]
    if not steps or not state:
        return None
    return 1e3 * sum(e["dur"] for e in state) / len(steps)
