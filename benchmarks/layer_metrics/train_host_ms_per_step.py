"""Mean milliseconds of ``jit.TrainStep.__call__`` per step of the traced
window: the program's ``step`` span on its ``train`` track. The call
does not wait for the device, so all of it is host time."""

from benchmarks import program_spans


def read(ctx):
    steps = program_spans.spans(program_spans.window_events(ctx),
                                "step", "train")
    if not steps:
        return None
    return 1e3 * sum(s["dur"] for s in steps) / len(steps)
