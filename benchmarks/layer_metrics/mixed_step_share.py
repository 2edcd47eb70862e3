"""Steps that held a prefill chunk over all steps of the traced window:
the scheduler's choice of program."""


def read(ctx):
    steps = ctx["record"]["steps"]
    if not steps:
        return None
    return 100.0 * sum(1 for s in steps if s.mixed) / len(steps)
