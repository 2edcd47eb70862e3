"""Mean host-clock milliseconds of an engine step that held a prefill
chunk (the mixed program), over the traced window."""


def read(ctx):
    ts = [s.t1 - s.t0 for s in ctx["record"]["steps"] if s.mixed]
    return 1e3 * sum(ts) / len(ts) if ts else None
