"""Mean host-clock milliseconds of an engine step that held no prefill
chunk (the decode program), over the traced window."""


def read(ctx):
    ts = [s.t1 - s.t0 for s in ctx["record"]["steps"] if not s.mixed]
    return 1e3 * sum(ts) / len(ts) if ts else None
