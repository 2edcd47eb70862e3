"""p95 of the engine steps a first token took: the ``steps`` argument of
the program's ``first_token`` points inside the traced window (from the
request's first admission through the step that emitted it)."""

import numpy as np

from benchmarks import request_spans


def read(ctx):
    steps = [e["args"]["steps"] for e in request_spans.first_tokens(ctx)]
    if not steps:
        return None
    return float(np.percentile(np.asarray(steps, np.float64), 95))
