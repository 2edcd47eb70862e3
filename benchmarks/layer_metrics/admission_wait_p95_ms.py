"""p95 of the time a request waited for a slot: ``queued`` begin to end
on its own track of the program's trace, over the requests admitted
inside the traced window."""

import numpy as np

from benchmarks import program_spans


def read(ctx):
    waits = program_spans.queued_waits(ctx)
    if not waits:
        return None
    return 1e3 * float(np.percentile(np.asarray(waits, np.float64), 95))
