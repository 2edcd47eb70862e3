"""p95 of the time to first token as the engine saw it: from a request's
first ``queued`` begin on its track to its ``first_token`` point, over
the requests whose first token lies inside the traced window."""

import numpy as np

from benchmarks import request_spans


def read(ctx):
    ttfts = request_spans.engine_ttfts(ctx)
    if not ttfts:
        return None
    return 1e3 * float(np.percentile(np.asarray(ttfts, np.float64), 95))
