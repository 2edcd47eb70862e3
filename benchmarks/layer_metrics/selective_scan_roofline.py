"""The selective scan's share of its roofline. The recurrence is
elementwise work on the vector unit: 6 operations for each value of
state, which it reads and writes in float32 (0.75 operations a byte,
where the chip's peaks meet at 241), so its bound is bytes: what the scans of the traced window's mixed-program
steps cannot avoid (``counts_jamba.mixed_step_scan_bytes``: the state of
every slot with a live row, in and out, and each live row's operands
and result, from the harness's record) over peak bandwidth, against the
device time of every ``selective_scan_rows`` event of the trace. It
counts the same work whatever implements it; a trace without such an
event gives nothing."""

from benchmarks import counts_jamba as counts
from benchmarks import trace

KERNEL = "selective_scan_rows"


def read(ctx):
    n, seconds = trace.kernel_totals(ctx["trace"], KERNEL)
    if not n or seconds <= 0:
        return None
    cfg = ctx["cfg"]
    chunk = cfg["engine"]["prefill_chunk"]
    nbytes = sum(counts.mixed_step_scan_bytes(cfg, s, chunk)
                 for s in ctx["record"]["steps"] if s.mixed)
    if not nbytes:
        return None
    return 100.0 * (nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
