"""The paged decode kernel's share of its roofline. Memory bounds it:
one query row per slot reads the slot's whole live K/V. Bytes: the live
context of every decoding slot at each decode-program step, from the
harness's record, x K/V bytes per token over all layers; time: the
device time of every ``paged_attention_decode`` event of the trace."""

from benchmarks import counts, trace

KERNEL = "paged_attention_decode"


def read(ctx):
    n, seconds = trace.kernel_totals(ctx["trace"], KERNEL)
    if not n or seconds <= 0:
        return None
    cfg = ctx["cfg"]
    nbytes = sum(counts.decode_attn_bytes(cfg, s.decode_contexts)
                 for s in ctx["record"]["steps"] if not s.mixed)
    if not nbytes:
        return None
    return 100.0 * (nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
