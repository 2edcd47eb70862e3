"""The paged decode kernel's share of its roofline in the ``nemotron_h``
family, whose attention layers are few: the live context of every
decoding slot at each decode-program step, from the harness's record, x
K/V bytes per token of ONE attention layer x the attention layers, over
the device time of every ``paged_attention_decode`` event x peak
bandwidth."""

from benchmarks import counts_nemotron_h as counts
from benchmarks import trace

KERNEL = "paged_attention_decode"


def read(ctx):
    n, seconds = trace.kernel_totals(ctx["trace"], KERNEL)
    if not n or seconds <= 0:
        return None
    cfg = ctx["cfg"]
    per_token = (counts.kv_bytes_per_token_layer(cfg)
                 * counts.kinds(cfg)[counts.ATTENTION])
    nbytes = per_token * sum(sum(s.decode_contexts)
                             for s in ctx["record"]["steps"] if not s.mixed)
    if not nbytes:
        return None
    return 100.0 * (nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
