"""The latent decode kernel's share of its roofline. It sits at the
chip's ridge: a cached row of 576 values is 1152 bytes and every one of
128 heads takes 2 x (576 + 512) operations against it, 242 operations a
byte where the chip's peaks meet at 241. So the least time of a
decode-program step is the larger of its live latent bytes over peak
bandwidth and its operations over the bf16 peak (both from the live
context of every decoding slot, by the harness's record, x the layers),
and the share is the sum of those over the device time of every
``paged_latent_attention_decode`` event of the trace."""

from benchmarks import counts_pangu_moe as counts
from benchmarks import trace

KERNEL = "paged_latent_attention_decode"


def read(ctx):
    n, seconds = trace.kernel_totals(ctx["trace"], KERNEL)
    if not n or seconds <= 0:
        return None
    cfg, peaks = ctx["cfg"], ctx["peaks"]
    layers = cfg["num_hidden_layers"]
    per_key = max(
        counts.latent_bytes_per_token_layer(cfg) / peaks["hbm_bytes_per_s"],
        counts.latent_kernel_flops_per_key(cfg) / peaks["bf16_flops"])
    keys = sum(sum(s.decode_contexts)
               for s in ctx["record"]["steps"] if not s.mixed)
    if not keys:
        return None
    return 100.0 * per_key * keys * layers / seconds
