"""The flash forward kernel's share of its roofline. Compute bounds it
at these shapes (4096 x 4096 causal scores per head against 3 x 4096 x
128 values read). Operations of one causal forward call from the shapes,
x the calls in the trace (the forward and, under remat, its
recomputation are both calls), over their device time x bf16 peak."""

from benchmarks import counts, trace

KERNEL = "flash_attention_fwd"


def read(ctx):
    n, seconds = trace.kernel_totals(ctx["trace"], KERNEL)
    if not n or seconds <= 0:
        return None
    spec = ctx["spec"]
    flops = n * counts.flash_fwd_flops(ctx["cfg"], spec["batch"], spec["seq"])
    return 100.0 * (flops / ctx["peaks"]["bf16_flops"]) / seconds
