"""The whole serving step's share of the chip's bf16 peak for the
``pangu_moe`` family: forward operations of every token row the window
processed (latent attention's projections, the dense MLP, router and
shared expert, the expected held experts of a row, the head slice:
``counts_pangu_moe.row_flops``) plus attention's operations from each
row's context in the published per-head form, over window seconds x
peak. Counted from the harness's record, not from the program."""

from benchmarks import counts_pangu_moe as counts


def read(ctx):
    rec, cfg = ctx["record"], ctx["cfg"]
    steps = rec["steps"]
    if not steps:
        return None
    flops = sum(counts.forward_flops(cfg, s.rows, s.attn_keys)
                for s in steps)
    return 100.0 * flops / (rec["window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
