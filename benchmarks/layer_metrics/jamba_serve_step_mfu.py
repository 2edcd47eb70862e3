"""The whole serving step's share of the chip's bf16 peak for the
``jamba`` family: forward operations of every token row the window
processed (the Mamba-1 mixers' matmuls and recurrence, the attention
projections, the feed-forward of every block, the head over the whole
vocabulary: ``counts_jamba.row_flops``) plus attention's operations from
each row's context, over window seconds x peak. Counted from the
harness's record, not from the program."""

from benchmarks import counts_jamba as counts


def read(ctx):
    rec, cfg = ctx["record"], ctx["cfg"]
    steps = rec["steps"]
    if not steps:
        return None
    flops = sum(counts.forward_flops(cfg, s.rows, s.attn_keys)
                for s in steps)
    return 100.0 * flops / (rec["window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
