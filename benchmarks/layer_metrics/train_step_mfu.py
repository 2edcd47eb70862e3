"""The whole train step's share of the chip's bf16 peak: operations the
forward and backward passes need per token (6 x matrix parameters, head
included, embedding lookup not, plus causal attention; recomputation not
counted) x tokens per second of the traced window, over peak."""

from benchmarks import counts


def read(ctx):
    rec, cfg = ctx["record"], ctx["cfg"]
    if not rec["steps"]:
        return None
    tokens = len(rec["steps"]) * rec["tokens_per_step"]
    per_token = counts.train_flops_per_token(cfg, ctx["spec"]["seq"])
    return 100.0 * per_token * tokens / (rec["window_s"] * ctx["chips"]
                                         * ctx["peaks"]["bf16_flops"])
