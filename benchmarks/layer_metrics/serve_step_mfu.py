"""The whole serving step's share of the chip's bf16 peak: forward
operations of every token row the window processed (prompt and output
rows alike: 2 x matrix parameters, head included, embedding lookup not)
plus attention's operations from each row's context, over window seconds
x peak. Counted from the harness's record, not from the program."""

from benchmarks import counts


def read(ctx):
    rec, cfg = ctx["record"], ctx["cfg"]
    steps = rec["steps"]
    if not steps:
        return None
    per_key = (4 * cfg["num_attention_heads"] * cfg["head_dim"]
               * cfg["num_hidden_layers"])
    flops = sum(counts.forward_flops(cfg, s.rows, per_key * s.attn_keys)
                for s in steps)
    return 100.0 * flops / (rec["window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
