"""Operations and bytes of the ``pangu_moe`` family (multi-head latent
attention, a gated dense MLP in the leading layers, gated routed experts
beside a shared expert after them), counted from a configuration's
shapes and from the harness's own record: never from what the program
says it did. ``benchmarks/counts.py`` is the dense decoder's.

Conventions as there: a multiply-add is 2 operations; the embedding
lookup, the norms, the rotary embedding and the activations count
nothing. Every count is a LOWER bound on what the step must do (a share
of a peak read from it cannot pass 100% by the count's fault): a row's
held experts are counted at their expectation ``top_k x held / routed``,
the weights a decode step must read at the experts its rows are expected
to touch, a cached row at its 576 values (the pool pads it to 640), and
attention against a key at the published per-head form, ``heads x (nope
+ rope + v)``, which is the cheaper of the two forms for a whole step;
only the decode kernel, whose inputs are the latent rows, is counted in
the absorbed form it has to take.
"""

from __future__ import annotations

from benchmarks.families.pangu_moe import DENSE, MOE, layer_kinds


def kinds(cfg: dict) -> dict[str, int]:
    pat = layer_kinds(cfg)
    return {k: pat.count(k) for k in (DENSE, MOE)}


def attention_matmul_params(cfg: dict) -> int:
    """The five projections of one latent attention layer. The per-head
    key/value up-projection counts once a row in either form: made from
    the latent of every row (unabsorbed), or carried over the query and
    the output of every row (absorbed)."""
    hid, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (hid * ql + ql * h * (dn + dr) + hid * (kvl + dr)
            + kvl * h * (dn + dv) + h * dv * hid)


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_dense_params(cfg: dict) -> int:
    """What every row of an expert layer is multiplied by: the router
    and the shared expert."""
    return (cfg["hidden_size"] * cfg["n_routed_experts"]
            + cfg["n_shared_experts"] * expert_params(cfg))


def held_experts_per_row(cfg: dict) -> float:
    return (cfg["num_experts_per_tok"] * cfg["experts_held"][1]
            / cfg["n_routed_experts"])


def total_params(cfg: dict) -> int:
    """Every parameter the configuration holds on this chip."""
    n, hid = kinds(cfg), cfg["hidden_size"]
    block = (attention_matmul_params(cfg) + cfg["q_lora_rank"]
             + cfg["kv_lora_rank"] + 4 * hid)
    bias = (cfg["n_routed_experts"]
            if cfg["assumed"]["router_score_bias"] else 0)
    moe = (moe_dense_params(cfg) + bias
           + cfg["experts_held"][1] * expert_params(cfg))
    return ((n[DENSE] + n[MOE]) * block + n[DENSE] * dense_mlp_params(cfg)
            + n[MOE] * moe + 2 * cfg["vocab_size"] * hid + hid)


def row_flops(cfg: dict) -> float:
    """Forward operations of ONE token row outside attention's scores:
    the attention projections, the dense MLP, the router and shared
    expert, the EXPECTED held experts, and the head slice."""
    n = kinds(cfg)
    per_moe = 2 * (moe_dense_params(cfg)
                   + held_experts_per_row(cfg) * expert_params(cfg))
    return ((n[DENSE] + n[MOE]) * 2 * attention_matmul_params(cfg)
            + n[DENSE] * 2 * dense_mlp_params(cfg) + n[MOE] * per_moe
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def attn_flops_per_key(cfg: dict) -> int:
    """QK^T and PV of one query row against one key in the published
    per-head form, all layers together."""
    return (2 * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
               + cfg["v_head_dim"]) * cfg["num_hidden_layers"])


def forward_flops(cfg: dict, rows: int, attn_keys: int) -> float:
    return row_flops(cfg) * rows + attn_flops_per_key(cfg) * attn_keys


def latent_row_values(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_bytes_per_token_layer(cfg: dict, bytes_per_value: int = 2) -> int:
    """The cached row of one token in ONE layer."""
    return latent_row_values(cfg) * bytes_per_value


def latent_kernel_flops_per_key(cfg: dict) -> int:
    """The decode kernel, one layer, one slot's query row against one
    cached row: every head's score over the whole row and its mix of
    the row's latent part (the absorbed form)."""
    return (2 * cfg["num_attention_heads"]
            * (latent_row_values(cfg) + cfg["kv_lora_rank"]))


def experts_touched_expected(cfg: dict, rows: int) -> float:
    """The share of the held experts that ``rows`` rows are expected to
    touch: 1 - (1 - top_k / routed)^rows."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts"]
    return 1.0 - miss ** rows


def decode_step_bytes(cfg: dict, contexts, weight_bytes: int = 2) -> float:
    """Bytes a decode-program step over ``len(contexts)`` live rows
    cannot avoid: every weight outside the experts once, the held
    experts its rows are expected to touch, the live latent rows read."""
    n, hid = kinds(cfg), cfg["hidden_size"]
    dense = ((n[DENSE] + n[MOE]) * attention_matmul_params(cfg)
             + n[DENSE] * dense_mlp_params(cfg)
             + n[MOE] * moe_dense_params(cfg) + hid * cfg["vocab_size"])
    experts = (n[MOE] * cfg["experts_held"][1] * expert_params(cfg)
               * experts_touched_expected(cfg, len(contexts)))
    return (weight_bytes * (dense + experts)
            + sum(contexts) * latent_bytes_per_token_layer(cfg)
            * cfg["num_hidden_layers"])
