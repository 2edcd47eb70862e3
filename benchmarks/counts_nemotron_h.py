"""Operations and bytes of the ``nemotron_h`` family (Mamba-2, attention
and LatentMoE blocks by a pattern string), counted from a
configuration's shapes and from the harness's own record: never from
what the program says it did. ``benchmarks/counts.py`` is the dense
decoder's.

Conventions as there: a multiply-add is 2 operations; the embedding
lookup, the norms, the conv and the activations count nothing. Every
count is a LOWER bound on what the step must do (a share of a peak read
from it cannot pass 100% by the count's fault): a row's held experts are
counted at their expectation ``top_k x held / routed``, the weights a
decode step must read at the experts its rows are expected to touch.
"""

from __future__ import annotations

from benchmarks.families.nemotron_h import ATTENTION, MAMBA, MOE, mamba_dims


def kinds(cfg: dict) -> dict[str, int]:
    pat = cfg["hybrid_override_pattern"]
    return {k: pat.count(k) for k in (MAMBA, ATTENTION, MOE)}


def mamba_matmul_params(cfg: dict) -> int:
    d, hid = mamba_dims(cfg), cfg["hidden_size"]
    return hid * (2 * d["d_inner"] + 2 * d["g"] * d["n"] + d["h"]) \
        + d["d_inner"] * hid


def attention_matmul_params(cfg: dict) -> int:
    hid, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * hid * nq * d + 2 * hid * nkv * d


def expert_params(cfg: dict) -> int:
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def moe_dense_params(cfg: dict) -> int:
    """What every row of an expert layer is multiplied by: the router,
    the two latent projections and the shared expert."""
    hid = cfg["hidden_size"]
    fs = cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
    return (hid * cfg["n_routed_experts"] + 2 * hid * cfg["moe_latent_size"]
            + 2 * hid * fs)


def held_experts_per_row(cfg: dict) -> float:
    return (cfg["num_experts_per_tok"] * cfg["experts_held"][1]
            / cfg["n_routed_experts"])


def total_params(cfg: dict) -> int:
    """Every parameter the configuration holds on this chip."""
    n, hid, d = kinds(cfg), cfg["hidden_size"], mamba_dims(cfg)
    mamba = (mamba_matmul_params(cfg) + d["conv_dim"] * (cfg["conv_kernel"]
             + 1) + 3 * d["h"] + d["d_inner"] + hid)
    attn = attention_matmul_params(cfg) + hid
    moe = (moe_dense_params(cfg) + cfg["n_routed_experts"] + hid
           + cfg["experts_held"][1] * expert_params(cfg))
    return (n[MAMBA] * mamba + n[ATTENTION] * attn + n[MOE] * moe
            + 2 * cfg["vocab_size"] * hid + hid)


def row_flops(cfg: dict) -> float:
    """Forward operations of ONE token row outside attention's scores:
    the mixers' matmuls, the recurrence (per Mamba layer and head: decay
    and update of S, 2 x p x n each, and S C, 2 x p x n: 6 x h x p x n),
    the router, latent projections and shared expert, the EXPECTED held
    experts, and the head slice."""
    n, d = kinds(cfg), mamba_dims(cfg)
    per_mamba = 2 * mamba_matmul_params(cfg) + 6 * d["h"] * d["p"] * d["n"]
    per_moe = 2 * (moe_dense_params(cfg)
                   + held_experts_per_row(cfg) * expert_params(cfg))
    return (n[MAMBA] * per_mamba + n[ATTENTION] * 2
            * attention_matmul_params(cfg) + n[MOE] * per_moe
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def attn_flops_per_key(cfg: dict) -> int:
    """QK^T and PV of one query row against one key, the attention
    layers together."""
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * kinds(cfg)[ATTENTION])


def forward_flops(cfg: dict, rows: int, attn_keys: int) -> float:
    return row_flops(cfg) * rows + attn_flops_per_key(cfg) * attn_keys


def kv_bytes_per_token_layer(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V of one token in ONE attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def state_bytes_per_slot(cfg: dict, conv_bytes: int = 2,
                         ssm_bytes: int = 4) -> int:
    """The recurrent state one request carries, all Mamba layers: the
    conv window and S."""
    d = mamba_dims(cfg)
    return kinds(cfg)[MAMBA] * (
        (cfg["conv_kernel"] - 1) * d["conv_dim"] * conv_bytes
        + d["h"] * d["p"] * d["n"] * ssm_bytes)


def experts_touched_expected(cfg: dict, rows: int) -> float:
    """The share of the held experts that ``rows`` rows are expected to
    touch: 1 - (1 - top_k / routed)^rows."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts"]
    return 1.0 - miss ** rows


def decode_step_bytes(cfg: dict, contexts, weight_bytes: int = 2) -> float:
    """Bytes a decode-program step over ``len(contexts)`` live rows
    cannot avoid: every weight outside the experts once, the held
    experts its rows are expected to touch, the live slots' state read
    and written, the live K/V read."""
    n, hid = kinds(cfg), cfg["hidden_size"]
    rows = len(contexts)
    dense = (n[MAMBA] * mamba_matmul_params(cfg)
             + n[ATTENTION] * attention_matmul_params(cfg)
             + n[MOE] * moe_dense_params(cfg) + hid * cfg["vocab_size"])
    experts = (n[MOE] * cfg["experts_held"][1] * expert_params(cfg)
               * experts_touched_expected(cfg, rows))
    return (weight_bytes * (dense + experts)
            + 2 * rows * state_bytes_per_slot(cfg)
            + sum(contexts) * kv_bytes_per_token_layer(cfg) * n[ATTENTION])
