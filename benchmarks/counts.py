"""Operations and bytes, counted from a configuration's shapes and from
the harness's own record of what it sent: never from what a kernel or
the program says it did. Shared by the per-layer metrics.

Conventions: a multiply-add is 2 operations; the embedding lookup is a
gather and counts nothing; recomputation (remat) counts nothing; causal
attention counts the triangle that the mask keeps.
"""

from __future__ import annotations


def layer_params(cfg: dict) -> int:
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nq * d + 2 * h * nkv * d + nq * d * h + 3 * h * f + 2 * h


def matmul_params(cfg: dict) -> int:
    """Parameters a token is multiplied by: every layer's matrices and
    the head (tied or not); not the embedding lookup, not the norms."""
    h = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * (layer_params(cfg) - 2 * h)
            + h * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    n = cfg["num_hidden_layers"] * layer_params(cfg) + v * h + h
    return n if cfg["tie_word_embeddings"] else n + h * v


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * bytes_per_value * cfg["num_hidden_layers"])


def attn_flops_row(cfg: dict, context: int) -> int:
    """Forward attention operations of ONE query row that sees
    ``context`` keys (QK^T and PV), all layers."""
    return (4 * context * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"])


def causal_attn_flops(cfg: dict, seq: int) -> int:
    """Forward attention operations of one causal sequence, all layers:
    row i sees i + 1 keys."""
    return (4 * (seq * (seq + 1) // 2) * cfg["num_attention_heads"]
            * cfg["head_dim"] * cfg["num_hidden_layers"])


def forward_flops(cfg: dict, rows: int, attn_flops: int) -> int:
    """Forward pass over ``rows`` token rows (head applied to each)."""
    return 2 * matmul_params(cfg) * rows + attn_flops


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """6N (N = matmul_params) plus forward-and-backward causal attention
    (3x the forward), per token of a ``seq``-token row."""
    return 6 * matmul_params(cfg) + 3 * causal_attn_flops(cfg, seq) / seq


def flash_fwd_flops(cfg: dict, batch: int, seq: int) -> int:
    """One causal flash-attention forward call (one layer)."""
    return batch * causal_attn_flops(cfg, seq) // cfg["num_hidden_layers"]


def decode_attn_bytes(cfg: dict, contexts, bytes_per_value: int = 2) -> int:
    """K/V bytes the decode attention of one step must read, all layers:
    the live context of every slot (its new token included)."""
    return sum(contexts) * kv_bytes_per_token(cfg, bytes_per_value)
