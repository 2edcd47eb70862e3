"""Operations and bytes of the ``jamba`` family (Mamba-1 mixers, an
attention mixer every ``attn_layer_period`` layers, a dense SwiGLU
feed-forward in every block, a tied head), counted from a
configuration's shapes and from the harness's own record: never from
what the program says it did. ``benchmarks/counts.py`` is the dense
decoder's.

Conventions as there: a multiply-add is 2 operations; the embedding
lookup, the norms, the conv and the activations count nothing. Every
count is a LOWER bound on what the step must do (a share of a peak read
from it cannot pass 100% by the count's fault): the selective scan is
counted at its LIVE rows and the state of the slots that have one,
whatever the kernel's blocks move besides.
"""

from __future__ import annotations

import math

from benchmarks.families.jamba import (ATTENTION, MAMBA, head_dim,
                                       layer_kinds, mamba_dims, param_shapes)


def kinds(cfg: dict) -> dict[str, int]:
    pat = layer_kinds(cfg)
    return {k: pat.count(k) for k in (MAMBA, ATTENTION)}


def mamba_matmul_params(cfg: dict) -> int:
    """in_proj, x_proj, dt_proj and out_proj of one Mamba-1 mixer."""
    m, hid = mamba_dims(cfg), cfg["hidden_size"]
    d, n, r = m["d"], m["n"], m["r"]
    return hid * 2 * d + d * (r + 2 * n) + r * d + d * hid


def attention_matmul_params(cfg: dict) -> int:
    hid, d = cfg["hidden_size"], head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * hid * nq * d + 2 * hid * nkv * d


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def total_params(cfg: dict) -> int:
    """Every parameter of the configuration, leaf by leaf (the
    embedding, which is the head, once)."""
    return sum(math.prod(shape) for shape in param_shapes(cfg).values())


def scan_flops_per_row(cfg: dict) -> int:
    """The recurrence of one row in ONE Mamba layer: per channel and
    state index the decay of ``h`` and its update (2 each) and the
    contraction with ``C`` (2)."""
    m = mamba_dims(cfg)
    return 6 * m["d"] * m["n"]


def row_flops(cfg: dict) -> int:
    """Forward operations of ONE token row outside attention's scores:
    the mixers' matmuls, the recurrence, the feed-forward of every block
    and the head."""
    n = kinds(cfg)
    return (n[MAMBA] * (2 * mamba_matmul_params(cfg)
                        + scan_flops_per_row(cfg))
            + n[ATTENTION] * 2 * attention_matmul_params(cfg)
            + (n[MAMBA] + n[ATTENTION]) * 2 * mlp_params(cfg)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def attn_flops_per_key(cfg: dict) -> int:
    """QK^T and PV of one query row against one key, the attention
    layers together."""
    return (4 * cfg["num_attention_heads"] * head_dim(cfg)
            * kinds(cfg)[ATTENTION])


def forward_flops(cfg: dict, rows: int, attn_keys: int) -> int:
    return row_flops(cfg) * rows + attn_flops_per_key(cfg) * attn_keys


def kv_bytes_per_token_layer(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V of one token in ONE attention layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * bytes_per_value


def ssm_state_bytes(cfg: dict, ssm_bytes: int = 4) -> int:
    """``h`` of one slot in ONE Mamba layer."""
    m = mamba_dims(cfg)
    return m["d"] * m["n"] * ssm_bytes


def state_bytes_per_slot(cfg: dict, conv_bytes: int = 2,
                         ssm_bytes: int = 4) -> int:
    """The recurrent state one request carries, all Mamba layers: the
    conv window and ``h``."""
    m = mamba_dims(cfg)
    return kinds(cfg)[MAMBA] * ((m["w"] - 1) * m["d"] * conv_bytes
                                + ssm_state_bytes(cfg, ssm_bytes))


def decode_step_bytes(cfg: dict, contexts, weight_bytes: int = 2) -> int:
    """Bytes a decode-program step over ``len(contexts)`` live rows
    cannot avoid: every weight once (the embedding as the head), the
    live slots' state read and written, the live K/V read."""
    return (weight_bytes * total_params(cfg)
            + 2 * len(contexts) * state_bytes_per_slot(cfg)
            + sum(contexts) * kv_bytes_per_token_layer(cfg)
            * kinds(cfg)[ATTENTION])


def scan_bytes(cfg: dict, slots_live: int, rows_live: int,
               operand_bytes: int = 4) -> int:
    """Bytes the selective scan of one mixed-program step cannot avoid,
    the Mamba layers together: the state of every slot that has a live
    row, in and out, and each live row's operands (``x``, ``dt``,
    ``B``, ``C``) and result (``y``) as the kernel's interface holds
    them (float32)."""
    m = mamba_dims(cfg)
    row = (3 * m["d"] + 2 * m["n"]) * operand_bytes
    return kinds(cfg)[MAMBA] * (2 * slots_live * ssm_state_bytes(cfg)
                                + rows_live * row)


def mixed_step_scan_bytes(cfg: dict, step, prefill_chunk: int) -> int:
    """``scan_bytes`` of one mixed step of the harness's record: its
    decode lanes are a row each, its chunk rows fill the fewest chunk
    lanes they can (a lower bound on the slots with a live row)."""
    decode = len(step.decode_contexts)
    chunk_rows = step.rows - decode
    return scan_bytes(cfg, decode + -(-chunk_rows // prefill_chunk),
                      step.rows)
