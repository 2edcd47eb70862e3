"""Operation and byte counts on Mistral-7B-v0.3's published sizes."""

import pytest

from benchmarks import counts


def test_layer_and_model_parameters(mistral_serve, mistral_train):
    assert counts.layer_params(mistral_serve) == 218_112_000   # 218.1M
    assert counts.total_params(mistral_serve) == 16 * 218_112_000 \
        + 2 * 32768 * 4096 + 4096                              # 3.76e9
    assert counts.total_params(mistral_train) == 922_775_552
    # matrices only: no norm gains, no embedding lookup, the head once
    assert counts.matmul_params(mistral_train) == \
        3 * (218_112_000 - 8192) + 4096 * 32768


def test_kv_bytes_per_token(mistral_serve):
    assert counts.kv_bytes_per_token(mistral_serve) == 65536
    assert counts.decode_attn_bytes(mistral_serve, [100, 28]) == 128 * 65536


def test_train_flops(mistral_train):
    per_token = counts.train_flops_per_token(mistral_train, 4096)
    n = counts.matmul_params(mistral_train)
    attn = 3 * 4 * (4096 * 4097 // 2) * 32 * 128 * 3 / 4096
    assert per_token == pytest.approx(6 * n + attn)
    # 20.6e12 a step of 4096 tokens; the embedding lookup (134M rows x 6)
    # and remat's second forward are not in it
    assert per_token * 4096 == pytest.approx(20.6e12, rel=0.01)
    assert per_token < 6 * counts.total_params(mistral_train) + attn


def test_attention_counts(mistral_train, mistral_serve):
    one = counts.flash_fwd_flops(mistral_train, 1, 4096)
    assert one == 4 * (4096 * 4097 // 2) * 32 * 128
    assert counts.causal_attn_flops(mistral_train, 4096) == 3 * one
    # a decode row that sees 300 keys, 16 layers
    assert counts.attn_flops_row(mistral_serve, 300) == 4 * 300 * 4096 * 16
    assert counts.forward_flops(mistral_serve, 2, 7) == \
        2 * 2 * counts.matmul_params(mistral_serve) + 7
