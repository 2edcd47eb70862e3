"""Paged-attention kernel ON THE CHIP — TPU-only; tools/run_tpu_checks.py
runs this file there. On the CPU suite every test skips from a fixture
(interpret-mode parity of the same kernel lives in tests/test_serving.py
and tests/test_serving_quant.py; tests/test_tpu_compile.py keeps the
compile for a described chip).

What only the chip can show: that the Mosaic-compiled kernel computes
what the interpreter computed. Each case runs ``paged_attention_tpu`` and
the XLA gather path (``_grouped_decode_attn`` over ``pool[tables]``) on
the same pool, ragged lengths included (one token, a page boundary, a
full table), at the serving widths (Llama-3-8B heads 32/8 and the bench
shape 16/8, page 16, bf16 and int8 pools); the latent kernel against
``_latent_attend`` at one row a slot and at a chunk of 64.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional.attention import (_grouped_decode_attn,
                                                _latent_attend)
from paddle_tpu.ops.pallas.paged_attention import (
    kernel_applicable, latent_kernel_applicable, paged_attention_tpu,
    paged_latent_attention_tpu)
from paddle_tpu.quantization.serving import QuantizedKV, kv_quantize


@pytest.fixture(autouse=True)
def _needs_tpu():
    if jax.default_backend() != "tpu":
        pytest.skip("on-chip kernel parity needs a TPU")


def _case(h, kvh, ps, M, dtype, quant, b=8, d=128, npages=600, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), dtype)
    pk = jnp.asarray(rng.standard_normal((npages, ps, kvh, d)), dtype)
    pv = jnp.asarray(rng.standard_normal((npages, ps, kvh, d)), dtype)
    # every slot owns M distinct pages (page 0 is the engine's scratch)
    tables = jnp.asarray(rng.permutation(np.arange(1, npages))[:b * M]
                         .reshape(b, M), jnp.int32)
    cap = ps * M
    lens = jnp.asarray([0, 1, ps - 1, ps, ps + 1, cap // 2, cap - 2,
                        cap - 1][:b], jnp.int32)
    if quant:
        pk, pv = kv_quantize(pk), kv_quantize(pv)
    return q, pk, pv, tables, lens


def _kernel(q, pk, pv, tables, lens):
    if isinstance(pk, QuantizedKV):
        return paged_attention_tpu(q, pk.q, pv.q, tables, lens,
                                   k_scale=pk.scale, v_scale=pv.scale)
    return paged_attention_tpu(q, pk, pv, tables, lens)


def _gather(q, pk, pv, tables, lens):
    b, d = q.shape[0], q.shape[-1]

    def g(pool):
        if isinstance(pool, QuantizedKV):
            kvh = pool.q.shape[2]
            return QuantizedKV(pool.q[tables].reshape(b, -1, kvh, d),
                               pool.scale[tables].reshape(b, -1, kvh))
        return pool[tables].reshape(b, -1, pool.shape[2], d)

    return _grouped_decode_attn(q, g(pk), g(pv), lens, 1.0 / np.sqrt(d))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("h,kvh", [(32, 8), (16, 8), (8, 2), (8, 8)])
def test_kernel_matches_gather_path_on_chip(h, kvh, quant):
    args = _case(h, kvh, ps=16, M=66, dtype=jnp.bfloat16, quant=quant)
    assert kernel_applicable(args[0].shape, tuple(args[1].shape))
    got = np.asarray(jax.jit(_kernel)(*args).astype(jnp.float32))
    want = np.asarray(jax.jit(_gather)(*args).astype(jnp.float32))
    assert np.isfinite(got).all()
    # bf16 q and probabilities on both sides, fp32 accumulation, but the
    # kernel's online softmax sums pages in another order: a few bf16
    # steps (2**-8) of outputs that are O(1)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_kernel_fp32_small_page_on_chip():
    """The interpret-mode test's own shape (fp32, page 8, kvh 2)."""
    args = _case(4, 2, ps=8, M=3, dtype=jnp.float32, quant=False, b=3,
                 npages=16)
    got = np.asarray(jax.jit(_kernel)(*args))
    want = np.asarray(jax.jit(_gather)(*args))
    # fp32 operands run the MXU at its default (bf16-pass) precision on
    # both sides
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_latent_kernel_matches_gather_path_on_chip():
    """``paged_latent_attention_decode`` at the widths of
    ``openpangu_ultra_moe_serve`` (128 heads against rows of 576 values
    padded to 640, V the first 512, page 16, tables of 257 pages) and
    ragged lengths: one row, around a page boundary, around a group of 8
    pages, a full table."""
    rng = np.random.default_rng(1)
    b, h, w, vw, ps, M = 8, 128, 640, 512, 16, 257
    pool = jnp.asarray(rng.standard_normal((b * M + 1, ps, w)) * 0.5,
                       jnp.bfloat16).at[..., 576:].set(0)
    q = jnp.asarray(rng.standard_normal((b, 1, h, w)), jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(b * M).reshape(b, M), jnp.int32)
    lens = jnp.asarray([0, ps - 1, ps, 8 * ps - 1, 8 * ps, 1500,
                        ps * M - 2, ps * M - 1], jnp.int32)
    assert latent_kernel_applicable(q.shape, pool.shape, vw)
    scale = 192 ** -0.5
    got = np.asarray(jax.jit(
        lambda q, p, t, n: paged_latent_attention_tpu(q, p, t, n, vw, scale)
    )(q, pool, tables, lens).astype(jnp.float32))
    want = np.asarray(jax.jit(
        lambda q, p, t, n: _latent_attend(
            q, p[t].reshape(b, -1, w), n, vw, scale)
    )(q, pool, tables, lens))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_latent_rows_kernel_matches_gather_path_on_chip():
    """``paged_latent_attention_rows`` at the widths of
    ``openpangu_ultra_moe_serve``'s mixed program (64 rows x 128 heads a
    slot, rows of 640, V the first 512, page 16, tables of 257 pages)
    against ``_latent_attend`` over the gathered table: whole chunks
    that start on and off a page and a group of pages, a chunk's short
    tail, a decode lane (one live row), an inactive slot, a full table;
    table entries past a slot's live pages point at page 0. Live rows
    agree as the decode kernel's do, dead rows are exactly zero."""
    rng = np.random.default_rng(2)
    b, t, h, w, vw, ps, M = 8, 64, 128, 640, 512, 16, 257
    pool = jnp.asarray(rng.standard_normal((b * M + 1, ps, w)) * 0.5,
                       jnp.bfloat16).at[..., 576:].set(0)
    q = jnp.asarray(rng.standard_normal((b, t, h, w)), jnp.bfloat16)
    lens = np.array([0, 8 * ps - 3, 1000, 1500, 2047, 777,
                     ps * M - t, ps * M - 1], np.int32)
    live = np.array([t, t, 37, 1, 9, 0, t, 1], np.int32)
    tables = 1 + rng.permutation(b * M).reshape(b, M).astype(np.int32)
    for s in range(b):
        tables[s, (lens[s] + max(live[s], 1) - 1) // ps + 1:] = 0
    assert latent_kernel_applicable(q.shape, pool.shape, vw)
    scale = 192 ** -0.5
    got = np.asarray(jax.jit(
        lambda q, p, tb, n, nl: paged_latent_attention_tpu(
            q, p, tb, n, vw, scale, nl)
    )(q, pool, tables, lens, live).astype(jnp.float32))
    want = np.asarray(jax.jit(
        lambda q, p, tb, n: _latent_attend(
            q, p[tb].reshape(b, -1, w), n, vw, scale)
    )(q, pool, tables, lens))
    assert np.isfinite(got).all()
    rows = np.arange(t)[None, :] < live[:, None]
    assert not got[~rows].any()
    np.testing.assert_allclose(got[rows], want[rows], rtol=2e-2, atol=2e-2)


def test_report_kernel_and_gather_host_times():
    """Not a gate on speed: prints the host-clock medians of both routes
    at the smoke's serving shape, so the first on-chip reading of the
    kernel is on record (read it with ``pytest -s``)."""
    report = {"device_kind": jax.devices()[0].device_kind}
    for name, quant in (("bf16", False), ("int8", True)):
        args = _case(32, 8, ps=16, M=66, dtype=jnp.bfloat16, quant=quant)
        args = args[:4] + (jnp.full((8,), 16 * 66 - 1, jnp.int32),)
        for route, fn in (("kernel", jax.jit(_kernel)),
                          ("gather", jax.jit(_gather))):
            jax.block_until_ready(fn(*args))
            times = []
            for _ in range(30):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                times.append(time.perf_counter() - t0)
            report[f"{name}_{route}_host_s_median"] = sorted(times)[15]
    print("\nPAGED_ATTENTION_HOST_TIMES " + json.dumps(report))
