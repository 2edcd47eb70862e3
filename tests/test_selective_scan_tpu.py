"""The Mamba-1 selective scan and multi-query paged attention ON THE CHIP
— TPU-only; tools/run_tpu_checks.py runs this file there. On the CPU
suite every test skips from a fixture (interpret-mode parity of the scan
kernel lives in tests/test_jamba.py; tests/test_tpu_compile.py keeps the
compiles for a described chip).

What only the chip can show: that the Mosaic-compiled
``selective_scan_rows`` computes what the ``lax.scan`` form computes at
the shapes of ``jamba2_3b_serve``'s mixed program (64 slots x 64 rows x
5120 channels, state ``[64, 16, 5120]``), what a call costs with the
cell's some 160 live rows and with all 4096, what the fused XLA
``selective_scan_step`` of the decode program costs against its byte
floor, and that the paged decode kernel at 20 query heads on ONE K/V
head agrees with the XLA gather path. Each timing is printed as one
JSON line (``-s``) and written to ``chiprun_out/``.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional import ssm
from paddle_tpu.nn.functional.attention import _grouped_decode_attn
from paddle_tpu.ops.pallas import paged_attention, selective_scan

B, K, D, N = 64, 64, 5120, 16
HBM_BYTES_PER_S = 819e9       # benchmarks/peaks.py, v5e


@pytest.fixture(autouse=True)
def _needs_tpu():
    if jax.default_backend() != "tpu":
        pytest.skip("on-chip kernel parity needs a TPU")


def _report(line: dict):
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/selective_scan_tpu.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    return dict(
        x=jnp.asarray(rng.standard_normal((B, K, D)), f32),
        dt=jnp.asarray(rng.uniform(0.001, 0.1, (B, K, D)), f32),
        A=-jnp.asarray(rng.uniform(1, 16, (D, N)), f32),
        B=jnp.asarray(rng.standard_normal((B, K, N)), f32),
        C=jnp.asarray(rng.standard_normal((B, K, N)), f32),
        D=jnp.asarray(rng.standard_normal((D,)), f32),
        state=jnp.asarray(rng.standard_normal((B, N, D)), f32))


def _cell_lanes():
    """What a mixed step of the cell holds: 2 chunk lanes of 64 rows and
    one of 36, 58 decode lanes of one row, 3 idle slots."""
    n_live = np.ones((B,), np.int32)
    n_live[[5, 40]] = 64
    n_live[17] = 36
    n_live[[0, 31, 63]] = 0
    return jnp.asarray(n_live)


def _time(fn, *args, repeats=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


@pytest.mark.parametrize("lanes", ["cell", "all_live", "none_live"])
def test_kernel_matches_the_scan_on_chip(lanes):
    a = _inputs()
    n_live = {"cell": _cell_lanes(), "all_live": jnp.full((B,), K, jnp.int32),
              "none_live": jnp.zeros((B,), jnp.int32)}[lanes]
    assert selective_scan.kernel_applicable(a["x"].shape, a["state"].shape)
    kern = jax.jit(selective_scan.selective_scan_tpu)
    scan = jax.jit(ssm._selective_scan_rows_xla)
    y, h = kern(**a, n_live=n_live)
    want_y, want_h = scan(**a, n_live=n_live)
    y, h, want_y, want_h = (np.asarray(v) for v in (y, h, want_y, want_h))
    # both float32, the same order of operations but for the sum over
    # the 16 state indices and the compiler's fused multiply-adds
    assert np.abs(y - want_y).max() < 1e-3 * max(1.0, np.abs(want_y).max())
    assert np.abs(h - want_h).max() < 1e-3 * np.abs(want_h).max()
    live = np.asarray(n_live)
    for s in np.flatnonzero(live < K):
        assert not y[s, live[s]:].any()
    for s in np.flatnonzero(live == 0):
        assert np.array_equal(h[s], np.asarray(a["state"][s]))
    seconds = _time(lambda: kern(**a, n_live=n_live))
    rows = int(live.sum())
    _report({"check": "selective_scan_rows", "lanes": lanes,
             "rows_live": rows, "slots_live": int((live > 0).sum()),
             "ms_per_call": 1e3 * seconds,
             "scan_xla_ms_per_call": 1e3 * _time(
                 lambda: scan(**a, n_live=n_live), repeats=2)})


def test_the_decode_program_s_step_against_its_byte_floor():
    """XLA's fused ``selective_scan_step`` over 64 slots, the state
    donated as the decode program donates it: one read and one write of
    ``[64, 16, 5120]`` float32 is the floor. (Alone it reads a sixth of
    the floor; a call alone is not a call inside the program: PERF.md,
    PR 37.)"""
    a = _inputs(1)
    step = jax.jit(ssm.selective_scan_step, donate_argnums=(6,))
    args = (a["x"][:, 0], a["dt"][:, 0], a["A"], a["B"][:, 0], a["C"][:, 0],
            a["D"])
    y, state = step(*args, a["state"])
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(50):
        y, state = step(*args, state)
    jax.block_until_ready(state)
    seconds = (time.perf_counter() - t0) / 50
    floor = 2 * B * N * D * 4 / HBM_BYTES_PER_S
    _report({"check": "selective_scan_step", "ms_per_call": 1e3 * seconds,
             "floor_ms": 1e3 * floor, "share_of_floor": floor / seconds})
    assert np.isfinite(np.asarray(y)).all()


def test_twenty_query_heads_on_one_kv_head_match_the_gather_path():
    rng = np.random.default_rng(2)
    b, h, d, ps, M, npages = 64, 20, 128, 16, 129, 8257
    bf = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), bf)
    pk = jnp.asarray(rng.standard_normal((npages, ps, 1, d)), bf)
    pv = jnp.asarray(rng.standard_normal((npages, ps, 1, d)), bf)
    tables = jnp.asarray(rng.permutation(np.arange(1, npages))[:b * M]
                         .reshape(b, M), jnp.int32)
    lens = jnp.asarray(rng.integers(0, ps * M - 1, b), jnp.int32)
    lens = lens.at[:4].set(jnp.asarray([0, 1, ps - 1, ps * M - 1]))
    assert paged_attention.kernel_applicable(q.shape, pk.shape)
    kern = jax.jit(paged_attention.paged_attention_tpu)

    def gather(q, pk, pv, tables, lens):
        g = lambda pool: pool[tables].reshape(b, -1, 1, d)
        return _grouped_decode_attn(q, g(pk), g(pv), lens, 1.0 / np.sqrt(d))

    got = np.asarray(kern(q, pk, pv, tables, lens).astype(jnp.float32))
    want = np.asarray(jax.jit(gather)(q, pk, pv, tables, lens)
                      .astype(jnp.float32))
    assert np.abs(got - want).max() < 2e-2
    _report({"check": "paged_attention_decode_20_on_1",
             "ms_per_call": 1e3 * _time(kern, q, pk, pv, tables, lens),
             "live_kv_bytes": int(2 * 2 * d * (np.asarray(lens) + 1).sum())})
