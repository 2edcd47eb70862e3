"""The ``jamba`` family in the harness, at a tiny size on the CPU (run by
hand with the rest of ``benchmarks/tests``; tier-1 collects it through
``tests/test_bench_jamba.py``): weights and reference agree, a rehearsal
of the cell, the counts, and the control and both planted faults through
the same ``_verdict``.

The faults and the control are read on the float32 toy, as
``test_control.py`` reads the dense decoder's: at a toy width bfloat16's
own noise is as large as what is to be seen."""

import contextlib
import json
import os
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import counts_jamba as counts
from benchmarks import run
from benchmarks import weights as W
from benchmarks.families import jamba as fam
from benchmarks.reference import jamba as ref
from benchmarks.tests import faults_jamba as faults
from benchmarks.tests.conftest import DATA, ROOT

BENCH = os.path.join(DATA, "tiny_bench_jamba.json")
CELL = "jamba2_3b_serve.decode_c64"
# set from three seeds on the CPU (PR 37): the float32 program reads
# max_gap 0.0, mean_gap 0.0; the bfloat16 control max_gap 2.3e-3 to
# 3.6e-3, mean_gap 6.4e-6 to 1.0e-5; "state_not_cleared" 0.41 and 0.0106,
# "norms_left_out" 1.10 and 0.0624
LIMITS_F32 = {"max_gap": 2e-4, "mean_gap": 2e-6}


def run_tiny(workload, seed=2 ** 31 + 11, seconds=2.0, trace=False,
             control=False, limits=None, fault=None):
    bench, cell, cfg, spec = run.load_cell(workload, BENCH)
    if limits is not None:
        cfg["limits"] = limits
    with (faults.FAULTS[fault]() if fault else contextlib.nullcontext()):
        return run.run_cell(bench, cell, cfg, spec, seed, seconds, trace,
                            require_tpu=False, control=control)


def _published():
    with open(os.path.join(ROOT, "benchmarks/configs/jamba2_3b_serve.json")) as f:
        return json.load(f)


def _tiny(name="tiny_jamba_serve.json"):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_the_published_config_is_whole():
    cfg = _published()
    shapes = fam.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 3_029_337_472
    assert counts.total_params(cfg) == 3_029_337_472
    assert "lm_head.weight" not in shapes
    assert fam.layer_kinds(cfg) == "MMMMMMM*MMMMMMMMMMMMM*MMMMMM"
    mamba = sum(int(np.prod(s)) for k, s in
                fam.layer_shapes(cfg, fam.MAMBA).items())
    attn = sum(int(np.prod(s)) for k, s in
               fam.layer_shapes(cfg, fam.ATTENTION).items())
    assert (mamba, attn) == (104_161_472, 76_682_240)
    assert cfg["reduced"] == []
    for key, value in cfg["published"].items():     # nothing is cut
        assert cfg[key] == value, key
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["jamba2_3b_serve"]
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]


def test_derived_leaves_give_decays_of_a_trained_model():
    cfg, seed = _published(), 2 ** 31 + 9
    d, n = 2 * cfg["hidden_size"], cfg["mamba_d_state"]
    a = -np.exp(np.asarray(ref._leaf(
        seed, "model.layers.0.mamba.A_log", (d, n), cfg)))
    assert np.allclose(a, -np.arange(1, n + 1)[None, :], rtol=1e-6)
    bias = np.asarray(ref._leaf(
        seed, "model.layers.0.mamba.dt_proj.bias", (d,), cfg), np.float64)
    dt = np.log1p(np.exp(bias))
    # log-spread over 0.001-0.1 (bfloat16's rounding of the bias moves
    # an end by under a percent)
    assert 0.00099 < dt.min() < 0.0011 and 0.09 < dt.max() < 0.101
    assert 0.4 < np.mean(dt < 0.01) < 0.6
    decay = np.exp(dt[:, None] * a)
    assert decay.min() > 0.2 and decay.max() < 0.9991
    conv = np.asarray(ref._leaf(
        seed, "model.layers.0.mamba.conv1d_weight", (d, 4), cfg))
    assert 0.3 < conv.std() < 0.34                  # noise x 16


def test_weights_and_reference_agree_leaf_for_leaf():
    cfg, seed = _tiny(), 2 ** 31 + 9
    model = fam.build_model(cfg, W.make_weights(
        seed, fam.param_shapes(cfg), jnp.bfloat16))
    sd = model.state_dict()
    assert set(sd) == set(fam.param_shapes(cfg))
    for k, shp in fam.param_shapes(cfg).items():
        assert np.array_equal(np.asarray(sd[k], np.float32),
                              np.asarray(ref._leaf(seed, k, shp, cfg))), k


def test_reference_logits_match_the_bf16_program():
    cfg, seed = _tiny(), 2 ** 31 + 9
    model = fam.build_model(cfg, W.make_weights(
        seed, fam.param_shapes(cfg), jnp.bfloat16))
    model.eval()
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 48))
    got = np.asarray(model(jnp.asarray(ids, jnp.int32)).astype(jnp.float32))[0]
    want = ref.logits_rows(seed, cfg, [ids[0].tolist()], [0])[0]
    # bf16 program against the float32 reference
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max()
    low = ref.logits_rows(seed, cfg, [ids[0].tolist()], [0],
                          precision="int8")[0]
    assert 0 < np.abs(low - want).max() < 0.2 * np.abs(want).max()


def test_counts_against_hand_counts():
    cfg = _published()
    assert counts.kinds(cfg) == {fam.MAMBA: 26, fam.ATTENTION: 2}
    assert counts.mamba_matmul_params(cfg) == (
        2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    assert counts.attention_matmul_params(cfg) == 2 * 2560 * 2560 + 2 * 2560 * 128
    assert counts.mlp_params(cfg) == 62_914_560
    assert counts.state_bytes_per_slot(cfg) == 26 * (327_680 + 30_720)
    assert counts.kv_bytes_per_token_layer(cfg) == 512
    # a token row: 2 operations a matmul parameter (the head is the
    # embedding, counted once) and 6 a (channel, state index) a scan
    matmul = (26 * counts.mamba_matmul_params(cfg)
              + 2 * counts.attention_matmul_params(cfg)
              + 28 * counts.mlp_params(cfg) + 2560 * 65536)
    assert counts.row_flops(cfg) == 2 * matmul + 26 * 6 * 5120 * 16
    assert abs(counts.row_flops(cfg) - 6.065e9) < 1e6
    assert counts.forward_flops(cfg, 3, 100) == (
        3 * counts.row_flops(cfg) + 100 * 4 * 20 * 128 * 2)
    # a decode step over 64 slots of 200 tokens: 6.06 GB of weights,
    # 2 x 596 MB of state, 13 MB of K/V
    step = counts.decode_step_bytes(cfg, [200] * 64)
    assert step == (2 * 3_029_337_472 + 2 * 64 * 9_318_400
                    + 64 * 200 * 512 * 2)
    # the scan of a mixed step: 2 chunk lanes of 64 and 36 rows beside
    # 60 decode lanes; a slot's state is 327,680 bytes in and out, a
    # row's x, dt, y and B, C are float32
    mixed = SimpleNamespace(rows=160, decode_contexts=(9,) * 60)
    assert counts.mixed_step_scan_bytes(cfg, mixed, 64) == 26 * (
        2 * 62 * 327_680 + 160 * (3 * 5120 + 32) * 4)


def test_serving_cell_rehearsal():
    r = run_tiny("tiny_jamba_serve.decode")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 4
    assert set(r["metrics"]) == {"serve_out_tokens_per_s", "itl_p95_ms",
                                 "ttft_p95_ms", "setup_s"}
    assert r["compared"]["max_gap"]["value"] <= 0.1
    assert r["notes"]["served_requests"] >= 4


def test_serving_cell_traced_rehearsal_reads_the_new_metrics():
    r = run_tiny("tiny_jamba_serve.decode", seconds=1.5, trace=True)
    assert r["correct"]
    m = r["metrics"]
    assert {"decode_step_ms", "mixed_step_ms", "jamba_serve_step_mfu",
            "jamba_decode_step_mbu", "scan_rows_live_share",
            "engine_state_ms_per_step", "sampler_useful_row_share"} <= set(m)
    assert "selective_scan_roofline" not in m        # no device plane
    # 4 slots x 16 rows a mixed dispatch, a few of them live
    assert 0 < m["scan_rows_live_share"]["value"] < 100


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_comes_out_not_correct(fault):
    r = run_tiny("tiny_jamba_serve_f32.decode_long", seconds=8.0,
                 limits=LIMITS_F32, fault=fault)
    assert not r["correct"], r["compared"]
    assert any(c["value"] > c["limit"] for k, c in r["compared"].items()
               if k in LIMITS_F32)


def test_control_fails_and_program_passes():
    r = run_tiny("tiny_jamba_serve_f32.decode_long", seconds=8.0,
                 control=True, limits=LIMITS_F32)
    assert r["correct"], r["compared"]
    assert r["notes"]["control_correct"] is False
    assert any(v["value"] > 3 * v["limit"]
               for v in r["notes"]["control"].values())


def test_benchmark_json_names_the_new_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2_3b_serve", "decode_c64", 1)
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in bench[g]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"serve_out_tokens_per_s", "itl_p95_ms", "setup_s",
            "jamba_serve_step_mfu", "jamba_decode_step_mbu",
            "selective_scan_roofline", "scan_rows_live_share",
            "engine_state_ms_per_step"} <= reported
    # another family's counts
    assert not reported & {"serve_step_mfu", "nemotron_h_serve_step_mfu",
                           "paged_attention_decode_roofline",
                           "expert_rows_held_share"}
    for name in ("jamba_serve_step_mfu", "jamba_decode_step_mbu",
                 "selective_scan_roofline", "scan_rows_live_share"):
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", f"{name}.py"))
