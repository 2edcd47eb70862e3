"""The ``nemotron_h`` family in the harness, at a tiny size on the CPU
(run by hand with the rest of ``benchmarks/tests``): weights and
reference agree leaf for leaf, a rehearsal of the cell, the counts, and
the control and both planted faults through the same ``_verdict``.

The faults and the control are read on the float32 toy, as
``test_control.py`` reads the dense decoder's: at a toy width bfloat16's
own noise is as large as what is to be seen."""

import contextlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import counts_nemotron_h as counts
from benchmarks import run
from benchmarks import weights as W
from benchmarks.families import nemotron_h as fam
from benchmarks.reference import nemotron_h as ref
from benchmarks.tests import faults_nemotron_h as faults
from benchmarks.tests.conftest import DATA, ROOT

BENCH = os.path.join(DATA, "tiny_bench_nemotron_h.json")
# set from three seeds on the CPU (PR 29): the float32 program reads
# max_gap 0.0 (one seed 6e-8), mean_gap 0.0; the bf16 control max_gap
# 1.7e-3, mean_gap 7.7e-6
LIMITS_F32 = {"max_gap": 2e-4, "mean_gap": 2e-6}


def run_tiny(workload, seed=2 ** 31 + 11, seconds=2.0, trace=False,
             control=False, limits=None, fault=None):
    bench, cell, cfg, spec = run.load_cell(workload, BENCH)
    if limits is not None:
        cfg["limits"] = limits
    with (faults.FAULTS[fault]() if fault else contextlib.nullcontext()):
        return run.run_cell(bench, cell, cfg, spec, seed, seconds, trace,
                            require_tpu=False, control=control)


def _tiny(name="tiny_nemotron_h_serve.json"):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_weights_and_reference_agree_leaf_for_leaf():
    cfg, seed = _tiny(), 2 ** 31 + 9
    model = fam.build_model(cfg, W.make_weights(
        seed, fam.param_shapes(cfg), jnp.bfloat16))
    sd = model.state_dict()
    for k, shp in fam.param_shapes(cfg).items():
        assert np.array_equal(np.asarray(sd[k], np.float32),
                              np.asarray(ref._leaf(seed, k, shp, cfg))), k


def test_reference_logits_match_the_program():
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


def test_serving_cell_rehearsal():
    r = run_tiny("tiny_nemotron_h_serve.decode")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 4
    assert set(r["metrics"]) == {"serve_out_tokens_per_s", "itl_p95_ms",
                                 "ttft_p95_ms", "setup_s"}
    assert r["compared"]["max_gap"]["value"] <= 0.05
    assert r["notes"]["served_requests"] >= 4


def test_serving_cell_traced_rehearsal_reads_the_new_counters():
    r = run_tiny("tiny_nemotron_h_serve.decode", seconds=1.5, trace=True)
    assert r["correct"]
    m = r["metrics"]
    assert {"decode_step_ms", "mixed_step_ms", "nemotron_h_serve_step_mfu",
            "nemotron_h_decode_step_mbu", "expert_rows_held_share",
            "experts_touched_share", "engine_state_ms_per_step"} <= set(m)
    assert "nemotron_h_paged_attention_roofline" not in m    # no device plane
    # 4 of 8 experts held: half of the assignments, give or take the
    # toy's uneven router
    assert 35 < m["expert_rows_held_share"]["value"] < 65
    assert 0 < m["experts_touched_share"]["value"] <= 100


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_comes_out_not_correct(fault):
    r = run_tiny("tiny_nemotron_h_serve_f32.decode_long", seconds=8.0,
                 limits=LIMITS_F32, fault=fault)
    assert not r["correct"], r["compared"]
    assert any(c["value"] > c["limit"] for k, c in r["compared"].items()
               if k in LIMITS_F32)


def test_control_fails_and_program_passes():
    r = run_tiny("tiny_nemotron_h_serve_f32.decode_long", seconds=8.0,
                 control=True, limits=LIMITS_F32)
    assert r["correct"], r["compared"]
    assert r["notes"]["control_correct"] is False
    assert any(v["value"] > 3 * v["limit"]
               for v in r["notes"]["control"].values())


def test_counts_reproduce_the_published_size():
    with open(os.path.join(
            ROOT, "benchmarks/configs/nemotron3_super_serve.json")) as f:
        cfg = json.load(f)
    assert abs(counts.total_params(cfg) - 4.648e9) < 1e6
    whole = dict(cfg["published"], experts_held=[0, 512])
    assert abs(counts.total_params(whole) - 120.67e9) < 1e7
    # a token touches 22 experts a layer: "A12B"
    active = dict(whole, experts_held=[0, 22])
    assert abs(counts.total_params(active) - 12.8e9) < 0.1e9
    assert counts.state_bytes_per_slot(cfg) == 5 * (
        128 * 64 * 128 * 4 + 10240 * 3 * 2)
    assert abs(counts.held_experts_per_row(cfg) - 5.5) < 1e-9
    assert abs(counts.experts_touched_expected(cfg, 64) - 0.94) < 0.005
    for key, value in cfg["published"].items():     # no width is cut
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key


def test_benchmark_json_names_the_new_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "nemotron3_super_serve.decode_c64"
    assert cell in [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        if m["name"] in ("serve_step_mfu", "paged_attention_decode_roofline"):
            assert cell not in m["workloads"]      # a dense decoder's counts
