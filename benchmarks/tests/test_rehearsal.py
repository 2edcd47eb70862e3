"""Both cells end to end at a tiny size on the CPU (everything of a run
but the look for a chip), and the command's refusal to report without a
TPU."""

import json
import os
import subprocess
import sys

from benchmarks.tests.conftest import ROOT, run_tiny


def _result_keys(r):
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(r)[-1] == "compared"     # the numbers compared come last
    for c in r["compared"].values():
        assert set(c) == {"value", "limit"}


def test_serving_cell_rehearsal():
    r = run_tiny("tiny_serve.decode", seconds=2.0)
    _result_keys(r)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 4
    assert set(r["metrics"]) == {"serve_out_tokens_per_s", "itl_p95_ms",
                                 "ttft_p95_ms", "setup_s"}
    assert r["compared"]["max_gap"]["value"] <= 0.05
    assert r["notes"]["served_requests"] >= 4     # every finished request


def test_serving_cell_traced_rehearsal():
    """A CPU trace has no device plane: the readers of the trace find
    nothing and leave their metric out; the host-clock ones report."""
    r = run_tiny("tiny_serve.decode", seconds=1.5, trace=True)
    _result_keys(r)
    assert r["correct"]
    assert {"decode_step_ms", "mixed_step_ms", "mixed_step_share",
            "serve_step_mfu"} <= set(r["metrics"])
    assert "paged_attention_decode_roofline" not in r["metrics"]
    assert "device_idle_share.serve" not in r["metrics"]
    assert 0 < r["metrics"]["mixed_step_share"]["value"] < 100
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_training_cell_rehearsal():
    r = run_tiny("tiny_train.seq", seconds=1.5)
    _result_keys(r)
    assert r["correct"] and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    c = r["compared"]
    assert c["loss_gap"]["value"] < 5e-3
    assert c["grad_norm_gap"]["value"] < 0.05
    assert c["delta_norm_gap"]["value"] < 0.05


def test_command_refuses_to_report_on_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "mistral_7b_serve.decode", "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_unknown_device_kind_is_refused():
    import pytest
    from benchmarks.peaks import UnknownDevice, peaks_for
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9")


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    from benchmarks import run, traffic
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
        mix = traffic.load(w["traffic"])
        assert run.find_driver(mix["kind"]) and mix["why"]
        if mix["kind"] == "closed_loop":     # served lengths have a source
            assert mix["source"] and mix["assumed"]
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))


def test_check_sample_is_every_finished_request_or_every_client():
    from benchmarks.drivers.closed_loop import Req, check_sample

    def req(client, k, n_prompt, n_out, t_end, reason="length"):
        return Req(client, k, [1] * n_prompt, n_out, 0.0,
                   tokens=[2] * n_out, t_tokens=[t_end] * n_out,
                   reason=reason)

    done = [req(c, k, 10 + c, 5 + k, 1.0 + k) for c in range(4)
            for k in range(3)]
    done.append(req(0, 9, 10, 5, 99.0))           # finished after the close
    done.append(req(1, 9, 10, 5, 2.0, "error"))   # did not finish by length
    rec = {"t_open": 0.0, "t_last": 10.0, "done": done}
    every = check_sample(rec, {"check_requests": 12}, 7)
    assert len(every) == 12 and all(r.k < 3 for r in every)
    some = check_sample(rec, {"check_requests": 5}, 7)
    assert len(some) == 5 and {r.client for r in some} == {0, 1, 2, 3}
    assert some[0].client == 3 and some[0].k == 2            # the longest
    assert some == check_sample(rec, {"check_requests": 5}, 7)
