"""The control has to come out as not correct: the reference put in the
program's place and computed in the next precision down. On the chip the
cells' controls are int8 (serving) and fp8 (training) against bf16 cells
(PERF.md section 2 has those readings); a CPU test cannot hold those
sizes, and at a toy width bf16's own noise is as large as int8's. So the
test keeps the same rule one step up, where a toy size separates it: a
float32 configuration of the same family, whose control is bfloat16."""

from benchmarks.tests.conftest import run_tiny

# set from three seeds on the CPU (PR 26): the float32 program reads
# max_gap 0.0 and 1e-7 on the training numbers; the bf16 control reads
# max_gap 0.0027, mean_gap 1.9e-5, grad_norm_gap 1.3e-3, loss_gap 7.5e-5
LIMITS_SERVE = {"max_gap": 2e-4, "mean_gap": 2e-6}
LIMITS_TRAIN = {"loss_gap": 5e-6, "grad_norm_gap": 5e-5,
                "delta_norm_gap": 2e-5}


def test_serving_control_fails_and_program_passes():
    # some 500 served tokens: at a flip rate near 2% under bfloat16, none
    # flipping is a one-in-some-thousands event
    r = run_tiny("tiny_serve_f32.decode_long", seconds=10.0, control=True,
                 limits=LIMITS_SERVE)
    assert r["correct"], r["compared"]
    n = r["notes"]
    # the control went through the harness's own verdict, and failed it
    assert n["control_correct"] is False
    c = n["control"]
    assert set(c) == set(LIMITS_SERVE)
    assert all(v["limit"] == LIMITS_SERVE[k] for k, v in c.items())
    assert any(v["value"] > 3 * v["limit"] for v in c.values())


def test_training_control_and_fault_fail_and_program_passes():
    r = run_tiny("tiny_train_f32.seq", seconds=1.0, control=True,
                 limits=LIMITS_TRAIN)
    assert r["correct"], r["compared"]
    n = r["notes"]
    assert n["control_correct"] is False and n["fault_correct"] is False
    control, fault = n["control"], n["fault_half_batch"]
    assert set(control) == set(fault) == set(LIMITS_TRAIN)
    assert any(v["value"] > 3 * v["limit"] for v in control.values())
    g = fault["grad_norm_gap"]
    assert g["value"] > 10 * g["limit"]


def test_verdict_needs_every_number_under_a_limit_of_its_own():
    from benchmarks.check import _verdict

    limits = {"a": 1.0, "b": 2.0}
    assert _verdict({"a": 1.0, "b": 0.5}, limits)[0]
    assert not _verdict({"a": 1.1, "b": 0.5}, limits)[0]      # over
    assert not _verdict({"a": 0.1, "c": 0.0}, limits)[0]      # no limit
    assert not _verdict({"a": float("nan")}, limits)[0]
    assert not _verdict({}, limits)[0]                        # nothing read
    assert _verdict({"a": 3.0}, limits)[1] == {
        "a": {"value": 3.0, "limit": 1.0}}
