"""The reduction from a trace to numbers, on one small trace recorded on
a TPU v5 lite chip (three calls of a jitted function holding the flash
forward kernel and a matmul, each under a ``bench.step`` span)."""

import os

import pytest

from benchmarks import trace
from benchmarks.tests.conftest import DATA

TINY = os.path.join(DATA, "tiny_v5e.xplane.pb")


def test_union_and_gaps():
    busy, gaps = trace.union_seconds([(0, 10), (5, 20), (30, 40), (32, 35)])
    assert busy == pytest.approx(30e-9)
    assert gaps == [(20, 30)]
    assert trace.union_seconds([]) == (0.0, [])


def test_parse_op_and_kernel_names():
    p = trace.parse_op(
        '%jvp_flash_attention_fwd_.3 = (bf16[32,4096,128]{2,1,0:T(8,128)(2,1)'
        'S(1)}, f32[32,4096,1]{2,1,0:T(8,128)}) custom-call(bf16[32,4096,128]'
        '{2,1,0} %x), custom_call_target="tpu_custom_call"')
    assert p["base"] == "jvp_flash_attention_fwd" and p["pallas"]
    assert p["opcode"] == "custom-call" and p["shape"] == "bf16[32,4096,128]"
    assert trace.kernel_matches(p["base"], "flash_attention_fwd")
    assert not trace.kernel_matches("flash_attention_bwd_dq",
                                    "flash_attention_fwd")
    f = trace.parse_op("%fusion.447 = bf16[4096,32768]{1,0:T(8,128)(2,1)} "
                       "fusion(f32[]{:T(128)S(6)} %sub.182), kind=kOutput, "
                       "calls=%fused_computation.620")
    assert (f["instr"], f["opcode"], f["fusion_kind"], f["pallas"]) == \
        ("fusion.447", "fusion", "kOutput", False)


def test_reduce_recorded_trace():
    r = trace.reduce_trace(TINY)
    assert len(r["chips"]) == 1 and r["chips"][0]["n_ops"] == 18
    assert r["chips"][0]["busy_s"] == pytest.approx(38.234e-6, rel=1e-3)
    n, seconds = trace.kernel_totals(r, "flash_attention_fwd")
    assert n == 3 and seconds == pytest.approx(26.813e-6, rel=1e-3)
    assert trace.kernel_totals(r, "paged_attention_decode") == (0, 0.0)
    assert [n_ for n_, _ in r["host_spans"]] == ["bench.step"] * 3
    b = trace.breakdown(r)
    assert b["device_ops"][0][0] == ("jit_f/flash_attention_fwd "
                                     "bf16[4,512,128] x3 (e.g. flash_attention_fwd.1)")
    assert b["device_ops"][0][1] == pytest.approx(seconds)
    # the gaps between the three calls fall under the harness's span
    assert b["idle_gaps"][0][0].startswith("bench.step/")
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
