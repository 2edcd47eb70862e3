"""chip_smoke.py rehearsed on the CPU: the SAME phase functions the chip
run calls, at a tiny size (rehearsals 1 and 2 of the on-chip-measurement
guide kept as tests), and the contract a machine without a TPU must see.

Nothing here says the system runs on a chip — only ``python chip_smoke.py``
there does. What it guards: the phases' control flow, arguments and
checks keep working, so a chip call is not spent finding a typo.
"""

import json
import pathlib
import subprocess
import sys

import pytest

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

import chip_smoke  # noqa: E402

# llama_tiny widths with 8/4 heads (tp=4 needs kv heads % 4 == 0)
TINY = chip_smoke.Sizes(
    preset="llama_tiny",
    widths={"num_attention_heads": 8, "num_key_value_heads": 4},
    train_layers=2, train_seq=64, train_steps=4, ref_seq=32,
    serve_layers=2, num_pages=64, page_size=8, max_slots=4,
    prompt_lens=(5, 9, 17, 30), max_new=6, compare=(0, 3), int8_prompt=12,
    mc_layers=2, mc_prompt_lens=(5, 12), mc_max_new=4, mc_train_seq=32)


def test_default_sizes_are_llama_3_8b_widths_cut_in_depth_only():
    from paddle_tpu.models.llama import llama_3_8b
    sz = chip_smoke.Sizes()
    cfg = chip_smoke._llama_config(sz, sz.serve_layers)
    want = llama_3_8b(dtype="bfloat16")
    for f in ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "vocab_size", "rope_theta",
              "max_position_embeddings"):
        assert getattr(cfg, f) == getattr(want, f), f
    assert (cfg.num_hidden_layers, sz.train_layers) == (16, 2)
    assert not sz.widths


def test_train_phase_rehearsal():
    r = chip_smoke.train_phase(TINY, seed=0)
    chip_smoke.check_train(r, on_chip=False)
    assert len(r["losses"]) == 4
    # on the CPU the kernels run interpreted: none is in the program, and
    # the on-chip check refuses exactly that
    assert r["kernels_in_step"] == {}
    with pytest.raises(chip_smoke.SmokeFailure, match="flash_attention_fwd"):
        chip_smoke.check_train(r, on_chip=True)


def test_serve_phase_rehearsal():
    r = chip_smoke.serve_phase(TINY, seed=0)
    chip_smoke.check_serve(r, TINY, on_chip=False)
    assert r["bf16"]["finished"] == 4 and r["int8"]["finished"] == 1
    # fp32-exact on the CPU: the gather path IS generate()'s core
    assert all(s["identical_to_generate"]
               for s in r["bf16"]["streams_vs_generate"])
    with pytest.raises(chip_smoke.SmokeFailure, match="paged-attention"):
        chip_smoke.check_serve(r, TINY, on_chip=True)
    # a stream that left the reference's tolerance fails the phase
    r["bf16"]["streams_vs_generate"][0]["gap_ok"] = False
    with pytest.raises(chip_smoke.SmokeFailure, match="req-0"):
        chip_smoke.check_serve(r, TINY, on_chip=False)


def test_multichip_phase_rehearsal_on_virtual_devices():
    r = chip_smoke.multichip_phase(TINY, seed=0)
    chip_smoke.check_multichip(r, TINY, on_chip=False)
    for name in ("tp4", "pp2_tp2"):
        assert r[name]["streams_identical_to_one_chip"] is True
        assert r[name]["kv_bytes_per_token_shard"] * 4 == \
            r["one_chip"]["kv_bytes_per_token_shard"]


def test_kernel_counts_reads_names_through_autodiff_wrappers():
    text = "\n".join(
        f'  %k{i} = f32[8] custom-call(), custom_call_target='
        f'"tpu_custom_call", metadata={{op_name="{op}"}}'
        for i, op in enumerate((
            "jit(step)/jvp(fused_rms_norm)/pallas_call",
            "jit(step)/transpose(jvp(flash_attention_bwd_dq))/pallas_call",
            "jit(decode_step)/paged_attention_decode/pallas_call",
            "jit(decode_step)/paged_attention_decode/pallas_call")))
    text += '\n  %x = f32[8] custom-call(), custom_call_target="Sharding"'
    assert chip_smoke.kernel_counts(text) == {
        "fused_rms_norm": 1, "flash_attention_bwd_dq": 1,
        "paged_attention_decode": 2}


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_without_a_tpu_it_fails_and_prints_no_result(argv):
    out = subprocess.run([sys.executable, str(_REPO / "chip_smoke.py"),
                          *argv], capture_output=True, text=True,
                         timeout=300, cwd=_REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_last_line_shape(monkeypatch, capsys):
    """The one line the driver reads, with the phases stubbed out and a
    device that says it is a TPU."""
    import jax

    class FakeTpu:
        platform, device_kind, id = "tpu", "TPU v5 lite", 0

        def memory_stats(self):
            return {"bytes_in_use": 1, "peak_bytes_in_use": 2}

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setattr(chip_smoke, "train_phase", lambda sz, seed: {})
    monkeypatch.setattr(chip_smoke, "serve_phase", lambda sz, seed: {})
    monkeypatch.setattr(chip_smoke, "check_train", lambda *a, **k: None)
    monkeypatch.setattr(chip_smoke, "check_serve", lambda *a, **k: None)
    import paddle_tpu.utils.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off-in-test")
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
