"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip: train phase, serve phase
    python chip_smoke.py --chips 4  # four chips: only the cross-chip phase

One process; it touches JAX itself and starts no child that needs the
chip. It fails (non-zero exit, no ``"ok": true``) unless
``jax.devices()[0].platform == "tpu"``, and every phase fails the run by
raising: nothing is caught and carried past.

Default run, through the public entry points only, at the widths of
``models.llama.llama_3_8b`` (hidden 4096, ffn 14336, heads 32/8, vocab
128256, rope_theta 500000) cut in depth, weights random from ``--seed``:

- ``train``: 2 layers, tied embedding, remat, seq 4096, batch 1, bf16
  params, fp32 AdamW (the ``bench_llama8b_shape`` cut) through
  ``pt.optimizer.AdamW`` + ``pt.jit.TrainStep``; four steps on one
  repeated batch; the flash and fused-norm kernels must be in the
  compiled step; a short slice is compared with plain XLA attention.
- ``serve``: 16 layers bf16 through ``ServingEngine`` (page 16, 8
  slots): ``warm_programs()``, 8 ragged requests fed by ``add_request``
  while ``step()`` runs, then one request through the int8 arm. The
  paged-attention kernel must be in both decode programs; two streams
  are compared with ``model.generate()`` and with the model's own
  contiguous-cache logits (see ``_check_stream``).

Every earlier line is one JSON object with a ``"phase"`` key, printed as
its part finishes; times are host-clock seconds around
``block_until_ready``, nothing else. The last
line is ``{"ok": true, "device": {...}}`` and nothing else goes in it.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import re
import statistics
import sys
import time


class SmokeFailure(RuntimeError):
    """A phase found a wrong result (the run exits non-zero)."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke runs. The defaults ARE the smoke; the CPU rehearsal
    in tests/test_chip_smoke.py passes a tiny instance to the same phase
    functions."""

    preset: str = "llama_3_8b"          # models.llama factory: the widths
    widths: dict = dataclasses.field(default_factory=dict)  # rehearsal only
    train_layers: int = 2
    train_seq: int = 4096
    train_steps: int = 4
    ref_seq: int = 512                  # flash-vs-XLA slice (>= flash_min_seq)
    serve_layers: int = 16
    num_pages: int = 1024               # x 16 tokens x 64 KB = 1 GiB bf16
    page_size: int = 16
    max_slots: int = 8
    prompt_lens: tuple = (64, 150, 257, 384, 530, 700, 901, 1024)
    max_new: int = 32
    compare: tuple = (0, 7)             # requests checked against generate()
    arrive_every: int = 2               # engine steps between arrivals
    int8_prompt: int = 300
    # --chips 4
    mc_layers: int = 8
    mc_prompt_lens: tuple = (64, 200, 333, 512)
    mc_max_new: int = 16
    mc_train_seq: int = 2048

    def max_pages_per_slot(self, lens=None, new=None) -> int:
        lens = self.prompt_lens if lens is None else lens
        new = self.max_new if new is None else new
        return -(-(max(lens) + new) // self.page_size) + 1


def emit(phase: str, **fields) -> None:
    """One JSON line, printed as soon as its part of a phase is done (a
    run that dies later has still said how far it got)."""
    print(json.dumps({"phase": phase, **fields}), flush=True)


def kernel_counts(program_text: str) -> dict[str, int]:
    """Pallas kernels in a compiled program, by the pallas_call's name:
    every ``tpu_custom_call`` line carries its scope in ``op_name``, bare
    (``.../paged_attention_decode/pallas_call``) or inside autodiff
    wrappers (``.../transpose(jvp(flash_attention_bwd_dq))/pallas_call``)."""
    counts: dict[str, int] = {}
    for line in program_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'([A-Za-z_]\w*)\)*/pallas_call', line)
        name = m.group(1) if m else "unnamed"
        counts[name] = counts.get(name, 0) + 1
    return counts


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _memory(devices) -> list[dict]:
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


def _release() -> None:
    """Drop a finished phase's arrays and executables before the next
    one: the train phase alone fills the chip."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


def _llama_config(sz: Sizes, layers: int, **kw):
    """The preset's own widths and rope table, cut in depth only."""
    from paddle_tpu.models import llama
    base = getattr(llama, sz.preset)(dtype="bfloat16", **kw)
    return dataclasses.replace(base, num_hidden_layers=layers, **sz.widths)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _train_step(sz: Sizes, seed: int, mesh=None, **axes):
    """The ``bench_llama8b_shape`` cut — tied embedding, remat, bf16
    params, fp32 AdamW — as (model, TrainStep); under ``mesh`` the
    parameters are placed by their declared specs first."""
    import paddle_tpu as pt
    from paddle_tpu.distributed.fleet.meta_parallel import \
        apply_hybrid_shardings
    from paddle_tpu.models.llama import LlamaForCausalLM

    pt.seed(seed)
    cfg = dataclasses.replace(_llama_config(sz, sz.train_layers, **axes),
                              tie_word_embeddings=True, recompute=True)
    model = LlamaForCausalLM(cfg)
    if mesh is not None:
        apply_hybrid_shardings(model, mesh)
    opt = pt.optimizer.AdamW(learning_rate=1e-4, parameters=model)
    step = pt.jit.TrainStep(model, opt,
                            lambda logits, labels: model.loss(logits, labels))
    return model, step


def _batch(seed: int, vocab: int, seq: int):
    import jax.numpy as jnp
    import numpy as np
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (1, seq)), jnp.int32)


def train_phase(sz: Sizes, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.nn.functional as F

    model, step = _train_step(sz, seed, mp_axis=None, fsdp_axis=None)
    cfg = model.config
    ids = _batch(seed, cfg.vocab_size, sz.train_seq)

    # small-input reference BEFORE the first update: the same weights
    # through the default route (Pallas flash on a TPU) and through plain
    # XLA attention; bf16 both, so the tolerance is a few bf16 steps of
    # the largest logit
    short = ids[:, :sz.ref_seq]
    eval_step = pt.jit.EvalStep(model)
    got = np.asarray(eval_step(short).astype(jnp.float32))
    with F.sdp_kernel(enable_flash=False):
        want = np.asarray(pt.jit.EvalStep(model)(short).astype(jnp.float32))
    ref_scale = float(np.max(np.abs(want)))
    ref_err = float(np.max(np.abs(got - want)))
    ref_tol = 16 * 2.0 ** -8 * ref_scale

    # the program that runs: which kernels are in it, does it fit
    t0 = time.perf_counter()
    compiled = step.lower(ids, ids).compile()
    aot_s = time.perf_counter() - t0
    kernels = kernel_counts(compiled.as_text())
    mem = compiled.memory_analysis()

    losses, step_s = [], []
    for _ in range(sz.train_steps):
        t0 = time.perf_counter()
        loss = step(ids, ids)
        losses.append(float(jax.block_until_ready(loss)))
        step_s.append(time.perf_counter() - t0)
    result = {
        "model": {"preset": sz.preset, "layers": sz.train_layers,
                  "hidden": cfg.hidden_size, "ffn": cfg.intermediate_size,
                  "heads": f"{cfg.num_attention_heads}/"
                           f"{cfg.num_key_value_heads}",
                  "vocab": cfg.vocab_size, "seq": sz.train_seq, "batch": 1,
                  "params": model.num_params(), "tied": True, "remat": True},
        "losses": [round(v, 4) for v in losses],
        "first_step_incl_compile_host_s": round(step_s[0], 3),
        "step_host_s": [round(v, 4) for v in step_s[1:]],
        "aot_compile_host_s": round(aot_s, 3),
        "kernels_in_step": kernels,
        "attention_route": ("pallas flash (fwd, dq, dkv)"
                            if kernels.get("flash_attention_fwd")
                            else "XLA attention"),
        "program_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "aliased": mem.alias_size_in_bytes},
        "flash_vs_xla": {"seq": sz.ref_seq, "max_abs_err": ref_err,
                         "max_abs_logit": ref_scale, "tol": ref_tol},
    }
    emit("train", **result)
    del step, model, eval_step, compiled
    _release()
    return result


def check_train(r: dict, on_chip: bool) -> None:
    import math
    losses = r["losses"]
    _require(all(math.isfinite(v) for v in losses),
             f"train: non-finite loss in {losses}")
    _require(losses[-1] < losses[0],
             f"train: loss did not fall over {len(losses)} steps on one "
             f"repeated batch: {losses}")
    fx = r["flash_vs_xla"]
    _require(fx["max_abs_err"] <= fx["tol"],
             f"train: default route and XLA attention disagree on a "
             f"{fx['seq']}-token slice: {fx}")
    if on_chip:
        k = r["kernels_in_step"]
        for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                     "flash_attention_bwd_dkv", "fused_rms_norm"):
            _require(k.get(name, 0) > 0,
                     f"train: Pallas kernel {name} is not in the compiled "
                     f"step (found {k})")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _prompts(rng, vocab: int, lens) -> list[list[int]]:
    return [rng.integers(0, vocab, n).tolist() for n in lens]


def _run_engine(eng, prompts, max_new: int, arrive_every: int) -> dict:
    """Feed the requests while the engine steps (one more every
    ``arrive_every`` steps) until the scheduler is empty; host-clock time
    of every step."""
    pending = list(enumerate(prompts))
    rids, step_s, kinds = {}, [], []
    n_steps = 0
    n_mixed = eng.metrics.summary()["mixed_steps"]
    while pending or eng.scheduler.has_work():
        if pending and n_steps % arrive_every == 0:
            i, p = pending.pop(0)
            rids[i] = eng.add_request(p, max_new)
        t0 = time.perf_counter()
        eng.step()          # ends in the engine's own device sync
        step_s.append(time.perf_counter() - t0)
        mixed = eng.metrics.summary()["mixed_steps"]
        kinds.append("mixed" if mixed > n_mixed else "decode")
        n_mixed = mixed
        n_steps += 1
        _require(n_steps < 10_000, "serve: engine did not drain")
    streams = {i: list(eng.request(rid).tokens) for i, rid in rids.items()}
    reasons = {i: eng.request(rid).finish_reason for i, rid in rids.items()}
    return {"streams": streams, "reasons": reasons, "step_s": step_s,
            "kinds": kinds}


def _engine_report(eng, run: dict) -> dict:
    """What every engine arm reports: did the requests finish, how many
    programs, host-clock medians per step kind, what is in the programs."""
    by = {"mixed": [], "decode": []}
    for k, s in zip(run["kinds"], run["step_s"]):
        by[k].append(s)
    return {
        "finished": sum(r == "length" for r in run["reasons"].values()),
        "requests": len(run["streams"]),
        "tokens": sorted({len(s) for s in run["streams"].values()}),
        "program_counts": eng.step_program_counts(),
        **{f"{k}_steps": len(v) for k, v in by.items()},
        **{f"{k}_step_host_s_median":
           (round(statistics.median(v), 5) if v else None)
           for k, v in by.items()},
        "programs": _engine_programs(eng),
    }


def _generate(model, prompt, n: int) -> list[int]:
    import jax.numpy as jnp
    import numpy as np
    out = model.generate(jnp.asarray([prompt], jnp.int32), max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):].tolist()


def _check_stream(model, prompt, stream, tag: str, ref_stream=None) -> dict:
    """One engine stream against the model's own reference
    (``ref_stream``: ``generate()``'s tokens, computed here if not given).

    On the chip the paged kernel's online softmax and the contiguous
    path's ``_grouped_decode_attn`` reduce in different orders, so the
    CPU contract "bitwise equal to generate()" is reported, not
    required. What is required: teacher-forcing the ENGINE's stream
    through the contiguous-cache forward (the numeric program of
    ``generate()``'s prefill), every emitted token must be the
    reference's argmax to within ``tol`` = 16 bf16 steps (16 * 2**-8) of
    the row's largest |logit|. A kernel that read the wrong page yields
    tokens that are random to the reference — gaps of the order of the
    whole logit spread, many times ``tol``."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt

    s0, n = len(prompt), len(stream)
    if ref_stream is None:
        ref_stream = _generate(model, prompt, n)
    seq = jnp.asarray([prompt + stream[:-1]], jnp.int32)
    caches = model.init_kv_caches(1, seq.shape[1])
    # EvalStep passes the weights as arguments (a jit closing over the
    # model would bake 9 GB of constants into the program)
    logits, _ = pt.jit.EvalStep(model)(seq, None, caches, 0)
    ref_rows = np.asarray(logits[0, s0 - 1:].astype(jnp.float32))  # [n, V]
    top = ref_rows.max(axis=-1)
    gaps = top - ref_rows[np.arange(n), np.asarray(stream)]
    tol = 16 * 2.0 ** -8 * np.abs(ref_rows).max(axis=-1)
    first_diff = next((i for i, (a, b) in
                       enumerate(zip(stream, ref_stream)) if a != b), None)
    return {"request": tag, "prompt_len": s0, "tokens": n,
            "identical_to_generate": stream == ref_stream,
            "first_diverging_token": first_diff,
            "max_gap": float(gaps.max()), "min_tol": float(tol.min()),
            "gap_ok": bool(np.all(gaps <= tol)),
            "max_abs_logit": float(np.abs(ref_rows).max()),
            "logit_std": float(ref_rows.std())}


def _engine_programs(eng) -> dict:
    from paddle_tpu.ops.pallas.paged_attention import kernel_applicable
    out = {}
    for name, lowered in eng.lower_step_programs().items():
        t0 = time.perf_counter()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        out[name] = {"kernels": kernel_counts(compiled.as_text()),
                     "aot_compile_host_s": round(time.perf_counter() - t0, 3),
                     "arguments_bytes": mem.argument_size_in_bytes,
                     "temp_bytes": mem.temp_size_in_bytes}
    # the visible rule that routes each step shape (a rule, not a caught
    # exception): one query row takes the kernel, chunk rows the gather
    cfg = eng.model.config
    pool_shape = (eng.pool.num_pages, eng.page_size,
                  cfg.num_key_value_heads // eng.tp, cfg.head_dim)
    heads = cfg.num_attention_heads // eng.tp
    out["route_rule"] = {
        "decode": bool(kernel_applicable(
            (eng.max_slots, 1, heads, cfg.head_dim), pool_shape)),
        "mixed": bool(kernel_applicable(
            (eng.max_slots, eng.prefill_chunk, heads, cfg.head_dim),
            pool_shape)),
        "rule": "ops.pallas.paged_attention.kernel_applicable: one query "
                "row, head_dim % 128 == 0, page_size % 8 == 0, "
                "heads % kv_heads == 0; otherwise the XLA gather path"}
    return out


def serve_phase(sz: Sizes, seed: int) -> dict:
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.quantization.serving import quantize_for_serving
    from paddle_tpu.serving import ServingEngine

    pt.seed(seed)
    M = sz.max_pages_per_slot()
    cfg = _llama_config(sz, sz.serve_layers, mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed + 1)
    prompts = _prompts(rng, cfg.vocab_size, sz.prompt_lens)

    def engine(**kw):
        return ServingEngine(model, num_pages=sz.num_pages,
                             page_size=sz.page_size, max_slots=sz.max_slots,
                             max_pages_per_slot=M, **kw)

    result = {"model": {
        "preset": sz.preset, "layers": sz.serve_layers,
        "why_this_depth": "deepest power of two whose bf16 weights (untied "
                          "head) leave room on a 16 GB chip for the pool "
                          "twice (steps do not donate it) and the mixed "
                          "step's temporaries",
        "params": model.num_params(), "num_pages": sz.num_pages,
        "page_size": sz.page_size, "max_slots": sz.max_slots,
        "max_pages_per_slot": M}}

    emit("serve.model", **result["model"])

    eng = engine()
    t0 = time.perf_counter()
    eng.warm_programs()
    jax.block_until_ready(eng.pool.pools)
    warm_s = time.perf_counter() - t0
    run = _run_engine(eng, prompts, sz.max_new, sz.arrive_every)
    result["bf16"] = {
        "warm_programs_host_s": round(warm_s, 3),
        **_engine_report(eng, run),
        "kv_bytes_per_token": eng.pool.kv_bytes_per_token(),
    }
    emit("serve.bf16", **result["bf16"])
    result["bf16"]["streams_vs_generate"] = [
        _check_stream(model, prompts[i], run["streams"][i], f"req-{i}")
        for i in sz.compare]
    emit("serve.bf16.streams",
         streams_vs_generate=result["bf16"]["streams_vs_generate"])
    del eng
    _release()

    # the int8 arm: int8 weights (in place: the chip has no room for a
    # second copy) + int8 KV pool, one request through the same engine API
    model = quantize_for_serving(model, inplace=True)
    eng = engine(kv_quant=True)
    eng.warm_programs()
    p8 = _prompts(rng, cfg.vocab_size, (sz.int8_prompt,))
    run8 = _run_engine(eng, p8, sz.max_new, 1)
    result["int8"] = {
        **_engine_report(eng, run8),
        "tokens_in_vocab": all(0 <= t < cfg.vocab_size
                               for t in run8["streams"][0]),
        "kv_bytes_per_token": eng.pool.kv_bytes_per_token(),
        "kv_quant_err_bound": eng.metrics.summary().get("kv_quant_err_bound"),
    }
    emit("serve.int8", **result["int8"])
    del eng, model
    _release()
    return result


def check_serve(r: dict, sz: Sizes, on_chip: bool) -> None:
    from paddle_tpu.ops.pallas.paged_attention import KERNEL_NAME
    for arm, n_req in (("bf16", len(sz.prompt_lens)), ("int8", 1)):
        a = r[arm]
        _require(a["finished"] == n_req and a["tokens"] == [sz.max_new],
                 f"serve[{arm}]: not every request finished with "
                 f"{sz.max_new} tokens: {a['finished']}/{n_req}, "
                 f"lengths {a['tokens']}")
        _require(a["program_counts"] == {"decode": 1, "mixed": 1},
                 f"serve[{arm}]: step programs retraced: "
                 f"{a['program_counts']}")
        _require(a["decode_steps"] > 0 and a["mixed_steps"] > 0,
                 f"serve[{arm}]: a step program never ran: {a}")
        if on_chip:
            progs = a["programs"]
            _require(progs["route_rule"]["decode"]
                     and progs["decode"]["kernels"].get(KERNEL_NAME, 0) > 0,
                     f"serve[{arm}]: the Pallas paged-attention kernel is "
                     f"not in the decode program: {progs}")
    _require(r["int8"]["tokens_in_vocab"], "serve[int8]: token out of vocab")
    for s in r["bf16"]["streams_vs_generate"]:
        _require(s["gap_ok"],
                 f"serve: {s['request']} emitted a token whose reference "
                 f"logit is further than tol below the reference argmax: "
                 f"{s}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def multichip_phase(sz: Sizes, seed: int) -> dict:
    """``ServingEngine(tp=4)`` and ``ServingEngine(pp=2, tp=2)`` against
    the one-chip engine on the same prompts (same rule as the serve
    phase), then one ``TrainStep`` on an ``{"fsdp": 2, "mp": 2}`` mesh
    against the single-device loss for the same batch."""
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine

    devs = jax.devices()[:4]
    result: dict = {}

    # ---- serving across chips ------------------------------------------
    pt.seed(seed)
    M = sz.max_pages_per_slot(sz.mc_prompt_lens, sz.mc_max_new)
    cfg = _llama_config(sz, sz.mc_layers)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed + 2)
    prompts = _prompts(rng, cfg.vocab_size, sz.mc_prompt_lens)
    result["serve_model"] = {"preset": sz.preset, "layers": sz.mc_layers,
                             "params": model.num_params(),
                             "param_bytes": 2 * model.num_params()}
    emit("multichip.model", **result["serve_model"])
    compare = (0, len(prompts) - 1)
    refs = {i: _generate(model, prompts[i], sz.mc_max_new) for i in compare}
    streams = {}
    for name, kw in (("one_chip", {}), ("tp4", {"tp": 4}),
                     ("pp2_tp2", {"pp": 2, "tp": 2})):
        eng = ServingEngine(model, num_pages=sz.num_pages // 2,
                            page_size=sz.page_size,
                            max_slots=len(prompts), max_pages_per_slot=M,
                            tp_devices=devs if kw else None, **kw)
        t0 = time.perf_counter()
        eng.warm_programs()
        jax.block_until_ready(eng.pool.pools)
        warm_s = time.perf_counter() - t0
        placed = _memory(devs)
        run = _run_engine(eng, prompts, sz.mc_max_new, sz.arrive_every)
        streams[name] = run["streams"]
        result[name] = {
            "warm_programs_host_s": round(warm_s, 3),
            "bytes_in_use_after_placement": [m["bytes_in_use"]
                                             for m in placed],
            **_engine_report(eng, run),
            "kv_bytes_per_token_shard": eng.pool.kv_bytes_per_token_shard(),
            "streams_identical_to_one_chip": (
                None if name == "one_chip"
                else run["streams"] == streams["one_chip"]),
            "streams_vs_generate": [
                _check_stream(model, prompts[i], run["streams"][i],
                              f"req-{i}", refs[i])
                for i in compare],
        }
        emit(f"multichip.{name}", **result[name])
        del eng
        _release()
    del model
    _release()

    # ---- hybrid training across chips ----------------------------------
    ids = _batch(seed, cfg.vocab_size, sz.mc_train_seq)

    def one_step(mesh):
        model, step = _train_step(sz, seed, mesh)
        kernels = kernel_counts(step.lower(ids, ids).compile().as_text())
        placed = _memory(devs)
        t0 = time.perf_counter()
        loss = float(jax.block_until_ready(step(ids, ids)))
        dt = time.perf_counter() - t0
        param_bytes = 2 * model.num_params()
        del step, model
        _release()
        return {"loss": loss, "first_step_incl_compile_host_s": round(dt, 3),
                "kernels_in_step": kernels, "param_bytes": param_bytes,
                "bytes_in_use_after_placement": [m["bytes_in_use"]
                                                 for m in placed]}

    result["train_one_chip"] = one_step(None)
    emit("multichip.train_one_chip", **result["train_one_chip"])
    mesh = pt.make_mesh({"fsdp": 2, "mp": 2}, devices=devs)
    with pt.use_mesh(mesh):
        result["train_fsdp2_mp2"] = one_step(mesh)
    emit("multichip.train_fsdp2_mp2", **result["train_fsdp2_mp2"])
    return result


def _require_spread(name: str, used: list, param_bytes: int) -> None:
    """Parameters and pool must not all land on device 0 (meshes and
    device groups slice ``jax.devices()`` in order): every OTHER device
    holds at least an eighth of the parameter bytes — a quarter is an
    even split over four, and device 0 also keeps the unsharded copy the
    references run on."""
    _require(all(u is not None and u >= param_bytes // 8 for u in used[1:]),
             f"{name}: devices 1-3 hold less than 1/8 of the "
             f"{param_bytes} parameter bytes each: bytes_in_use {used}")


def check_multichip(r: dict, sz: Sizes, on_chip: bool) -> None:
    import math
    n = len(sz.mc_prompt_lens)
    for name in ("one_chip", "tp4", "pp2_tp2"):
        a = r[name]
        _require(a["finished"] == n,
                 f"{name}: {a['finished']}/{n} requests finished")
        _require(a["program_counts"] == {"decode": 1, "mixed": 1},
                 f"{name}: step programs retraced: {a['program_counts']}")
        for s in a["streams_vs_generate"]:
            _require(s["gap_ok"], f"{name}: {s['request']} left the "
                                  f"reference's tolerance: {s}")
        if on_chip and name != "one_chip":
            _require_spread(name, a["bytes_in_use_after_placement"],
                            r["serve_model"]["param_bytes"])
    l1, l4 = r["train_one_chip"]["loss"], r["train_fsdp2_mp2"]["loss"]
    _require(math.isfinite(l1) and math.isfinite(l4),
             f"multichip train: non-finite loss {l1} {l4}")
    # same weights, same batch, bf16: sharded matmuls reduce in another
    # order, so the losses agree to bf16 resolution, not bitwise
    _require(abs(l1 - l4) <= 2.0 ** -6 * abs(l1),
             f"multichip train: fsdp2 x mp2 loss {l4} vs one chip {l1}")
    if on_chip:
        _require_spread("train_fsdp2_mp2",
                        r["train_fsdp2_mp2"]["bytes_in_use_after_placement"],
                        r["train_fsdp2_mp2"]["param_bytes"])
        _require(r["train_fsdp2_mp2"]["kernels_in_step"]
                 .get("flash_attention_fwd", 0) > 0,
                 f"multichip train: the flash kernel is not in the meshed "
                 f"step: {r['train_fsdp2_mp2']['kernels_in_step']}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip phase (tp=4, pp=2 x "
                         "tp=2, fsdp=2 x mp=2) and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    import jaxlib

    from paddle_tpu.io.native_loader import native_available
    from paddle_tpu.utils.compile_cache import (CacheCounter,
                                                enable_compile_cache)
    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    t_start = time.perf_counter()
    emit("start", platform=dev.platform, device_kind=dev.device_kind,
         device_count=len(devices), chips=args.chips, seed=args.seed,
         jax=jax.__version__, jaxlib=jaxlib.__version__,
         backend_version="; ".join(
             jax.extend.backend.get_backend().platform_version.split("\n")),
         compile_cache_dir=cache_dir,
         native_loader_available=native_available())
    sz = Sizes()
    if args.chips == 4:
        r = multichip_phase(sz, args.seed)
        emit("multichip.end", memory=_memory(devices[:4]),
             compile_cache=cache.counts())
        check_multichip(r, sz, on_chip=True)
    else:
        r = train_phase(sz, args.seed)
        emit("train.end", memory=_memory(devices[:1]),
             compile_cache=cache.counts())
        check_train(r, on_chip=True)
        r = serve_phase(sz, args.seed)
        emit("serve.end", memory=_memory(devices[:1]),
             compile_cache=cache.counts())
        check_serve(r, sz, on_chip=True)
    emit("done", wall_host_s=round(time.perf_counter() - t_start, 1),
         compile_cache=cache.counts())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
