"""Serving: batched generation with compiled prefill + one-program decode.

The serving workflow (parity: the reference's AnalysisPredictor +
FusedMultiTransformer KV-cache decode): ``model.generate`` runs ONE jitted
prefill over the prompt and the WHOLE token loop as ONE jitted ``lax.scan``
over a fixed-size KV cache — two compiled programs total, cached on the
model per (batch, prompt_len, new_tokens) signature, so a serving loop
never retraces. Greedy and nucleus (top-p) sampling both ride the same
programs.

Runs on CPU as-is:

    python examples/serve_generate.py
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny


def main():
    pt.seed(0)
    # the test-scale Llama config so the example runs in seconds on CPU;
    # the same code path serves llama_3_8b on a chip
    cfg = llama_tiny(mp_axis=None, fsdp_axis=None)
    model = LlamaForCausalLM(cfg)
    model.eval()

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (2, 12))  # [batch, prompt_len]

    # greedy: deterministic continuation
    out = model.generate(prompts, max_new_tokens=16)
    print("greedy      :", np.asarray(out)[0, 12:].tolist())

    # the second call with the same signature reuses the compiled
    # prefill + scan-decode programs (no retrace) — the serving pattern
    out2 = model.generate(prompts, max_new_tokens=16)
    assert np.array_equal(np.asarray(out), np.asarray(out2))
    assert model.decode_cache_stats()["signatures"] == 1  # one entry

    # nucleus sampling: seeded, reproducible
    s1 = model.generate(prompts, max_new_tokens=16, do_sample=True,
                        top_p=0.9, temperature=0.8, seed=7)
    s2 = model.generate(prompts, max_new_tokens=16, do_sample=True,
                        top_p=0.9, temperature=0.8, seed=7)
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    print("sampled     :", np.asarray(s1)[0, 12:].tolist())

    # token-by-token debugging path (identical greedy tokens)
    dbg = model.generate(prompts, max_new_tokens=16, jit_loop=False)
    assert np.array_equal(np.asarray(out), np.asarray(dbg))
    print("eager-loop  : identical to scan decode")
    # program economy: greedy reuses ONE (prefill, decode) pair across its
    # two calls; the sampled signature adds its own pair; the eager loop
    # adds its per-token step program — all visible through the PUBLIC
    # decode_cache_stats() accessor (never poke private model attributes)
    stats = model.decode_cache_stats()
    print(f"ok: {stats['signatures']} cached signatures "
          f"(capacity {stats['capacity']}) served 10 sequences")

    # --- continuous batching: ragged prompts, one paged KV pool ---------
    # generate() pads a fixed batch to the longest prompt; the serving
    # engine (SERVING.md) instead shares a paged pool with iteration-level
    # scheduling — and its greedy tokens are bitwise identical to
    # per-request generate()
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(model, num_pages=64, page_size=4, max_slots=4)
    ragged = [list(rng.integers(0, cfg.vocab_size, n)) for n in (5, 12, 9)]
    rids = [eng.add_request(p, max_new_tokens=8) for p in ragged]
    results = eng.run_to_completion()
    for p, rid in zip(ragged, rids):
        ref = np.asarray(model.generate(np.asarray([p]),
                                        max_new_tokens=8))[0, len(p):]
        assert results[rid] == ref.tolist()
    assert eng.decode_program_count() == 1  # churn never retraced decode
    print("engine      :", results[rids[0]],
          f"(3 ragged requests, decode stayed 1 program, "
          f"{eng.metrics.summary()['tokens_generated']} tokens)")

    # --- automatic prefix caching: shared system prompt -----------------
    # the chat-serving workload (SERVING.md "Prefix caching"): every
    # request repeats the same long system prompt. The first prefill
    # registers its pages in the pool's content-hash index; the rest map
    # them and prefill only their own suffix — same bitwise tokens, a
    # fraction of the prefill work, visible as cache_hit_rate
    eng2 = ServingEngine(model, num_pages=64, page_size=4, max_slots=4,
                         max_pages_per_slot=16)
    system = list(rng.integers(0, cfg.vocab_size, 24))
    users = [list(rng.integers(0, cfg.vocab_size, n)) for n in (4, 7, 3)]
    rid0 = eng2.add_request(system + users[0], max_new_tokens=8)
    eng2.step()  # first request prefills + registers the shared pages
    more = [eng2.add_request(system + u, max_new_tokens=8)
            for u in users[1:]]
    shared_res = eng2.run_to_completion()
    for u, rid in zip(users, [rid0] + more):
        p = system + u
        ref = np.asarray(model.generate(np.asarray([p]),
                                        max_new_tokens=8))[0, len(p):]
        assert shared_res[rid] == ref.tolist()  # cache hits change nothing
    m = eng2.metrics.summary()
    print(f"prefix cache: hit_rate={m['cache_hit_rate']:.2f} "
          f"({m['prefill_cached_tokens']}/{m['prefill_tokens']} prefill "
          f"tokens served from cached pages, {m['prefix_hits']} hits, "
          f"tokens bitwise identical to cold generate())")

    # --- int8 quantized serving: KV cache + weight streaming ------------
    # decode is bandwidth-bound: every step re-reads the weights and the
    # whole KV cache. kv_quant=True stores pages as int8 codes + per-row
    # fp32 absmax scales (~half the KV bytes); quantize_for_serving swaps
    # decode matmuls to int8 weights dequantized in the matmul epilogue
    # (SERVING.md "Quantized KV & weights"). Greedy tokens match the fp
    # cache on this workload — the error model bounds per-element dequant
    # error at scale/2, and tests/test_serving_quant.py holds >=99%
    # token agreement (test_teacher_forced_decisive_agreement_vs_fp_cache).
    from paddle_tpu.quantization import quantize_for_serving, \
        serving_state_bytes
    eng3 = ServingEngine(model, num_pages=64, page_size=4, max_slots=4,
                         kv_quant=True)
    rids3 = [eng3.add_request(p, max_new_tokens=8) for p in ragged[:2]]
    res3 = eng3.run_to_completion()
    assert all(res3[r3] == results[r] for r3, r in zip(rids3, rids[:2]))
    assert eng3.decode_program_count() == 1
    qm = eng3.metrics.summary()
    qmodel = quantize_for_serving(model)
    fp_b, q_b = serving_state_bytes(model), serving_state_bytes(qmodel)
    print(f"int8 serving: tokens identical to fp cache, "
          f"kv {eng3.pool.kv_bytes_per_token()}B/token vs "
          f"{eng.pool.kv_bytes_per_token()}B fp, err_bound="
          f"{qm['kv_quant_err_bound']:.4f}, weights {fp_b/1e6:.1f}MB -> "
          f"{q_b/1e6:.1f}MB")

    # --- tiered KV cache: spill to host RAM, restore on hit -------------
    # when the HBM pool LRU-evicts a cached page, host_tier=True demotes
    # its bytes to a bounded host-RAM pool instead of losing them; a
    # later request whose prefix walks into the tier restores the pages
    # bit-exactly at admission time (SERVING.md "KV tiering & traffic
    # harness"). Pool sized so two alternating tenants cannot coexist:
    # every tenant switch evicts the other tenant's pages, every return
    # restores them — and the tokens STILL match cold generate()
    from paddle_tpu.serving import HostTier
    eng4 = ServingEngine(model, num_pages=14, page_size=4, max_slots=1,
                         host_tier=True)
    systems = [list(rng.integers(0, cfg.vocab_size, 24)) for _ in range(2)]
    for i in range(4):
        p = systems[i % 2] + list(rng.integers(0, cfg.vocab_size, 6))
        rid = eng4.add_request(p, max_new_tokens=6)
        ref = np.asarray(model.generate(np.asarray([p]),
                                        max_new_tokens=6))[0, len(p):]
        assert eng4.run_to_completion()[rid] == ref.tolist()
    assert eng4.decode_program_count() == 1  # restores are host-side
    ps = eng4.pool.stats()       # host-tier breakdown rides pool.stats()
    tm = eng4.metrics.summary()
    assert ps["restored_pages"] > 0
    print(f"tiered kv   : hit_rate={tm['cache_hit_rate']:.2f} "
          f"(hbm={tm['tier_hbm_hit_rate']:.2f} "
          f"host={tm['tier_host_hit_rate']:.2f}), spilled "
          f"{ps['spilled_pages']} pages / restored {ps['restored_pages']} "
          f"({ps['host_pool_bytes']}B in host pool), tokens bitwise "
          f"identical through the host round-trip")

    # HostTier(max_bytes=...) bounds the host pool; Workload/make_workload
    # (paddle_tpu.serving.workload) builds the seeded Poisson multi-tenant
    # traces that tests/test_serving_tiering.py replays against it
    _ = HostTier


if __name__ == "__main__":
    main()
