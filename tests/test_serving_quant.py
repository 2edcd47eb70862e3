"""paddle_tpu.quantization.serving — int8 KV cache + int8 weight
streaming for the paged serving engine (SERVING.md "Quantized KV &
weights").

The contracts under test:

1. FORMAT — QuantizedKV roundtrip error is bounded by scale/2 per
   element, exact zeros stay exact (masked-garbage-is-zero survives
   quantization), and the codes/scales pair is a jax pytree that rides
   jit carries.
2. ONE PROGRAM — the int8 engine keeps the fp engine's design contract:
   decode stays ONE compiled program under churn, and its greedy tokens
   are bitwise identical to ``generate(kv_dtype="int8")`` (both arms
   quantize at cache-write and dequantize in the SAME shared GQA core).
3. COMPOSITION — prefix caching (hash roots namespaced per storage
   format, COW copies carry scales), preempt-and-recompute, and the NaN
   quarantine (poison-by-scale: int8 codes cannot hold a NaN, so the
   fp32 scale row carries the sentinel; the scrub must zero codes AND
   scales) all hold with the quantized pool.
4. WEIGHT STREAMING — quantize_for_serving swaps decode matmuls to
   int8 + per-channel scales with the dequant fused into the matmul
   epilogue, cutting serving_state_bytes roughly in half.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.distributed import fault
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.quantization import (Int8ServingLinear, QuantizedKV,
                                     kv_dequantize, kv_quantize,
                                     quantize_for_serving,
                                     serving_state_bytes)
from paddle_tpu.serving import KVCachePool, ServingEngine, ServingMetrics

RNG = np.random.default_rng(11)


@pytest.fixture(scope="module")
def model():
    pt.seed(123)
    m = LlamaForCausalLM(llama_tiny(dtype="float32",
                                    mp_axis=None, fsdp_axis=None))
    m.eval()
    return m


@pytest.fixture
def fault_free(monkeypatch):
    fault.deactivate()
    monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
    monkeypatch.delenv("PROCESS_ID", raising=False)
    monkeypatch.delenv("PADDLE_RESTART_EPOCH", raising=False)
    yield
    fault.deactivate()


def _reference(model, prompt, max_new, **kw):
    out = model.generate(jnp.asarray([prompt]), max_new_tokens=max_new, **kw)
    return np.asarray(out)[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# the QuantizedKV format
# ---------------------------------------------------------------------------

class TestQuantizedKV:
    def test_roundtrip_error_bounded_by_half_scale(self):
        x = jnp.asarray(RNG.standard_normal((4, 16, 2, 32)), jnp.float32)
        c = kv_quantize(x)
        assert c.q.dtype == jnp.int8
        assert c.scale.shape == (4, 16, 2)
        back = kv_dequantize(c)
        err = np.abs(np.asarray(back) - np.asarray(x))
        bound = np.asarray(c.scale)[..., None] / 2.0 + 1e-7
        assert (err <= bound).all()

    def test_zero_rows_roundtrip_exactly(self):
        # the paged pool's unwritten positions are zeros the attention
        # mask relies on — quantization must keep them EXACT zeros
        # (scale 0 -> guarded divide -> dequant exact 0)
        x = jnp.zeros((2, 4, 1, 8), jnp.float32)
        c = kv_quantize(x)
        assert not np.asarray(c.q).any()
        assert not np.asarray(c.scale).any()
        assert not np.asarray(kv_dequantize(c)).any()

    def test_codes_clipped_and_scale_is_absmax_over_127(self):
        x = jnp.asarray([[[[-3.0, 0.5, 127.0]]]], jnp.float32)
        c = kv_quantize(x)
        np.testing.assert_allclose(np.asarray(c.scale), [[[1.0]]])
        assert np.abs(np.asarray(c.q)).max() <= 127

    def test_pytree_rides_jit(self):
        x = jnp.asarray(RNG.standard_normal((2, 4, 1, 8)), jnp.float32)
        c = kv_quantize(x)
        leaves = jax.tree_util.tree_leaves(c)
        assert len(leaves) == 2

        @jax.jit
        def f(c):
            return kv_dequantize(c) * 2.0

        np.testing.assert_allclose(np.asarray(f(c)),
                                   2.0 * np.asarray(kv_dequantize(c)))

    def test_shape_dtype_nbytes_delegate_to_codes(self):
        c = kv_quantize(jnp.ones((2, 4, 3, 8), jnp.float32))
        assert c.shape == (2, 4, 3, 8)
        assert c.ndim == 4
        assert c.dtype == jnp.int8
        assert c.nbytes == 2 * 4 * 3 * 8 + 2 * 4 * 3 * 4

    def test_write_order_invariance(self):
        # prefill-write and decode-append must quantize a row bitwise
        # identically: per-row absmax is order-exact, so quantizing a
        # block equals quantizing its rows one at a time
        x = jnp.asarray(RNG.standard_normal((1, 8, 2, 16)), jnp.float32)
        whole = kv_quantize(x)
        rows = [kv_quantize(x[:, i]) for i in range(8)]
        for i, r in enumerate(rows):
            assert np.array_equal(np.asarray(whole.q[:, i]),
                                  np.asarray(r.q))
            assert np.array_equal(np.asarray(whole.scale[:, i]),
                                  np.asarray(r.scale))


# ---------------------------------------------------------------------------
# the quantized pool
# ---------------------------------------------------------------------------

class TestQuantizedPool:
    def test_quantized_pool_layout_and_bytes(self):
        pool = KVCachePool(num_layers=2, num_pages=8, page_size=4,
                           num_kv_heads=2, head_dim=16, quantized=True)
        pk, pv = pool.pools[0]
        assert isinstance(pk, QuantizedKV) and isinstance(pv, QuantizedKV)
        assert pk.q.shape == (8, 4, 2, 16) and pk.q.dtype == jnp.int8
        assert pk.scale.shape == (8, 4, 2)
        assert pool.stats()["kv_quant"] == 1
        # per token: 2 arms * 2 layers * (kvh*d codes + kvh*4 scale)
        assert pool.kv_bytes_per_token() == 2 * 2 * (2 * 16 + 2 * 4)
        fp = KVCachePool(2, 8, 4, 2, 16, dtype=jnp.bfloat16)
        assert fp.kv_bytes_per_token() == 2 * 2 * (2 * 16 * 2)
        assert fp.stats()["kv_quant"] == 0

    def test_hash_roots_namespaced_per_format(self):
        # the SAME tokens must never alias across storage formats: an
        # fp-written page answering an int8 lookup (or vice versa) would
        # feed one engine the other's bytes
        from paddle_tpu.serving.kv_cache import _HASH_ROOT, _HASH_ROOT_INT8
        assert _HASH_ROOT != _HASH_ROOT_INT8
        fp = KVCachePool(1, 8, 4, 2, 8, cache_enabled=True)
        q = KVCachePool(1, 8, 4, 2, 8, cache_enabled=True, quantized=True)
        toks = np.arange(8, dtype=np.int64)
        pages = fp.alloc(2)
        fp.register_prefix(toks, pages)
        assert fp.match_prefix(toks).cached_tokens == 8
        assert not q.match_prefix(toks).hit  # different root: no hit
        qpages = q.alloc(2)
        q.register_prefix(toks, qpages)
        assert q.match_prefix(toks).cached_tokens == 8

    def test_scrub_zeroes_codes_and_scales(self):
        pool = KVCachePool(1, 8, 4, 2, 8, quantized=True)
        pages = pool.alloc(1)
        page = pages[0]
        pk, pv = pool.pools[0]
        pool.pools[0] = (
            QuantizedKV(pk.q.at[page].set(7),
                        pk.scale.at[page].set(jnp.nan)),
            pv)
        pool.scrub(pages)
        pool.free(pages)
        pk, _ = pool.pools[0]
        assert not np.asarray(pk.q[page]).any()
        assert np.isfinite(np.asarray(pk.scale[page])).all()
        assert not np.asarray(pk.scale[page]).any()

    def test_cow_copies_codes_and_scales(self):
        pool = KVCachePool(1, 8, 4, 2, 8, quantized=True)
        src, dst = pool.alloc(2)
        pk, pv = pool.pools[0]
        pool.pools[0] = (
            QuantizedKV(pk.q.at[src].set(5),
                        pk.scale.at[src].set(0.25)),
            pv)
        pool.cow_into(src, dst)
        pk, _ = pool.pools[0]
        assert (np.asarray(pk.q[dst]) == 5).all()
        np.testing.assert_allclose(np.asarray(pk.scale[dst]), 0.25)


# ---------------------------------------------------------------------------
# the int8 engine: parity, one-program, composition with PRs 3-6
# ---------------------------------------------------------------------------

class TestInt8Engine:
    def test_engine_matches_int8_generate_bitwise(self, model):
        """The engine's int8 tokens == generate(kv_dtype="int8") — both
        arms quantize at cache-write and dequantize in the one shared
        GQA core, so their streams agree bitwise, not just closely. A
        second epoch of join/leave churn must not mint a second decode
        program (the tentpole's one-program contract; 3-epoch version in
        test_no_retrace_across_epochs_int8)."""
        prompts = [list(RNG.integers(0, 512, n)) for n in (5, 9)]
        refs = [_reference(model, p, 6, kv_dtype="int8") for p in prompts]
        eng = ServingEngine(model, num_pages=64, page_size=4, max_slots=4,
                            kv_quant=True)
        rids = [eng.add_request(p, 6) for p in prompts]
        res = eng.run_to_completion(max_steps=200)
        for rid, ref in zip(rids, refs):
            assert res[rid] == ref
        assert eng.decode_program_count() == 1
        assert eng.stats()["kv_quant"] is True
        r2 = eng.add_request(prompts[0], 6)
        assert eng.run_to_completion(max_steps=100)[r2] == refs[0]
        assert eng.decode_program_count() == 1

    def test_kv_dtype_int8_is_an_alias_for_kv_quant(self, model):
        # constructor-level wiring only — programs compile lazily, so
        # this stays cheap; the decode path itself runs in the bitwise
        # parity test above
        eng = ServingEngine(model, num_pages=32, page_size=4, max_slots=2,
                            kv_dtype="int8")
        assert eng.kv_quant and eng.pool.quantized
        assert eng.metrics.kv_quant_enabled == 1

    @pytest.mark.slow
    def test_no_retrace_across_epochs_int8(self, model):
        eng = ServingEngine(model, num_pages=64, page_size=4, max_slots=4,
                            kv_quant=True)
        for epoch in range(3):
            for n in [3 + epoch, 5, 8][: 2 + epoch % 2]:
                eng.add_request(list(RNG.integers(0, 512, n)), 4 + epoch)
            eng.run_to_completion(max_steps=200)
            assert eng.decode_program_count() == 1, f"retraced epoch {epoch}"

    @pytest.mark.slow
    def test_greedy_agreement_vs_fp_cache(self, model):
        """Bounded-error acceptance: >=99% of greedy tokens agree with
        the fp cache across the trace, free-running (on the tiny model
        the streams happen to agree exactly)."""
        prompts = [list(RNG.integers(0, 512, n)) for n in (6, 11, 4, 9)]
        refs = [_reference(model, p, 10) for p in prompts]
        eng = ServingEngine(model, num_pages=64, page_size=4, max_slots=4,
                            kv_quant=True)
        rids = [eng.add_request(p, 10) for p in prompts]
        res = eng.run_to_completion(max_steps=200)
        agree = sum(int(a == b) for rid, ref in zip(rids, refs)
                    for a, b in zip(res[rid], ref))
        total = sum(len(r) for r in refs)
        assert agree / total >= 0.99

    def test_teacher_forced_decisive_agreement_vs_fp_cache(self, model):
        """The accuracy contract (SERVING.md "Quantized KV & weights"):
        with the same tokens fed to both caches, so that a flip is
        quantization error and not the cascade after an earlier flip,
        int8 KV agrees with fp on >=99% of the decisive positions: those
        whose fp top-2 margin exceeds twice the logit error seen there."""
        rng = np.random.default_rng(12)    # its own: RNG's later draws stay
        prompts = [list(rng.integers(0, 512, n)) for n in (6, 11, 4, 9)]
        agree = decisive = total = 0
        for p in prompts:
            toks = _reference(model, p, 10)
            total += len(toks)
            ids = jnp.asarray([list(p) + toks], jnp.int32)
            n = ids.shape[1]
            lg = [np.asarray(model(ids, kv_caches=model.init_kv_caches(
                1, n, **kw))[0][0], np.float32)[len(p) - 1:n - 1]
                for kw in ({}, {"dtype": "int8"})]
            err = np.abs(lg[0] - lg[1]).max(-1)
            top2 = np.sort(lg[0], axis=-1)
            dec = top2[:, -1] - top2[:, -2] > 2.0 * err
            agree += int((dec & (lg[0].argmax(-1) == lg[1].argmax(-1))).sum())
            decisive += int(dec.sum())
        assert decisive > total // 2
        assert agree / decisive >= 0.99

    @pytest.mark.slow
    def test_prefix_hit_parity_int8(self, model):
        """Shared-prefix requests on the int8 pool: followers map cached
        int8 pages (codes + scales move together) and stay bitwise equal
        to the cold int8 reference. (The storage-format namespacing that
        makes this safe is covered fast by
        TestQuantizedPool::test_hash_roots_namespaced_per_format.)"""
        shared = list(RNG.integers(0, 512, 12))
        prompts = [shared + list(RNG.integers(0, 512, n)) for n in (4, 6)]
        refs = [_reference(model, p, 6, kv_dtype="int8") for p in prompts]
        eng = ServingEngine(model, num_pages=64, page_size=4, max_slots=4,
                            max_pages_per_slot=16, kv_quant=True)
        r0 = eng.add_request(prompts[0], 6)
        eng.step()
        r1 = eng.add_request(prompts[1], 6)
        res = eng.run_to_completion(max_steps=100)
        assert res[r0] == refs[0]
        assert res[r1] == refs[1]
        assert eng.metrics.summary()["prefix_hits"] >= 1

    @pytest.mark.slow
    def test_partial_page_cow_int8(self, model):
        """COW through a frozen partial int8 page: the copy carries the
        scale rows, the diverging extensions stay bitwise correct, and
        the cached page itself replays untouched. (The scale-copy
        mechanism itself is covered fast by
        TestQuantizedPool::test_cow_copies_codes_and_scales.)"""
        shared = list(RNG.integers(0, 512, 6))
        eng = ServingEngine(model, num_pages=64, page_size=4, max_slots=4,
                            max_pages_per_slot=16, kv_quant=True)
        r0 = eng.add_request(shared, 2)
        out0 = eng.run_to_completion(max_steps=50)[r0]
        assert out0 == _reference(model, shared, 2, kv_dtype="int8")
        hist = shared + out0
        prompts = [hist + list(RNG.integers(0, 512, n)) for n in (3, 2)]
        refs = [_reference(model, p, 6, kv_dtype="int8") for p in prompts]
        rids = [eng.add_request(p, 6) for p in prompts]
        res = eng.run_to_completion(max_steps=100)
        for rid, ref in zip(rids, refs):
            assert res[rid] == ref
        assert eng.metrics.summary()["prefix_cow_copies"] >= 1
        r3 = eng.add_request(shared, 2)
        assert eng.run_to_completion(max_steps=50)[r3] == out0

    @pytest.mark.slow
    def test_parity_through_preemption_int8(self, model):
        # int8 preempt-and-recompute parity also runs fast via
        # TestInt8Chaos::test_alloc_storm_preempts_int8_deterministic
        prompts = [list(RNG.integers(0, 512, n)) for n in (6, 7)]
        refs = [_reference(model, p, 8, kv_dtype="int8") for p in prompts]
        eng = ServingEngine(model, num_pages=7, page_size=4, max_slots=2,
                            max_pages_per_slot=6, kv_quant=True)
        rids = [eng.add_request(p, 8) for p in prompts]
        res = eng.run_to_completion(max_steps=500)
        assert eng.scheduler.num_preemptions > 0, \
            "config failed to exercise preemption"
        for rid, ref in zip(rids, refs):
            assert res[rid] == ref
        assert eng.decode_program_count() == 1

    def test_metrics_and_prometheus_gauges(self):
        # gauge logic lives entirely in ServingMetrics — no engine
        # needed (the engine-side feed of on_kv_quant_scale is covered
        # by test_llm_predictor_quant_flags / the trace-instant test)
        mx = ServingMetrics()
        mx.set_kv_quant(True)
        mx.on_kv_quant_scale(0.25)
        mx.on_kv_quant_scale(0.125)   # gauge is a running max
        m = mx.summary()
        assert m["kv_quant_enabled"] == 1
        assert m["kv_quant_scale_max"] == 0.25
        assert m["kv_quant_err_bound"] == 0.125
        from paddle_tpu.observability import render_prometheus
        text = render_prometheus(m)
        assert "paddle_serving_kv_quant_enabled 1" in text
        assert "paddle_serving_kv_quant_err_bound" in text
        # fp metrics keep the schema, gauges at zero
        m2 = ServingMetrics().summary()
        assert m2["kv_quant_enabled"] == 0
        assert m2["kv_quant_err_bound"] == 0.0

    @pytest.mark.slow
    def test_kv_quantize_trace_instant(self, model):
        from paddle_tpu.observability import Tracer
        tracer = Tracer()
        eng = ServingEngine(model, num_pages=32, page_size=4, max_slots=2,
                            kv_quant=True, tracer=tracer)
        eng.add_request(list(RNG.integers(0, 512, 5)), 3)
        eng.run_to_completion(max_steps=50)
        names = {ev.get("name") for ev in tracer.events}
        assert "kv_quantize" in names


@pytest.mark.faults
class TestInt8Chaos:
    @pytest.mark.slow
    def test_poison_by_scale_quarantines_and_scrubs(self, model,
                                                    fault_free):
        """int8 codes cannot hold a NaN, so the poison lands in the fp32
        scale row and propagates through dequant to the nonfinite logit
        sentinel: the victim is quarantined, survivors' int8 streams
        stay bitwise intact, and the scrub zeroes codes AND scales.
        (The scrub mechanics run fast in
        TestQuantizedPool::test_scrub_zeroes_codes_and_scales.)"""
        prompts = [list(RNG.integers(0, 512, n)) for n in (5, 7, 4)]
        refs = [_reference(model, p, 8, kv_dtype="int8") for p in prompts]
        fault.activate(fault.FaultPlan([
            fault.FaultSpec(site="serving.decode", action="poison",
                            step=3, match=r"^victim$"),
        ]))
        eng = ServingEngine(model, num_pages=64, page_size=4, max_slots=4,
                            kv_quant=True)
        res_ids = [eng.add_request(prompts[0], 8, rid="ok-0"),
                   eng.add_request(prompts[1], 8, rid="victim"),
                   eng.add_request(prompts[2], 8, rid="ok-1")]
        del res_ids
        res = eng.run_to_completion(max_steps=200)
        victim = eng.request("victim")
        assert victim.finish_reason == "nonfinite"
        assert len(victim.tokens) < 8
        assert victim.tokens == refs[1][: len(victim.tokens)]
        assert res["ok-0"] == refs[0] and res["ok-1"] == refs[2]
        assert eng.metrics.summary()["quarantined"] == 1
        assert eng.decode_program_count() == 1
        # nothing non-finite survives: every scale row is finite again
        # and the quarantined pages' codes are zeroed
        for pk, pv in eng.pool.pools:
            assert np.isfinite(np.asarray(pk.scale)).all()
            assert np.isfinite(np.asarray(pv.scale)).all()
        eng.audit_pool()

    @pytest.mark.slow
    def test_alloc_storm_preempts_int8_deterministic(self, model,
                                                     fault_free):
        prompts = [list(RNG.integers(0, 512, n)) for n in (6, 7)]
        refs = [_reference(model, p, 10, kv_dtype="int8") for p in prompts]
        fault.activate(fault.FaultPlan([
            fault.FaultSpec(site="serving.alloc", action="raise",
                            prob=0.4, once=False),
        ], seed=11))
        eng = ServingEngine(model, num_pages=8, page_size=4, max_slots=2,
                            max_pages_per_slot=6, kv_quant=True)
        rids = [eng.add_request(p, 10) for p in prompts]
        res = eng.run_to_completion(max_steps=500)
        assert eng.scheduler.num_preemptions > 0
        for rid, ref in zip(rids, refs):
            assert res[rid] == ref
        assert eng.decode_program_count() == 1
        eng.audit_pool()


# ---------------------------------------------------------------------------
# contiguous generate() int8 arm + the Pallas kernel int8 mode
# ---------------------------------------------------------------------------

class TestContiguousInt8:
    @pytest.mark.slow
    def test_generate_int8_scan_equals_eager_loop(self, model):
        prompt = list(RNG.integers(0, 512, 7))
        scan = _reference(model, prompt, 6, kv_dtype="int8")
        eager = _reference(model, prompt, 6, kv_dtype="int8",
                           jit_loop=False)
        assert scan == eager

    def test_init_kv_caches_int8_layout(self, model):
        caches = model.init_kv_caches(2, 16, dtype="int8")
        ck, cv = caches[0]
        assert isinstance(ck, QuantizedKV)
        assert ck.q.dtype == jnp.int8
        assert ck.scale.dtype == jnp.float32
        assert ck.q.shape[:2] == (2, 16)
        assert ck.scale.shape == ck.q.shape[:3]


class TestPagedKernelInt8:
    def test_kernel_int8_matches_xla_gather_path(self):
        """The Pallas block-table kernel's quant mode (scales ride the
        same index map as their pages, dequant inside the page loop)
        against the XLA gather + shared-core reference on the SAME
        QuantizedKV pool — identical inputs, so only kernel math can
        differ (fp32 accumulation both sides)."""
        from paddle_tpu.nn.functional.attention import _grouped_decode_attn
        from paddle_tpu.ops.pallas.paged_attention import (
            kernel_applicable, paged_attention_tpu)
        b, h, kvh, d, ps, M, npages = 3, 4, 2, 128, 8, 3, 8
        assert kernel_applicable((b, 1, h, d), (npages, ps, kvh, d))
        q = jnp.asarray(RNG.standard_normal((b, 1, h, d)), jnp.float32)
        pk = kv_quantize(jnp.asarray(
            RNG.standard_normal((npages, ps, kvh, d)), jnp.float32))
        pv = kv_quantize(jnp.asarray(
            RNG.standard_normal((npages, ps, kvh, d)), jnp.float32))
        tables = jnp.asarray(RNG.integers(1, npages, (b, M)), jnp.int32)
        lens = jnp.asarray([5, ps * M - 1, ps + 3], jnp.int32)
        got = paged_attention_tpu(q, pk.q, pv.q, tables, lens,
                                  k_scale=pk.scale, v_scale=pv.scale)
        kg = QuantizedKV(pk.q[tables].reshape(b, M * ps, kvh, d),
                         pk.scale[tables].reshape(b, M * ps, kvh))
        vg = QuantizedKV(pv.q[tables].reshape(b, M * ps, kvh, d),
                         pv.scale[tables].reshape(b, M * ps, kvh))
        want = _grouped_decode_attn(q, kg, vg, lens, 1.0 / np.sqrt(d))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)

    def test_paged_attention_decode_routes_quantized(self):
        """The dispatcher accepts a QuantizedKV pool and agrees with
        manual dequantize-then-attend."""
        from paddle_tpu.nn.functional.attention import (
            _grouped_decode_attn, paged_attention_decode)
        b, h, kvh, d, ps, M, npages = 2, 4, 2, 16, 4, 3, 8
        q = jnp.asarray(RNG.standard_normal((b, 1, h, d)), jnp.float32)
        pk = kv_quantize(jnp.asarray(
            RNG.standard_normal((npages, ps, kvh, d)), jnp.float32))
        pv = kv_quantize(jnp.asarray(
            RNG.standard_normal((npages, ps, kvh, d)), jnp.float32))
        tables = jnp.asarray(RNG.integers(1, npages, (b, M)), jnp.int32)
        lens = jnp.asarray([3, ps * M - 1], jnp.int32)
        got = paged_attention_decode(q, pk, pv, tables, lens)
        kg = kv_dequantize(QuantizedKV(
            pk.q[tables].reshape(b, M * ps, kvh, d),
            pk.scale[tables].reshape(b, M * ps, kvh)))
        vg = kv_dequantize(QuantizedKV(
            pv.q[tables].reshape(b, M * ps, kvh, d),
            pv.scale[tables].reshape(b, M * ps, kvh)))
        want = _grouped_decode_attn(q, kg, vg, lens, 1.0 / np.sqrt(d))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# int8 weight streaming
# ---------------------------------------------------------------------------

class TestWeightStreaming:
    def test_int8_linear_matches_dequant_reference(self):
        from paddle_tpu import nn
        from paddle_tpu.quantization import _dequantize_weight
        pt.seed(5)
        lin = nn.Linear(32, 48)
        lin.eval()
        qlin = Int8ServingLinear.from_linear(lin)
        x = jnp.asarray(RNG.standard_normal((4, 32)), jnp.float32)
        got = qlin(x)
        wref = _dequantize_weight(qlin.weight_q, qlin.weight_scale,
                                  dtype=jnp.float32)
        want = x @ wref + lin.bias
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # and close to the fp layer (absmax int8, per-channel)
        np.testing.assert_allclose(np.asarray(got), np.asarray(lin(x)),
                                   rtol=0.1, atol=0.1)

    def test_quantize_for_serving_swaps_and_shrinks(self, model):
        fp_bytes = serving_state_bytes(model)
        qm = quantize_for_serving(model)
        q_bytes = serving_state_bytes(qm)
        assert fp_bytes / q_bytes > 1.8  # embeddings stay fp; matmuls ~4x
        n_q = sum(1 for _, s in qm.named_sublayers()
                  if isinstance(s, Int8ServingLinear))
        assert n_q == 4 * len(qm.model.layers) + 3 * len(qm.model.layers)
        # the source model is untouched (deepcopy semantics)
        assert not any(isinstance(s, Int8ServingLinear)
                       for _, s in model.named_sublayers())

    @pytest.mark.slow
    def test_quantized_model_generate_close_to_fp(self, model):
        """Bounded-error check between two DIFFERENT models (fp vs int8
        weights): greedy streams are autoregressive, so one near-tie
        argmax flip cascades — score the divergence-free PREFIX, not
        per-token agreement after the fork."""
        prompt = list(RNG.integers(0, 512, 8))
        ref = _reference(model, prompt, 8)
        qm = quantize_for_serving(model)
        got = _reference(qm, prompt, 8)
        div = next((i for i, (a, b) in enumerate(zip(ref, got))
                    if a != b), len(ref))
        assert div >= len(ref) // 2, (ref, got)

    @pytest.mark.slow
    def test_full_int8_engine_weights_and_kv(self, model):
        """Both halves at once: int8 weight streaming + int8 KV through
        the serving engine — the deployment configuration."""
        prompts = [list(RNG.integers(0, 512, n)) for n in (5, 8)]
        qm = quantize_for_serving(model)
        refs = [_reference(qm, p, 6, kv_dtype="int8") for p in prompts]
        eng = ServingEngine(qm, num_pages=64, page_size=4, max_slots=4,
                            kv_quant=True)
        rids = [eng.add_request(p, 6) for p in prompts]
        res = eng.run_to_completion(max_steps=100)
        for rid, ref in zip(rids, refs):
            assert res[rid] == ref
        assert eng.decode_program_count() == 1

    @pytest.mark.slow
    def test_llm_predictor_quant_flags(self, model):
        from paddle_tpu.inference import create_llm_predictor
        prompts = [list(RNG.integers(0, 512, n)) for n in (4, 7)]
        pred = create_llm_predictor(model, num_pages=32, page_size=4,
                                    max_slots=4, kv_quant=True,
                                    weight_quant=True)
        assert pred.engine.kv_quant
        assert any(isinstance(s, Int8ServingLinear)
                   for _, s in pred.model.named_sublayers())
        outs = pred.generate(prompts, max_new_tokens=4)
        assert all(len(o) == 4 for o in outs)
        assert pred.metrics_summary()["kv_quant_enabled"] == 1
