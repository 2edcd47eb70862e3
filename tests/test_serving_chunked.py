"""Chunked prefill + mixed prefill/decode steps (SERVING.md "Chunked
prefill & mixed steps").

The chunked contracts:

1. BITWISE PARITY — emitted streams with chunking on are bitwise
   identical to ``generate()`` and to the unchunked arm, for every
   chunk size, composed with prefix caching, int8 KV, speculative
   verify and preemption/recompute. Chunk boundaries are data, never
   semantics.
2. O(1) PROGRAMS — ``step_program_counts() == {"decode": 1, "mixed": 1}``
   under churn, mixed prefill/decode steps, varying chunk sizes and
   mid-prompt preemption: the pow2 suffix-bucket prefill family is gone
   and ``stats()["prefill_programs"]`` reads the ONE mixed program.
3. BUDGET METERING — per-step prefill chunk tokens never exceed the
   prefill token budget (minus the verify reserve), FCFS over
   prefilling slots, with the oldest slot always advancing.
4. FINAL-CHUNK REGISTRATION — prefix pages commit on the final chunk
   only: a request preempted mid-prompt registers nothing and leaks no
   COW refs (first-writer-wins preserved).
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.distributed import fault
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability import Tracer
from paddle_tpu.serving import (SamplingParams, ServingEngine,
                                SpeculativeConfig, WorkloadSpec,
                                heavy_tail_workload, make_workload)

RNG = np.random.default_rng(31)

# one long prompt (several chunks at chunk=8) + short companions
P_LONG = RNG.integers(0, 512, 29).tolist()
P_A = RNG.integers(0, 512, 5).tolist()
P_B = RNG.integers(0, 512, 7).tolist()
MAX_NEW = 8


@pytest.fixture(scope="module")
def model():
    pt.seed(123)
    m = LlamaForCausalLM(llama_tiny(dtype="float32",
                                    mp_axis=None, fsdp_axis=None))
    m.eval()
    return m


@pytest.fixture(scope="module")
def refs(model):
    return {id_: _reference(model, p, MAX_NEW)
            for id_, p in (("long", P_LONG), ("a", P_A), ("b", P_B))}


@pytest.fixture
def fault_free():
    fault.deactivate()
    yield
    fault.deactivate()


def _reference(model, prompt, max_new, **kw):
    out = model.generate(jnp.asarray([prompt]), max_new_tokens=max_new, **kw)
    return np.asarray(out)[0, len(prompt):].tolist()


def _engine(model, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_pages_per_slot", 16)
    return ServingEngine(model, **kw)


class TestChunkedParity:
    @pytest.mark.parametrize("chunk", [1, 4, 8, 64])
    def test_chunk_size_never_changes_the_stream(self, model, refs, chunk):
        eng = _engine(model, chunked=True, prefill_chunk=chunk)
        rids = [eng.add_request(p, MAX_NEW)
                for p in (P_LONG, P_A, P_B)]
        res = eng.run_to_completion(max_steps=400)
        for rid, ref in zip(rids, (refs["long"], refs["a"], refs["b"])):
            assert res[rid] == ref, f"chunk={chunk}"
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}

    def test_decode_interleaves_with_chunks(self, model, refs):
        """The tentpole behavior: while the long prompt streams through
        in budget-sized chunks, an already-decoding request keeps
        emitting EVERY step instead of stalling behind the prefill."""
        eng = _engine(model, chunked=True, prefill_chunk=4,
                      prefill_token_budget=4)
        rid_a = eng.add_request(P_A, MAX_NEW)
        eng.step()                      # a's prompt (5 toks > budget 4)
        eng.step()                      # ... finishes chunking, emits
        assert len(eng.request(rid_a).tokens) == 1
        rid_l = eng.add_request(P_LONG, MAX_NEW)
        emitted = []
        for _ in range(6):              # long prompt: 29 toks / 4 per step
            n0 = len(eng.request(rid_a).tokens)
            eng.step()
            emitted.append(len(eng.request(rid_a).tokens) - n0)
            assert eng.request(rid_l).prefilling or \
                eng.request(rid_l).tokens
        # a decoded on every one of those mixed steps
        assert all(n == 1 for n in emitted)
        res = eng.run_to_completion(max_steps=200)
        assert res[rid_a] == refs["a"]
        assert res[rid_l] == refs["long"]

    @pytest.mark.slow
    def test_parity_composed_with_prefix_cache_and_int8(self, model):
        shared = RNG.integers(0, 512, 18).tolist()
        prompts = [shared + RNG.integers(0, 512, n).tolist()
                   for n in (3, 5)]
        for kv_quant in (False, True):
            # int8 reference is generate(kv_dtype="int8") — the quant
            # parity contract from test_serving_quant
            kw = {"kv_dtype": "int8"} if kv_quant else {}
            refs_ = [_reference(model, p, 6, **kw) for p in prompts]
            eng = _engine(model, chunked=True, prefill_chunk=8,
                          kv_quant=kv_quant)
            rid0 = eng.add_request(prompts[0], 6)
            eng.step()  # registration commits on the final chunk...
            eng.step()
            eng.step()
            rid1 = eng.add_request(prompts[1], 6)
            res = eng.run_to_completion(max_steps=200)
            assert res[rid0] == refs_[0], f"kv_quant={kv_quant}"
            assert res[rid1] == refs_[1], f"kv_quant={kv_quant}"
            # ...so the second arrival shares the full shared pages
            assert eng.metrics.summary()["prefix_hits"] >= 1

    def test_parity_composed_with_speculation(self, model, refs):
        eng = _engine(model, chunked=True, prefill_chunk=8,
                      speculative=SpeculativeConfig(k=4))
        rids = [eng.add_request(p, MAX_NEW) for p in (P_LONG, P_A)]
        res = eng.run_to_completion(max_steps=400)
        assert res[rids[0]] == refs["long"]
        assert res[rids[1]] == refs["a"]
        # spec verify rides the SAME mixed program as the chunks
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}
        assert eng.verify_program_count() == 1

    @pytest.mark.slow
    def test_sampled_stream_parity_across_chunk_sizes(self, model):
        sp = SamplingParams(do_sample=True, top_p=0.9, temperature=0.8,
                            seed=17)
        outs = []
        for chunk in (4, 64):
            eng = _engine(model, chunked=True, prefill_chunk=chunk)
            rid = eng.add_request(P_LONG, MAX_NEW,
                                  sampling=SamplingParams(**sp.__dict__))
            outs.append(eng.run_to_completion(max_steps=200)[rid])
        assert outs[0] == outs[1]

    def test_unchunked_arm_matches_chunked_arm(self, model, refs):
        outs = []
        for chunked in (False, True):
            eng = _engine(model, chunked=chunked, prefill_chunk=8)
            rids = [eng.add_request(p, MAX_NEW) for p in (P_LONG, P_B)]
            res = eng.run_to_completion(max_steps=400)
            outs.append([res[r] for r in rids])
        assert outs[0] == outs[1] == [refs["long"], refs["b"]]


class TestChunkedPrograms:
    @pytest.mark.slow
    def test_o1_programs_over_churn_epochs_with_preemption(self, model,
                                                           fault_free):
        """3 churn epochs on a page-starved engine (mid-prompt
        preemption guaranteed by an injected alloc storm): program
        counts stay {"decode": 1, "mixed": 1} throughout and streams
        replay bitwise after recompute."""
        fault.activate(fault.FaultPlan([
            fault.FaultSpec(site="serving.alloc", action="raise",
                            prob=0.35, once=False),
        ], seed=9))
        eng = _engine(model, num_pages=20, max_slots=2,
                      max_pages_per_slot=12, chunked=True,
                      prefill_chunk=4)
        for epoch in range(3):
            prompts = [RNG.integers(0, 512, n).tolist()
                       for n in (17 + epoch, 6)]
            refs_ = [_reference(model, p, 6) for p in prompts]
            rids = [eng.add_request(p, 6) for p in prompts]
            res = eng.run_to_completion(max_steps=500)
            for rid, ref in zip(rids, refs_):
                assert res[rid] == ref, f"epoch {epoch}"
            assert eng.step_program_counts() == \
                {"decode": 1, "mixed": 1}, f"retraced in epoch {epoch}"
        assert eng.scheduler.num_preemptions > 0
        assert eng.stats()["prefill_programs"] == 1

    def test_warm_programs_compiles_both_shapes(self, model):
        eng = _engine(model, chunked=True, prefill_chunk=8)
        assert eng.step_program_counts() == {"decode": 0, "mixed": 0}
        eng.warm_programs()
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}
        eng.warm_programs()  # idempotent
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}
        # the warm dispatch wrote nothing but scratch
        assert eng.pool.num_in_use == 0

    def test_retrace_sentinel_names_the_mixed_program(self, model):
        tr = Tracer()
        eng = _engine(model, chunked=True, prefill_chunk=8, tracer=tr)
        rid = eng.add_request(P_LONG, 4)
        eng.run_to_completion(max_steps=200)
        progs = {e["args"]["program"] for e in tr.events
                 if e["name"] == "compile"}
        assert progs <= {"decode", "mixed"}
        assert "mixed" in progs
        chunks = [e for e in tr.events if e["name"] == "chunk"]
        assert len(chunks) >= 1
        assert all(e["track"] == rid for e in chunks)


class TestChunkBudget:
    def test_chunk_tokens_metered_by_budget(self, model):
        """Per-step chunk tokens never exceed the prefill budget, and a
        long prompt takes ceil(len/budget) steps to materialize."""
        eng = _engine(model, chunked=True, prefill_chunk=64,
                      prefill_token_budget=8)
        rid = eng.add_request(P_LONG, 4)   # 29 prompt tokens
        req = eng.request(rid)
        steps = 0
        while req.prefilling or not req.tokens:
            c0 = req.context_len
            eng.step()
            assert req.context_len - c0 <= 8
            steps += 1
            assert steps < 20
        assert steps == -(-29 // 8)  # 4 steps of <= 8 chunk tokens
        last = eng.metrics.summary()
        assert last["chunk_tokens_total"] == 29
        assert last["mixed_steps"] == 4

    def test_oldest_prefilling_slot_always_advances(self, model):
        """Zero/negative leftover budget (verify reserve can eat it
        all) still advances the oldest prefilling slot — the
        no-starvation guarantee behind the stall detector."""
        eng = _engine(model, chunked=True, prefill_chunk=4,
                      prefill_token_budget=1,
                      speculative=SpeculativeConfig(k=4))
        rid = eng.add_request(P_LONG, 2)
        req = eng.request(rid)
        for _ in range(40):
            if not req.prefilling and req.tokens:
                break
            c0 = req.context_len
            eng.step()
            assert req.context_len > c0 or req.tokens
        assert req.tokens  # progressed to emission despite budget 1

    def test_fcfs_no_queue_jumping(self, model):
        """Two prefilling slots: the younger one only chunks with
        leftover budget after the older one's chunk."""
        eng = _engine(model, chunked=True, prefill_chunk=8,
                      prefill_token_budget=8)
        r0 = eng.add_request(P_LONG, 2)
        eng.step()  # r0 chunks 8
        r1 = eng.add_request(RNG.integers(0, 512, 20).tolist(), 2)
        eng.step()  # r0 chunks 8 more; r1 gets nothing (budget gone)
        assert eng.request(r0).context_len == 16
        assert eng.request(r1).context_len == 0
        eng.run_to_completion(max_steps=100)
        assert len(eng.request(r0).tokens) == 2
        assert len(eng.request(r1).tokens) == 2


class TestFinalChunkRegistration:
    def test_mid_prompt_preemption_registers_nothing(self, model,
                                                     fault_free):
        """Satellite 1 regression: preempt a request BETWEEN chunks —
        no partial-prompt pages may enter the prefix index, no COW refs
        may leak, and the recompute still replays bitwise."""
        prompt = RNG.integers(0, 512, 24).tolist()
        ref = _reference(model, prompt, 6)
        eng = _engine(model, num_pages=16, max_slots=2,
                      max_pages_per_slot=10, chunked=True,
                      prefill_chunk=4, prefill_token_budget=4)
        rid = eng.add_request(prompt, 6)
        eng.step()  # one 4-token chunk in flight, 20 to go
        req = eng.request(rid)
        assert req.prefilling and req.context_len == 4
        # force a mid-prompt preemption through the scheduler's own path
        victim = eng.scheduler._preempt_youngest(eng.pool)
        assert victim is req and req.pages == []
        # nothing registered: the same prompt must miss the cache
        # entirely, and no COW copies may have been taken
        assert eng.pool.match_prefix(prompt).cached_tokens == 0
        assert eng.pool.counters["prefix_cow_copies"] == 0
        res = eng.run_to_completion(max_steps=300)
        assert res[rid] == ref

    def test_injected_chunk_failure_never_registers(self, model,
                                                    fault_free):
        fault.activate(fault.FaultPlan([
            fault.FaultSpec(site="serving.prefill", action="raise",
                            match=r"^doomed$"),
        ], seed=3))
        eng = _engine(model, chunked=True, prefill_chunk=4)
        prompt = RNG.integers(0, 512, 10).tolist()
        rid = eng.add_request(prompt, 4, rid="doomed")
        ok = eng.add_request(P_A, 4, rid="ok")
        res = eng.run_to_completion(max_steps=100)
        assert eng.request("doomed").finish_reason == "injected"
        assert res["doomed"] == []
        assert len(res["ok"]) == 4
        assert eng.pool.match_prefix(prompt).cached_tokens == 0

    @pytest.mark.slow
    def test_first_writer_wins_when_two_chunkers_share(self, model):
        """Two same-step requests over one shared prefix both chunk to
        completion in the same dispatches; both register at their final
        chunks and first-writer-wins keeps exactly one copy indexed."""
        shared = RNG.integers(0, 512, 16).tolist()
        prompts = [shared + RNG.integers(0, 512, n).tolist()
                   for n in (2, 3)]
        refs_ = [_reference(model, p, 4) for p in prompts]
        eng = _engine(model, chunked=True, prefill_chunk=8)
        rids = [eng.add_request(p, 4) for p in prompts]
        res = eng.run_to_completion(max_steps=100)
        for rid, ref in zip(rids, refs_):
            assert res[rid] == ref
        # a later arrival hits the one surviving copy
        rid2 = eng.add_request(shared + [7, 8, 9], 4)
        eng.step()
        assert eng.metrics.summary()["prefix_hits"] >= 1
        eng.run_to_completion(max_steps=100)


class TestChunkedMetrics:
    def test_mixed_batch_gauges(self, model):
        eng = _engine(model, chunked=True, prefill_chunk=4,
                      prefill_token_budget=4)
        rid_a = eng.add_request(P_A, MAX_NEW)
        eng.run_to_completion(max_steps=100)
        s = eng.metrics.summary()
        assert s["chunked_enabled"] == 1
        assert s["mixed_steps"] >= 1
        assert s["chunk_tokens_total"] == len(P_A)
        assert s["chunks_dispatched_total"] >= 2  # 5 tokens / 4-chunks
        for key in ("chunk_prefill_tokens_last", "chunk_decode_slots_last",
                    "chunks_in_flight"):
            assert key in s
        # unchunked arm reports the flag off but the same schema
        eng2 = _engine(model, chunked=False)
        s2 = eng2.metrics.summary()
        assert s2["chunked_enabled"] == 0
        assert s2["mixed_steps"] == 0

    def test_prometheus_exports_chunk_gauges(self, model):
        from paddle_tpu.observability import (parse_prometheus,
                                              render_prometheus)
        eng = _engine(model, chunked=True, prefill_chunk=4)
        eng.add_request(P_A, 4)
        eng.run_to_completion(max_steps=50)
        page = render_prometheus(eng.metrics.summary(), eng.pool.stats())
        parsed = parse_prometheus(page)
        assert parsed["paddle_serving_chunked_enabled"] == 1
        assert parsed["paddle_serving_chunk_tokens_total"] == len(P_A)
        assert "paddle_serving_mixed_steps" in parsed


class TestHeavyTailWorkload:
    def test_preset_is_deterministic_and_heavy_tailed(self):
        wl = heavy_tail_workload(seed=5, n_requests=64)
        wl2 = heavy_tail_workload(seed=5, n_requests=64)
        assert [(r.rid, r.prompt, r.max_new_tokens, r.arrival_step)
                for r in wl] == \
               [(r.rid, r.prompt, r.max_new_tokens, r.arrival_step)
                for r in wl2]
        plens = sorted(len(r.prompt) for r in wl)
        # heavy tail: the top decile dwarfs the median
        assert plens[-1] >= 48
        assert plens[len(plens) // 2] <= 30
        # a different seed draws a different trace
        other = heavy_tail_workload(seed=6, n_requests=64)
        assert [r.prompt for r in other] != [r.prompt for r in wl]

    def test_lognormal_spec_validation(self):
        with pytest.raises(ValueError):
            make_workload(WorkloadSpec(suffix_dist="pareto"))

    def test_replay_on_chunked_engine_drains(self, model, fault_free):
        wl = heavy_tail_workload(seed=2, n_requests=6,
                                 suffix_clip=(24, 40), max_new=(2, 4),
                                 light_max_new=(4, 8))
        eng = _engine(model, chunked=True, prefill_chunk=8)
        out = wl.replay(eng, max_steps=400)
        assert out["submitted"] + out["shed"] == 6
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}


# ---------------------------------------------------------------------------
# drain / failover arriving MID-CHUNK on a prefilling slot
# ---------------------------------------------------------------------------

class TestMidChunkDrain:
    def _mid_chunk(self, eng, rid):
        """Step until ``rid`` is mid-prompt: some chunks consumed, the
        final chunk not yet dispatched."""
        guard = 0
        while True:
            req = eng.request(rid)
            if req.prefilling and req.context_len > 0:
                return req
            eng.step()
            guard += 1
            assert guard < 50, "never observed a mid-chunk slot"

    def test_drain_mid_chunk_stops_at_boundary_registers_nothing(
            self, model, fault_free):
        """SIGTERM between chunk steps: the drain preempts the slot at
        the chunk boundary — zero tokens emitted for the partial
        prompt, NOTHING registered in the prefix index (final-chunk
        registration), and the outcome is retriable."""
        eng = _engine(model, chunked=True, prefill_chunk=8)
        rid = eng.add_request(P_LONG, MAX_NEW)
        eng.step()
        req = self._mid_chunk(eng, rid)
        assert 0 < req.context_len < len(P_LONG)
        report = eng.drain(timeout_s=0.0)
        assert report[rid]["finish_reason"] == "preempted"
        assert report[rid]["retriable"] is True
        assert report[rid]["tokens"] == []      # prefill never finished
        assert eng.pool.counters["prefix_pages_registered"] == 0
        assert eng.pool.num_in_use == 0         # partial pages released
        eng.audit_pool()

    def test_failover_mid_chunk_replays_bitwise_on_survivor(
            self, model, fault_free):
        """Replica killed while its slot is mid-chunk: the surviving
        replica replays from scratch and the client stream is bitwise
        the single-engine run — a half-prefilled prompt contributes
        nothing (no tokens, no registered pages) to the replay."""
        from paddle_tpu.serving import FleetRouter
        ref = _reference(model, P_LONG, MAX_NEW)
        engines = [_engine(model, chunked=True, prefill_chunk=8)
                   for _ in range(2)]
        router = FleetRouter(engines)
        rid = router.submit(P_LONG, MAX_NEW)
        guard = 0
        while router.request(rid).replica is None:
            router.step()
            guard += 1
            assert guard < 50
        victim = router.request(rid).replica
        veng = engines[victim]
        req = self._mid_chunk(veng, rid)
        assert 0 < req.context_len < len(P_LONG)
        router.kill_replica(victim)
        out = router.run_to_completion(max_steps=400)
        assert out[rid] == ref                  # bitwise, exactly-once
        assert router.request(rid).emitted == len(ref)
        # the victim registered nothing for its partial prompt
        assert veng.pool.counters["prefix_pages_registered"] == 0
        survivor = engines[1 - victim]
        assert all(v <= 1
                   for v in survivor.step_program_counts().values())
        survivor.audit_pool()
