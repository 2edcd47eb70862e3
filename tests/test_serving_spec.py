"""paddle_tpu.serving.speculative — n-gram draft + multi-token verify.

The speculative contracts (SERVING.md "Speculative decoding"):

1. BITWISE PARITY — the emitted stream with speculation on is bitwise
   identical to the non-speculative engine (greedy AND sampled), which
   is itself bitwise identical to standalone ``generate()``. The verify
   step samples every position a draft can occupy (``spec_k`` rows a
   slot, never the chunk's padding) under the engine's standard
   ``fold_in(PRNGKey(seed), token_index)`` contract and emits its OWN
   samples — drafts only decide how many tokens a step emits, never
   which. Holds across churn, preemption, prefix-cache hits and int8 KV.
2. O(1) PROGRAMS — the engine owns exactly two per-step-shape programs
   (``[max_slots]`` decode + the ``[max_slots, chunk]`` MIXED step that
   carries prefill chunks and verify rows alike), each pinned at 1
   compiled instance under churn and arbitrary accept patterns
   (``step_program_counts()``; asserted over 3 churn epochs).
3. EXACT ROLLBACK — rejected draft rows are zeroed in-program and an
   in-window stop rewinds the accepted-but-unused tail, so no
   speculative garbage survives beyond ``context_len``
   (masked-garbage-is-zero at token granularity).
4. FLEET REPLAY — accepted-token streams replay bitwise on failover:
   the router's per-position dedup counts accepted positions, not
   steps.

Most engine tests share ONE module-scoped speculative engine (``eng4``)
and swap the drafter per test (drafters are stateless host objects, and
the parity contract makes the emitted stream drafter-independent) — a
fresh ServingEngine means recompiling decode + mixed, which is the
dominant cost of this file. The shared engine doubles as a cross-test
churn assertion: ``step_program_counts()`` must still be exactly
``{"decode": 1, "mixed": 1}`` after EVERY workload below.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.distributed import fault
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability import Tracer, parse_prometheus, \
    render_prometheus
from paddle_tpu.serving import (DraftProposer, FleetRouter, KVCachePool,
                                NgramDrafter, Request, SamplingParams,
                                Scheduler, ServingEngine, ServingMetrics,
                                SpeculativeConfig)

RNG = np.random.default_rng(23)

# Fixed prompts shared across tests: every (prompt_len, max_new) pair is
# a distinct generate() compile, so tests reuse the same three lengths
# and the same MAX_NEW wherever the scenario allows.
P5, P9, P12 = (RNG.integers(0, 512, n).tolist() for n in (5, 9, 12))
MAX_NEW = 8
KSPEC = 4


@pytest.fixture(scope="module")
def model():
    pt.seed(123)
    m = LlamaForCausalLM(llama_tiny(dtype="float32",
                                    mp_axis=None, fsdp_axis=None))
    m.eval()
    return m


@pytest.fixture(scope="module")
def refs(model):
    return {5: _reference(model, P5, MAX_NEW),
            9: _reference(model, P9, MAX_NEW),
            12: _reference(model, P12, MAX_NEW)}


@pytest.fixture(scope="module")
def eng4(model):
    return _spec_engine(model)


@pytest.fixture
def fault_free():
    fault.deactivate()
    yield
    fault.deactivate()


def _reference(model, prompt, max_new, **kw):
    out = model.generate(jnp.asarray([prompt]), max_new_tokens=max_new, **kw)
    return np.asarray(out)[0, len(prompt):].tolist()


def _req(prompt, tokens=()):
    r = Request(rid="r", prompt=list(prompt), max_new_tokens=64)
    r.tokens = list(tokens)
    return r


class OracleDrafter(DraftProposer):
    """Proposes the TRUE future tokens from a reference stream — every
    draft accepts, so a request finishes in ~max_new/k verify steps.
    The inverse, ``WrongDrafter``, never matches."""

    def __init__(self, refs: dict[str, list[int]]):
        self.refs = refs

    def propose(self, req, k):
        ref = self.refs.get(req.rid)
        if ref is None:
            return []
        done = len(req.tokens)
        return ref[done:done + k]


class RepeatDrafter(DraftProposer):
    """Proposes the last context token k times — the cheapest real
    drafter (great on repetitive text). Here it guarantees every decode
    step goes through the mixed program regardless of prompt content,
    which pins the program-count assertions; parity is unaffected
    because the emitted stream never depends on the drafter."""

    def propose(self, req, k):
        ctx = req.tokens or req.prompt
        return [int(ctx[-1])] * k


class WrongDrafter(DraftProposer):
    """Proposes tokens guaranteed to be rejected (vocab-shifted oracle)."""

    def __init__(self, refs: dict[str, list[int]], vocab: int):
        self.refs = refs
        self.vocab = vocab

    def propose(self, req, k):
        ref = self.refs.get(req.rid, [])
        done = len(req.tokens)
        return [(t + 1) % self.vocab for t in ref[done:done + k]]


def _spec_engine(model, spec=True, **kw):
    cfg = dict(num_pages=64, page_size=4, max_slots=4, max_pages_per_slot=16)
    cfg.update(kw)
    if spec is True:
        spec = SpeculativeConfig(k=KSPEC, drafter=RepeatDrafter())
    return ServingEngine(model, speculative=spec, **cfg)


def _arm(eng, drafter=None):
    """Reset the shared engine for one test: fresh metrics (spec
    re-armed, as the bench harness does) + the test's drafter."""
    eng.metrics = ServingMetrics()
    eng.metrics.set_spec(True)
    eng._drafter = drafter if drafter is not None else RepeatDrafter()
    return eng


# ---------------------------------------------------------------------------
# drafter units (no model)
# ---------------------------------------------------------------------------

class TestNgramDrafter:
    def test_matches_longest_ngram_first(self):
        d = NgramDrafter(max_ngram=2, min_ngram=1)
        # trailing bigram (3, 4) recurs at position 1 -> continuation 5 6
        assert d.propose(_req([9, 3, 4, 5, 6, 3, 4]), 2) == [5, 6]

    def test_falls_back_to_shorter_ngram(self):
        d = NgramDrafter(max_ngram=3, min_ngram=1)
        # no trigram/bigram recurrence; unigram 4 recurs -> continuation
        assert d.propose(_req([4, 7, 8, 4]), 2) == [7, 8]

    def test_rightmost_occurrence_wins(self):
        d = NgramDrafter(max_ngram=1, min_ngram=1)
        # token 2 occurs at 0 (-> 5) and at 2 (-> 6): most recent wins
        assert d.propose(_req([2, 5, 2, 6, 2]), 1) == [6]

    def test_no_match_returns_empty(self):
        d = NgramDrafter()
        assert d.propose(_req([1, 2, 3, 4]), 4) == []
        assert d.propose(_req([1]), 4) == []
        assert d.propose(_req([1, 2, 1, 2]), 0) == []  # k = 0

    def test_draft_spans_prompt_and_generated_history(self):
        d = NgramDrafter(max_ngram=2, min_ngram=1)
        # the match crosses the prompt/tokens boundary
        assert d.propose(_req([8, 9, 1], tokens=[2, 8, 9]), 3) == [1, 2, 8]

    def test_caps_at_k(self):
        d = NgramDrafter(max_ngram=1, min_ngram=1)
        got = d.propose(_req([5, 1, 2, 3, 4, 5]), 2)
        assert got == [1, 2]

    def test_validation(self):
        with pytest.raises(ValueError):
            NgramDrafter(max_ngram=2, min_ngram=3)
        with pytest.raises(ValueError):
            SpeculativeConfig(k=1)

    def test_config_drafter_passthrough(self):
        d = NgramDrafter(max_ngram=5)
        assert SpeculativeConfig(k=3, drafter=d).make_drafter() is d
        assert isinstance(SpeculativeConfig(k=3).make_drafter(),
                          NgramDrafter)


# ---------------------------------------------------------------------------
# scheduler accounting
# ---------------------------------------------------------------------------

class TestSpecScheduler:
    def _pool(self, pages=16, ps=4):
        return KVCachePool(1, pages, ps, 2, 8)

    def test_verify_token_reserve(self):
        pool = self._pool()
        sched = Scheduler(max_slots=4, prefill_token_budget=32)
        assert sched.verify_token_reserve() == 0
        sched.spec_k = 4
        sched.add(Request(rid="a", prompt=[1, 2, 3], max_new_tokens=4))
        sched.admit(pool)
        assert sched.verify_token_reserve() == 3  # (k-1) per running slot

    def test_admit_charges_verify_rows_like_prefill(self):
        pool = self._pool()
        sched = Scheduler(max_slots=4, prefill_token_budget=8)
        sched.spec_k = 4
        for i in range(3):
            sched.add(Request(rid=f"r{i}", prompt=[1, 2, 3, 4],
                              max_new_tokens=4))
        admitted = sched.admit(pool)
        # r0: 4 prefill + 3 verify rows = 7 of 8; r1 (another 4) exceeds
        # the remaining budget — without the verify charge both fit
        assert [r.rid for r in admitted] == ["r0"]

    def test_ensure_decode_pages_covers_draft_writes(self):
        pool = self._pool(pages=16, ps=4)
        sched = Scheduler(max_slots=2)
        sched.add(Request(rid="a", prompt=[1, 2, 3], max_new_tokens=8))
        (req,) = sched.admit(pool)
        assert len(req.pages) == 1          # context_len 3 of page_size 4
        req.draft_tokens = [7, 7, 7]        # writes at positions 3..6
        sched.ensure_decode_pages(pool)
        assert len(req.pages) == 2          # position 6 needs page 2

    def test_release_clears_drafts(self):
        pool = self._pool()
        sched = Scheduler(max_slots=1)
        sched.add(Request(rid="a", prompt=[1, 2, 3], max_new_tokens=8))
        (req,) = sched.admit(pool)
        req.tokens = [5]
        req.draft_tokens = [7, 8]
        sched.finish(req, pool, "length")
        assert req.draft_tokens == []


# ---------------------------------------------------------------------------
# engine: bitwise parity + O(1) programs
# ---------------------------------------------------------------------------

class TestSpecParity:
    def test_greedy_equivalence_staggered_arrivals(self, eng4, refs):
        # First use of the shared engine: a drafter that never proposes
        # keeps every DECODE step on the 1-token program — the mixed
        # program compiles once for the prefill chunk and must not
        # retrace when real drafts arrive below.
        class NoDrafter(DraftProposer):
            def propose(self, req, k):
                return []

        eng = _arm(eng4, NoDrafter())
        rid0 = eng.add_request(P5, 4)
        assert eng.run_to_completion(max_steps=50)[rid0] == refs[5][:4]
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}

        eng = _arm(eng4)
        rids = [eng.add_request(P5, MAX_NEW), eng.add_request(P9, MAX_NEW)]
        eng.step()
        rids.append(eng.add_request(P12, MAX_NEW))
        res = eng.run_to_completion(max_steps=200)
        for rid, ref in zip(rids, (refs[5], refs[9], refs[12])):
            assert res[rid] == ref
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}

    def test_greedy_equivalence_through_preemption(self, model, refs):
        """Preemption parity — and, on the same fresh engine, the full
        observability surface: draft/verify/rollback trace events, the
        one-time verify compile instant, and the Prometheus roundtrip
        of the spec counters (a fresh engine is needed to witness the
        compile event, so this test carries both loads)."""
        tr = Tracer()
        eng = _spec_engine(model, num_pages=7, max_slots=2,
                           max_pages_per_slot=6, tracer=tr)
        rids = [eng.add_request(p, MAX_NEW) for p in (P9, P12)]
        res = eng.run_to_completion(max_steps=500)
        assert eng.scheduler.num_preemptions > 0
        for rid, ref in zip(rids, (refs[9], refs[12])):
            assert res[rid] == ref
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}
        names = {e["name"] for e in tr.events}
        assert {"draft", "mixed_dispatch", "rollback"} <= names
        # the mixed program announces its compile exactly once (at the
        # first prefill chunk; verify rides the same program)
        compiles = [e for e in tr.events if e["name"] == "compile"
                    and e["args"].get("program") == "mixed"]
        assert len(compiles) == 1
        assert "decode_retraces" not in tr.counters
        # chrome export round-trips the new events
        doc = tr.chrome_trace()
        chrome_names = {e.get("name") for e in doc["traceEvents"]}
        assert {"draft", "mixed_dispatch", "rollback"} <= chrome_names
        # the spec counters survive the Prometheus render/parse roundtrip
        page = render_prometheus(eng.metrics.summary(), eng.pool.stats(),
                                 eng.tracer.counters)
        parsed = parse_prometheus(page)
        for key in ("paddle_serving_spec_accept_rate",
                    "paddle_serving_spec_draft_tokens_total",
                    "paddle_serving_spec_accepted_tokens_total",
                    "paddle_serving_spec_enabled",
                    "paddle_serving_pool_rewound_tokens"):
            assert key in parsed, key
        assert parsed["paddle_serving_spec_enabled"] == 1

    def test_sampled_stream_parity(self, model, eng4):
        """Sampled requests draw the SAME stream with speculation on —
        the verify step uses the identical fold_in(seed, token_index)
        keys — so speculation composes with the sampling contract."""
        sps = [SamplingParams(do_sample=True, top_p=0.9, temperature=0.8,
                              seed=7 + i) for i in range(2)]
        outs = []
        for eng in (ServingEngine(model, num_pages=64, page_size=4,
                                  max_slots=4, max_pages_per_slot=16),
                    _arm(eng4)):
            rids = [eng.add_request(p, MAX_NEW, sampling=sp)
                    for p, sp in zip((P5, P9), sps)]
            res = eng.run_to_completion(max_steps=200)
            outs.append([res[r] for r in rids])
        assert outs[0] == outs[1]

    def test_int8_kv_parity(self, model):
        """Speculation composes with the int8 KV pool: quantize-at-write
        per verify row, dequantize in the shared core — same stream."""
        outs = []
        for spec in (None, SpeculativeConfig(k=3)):
            eng = _spec_engine(model, spec=spec, kv_quant=True)
            rids = [eng.add_request(p, 6) for p in (P9, P12)]
            res = eng.run_to_completion(max_steps=200)
            outs.append([res[r] for r in rids])
        assert outs[0] == outs[1]

    def test_prefix_hit_churn_epochs_o1_programs(self, model, eng4):
        """3 churn epochs over a shared system prompt (prefix-cache hits
        on re-arrivals) with varying draft outcomes: parity holds and
        BOTH per-step-shape programs stay at exactly 1 compiled
        instance — O(1) in k, independent of accept patterns."""
        system = list(P9)
        eng = _arm(eng4)
        for epoch in range(3):
            prompts = [system + RNG.integers(0, 512, n).tolist()
                       for n in (2, 3)]
            refs = [_reference(model, p, 6) for p in prompts]
            rids = [eng.add_request(p, 6) for p in prompts]
            res = eng.run_to_completion(max_steps=300)
            for rid, ref in zip(rids, refs):
                assert res[rid] == ref, f"epoch {epoch}"
            assert eng.step_program_counts() == \
                {"decode": 1, "mixed": 1}, f"retraced in epoch {epoch}"
        assert eng.metrics.summary()["cache_hit_rate"] > 0
        assert eng.stats()["step_programs"] == {"decode": 1, "mixed": 1}

    def test_ngram_drafter_end_to_end(self, model, eng4):
        """Default n-gram drafter end to end: drafts are proposed and the
        stream still matches generate() exactly. The drafter matches the
        tail of prompt + GENERATED tokens, so whether it has anything to
        propose depends on the model's own stream — the prompt is chosen
        by asking the drafter itself, over the reference stream, not
        hard-coded to one JAX version's greedy output."""
        def drafts_somewhere(prompt, ref):
            return any(NgramDrafter().propose(_req(prompt, ref[:i]), KSPEC)
                       for i in range(1, len(ref)))

        # same length every try: one generate() program serves them all
        rng = np.random.default_rng(5)
        tries = (rng.integers(0, 512, 6).tolist() for _ in range(32))
        for prompt in tries:
            ref = _reference(model, prompt, 16)
            if drafts_somewhere(prompt, ref):
                break
        else:
            pytest.fail("no 6-token prompt in 32 tries whose greedy stream "
                        "gives the n-gram drafter a match")
        eng = _arm(eng4, NgramDrafter())
        rid = eng.add_request(prompt, 16)
        res = eng.run_to_completion(max_steps=100)
        assert res[rid] == ref
        assert eng.metrics.summary()["spec_draft_tokens_total"] > 0

    def test_oracle_drafter_full_accept_fewer_steps(self, eng4, refs):
        """A perfect drafter accepts everything: the stream is unchanged
        and the engine takes ~max_new/k verify steps instead of max_new
        decode steps."""
        eng = _arm(eng4, OracleDrafter({"fast": refs[9]}))
        s0 = eng.stats()["steps"]
        eng.add_request(P9, MAX_NEW, rid="fast")
        res = eng.run_to_completion(max_steps=50)
        assert res["fast"] == refs[9]
        s = eng.metrics.summary()
        assert s["spec_accept_rate"] == 1.0
        assert s["spec_draft_tokens_total"] == s["spec_accepted_tokens_total"]
        # prefill emits 1; the remaining 7 land in ceil(7/4) = 2 steps
        assert eng.stats()["steps"] - s0 <= 1 + 2

    def test_eos_inside_accept_window_truncates(self, eng4, refs):
        """eos landing mid-window stops the request AT the eos token even
        though later positions were accepted (exactly like sequential
        decode), and the unused tail is rewound."""
        ref = refs[9]
        eos = ref[2]
        k = ref.index(eos)
        eng = _arm(eng4, OracleDrafter({"e": ref}))
        rewound0 = eng.pool.counters["rewound_tokens"]
        eng.add_request(P9, MAX_NEW, eos_token_id=eos, rid="e")
        res = eng.run_to_completion(max_steps=50)
        assert res["e"] == ref[: k + 1]
        assert eng.request("e").finish_reason == "stop"
        if k + 1 < KSPEC:  # the stop landed inside the first accept window
            assert eng.pool.counters["rewound_tokens"] > rewound0


class TestSpecRollback:
    def test_rejected_rows_zeroed_all_rejected_still_exact(
            self, model, eng4, refs, fault_free):
        """A drafter that is always wrong: every step emits exactly one
        token (the stream stays exact), and after each verify step the
        rejected positions' KV is exactly zero — masked-garbage-is-zero
        at token granularity, proven by direct pool inspection."""
        ref = refs[9]
        eng = _arm(eng4, WrongDrafter({"w": ref}, model.config.vocab_size))
        eng.add_request(P9, MAX_NEW, rid="w")
        req = eng.request("w")
        eng.step()  # prefill + first token
        for _ in range(3):
            before = req.context_len
            eng.step()
            if req.done:
                break
            # every draft was rejected: exactly one token emitted, and
            # positions context_len .. before + k - 1 (the zapped draft
            # rows) must be exact zeros in every layer's pool
            assert req.context_len == before + 1
            ps = eng.page_size
            for p in range(req.context_len, before + KSPEC):
                if p // ps >= len(req.pages):
                    break
                page, off = req.pages[p // ps], p % ps
                for pk, pv in eng.pool.pools:
                    assert not np.asarray(pk[page, off]).any(), \
                        f"K garbage at position {p}"
                    assert not np.asarray(pv[page, off]).any(), \
                        f"V garbage at position {p}"
        assert eng.run_to_completion(max_steps=100)["w"] == ref
        s = eng.metrics.summary()
        assert s["spec_accept_rate"] == 0.0
        assert s["spec_draft_tokens_total"] > 0


# ---------------------------------------------------------------------------
# the mixed program samples only the rows whose sample can be emitted
# ---------------------------------------------------------------------------

_RNG_ROWS = np.random.default_rng(29)
P29, P7 = (_RNG_ROWS.integers(0, 512, n).tolist() for n in (29, 7))
SP29 = dict(do_sample=True, top_p=0.9, temperature=0.8, seed=17)
SP7 = dict(do_sample=True, top_p=0.7, temperature=1.3, seed=5)
ROWS_CHUNK, ROWS_SLOTS = 8, 3


def _sequential_sampled(model, prompt, n, sp):
    """The contract itself, with no engine in it: ``generate()``'s own
    prefill and one-token step programs over a contiguous cache, token
    ``i`` drawn with ``fold_in(PRNGKey(seed), i)``."""
    s0 = len(prompt)
    prefill, _, step = model.decode_programs(
        1, s0, n, s0 + n, True, sp["top_p"], sp["temperature"], None, None)
    state = model.state_dict(include_non_persistable_buffer=True)
    caches = model.init_kv_caches(1, s0 + n)

    def key(i):
        return jax.random.fold_in(jax.random.PRNGKey(sp["seed"]), i)

    tok, caches = prefill(state, jnp.asarray([prompt]), caches, key(0), None)
    out = [int(tok[0])]
    for i in range(1, n):
        tok, caches = step(state, tok, caches, s0 + i - 1, key(i), None)
        out.append(int(tok[0]))
    return out


@pytest.fixture(scope="module")
def sampled_refs(model, refs):
    return {"c": _sequential_sampled(model, P29, MAX_NEW, SP29),
            "v": _sequential_sampled(model, P7, 24, SP7),
            "g": refs[5]}


@pytest.fixture(scope="module")
def row_engines(model):
    """One engine per ``spec_k``, built on first use: 8-row chunks, so
    the 29-token prompt takes four of them."""
    engines = {}

    def get(k):
        if k not in engines:
            engines[k] = _spec_engine(
                model, spec=SpeculativeConfig(k=k) if k > 1 else None,
                max_slots=ROWS_SLOTS, prefill_chunk=ROWS_CHUNK)
        eng = engines[k]
        eng.metrics = ServingMetrics()
        eng.metrics.set_spec(k > 1)
        return eng
    return get


def _rows_shape(eng, k, refs, monkeypatch):
    samp, m, ok = eng._call_step(eng._mixed_step, eng._warm_lanes("mixed"))
    assert samp.shape == (ROWS_SLOTS, k)
    assert m.shape == ok.shape == (ROWS_SLOTS,)


def _rows_sampled_chunked_drafter(eng, k, refs, monkeypatch):
    """Sampled requests with a nucleus cut, a prompt of four chunks, a
    drafter whose guesses are mostly rejected: the streams are what
    sequential decode draws."""
    if k > 1:
        eng._drafter = RepeatDrafter()
    rids = [eng.add_request(P29, MAX_NEW, sampling=SamplingParams(**SP29)),
            eng.add_request(P5, MAX_NEW)]
    eng.step()
    rids.append(eng.add_request(P7, MAX_NEW, sampling=SamplingParams(**SP7)))
    res = eng.run_to_completion(max_steps=200)
    assert [res[r] for r in rids] == [refs["c"], refs["g"],
                                      refs["v"][:MAX_NEW]]
    if k > 1:
        assert eng.metrics.summary()["spec_draft_tokens_total"] > 0


def _rows_chunk_and_verify_in_one_step(eng, k, refs, monkeypatch):
    """A prompt's final chunk and a verify slot whose drafts all accept
    share a step: the chunk slot's first token is column 0 of its row
    of ``samp``, the verify slot's tokens columns ``0..m`` of its own."""
    if k > 1:
        eng._drafter = OracleDrafter({"v": refs["v"]})
    calls = []
    inner = eng._mixed_step

    def spy(*args):
        out = inner(*args)
        calls.append(tuple(np.asarray(a) for a in (*args[2:13], *out[:3])))
        return out
    spy._cache_size = inner._cache_size
    monkeypatch.setattr(eng, "_mixed_step", spy)
    eng.add_request(P7, 24, sampling=SamplingParams(**SP7), rid="v")
    eng.step()                       # "v" prefills and starts decoding
    eng.add_request(P29, MAX_NEW, sampling=SamplingParams(**SP29), rid="c")
    sv = eng.request("v").slot
    res = eng.run_to_completion(max_steps=200)
    assert res["v"] == refs["v"] and res["c"] == refs["c"]
    shared = []
    for (toks, tables, seq_lens, active, n_live, forced, temps, top_ps,
         greedy, seeds, counts, samp, m, ok) in calls:
        final = forced & (seq_lens + n_live == len(P29))
        if final.any() and active[sv] and not forced[sv]:
            shared.append((int(np.argmax(final)), counts, samp, m))
    assert len(shared) == 1
    sc, counts, samp, m = shared[0]
    assert samp.shape == (ROWS_SLOTS, k)
    assert int(samp[sc, 0]) == refs["c"][0]
    assert int(m[sv]) == k - 1       # the oracle's drafts all accept
    at = int(counts[sv])
    assert samp[sv, :k].tolist() == refs["v"][at:at + k]


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("case", [_rows_shape, _rows_sampled_chunked_drafter,
                                  _rows_chunk_and_verify_in_one_step],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_mixed_program_samples_spec_k_rows_a_slot(
        row_engines, sampled_refs, monkeypatch, case, k):
    eng = row_engines(k)
    case(eng, k, sampled_refs, monkeypatch)
    monkeypatch.undo()
    assert eng.step_program_counts()["mixed"] == 1
    if case is not _rows_shape:
        assert eng.step_program_counts() == {"decode": 1, "mixed": 1}


# ---------------------------------------------------------------------------
# metrics + observability
# ---------------------------------------------------------------------------

class TestSpecObservability:
    def test_metrics_accounting_and_histogram(self):
        m = ServingMetrics()
        m.set_spec(True)
        m.on_spec_draft(3)
        m.on_spec_draft(0)
        m.on_spec_verify(3, 2)
        m.on_spec_verify(3, 0)
        m.on_spec_verify(1, 1)
        s = m.summary()
        assert s["spec_enabled"] == 1
        assert s["spec_draft_tokens_total"] == 7
        assert s["spec_accepted_tokens_total"] == 3
        assert s["spec_accept_rate"] == pytest.approx(3 / 7)
        assert s["spec_draft_hit_rate"] == pytest.approx(0.5)
        h = m.spec_accept_histogram()
        assert h[3] == {"steps": 2, "accepted_mean": 1.0,
                        "accept_rate": pytest.approx(1 / 3)}
        assert h[1]["accept_rate"] == 1.0


# ---------------------------------------------------------------------------
# fleet failover with speculation on
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestSpecFleet:
    def test_kill_mid_run_replays_accepted_positions_bitwise(
            self, model, refs, fault_free):
        """Kill a replica mid-run with speculation enabled on every
        replica: failover replay stays exactly-once and bitwise. The
        router's emitted/produced dedup counts accepted POSITIONS (a
        verify step can emit several per request per step), not steps."""
        prompts = [P5, P9, P12]
        expect = [refs[5], refs[9], refs[12]]

        def mk():
            return _spec_engine(model, num_pages=64, page_size=16,
                                max_slots=4, max_pages_per_slot=8)

        router = FleetRouter([mk(), mk()])
        rids = [router.submit(p, MAX_NEW) for p in prompts]
        events = [ev for _ in range(3) for ev in router.step()]
        victim = router.request(rids[0]).replica
        replayed = sum(r.emitted for r in router._records.values()
                       if r.replica == victim)
        router.kill_replica(victim)
        while router.has_work():
            events.extend(router.step())
            assert router.stats()["steps"] < 500, "router hang"
        seen = {r: [] for r in rids}
        for ev in events:
            if ev["token"] is not None:
                seen[ev["rid"]].append(ev["token"])
        for rid, ref in zip(rids, expect):
            rec = router.request(rid)
            assert rec.tokens == ref            # bitwise vs generate()
            assert seen[rid] == ref             # exactly-once delivery
        # every emitted-then-replayed POSITION was verified + suppressed
        assert router.fleet_metrics.counters["replayed_tokens"] == replayed
        st = router.stats()
        for h in st["replica_health"]:
            if h["state"] != "dead":
                e = router.engines[h["replica"]]
                assert e.step_program_counts() == {"decode": 1, "mixed": 1}
