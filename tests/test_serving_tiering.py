"""paddle_tpu.serving.tiering — host-RAM KV spill tier + traffic harness.

The tiering contracts (SERVING.md "KV tiering & traffic harness"):

1. BITWISE RESTORE — a page that round-trips HBM -> host -> HBM carries
   exactly the bytes it spilled with, for fp32, bf16 AND int8 (codes
   and scales together); engine streams with tiering on are bitwise
   identical to ``model.generate()`` even when every shared prefix was
   served through a restore.
2. NEVER WRONG KV — a corrupted host payload (bit rot or the
   ``serving.restore`` fault site's ``poison``) is detected by the
   blake2b re-verify and falls back to recompute; quarantined pages
   never spill and quarantine purges their host entries.
3. NO NEW PROGRAMS — restores are host-side ``device_put``s at
   admission time; ``decode_program_count() == 1`` holds through spill/
   restore churn exactly as without a tier.
4. DETERMINISTIC TRAFFIC — a :class:`Workload` is a value: same seed,
   same trace, so A/B arms (tier off vs on) see identical arrivals and
   their deltas are attributable to the tier alone.

Chaos tests (deterministic FaultPlan replays) carry the ``faults``
marker, same as the serving/fleet suites.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.distributed import fault
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability import render_prometheus
from paddle_tpu.serving import (FleetRouter, HostTier, KVCachePool,
                                ServingEngine, ServingMetrics, Workload,
                                WorkloadRequest, WorkloadSpec, make_workload)

RNG = np.random.default_rng(23)


@pytest.fixture(scope="module")
def model():
    pt.seed(123)
    m = LlamaForCausalLM(llama_tiny(dtype="float32",
                                    mp_axis=None, fsdp_axis=None))
    m.eval()
    return m


@pytest.fixture
def fault_free(monkeypatch):
    """No FaultPlan leaks out of a chaos test; no rank env leaks in."""
    fault.deactivate()
    monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
    monkeypatch.delenv("PROCESS_ID", raising=False)
    monkeypatch.delenv("PADDLE_RESTART_EPOCH", raising=False)
    yield
    fault.deactivate()


def _reference(model, prompt, max_new, **kw):
    out = model.generate(jnp.asarray([prompt]), max_new_tokens=max_new, **kw)
    return np.asarray(out)[0, len(prompt):].tolist()


def _fill_pages(pool, pages, seed=0):
    """Write deterministic random content into ``pages`` of every layer
    (codes AND scales in quantized mode) so spill/restore has real bytes
    to round-trip."""
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(pages)
    for li, (pk, pv) in enumerate(pool.pools):
        pair = []
        for arr in (pk, pv):
            if hasattr(arr, "q"):      # QuantizedKV
                q = rng.integers(-127, 128,
                                 size=(len(pages),) + arr.q.shape[1:])
                s = rng.random((len(pages),) + arr.scale.shape[1:]) + 0.5
                arr = type(arr)(
                    arr.q.at[idx].set(jnp.asarray(q, arr.q.dtype)),
                    arr.scale.at[idx].set(jnp.asarray(s, arr.scale.dtype)))
            else:
                v = rng.standard_normal((len(pages),) + arr.shape[1:])
                arr = arr.at[idx].set(jnp.asarray(v, arr.dtype))
            pair.append(arr)
        pool.pools[li] = tuple(pair)


def _payloads(pool, pages):
    return [pool._page_payload(p) for p in pages]


def _assert_payloads_equal(a, b):
    assert len(a) == len(b)
    for xs, ys in zip(a, b):
        assert len(xs) == len(ys)
        for x, y in zip(xs, ys):
            assert x.dtype == y.dtype and x.shape == y.shape
            assert np.array_equal(np.asarray(x), np.asarray(y))


def _mk_pool(dtype="float32", **kw):
    cfg = dict(num_layers=2, num_pages=6, page_size=4, num_kv_heads=2,
               head_dim=8, host_tier=HostTier())
    if dtype == "int8":
        cfg["quantized"] = True
    else:
        cfg["dtype"] = jnp.dtype(dtype)
    cfg.update(kw)
    return KVCachePool(**cfg)


def _cache_two_pages(pool, tokens, seed=1):
    """Alloc+fill+register+release two full pages of ``tokens`` so they
    sit refcount-0 in the HBM LRU, ready to be evicted (and spilled)."""
    pages = pool.alloc(2)
    _fill_pages(pool, pages, seed=seed)
    pool.register_prefix(tokens, pages)
    before = _payloads(pool, pages)
    pool.release(pages)
    return pages, before


# ---------------------------------------------------------------------------
# HostTier: the bounded host-RAM LRU itself (pure numpy, no model)
# ---------------------------------------------------------------------------

class TestHostTier:
    def _page(self, seed=0, n=64):
        rng = np.random.default_rng(seed)
        return [rng.standard_normal(n).astype(np.float32),
                rng.integers(-127, 128, n).astype(np.int8)]

    def test_put_fetch_roundtrip_bitwise(self):
        tier = HostTier(max_bytes=1 << 20)
        arrays = self._page(0)
        assert tier.put("float32", "full", b"k1", arrays)
        got = tier.fetch("float32", "full", b"k1")
        for a, b in zip(arrays, got):
            assert np.array_equal(a, b) and a.dtype == b.dtype
        assert tier.counters["host_hits"] == 1
        assert tier.pool_bytes == sum(a.nbytes for a in arrays)

    def test_miss_counts(self):
        tier = HostTier()
        assert tier.fetch("float32", "full", b"nope") is None
        assert tier.counters["host_misses"] == 1

    def test_lru_eviction_under_byte_budget(self):
        one = sum(a.nbytes for a in self._page(0))
        tier = HostTier(max_bytes=2 * one)
        tier.put("float32", "full", b"a", self._page(1))
        tier.put("float32", "full", b"b", self._page(2))
        tier.fetch("float32", "full", b"a")     # refresh a's recency
        tier.put("float32", "full", b"c", self._page(3))
        assert not tier.has("float32", "full", b"b")   # LRU victim
        assert tier.has("float32", "full", b"a")
        assert tier.has("float32", "full", b"c")
        assert tier.counters["host_evictions"] == 1
        assert tier.pool_bytes <= tier.max_bytes

    def test_oversized_payload_refused_not_flushed(self):
        tier = HostTier(max_bytes=128)
        tier.put("float32", "full", b"a",
                 [np.zeros(16, np.float32)])            # 64 bytes, fits
        big = [np.zeros(64, np.float32)]                # 256 > budget
        assert not tier.put("float32", "full", b"b", big)
        assert tier.counters["spill_dropped"] == 1
        assert tier.has("float32", "full", b"a")        # not flushed for it

    def test_corrupt_detected_dropped_counted(self):
        tier = HostTier()
        tier.put("float32", "full", b"k", self._page(4))
        tier.corrupt("float32", "full", b"k")
        assert tier.fetch("float32", "full", b"k") is None
        assert tier.counters["restore_corrupt_detected"] == 1
        assert not tier.has("float32", "full", b"k")    # entry dropped
        # bytes accounting survives the drop
        assert tier.pool_bytes == 0

    def test_dtype_tag_namespacing(self):
        tier = HostTier()
        tier.put("float32", "full", b"k", self._page(5))
        assert not tier.has("int8", "full", b"k")
        assert not tier.has("bfloat16", "full", b"k")
        assert tier.fetch("int8", "full", b"k") is None

    def test_discard_and_restore_charge(self):
        tier = HostTier(restore_budget_frac=0.25)
        tier.put("float32", "partial", b"k", self._page(6))
        assert tier.discard("float32", "partial", b"k")
        assert not tier.discard("float32", "partial", b"k")
        assert tier.pool_bytes == 0
        assert tier.restore_charge(16) == 4
        assert tier.restore_charge(1) == 1      # ceil
        assert tier.restore_charge(0) == 0

    def test_zero_stats_schema_matches_stats(self):
        tier = HostTier()
        tier.put("float32", "full", b"k", self._page(7))
        assert set(tier.stats()) == set(HostTier.zero_stats())
        assert all(v == 0 for v in HostTier.zero_stats().values())

    def test_bad_config_rejected(self):
        with pytest.raises(ValueError):
            HostTier(max_bytes=0)
        with pytest.raises(ValueError):
            HostTier(restore_budget_frac=-0.1)


# ---------------------------------------------------------------------------
# Pool-level spill -> evict -> match(chain) -> restore
# ---------------------------------------------------------------------------

class TestPoolSpillRestore:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    def test_spill_restore_roundtrip_bitwise(self, dtype):
        pool = _mk_pool(dtype)
        tokens = list(range(10, 18))                   # 2 full pages
        pages, before = _cache_two_pages(pool, tokens)
        hold = pool.alloc(5)                           # evicts+spills both
        assert pool.host_tier.counters["spilled_pages"] == 2
        m = pool.match_prefix(tokens)
        assert m.cached_tokens == 0                    # gone from HBM
        assert len(m.chain) == 2 and m.host_tokens == 8
        assert m.total_cached == 8 and m.hit
        pool.free(hold)
        got, restored_tok = pool.restore_chain(m)
        assert len(got) == 2 and restored_tok == 8
        _assert_payloads_equal(before, _payloads(pool, got))
        assert pool.host_tier.counters["restored_pages"] == 2
        # restored pages are registered: a fresh match resolves in HBM
        m2 = pool.match_prefix(tokens)
        assert m2.cached_tokens == 8 and not m2.chain
        pool.release(got)

    def test_partial_page_spills_and_restores_bitwise(self):
        pool = _mk_pool("float32")
        tokens = list(range(30, 36))                   # 1 full + 2 partial
        pages = pool.alloc(2)
        _fill_pages(pool, pages, seed=3)
        pool.register_prefix(tokens, pages)
        before = _payloads(pool, pages)
        pool.release(pages)
        hold = pool.alloc(5)
        m = pool.match_prefix(tokens)
        assert len(m.chain) == 1
        assert m.host_partial_len == 2 and m.host_partial_key is not None
        assert m.total_cached == 6
        pool.free(hold)
        chain_pages, tok = pool.restore_chain(m)
        assert tok == 4
        payload = pool.fetch_host_partial(m)
        assert payload is not None
        dst = pool.alloc(1)[0]
        pool.restore_partial_into(dst, payload)
        _assert_payloads_equal(before, _payloads(pool, chain_pages + [dst]))
        # the partial landed in a PRIVATE page — not re-registered
        assert dst not in pool._page_key

    def test_restore_race_hbm_wins(self):
        """A chain key that is HBM-resident again by restore time is
        acquired, not fetched from host."""
        pool = _mk_pool("float32")
        tokens = list(range(50, 58))
        pages, before = _cache_two_pages(pool, tokens)
        hold = pool.alloc(5)
        m = pool.match_prefix(tokens)
        pool.free(hold)
        first, _ = pool.restore_chain(m)       # re-registers both keys
        hits_before = pool.host_tier.counters["host_hits"]
        again, tok = pool.restore_chain(m)     # same chain, now resident
        assert again == first and tok == 0     # acquired, zero restored
        assert pool.host_tier.counters["host_hits"] == hits_before
        for p in first:
            assert pool.refcount(p) == 2
        pool.release(first)
        pool.release(again)

    def test_quarantine_never_spills_and_purges_host_entry(self):
        pool = _mk_pool("float32")
        tokens = list(range(70, 78))
        pages, _ = _cache_two_pages(pool, tokens)
        # (a) quarantined-while-cached content must not spill later
        pool.quarantine(pages)
        pool.free(pool.alloc(5))               # churn: nothing to spill
        assert pool.host_tier.counters["spilled_pages"] == 0
        assert pool.host_tier.num_entries == 0
        # (b) content both HBM-registered and host-resident: quarantine
        # purges the host copy too
        pages2, _ = _cache_two_pages(pool, tokens, seed=2)
        hold = pool.alloc(5)                   # spill both
        assert pool.host_tier.num_entries == 2
        pool.free(hold)
        m = pool.match_prefix(tokens)
        got, _ = pool.restore_chain(m)         # resident again, same keys
        pool.quarantine(got)
        assert pool.host_tier.num_entries == 0
        pool.release(got)

    def test_shared_quarantined_page_blocked_from_spilling(self):
        pool = _mk_pool("float32")
        tokens = list(range(90, 98))
        pages = pool.alloc(2)
        _fill_pages(pool, pages, seed=5)
        pool.register_prefix(tokens, pages)    # still held (refcount 1)
        pool.quarantine(pages)                 # shared -> scrub-on-zero
        pool.release(pages)                    # scrubbed + freed now
        pool.free(pool.alloc(5))
        assert pool.host_tier.counters["spilled_pages"] == 0

    def test_corrupt_restore_falls_back_to_recompute(self):
        pool = _mk_pool("float32")
        tokens = list(range(110, 118))
        pages, _ = _cache_two_pages(pool, tokens)
        hold = pool.alloc(5)
        m = pool.match_prefix(tokens)
        pool.free(hold)
        # rot the FIRST chain entry in host RAM
        pool.host_tier.corrupt(pool._tier_tag, "full", m.chain[0])
        got, tok = pool.restore_chain(m)
        assert got == [] and tok == 0          # stop at the bad link
        assert pool.host_tier.counters["restore_corrupt_detected"] == 1
        # nothing was registered; the caller recomputes from scratch
        assert pool.match_prefix(tokens).cached_tokens == 0

    def test_no_tier_match_is_unchanged(self):
        pool = _mk_pool("float32", host_tier=None)
        tokens = list(range(130, 138))
        pages = pool.alloc(2)
        _fill_pages(pool, pages, seed=7)
        pool.register_prefix(tokens, pages)
        pool.release(pages)
        m = pool.match_prefix(tokens)
        assert m.cached_tokens == 8 == m.total_cached and not m.chain
        assert pool.restore_charge(m) == 0
        assert pool.stats()["host_tier"] == 0
        assert pool.stats()["host_pool_bytes"] == 0    # schema-stable

    def test_pool_stats_carry_host_breakdown(self):
        pool = _mk_pool("float32")
        tokens = list(range(150, 158))
        _cache_two_pages(pool, tokens)
        pool.free(pool.alloc(5))
        s = pool.stats()
        assert s["host_tier"] == 1
        assert s["spilled_pages"] == 2 and s["host_pool_pages"] == 2
        assert s["host_pool_bytes"] > 0
        # ...and render straight into the Prometheus page
        page = render_prometheus(pool_stats=s)
        assert "paddle_serving_pool_host_pool_bytes" in page
        assert "paddle_serving_pool_spilled_pages 2" in page

    def test_host_tier_int_shorthand_sets_budget(self):
        pool = _mk_pool("float32", host_tier=1 << 16)
        assert pool.host_tier.max_bytes == 1 << 16
        assert _mk_pool("float32", host_tier=True).host_tier is not None


# ---------------------------------------------------------------------------
# Workload: the deterministic traffic generator
# ---------------------------------------------------------------------------

class TestWorkload:
    def test_same_seed_same_trace(self):
        a = make_workload(seed=5, n_requests=24, rate=1.0)
        b = make_workload(seed=5, n_requests=24, rate=1.0)
        assert [(r.rid, r.arrival_step, r.prompt, r.max_new_tokens,
                 r.tenant) for r in a] == \
               [(r.rid, r.arrival_step, r.prompt, r.max_new_tokens,
                 r.tenant) for r in b]
        c = make_workload(seed=6, n_requests=24, rate=1.0)
        assert [r.prompt for r in a] != [r.prompt for r in c]

    def test_bursty_arrivals_respect_the_square_wave(self):
        wl = make_workload(seed=1, n_requests=40, arrival="bursty",
                           rate=0.5, burst_on=4, burst_off=12,
                           burst_factor=6.0, idle_factor=0.0)
        for r in wl:
            assert (r.arrival_step % 16) < 4    # idle windows are silent

    def test_zipf_head_is_hottest(self):
        wl = make_workload(seed=2, n_requests=200, rate=4.0,
                           tenants=4, zipf_alpha=1.5)
        counts = wl.stats()["tenant_counts"]
        assert counts[0] == max(counts) and counts[0] > counts[-1]

    def test_prompts_are_system_prefix_plus_bounded_suffix(self):
        spec = WorkloadSpec(seed=3, n_requests=30, rate=2.0,
                            system_len=(8, 12),
                            prompt_mix=((0.7, 4, 6), (0.3, 10, 16)),
                            max_new=(2, 5), vocab_size=64)
        wl = make_workload(spec)
        assert len(wl.system_prompts) == spec.tenants
        for sp in wl.system_prompts:
            assert 8 <= len(sp) <= 12
        for r in wl:
            sp = wl.system_prompts[r.tenant]
            assert r.prompt[:len(sp)] == sp
            assert 4 <= len(r.prompt) - len(sp) <= 16
            assert 2 <= r.max_new_tokens <= 5
            assert all(0 <= t < 64 for t in r.prompt)

    def test_stats_and_due_are_pure(self):
        wl = make_workload(seed=4, n_requests=10, rate=1.0)
        s = wl.stats()
        assert s["n_requests"] == 10 == len(wl)
        assert sum(s["tenant_counts"]) == 10
        assert sum(len(wl.due(t)) for t in range(wl.horizon + 1)) == 10
        assert wl.due(0) == wl.due(0)           # no cursor side effects

    def test_bad_specs_rejected(self):
        with pytest.raises(ValueError):
            make_workload(arrival="weibull")
        with pytest.raises(ValueError):
            make_workload(tenants=0)
        with pytest.raises(TypeError):
            make_workload(WorkloadSpec(), seed=1)
        with pytest.raises(ValueError):         # rate too low to place
            make_workload(n_requests=2, rate=0.0)

    def test_replay_mechanics_on_scripted_target(self):
        """Arrival-step pacing, shed counting and the drain tripwire,
        without compiling anything."""
        from paddle_tpu.serving.errors import QueueFullError

        class Target:
            def __init__(self, reject=()):
                self.reject = set(reject)
                self.seen = []          # (step, rid)
                self.steps = 0
                self.pending = 0

            def add_request(self, prompt, max_new, eos_token_id=None,
                            rid=None):
                if rid in self.reject:
                    raise QueueFullError("full")
                self.seen.append((self.steps, rid))
                self.pending += 1
                return rid

            def step(self):
                self.steps += 1
                if self.pending and self.steps % 2 == 0:
                    self.pending -= 1

            def has_work(self):
                return self.pending > 0

        wl = make_workload(seed=7, n_requests=6, rate=1.0)
        tgt = Target()
        out = wl.replay(tgt)
        assert out["submitted"] == 6 and out["shed"] == 0
        assert out["rids"] == [r.rid for r in wl.requests]
        for (step, rid), r in zip(tgt.seen, wl.requests):
            assert step == r.arrival_step       # submitted when due
        shed_rid = wl.requests[0].rid
        out2 = wl.replay(Target(reject={shed_rid}))
        assert out2["shed"] == 1 and out2["submitted"] == 5
        with pytest.raises(RuntimeError):       # never drains -> tripwire
            stuck = Target()
            stuck.step = lambda: None           # pending never drains
            wl.replay(stuck, max_steps=5)

    def test_replay_on_real_engine_is_deterministic(self, model):
        wl = make_workload(seed=8, n_requests=3, rate=1.0, tenants=2,
                           system_len=(4, 6), prompt_mix=((1.0, 2, 5),),
                           max_new=(2, 4), vocab_size=128)
        outs = []
        for _ in range(2):
            eng = ServingEngine(model, num_pages=64, page_size=4,
                                max_slots=2)
            res = wl.replay(eng, max_steps=500)
            assert res["submitted"] == 3 and res["shed"] == 0
            outs.append(eng.run_to_completion())    # drained: just collects
        assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Engine-level: tiering on, bitwise parity, one program
# ---------------------------------------------------------------------------

def _tenant_prompts(n_requests, system_len=24, suffix_len=6, tenants=2,
                    seed=31):
    """Alternating-tenant prompts sized so ~1.3 tenants fit in HBM:
    returning tenants must restore through the host tier."""
    rng = np.random.default_rng(seed)
    systems = [list(rng.integers(1, 500, system_len))
               for _ in range(tenants)]
    return [systems[i % tenants] + list(rng.integers(1, 500, suffix_len))
            for i in range(n_requests)]


class TestTieredEngine:
    def test_parity_with_real_restores_two_epochs(self, model, fault_free):
        """The acceptance run: serial alternating-tenant traffic through
        a pool that holds ~1.3 tenants, two epochs on ONE engine — every
        stream bitwise equals generate(), real restores happened, and
        the decode program count never moves."""
        prompts = _tenant_prompts(6)
        refs = [_reference(model, p, 6) for p in prompts]
        eng = ServingEngine(model, num_pages=14, page_size=4, max_slots=1,
                            prefill_token_budget=256, host_tier=HostTier())
        for epoch in range(2):
            for p, ref in zip(prompts, refs):
                rid = eng.add_request(p, 6)
                assert eng.run_to_completion(max_steps=100)[rid] == ref
            assert all(v == 1
                       for v in eng.step_program_counts().values()), epoch
        tier = eng.pool.host_tier
        assert tier.counters["restored_pages"] >= 12
        assert tier.counters["spilled_pages"] > 0
        assert eng.decode_program_count() == 1
        assert eng.stats()["host_tier"] is True
        # metrics surface the tier breakdown
        s = eng.metrics.summary()
        assert s["host_tier_enabled"] == 1
        assert s["prefill_restored_tokens"] > 0
        assert s["tier_host_hit_rate"] > 0
        assert s["spilled_bytes"] > 0 and s["restored_bytes"] > 0
        assert abs(s["tier_hbm_hit_rate"] + s["tier_host_hit_rate"]
                   + s["tier_miss_rate"] - 1.0) < 1e-9
        page = render_prometheus(s, eng.pool.stats())
        assert "paddle_serving_tier_host_hit_rate" in page
        assert "paddle_serving_spilled_bytes" in page

    def test_int8_tier_on_equals_tier_off_bitwise(self, model, fault_free):
        """Quantized KV: codes AND scales round-trip the host tier, so
        the tiered int8 engine matches the untiered one token-for-token
        while actually restoring pages; and under this forced eviction
        the hit rate is strictly higher with the tier (an eviction is a
        demotion, not a loss)."""
        prompts = _tenant_prompts(4, system_len=16, suffix_len=4)
        outs, hit_rates = [], []
        for tier in (None, HostTier()):
            eng = ServingEngine(model, num_pages=10, page_size=4,
                                max_slots=1, kv_quant=True, host_tier=tier)
            got = []
            for p in prompts:
                rid = eng.add_request(p, 4)
                got.append(eng.run_to_completion(max_steps=100)[rid])
            assert eng.decode_program_count() == 1
            outs.append(got)
            hit_rates.append(eng.metrics.summary()["cache_hit_rate"])
        assert outs[0] == outs[1]
        assert hit_rates[1] > hit_rates[0]
        assert eng.pool.host_tier.counters["restored_pages"] > 0
        assert eng.pool._tier_tag == "int8"

    def test_untiered_metrics_keep_tier_schema(self):
        m = ServingMetrics()
        s = m.summary()
        assert s["host_tier_enabled"] == 0
        assert s["spilled_bytes"] == 0 and s["tier_host_hit_rate"] == 0.0
        assert m.tier_hit_rates() == {"hbm": 0.0, "host": 0.0, "miss": 0.0}


# ---------------------------------------------------------------------------
# Chaos: the serving.spill / serving.restore fault sites
# ---------------------------------------------------------------------------

@pytest.mark.faults
class TestTieredChaos:
    def test_spill_storm_means_no_tier_not_wrong_tier(self, model,
                                                      fault_free):
        """Every spill dropped: hit-rate degrades to the untiered pool's
        but parity holds — a lost spill is a miss, never wrong KV."""
        fault.activate(fault.FaultPlan([
            fault.FaultSpec(site="serving.spill", action="raise",
                            once=False),
        ]))
        prompts = _tenant_prompts(4)
        refs = [_reference(model, p, 4) for p in prompts]
        eng = ServingEngine(model, num_pages=14, page_size=4, max_slots=1,
                            host_tier=HostTier())
        for p, ref in zip(prompts, refs):
            rid = eng.add_request(p, 4)
            assert eng.run_to_completion(max_steps=100)[rid] == ref
        tier = eng.pool.host_tier
        assert tier.num_entries == 0            # storm dropped everything
        assert tier.counters["spill_dropped"] > 0
        assert tier.counters["restored_pages"] == 0
        assert eng.decode_program_count() == 1
        eng.audit_pool()

    def test_restore_poison_detected_and_recomputed(self, model,
                                                    fault_free):
        """Every restore poisoned in host RAM: the digest re-verify
        catches each one and the scheduler recomputes — streams stay
        bitwise exact and wrong KV is never served."""
        fault.activate(fault.FaultPlan([
            fault.FaultSpec(site="serving.restore", action="poison",
                            once=False),
        ]))
        prompts = _tenant_prompts(4)
        refs = [_reference(model, p, 4) for p in prompts]
        eng = ServingEngine(model, num_pages=14, page_size=4, max_slots=1,
                            host_tier=HostTier())
        for p, ref in zip(prompts, refs):
            rid = eng.add_request(p, 4)
            assert eng.run_to_completion(max_steps=100)[rid] == ref
        tier = eng.pool.host_tier
        assert tier.counters["restore_corrupt_detected"] > 0
        assert tier.counters["restored_pages"] == 0
        assert eng.decode_program_count() == 1
        eng.audit_pool()

    def test_restore_fault_raise_falls_back(self, model, fault_free):
        """An injected restore failure (raise) on one chain key: those
        tokens recompute, counted as restore_failed, parity intact."""
        fault.activate(fault.FaultPlan([
            fault.FaultSpec(site="serving.restore", action="raise",
                            once=False),
        ]))
        prompts = _tenant_prompts(4)
        refs = [_reference(model, p, 4) for p in prompts]
        eng = ServingEngine(model, num_pages=14, page_size=4, max_slots=1,
                            host_tier=HostTier())
        for p, ref in zip(prompts, refs):
            rid = eng.add_request(p, 4)
            assert eng.run_to_completion(max_steps=100)[rid] == ref
        assert eng.pool.host_tier.counters["restore_failed"] > 0
        assert eng.pool.host_tier.counters["restored_pages"] == 0
        eng.audit_pool()

    def test_fleet_shared_tier_replica_kill_exact_or_classified(
            self, model, fault_free):
        """Two replicas share ONE HostTier (identical weights -> bitwise
        identical KV); a mid-run replica kill must leave every request
        bitwise exact or classified, with the tier active and no hang."""
        tier = HostTier()
        engines = [ServingEngine(model, num_pages=14, page_size=4,
                                 max_slots=1, prefill_token_budget=256,
                                 host_tier=tier) for _ in range(2)]
        router = FleetRouter(engines)
        prompts = _tenant_prompts(6)
        refs = [_reference(model, p, 4) for p in prompts]
        rids = [router.submit(p, 4) for p in prompts]
        for _ in range(3):
            router.step()
        victim = router.request(rids[0]).replica
        router.kill_replica(0 if victim is None else victim)
        out = router.run_to_completion(max_steps=600)   # hang tripwire
        classified = 0
        for rid, ref in zip(rids, refs):
            rec = router.request(rid)
            assert rec.finished
            if rec.finish_reason in ("stop", "length"):
                assert out[rid] == ref
            else:
                classified += 1
        assert classified == 0                  # failover replays exactly
        assert tier.counters["spilled_pages"] > 0
        for eng in engines:
            if eng.stats()["steps"]:
                eng.audit_pool()
