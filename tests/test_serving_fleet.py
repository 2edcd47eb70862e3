"""paddle_tpu.serving.fleet — replicated serving with failover replay.

The fleet contracts (SERVING.md "Engine fleet & failover"):

1. EXACTLY-ONCE — kill/stall/drain a replica at ANY point of a stream
   and the client-visible token sequence is bitwise identical to an
   unfailed run: replay regenerates, the router's emitted/produced
   dedup suppresses, nothing duplicates and nothing is lost. The
   property sweep kills at every possible emitted count k.
2. CLASSIFIED OR EXACT — under chaos (kill + stall + poison, one
   replica each) every request either matches single-engine
   ``generate()`` bitwise or ends in a typed/classified outcome; the
   router never hangs (``run_to_completion(max_steps=...)`` is the
   tripwire) and ``decode_program_count() == 1`` on every survivor.
3. HEALTH — transient dispatch failures trip a consecutive-failure
   circuit breaker (OPEN -> deterministic bounded backoff ->
   HALF_OPEN probe -> CLOSED), a full global queue sheds with the
   retryable ``FleetOverloadedError``, and an all-dead fleet sheds its
   queue with classified ``finish_reason="shed"`` instead of spinning.

Router logic is exercised on scripted fake engines (fast, tier-1); the
real-model chaos acceptance runs llama_tiny replicas behind ``slow`` /
``faults`` markers.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.distributed import fault
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability import (FlightRecorder, Tracer,
                                      parse_prometheus,
                                      render_fleet_prometheus)
from paddle_tpu.serving import (EngineDrainingError, FleetOverloadedError,
                                FleetRouter, QueueFullError,
                                RequestTooLargeError, SamplingParams,
                                SchedulerStalledError, ServingEngine,
                                ServingError)
from paddle_tpu.serving.fleet import CLOSED, DEAD, HALF_OPEN, OPEN

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


# ---------------------------------------------------------------------------
# scripted fake engine: the duck-typed surface the router depends on
# ---------------------------------------------------------------------------

class FakeScheduler:
    def __init__(self, max_queue_depth=None):
        self.waiting = []
        self.running = {}
        self.max_queue_depth = max_queue_depth

    @property
    def queue_depth(self):
        return len(self.waiting)

    def has_work(self):
        return bool(self.waiting or self.running)

    def live_requests(self):
        return list(self.waiting) + list(self.running.values())


class FakeReq:
    def __init__(self, rid, prompt, sampling):
        self.rid = rid
        self.prompt = prompt
        self.sampling = sampling
        self.produced = 0


class FakePool:
    """Just enough pool for affinity: a set of known prefixes."""

    def __init__(self, prefixes=()):
        self.cache_enabled = True
        self.fault_path = None
        self._prefixes = [list(p) for p in prefixes]

    def utilization(self):
        return 0.0

    def match_prefix(self, tokens):
        class M:
            cached_tokens = 0
        m = M()
        for p in self._prefixes:
            if list(tokens[:len(p)]) == p:
                m.cached_tokens = max(m.cached_tokens, len(p))
        return m


class FakeEngine:
    """Deterministic scripted engine: request [p0, ...] emits the stream
    p0*100, p0*100+1, ... — same tokens wherever (re)placed, which is
    exactly the determinism the real engine guarantees."""

    def __init__(self, max_slots=4, max_queue_depth=None, prefixes=(),
                 add_fails=0, stall_after=None):
        self.scheduler = FakeScheduler(max_queue_depth)
        self.pool = FakePool(prefixes)
        self._draining = False
        self.last_drain_events = []
        self.max_slots = max_slots
        self.add_fails = add_fails        # QueueFullError for first N adds
        self.stall_after = stall_after    # step() raises after N steps
        self.steps = 0
        self.flight_recorder = None

    def admission_check(self, prompt_len, max_new_tokens):
        if prompt_len + max_new_tokens > 10_000:
            raise RequestTooLargeError("scripted: never fits")

    def add_request(self, prompt, max_new_tokens, sampling=None,
                    eos_token_id=None, rid=None, deadline_s=None,
                    max_queue_wait_s=None):
        if self._draining:
            raise EngineDrainingError("draining")
        if self.add_fails > 0:
            self.add_fails -= 1
            raise QueueFullError("scripted queue full")
        r = FakeReq(rid, list(prompt), sampling)
        r.max_new = max_new_tokens
        if len(self.scheduler.running) < self.max_slots:
            slot = min(set(range(self.max_slots))
                       - set(self.scheduler.running))
            self.scheduler.running[slot] = r
        else:
            self.scheduler.waiting.append(r)
        return rid

    def step(self):
        self.steps += 1
        if self.stall_after is not None and self.steps > self.stall_after:
            raise SchedulerStalledError("scripted stall", {"step": self.steps})
        events = []
        while (self.scheduler.waiting
               and len(self.scheduler.running) < self.max_slots):
            slot = min(set(range(self.max_slots))
                       - set(self.scheduler.running))
            self.scheduler.running[slot] = self.scheduler.waiting.pop(0)
        for slot, r in sorted(self.scheduler.running.items()):
            tok = r.prompt[0] * 100 + r.produced
            r.produced += 1
            fin = r.produced >= r.max_new
            events.append({"rid": r.rid, "token": tok, "finished": fin,
                           "finish_reason": "length" if fin else None})
            if fin:
                del self.scheduler.running[slot]
        return events

    def drain(self, timeout_s=None):
        self._draining = True
        events = []
        for r in self.scheduler.waiting:
            events.append({"rid": r.rid, "token": None, "finished": True,
                           "finish_reason": "preempted"})
        self.scheduler.waiting.clear()
        while self.scheduler.running:
            events.extend(self.step())
        self.last_drain_events = events
        return {}

    def decode_program_count(self):
        return 1


def _expected(prompt, max_new):
    return [prompt[0] * 100 + i for i in range(max_new)]


# ---------------------------------------------------------------------------
# routing: admission, shedding, placement
# ---------------------------------------------------------------------------

class TestFleetRouting:
    def test_round_trip_two_replicas(self, fault_free):
        router = FleetRouter([FakeEngine(), FakeEngine()])
        r1 = router.submit([3], 4)
        r2 = router.submit([5], 4)
        out = router.run_to_completion(max_steps=50)
        assert out[r1] == _expected([3], 4)
        assert out[r2] == _expected([5], 4)
        assert router.request(r1).finish_reason == "length"
        assert not router.has_work()

    def test_global_queue_sheds_with_typed_error(self, fault_free):
        router = FleetRouter([FakeEngine()], max_queue_depth=2)
        router.submit([1], 2)
        router.submit([2], 2)
        with pytest.raises(FleetOverloadedError) as ei:
            router.submit([3], 2)
        assert ei.value.retryable is True
        assert router.fleet_metrics.counters["shed"] == 1
        assert router.metrics.counters["rejected_queue_full"] == 1

    def test_too_large_rejected_fleet_wide(self, fault_free):
        router = FleetRouter([FakeEngine(), FakeEngine()])
        with pytest.raises(RequestTooLargeError) as ei:
            router.submit([1], 20_000)
        assert ei.value.retryable is False
        assert router.metrics.counters["rejected_too_large"] == 1

    def test_draining_fleet_refuses_submission(self, fault_free):
        router = FleetRouter([FakeEngine()])
        router.drain()
        with pytest.raises(EngineDrainingError):
            router.submit([1], 2)

    def test_least_loaded_placement(self, fault_free):
        a, b = FakeEngine(max_slots=8), FakeEngine(max_slots=8)
        router = FleetRouter([a, b])
        for i in range(6):
            router.submit([i + 1], 4)
        router.step()
        # greedy least-loaded alternates 3/3
        assert len(a.scheduler.running) == 3
        assert len(b.scheduler.running) == 3

    def test_prefix_affinity_beats_emptier_replica(self, fault_free):
        cold = FakeEngine(max_slots=8)
        warm = FakeEngine(max_slots=8, prefixes=[[7, 7, 7]])
        router = FleetRouter([cold, warm])
        # load the warm replica so pure least-loaded would pick cold
        router.submit([1], 8)
        router.step()
        assert router.request("fleet-req-0").replica == 0
        rid = router.submit([7, 7, 7, 9], 4)
        router.step()
        assert router.request(rid).replica == 1  # affinity won

    def test_fleet_rid_uniqueness(self, fault_free):
        router = FleetRouter([FakeEngine()])
        router.submit([1], 2, rid="dup")
        with pytest.raises(ValueError, match="duplicate"):
            router.submit([2], 2, rid="dup")


# ---------------------------------------------------------------------------
# failover replay: the exactly-once property sweep
# ---------------------------------------------------------------------------

class TestFailoverReplay:
    def test_kill_at_every_emitted_count_stream_identical(self, fault_free):
        """THE exactly-once property: kill the serving replica at every
        possible client-visible token count k — the final stream must
        be bitwise identical to the unfailed run (no dup, no gap), with
        exactly k replayed-and-suppressed positions."""
        max_new = 8
        expected = _expected([7], max_new)
        for k in range(max_new):
            router = FleetRouter([FakeEngine(), FakeEngine()])
            rid = router.submit([7], max_new)
            guard = 0
            while router.request(rid).emitted < k:
                router.step()
                guard += 1
                assert guard < 50, "sweep runaway"
            # k=0: not dispatched yet — kill the replica placement WOULD
            # pick (dead-before-first-token is still a valid kill point)
            victim = router.request(rid).replica
            router.kill_replica(0 if victim is None else victim)
            out = router.run_to_completion(max_steps=100)
            assert out[rid] == expected, f"k={k}: {out[rid]}"
            assert router.request(rid).finish_reason == "length"
            assert router.fleet_metrics.counters["replayed_tokens"] == k
            assert router.fleet_metrics.counters["failovers"] == \
                (1 if victim is not None else 0)

    def test_chaos_kill_via_fault_site(self, fault_free):
        """fleet.replica_kill with match pinned to one replica index."""
        router = FleetRouter([FakeEngine(), FakeEngine()])
        fault.activate(fault.FaultPlan([
            fault.FaultSpec(site="fleet.replica_kill", action="raise",
                            step=2, match=r"^1$"),
        ]))
        rids = [router.submit([i + 1], 6) for i in range(4)]
        out = router.run_to_completion(max_steps=100)
        for i, rid in enumerate(rids):
            assert out[rid] == _expected([i + 1], 6)
        st = router.stats()
        assert st["replicas_ejected"] == 1
        assert st["replica_health"][1]["state"] == DEAD
        assert st["replica_health"][1]["dead_reason"] == "killed"
        assert st["fleet"]["failovers"] == 2  # replica 1 held 2 of the 4

    def test_stalled_replica_ejected_and_replayed(self, fault_free):
        router = FleetRouter([FakeEngine(stall_after=2), FakeEngine()])
        rids = [router.submit([i + 1], 6) for i in range(4)]
        out = router.run_to_completion(max_steps=100)
        for i, rid in enumerate(rids):
            assert out[rid] == _expected([i + 1], 6)
        st = router.stats()
        assert st["replicas_ejected"] == 1
        assert st["replica_health"][0]["dead_reason"] == "stalled"
        assert st["fleet"]["failovers"] >= 1

    def test_all_replicas_dead_sheds_classified(self, fault_free):
        router = FleetRouter([FakeEngine(), FakeEngine()])
        rid = router.submit([3], 4)
        router.kill_replica(0)
        router.kill_replica(1)
        out = router.run_to_completion(max_steps=10)   # must NOT hang
        assert out[rid] == []
        assert router.request(rid).finish_reason == "shed"
        assert router.fleet_metrics.counters["shed"] == 1
        assert not router.has_work()

    def test_replay_divergence_is_a_hard_error(self, fault_free):
        """A replica that replays DIFFERENT tokens breaks the
        determinism contract — the router must refuse to stream it."""

        class Liar(FakeEngine):
            def step(self):
                events = super().step()
                for ev in events:
                    if ev["token"] is not None:
                        ev["token"] += 1_000_000   # never matches
                return events

        router = FleetRouter([FakeEngine(), Liar()])
        rid = router.submit([5], 6)
        while router.request(rid).emitted < 2:
            router.step()
        assert router.request(rid).replica == 0   # least-loaded tie -> 0
        router.kill_replica(0)                    # replay lands on Liar
        with pytest.raises(RuntimeError, match="replay divergence"):
            router.run_to_completion(max_steps=50)


# ---------------------------------------------------------------------------
# health: circuit breaker, backoff, probing
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def test_breaker_opens_then_probes_then_closes(self, fault_free):
        eng = FakeEngine(add_fails=3)      # first 3 dispatches bounce
        router = FleetRouter([eng], breaker_threshold=3,
                             breaker_backoff_steps=2,
                             breaker_backoff_max=4)
        rid = router.submit([4], 3)
        router.step()   # 1st failure
        router.step()   # 2nd failure
        router.step()   # 3rd failure -> OPEN
        st = router.stats()["replica_health"][0]
        assert st["state"] == OPEN
        assert router.fleet_metrics.counters["breaker_opens"] == 1
        assert st["backoff_remaining"] > 0
        out = router.run_to_completion(max_steps=50)
        assert out[rid] == _expected([4], 3)      # placed after the probe
        assert router.stats()["replica_health"][0]["state"] == CLOSED
        assert router.fleet_metrics.counters["probes"] >= 1

    def test_half_open_failure_reopens_with_longer_backoff(self, fault_free):
        eng = FakeEngine(add_fails=4)      # probe itself fails once
        router = FleetRouter([eng], breaker_threshold=3,
                             breaker_backoff_steps=2,
                             breaker_backoff_max=8)
        rid = router.submit([4], 3)
        deadline = 0
        while router.fleet_metrics.counters["breaker_opens"] < 2:
            router.step()
            deadline += 1
            assert deadline < 60
        assert router.stats()["replica_health"][0]["state"] == OPEN
        out = router.run_to_completion(max_steps=80)
        assert out[rid] == _expected([4], 3)

    def test_jitter_is_deterministic(self):
        a = FleetRouter._jitter(1, 2, 8)
        b = FleetRouter._jitter(1, 2, 8)
        assert a == b
        assert 0 <= a < 8

    def test_health_fault_site_counts_as_breaker_failure(self, fault_free):
        router = FleetRouter([FakeEngine(), FakeEngine()],
                             breaker_threshold=1, breaker_backoff_steps=2)
        fault.activate(fault.FaultPlan([
            fault.FaultSpec(site="fleet.health", action="raise",
                            step=0, match=r"^1$"),
        ]))
        rid = router.submit([6], 3)
        router.step()
        st = router.stats()["replica_health"]
        assert st[1]["state"] == OPEN          # injected probe failure
        assert st[0]["state"] == CLOSED
        assert router.request(rid).replica == 0
        out = router.run_to_completion(max_steps=50)
        assert out[rid] == _expected([6], 3)

    def test_open_replica_keeps_stepping_inflight_work(self, fault_free):
        """The breaker gates NEW placements only."""
        eng = FakeEngine(max_slots=8)
        router = FleetRouter([eng], breaker_threshold=1)
        rid = router.submit([2], 5)
        router.step()                           # placed + first token
        eng.add_fails = 5                       # now dispatches bounce
        router.submit([3], 5)                   # will open the breaker
        out = router.run_to_completion(max_steps=300)
        assert out[rid] == _expected([2], 5)    # in-flight work finished


# ---------------------------------------------------------------------------
# drain + preemption guard
# ---------------------------------------------------------------------------

class TestFleetDrain:
    def test_drain_classifies_queued_and_finishes_running(self, fault_free):
        eng = FakeEngine(max_slots=1)
        router = FleetRouter([eng])
        r1 = router.submit([4], 3)
        router.step()                  # r1 running (1 token)
        r2 = router.submit([5], 3)     # stays in the router queue: slot busy
        eng.add_fails = 99
        router.step()
        report = router.drain()
        assert report[r1]["finish_reason"] == "length"
        assert report[r1]["tokens"] == _expected([4], 3)
        assert report[r1]["retriable"] is False
        assert report[r2]["finish_reason"] == "preempted"
        assert report[r2]["retriable"] is True
        assert report[r2]["tokens"] == []

    def test_preemption_guard_composes(self, fault_free):
        router = FleetRouter([FakeEngine(), FakeEngine()])
        guard = router.attach_preemption_guard()
        try:
            r1 = router.submit([4], 6)
            events = []
            it = router.stream()
            events.append(next(it))
            guard.request()            # SIGTERM equivalent
            events.extend(it)
            terminal = [e for e in events if e["finished"]]
            assert terminal and all(
                e["finish_reason"] in ("preempted", "length", "stop")
                for e in terminal)
            rec = router.request(r1)
            assert rec.finished
            # nothing the client saw is lost on the preempted path
            assert rec.tokens == _expected([4], 6)[:len(rec.tokens)]
        finally:
            guard.uninstall()

    def test_drain_is_reported_in_stats(self, fault_free):
        router = FleetRouter([FakeEngine()])
        router.drain()
        assert router.stats()["draining"] is True


# ---------------------------------------------------------------------------
# retryable attributes (satellite: machine-readable error surface)
# ---------------------------------------------------------------------------

class TestRetryableSurface:
    @pytest.mark.parametrize("cls,flag", [
        (ServingError, False),
        (QueueFullError, True),
        (RequestTooLargeError, False),
        (SchedulerStalledError, True),
        (EngineDrainingError, True),
        (FleetOverloadedError, True),
    ])
    def test_retryable_class_attribute(self, cls, flag):
        assert cls.retryable is flag
        if cls is SchedulerStalledError:
            assert cls("x").retryable is flag
        elif cls is not ServingError:
            assert cls("x").retryable is flag

    def test_fleet_overloaded_is_serving_error(self):
        assert issubclass(FleetOverloadedError, ServingError)


# ---------------------------------------------------------------------------
# observability: per-replica labels, fleet gauges, parseability
# ---------------------------------------------------------------------------

class TestFleetExport:
    def test_render_fleet_prometheus_round_trips(self, fault_free):
        router = FleetRouter([FakeEngine(), FakeEngine()])
        rid = router.submit([3], 4)
        router.step()
        router.kill_replica(router.request(rid).replica)
        router.run_to_completion(max_steps=50)
        text = render_fleet_prometheus(router)
        parsed = parse_prometheus(text)   # strict: every line well-formed
        assert parsed["paddle_serving_fleet_replicas"] == 2
        assert parsed["paddle_serving_fleet_replicas_live"] == 1
        assert parsed["paddle_serving_fleet_replicas_ejected"] == 1
        assert parsed["paddle_serving_fleet_failovers_total"] == 1
        assert parsed["paddle_serving_fleet_replayed_tokens_total"] >= 1
        assert parsed["paddle_serving_fleet_shed_total"] == 0
        # per-replica series carry the replica label
        ups = {k: v for k, v in parsed.items()
               if k.startswith("paddle_serving_fleet_replica_up")}
        assert len(ups) == 2
        assert sum(ups.values()) == 1     # one dead, one alive
        assert 'paddle_serving_fleet_replica_queue_depth{replica="0"}' \
            in parsed
        # the client-visible summary rides along unlabeled
        assert parsed["paddle_serving_tokens_generated"] == 4

    def test_parse_accepts_labels_rejects_garbage(self):
        parsed = parse_prometheus(
            'metric_a{replica="0"} 1\nmetric_a{replica="1"} 2\n')
        assert parsed == {'metric_a{replica="0"}': 1.0,
                          'metric_a{replica="1"}': 2.0}
        with pytest.raises(ValueError, match="malformed"):
            parse_prometheus('metric_a{replica=0} 1\n')
        with pytest.raises(ValueError, match="malformed"):
            parse_prometheus('metric_a{replica="0" 1\n')

    def test_router_spans_land_on_fleet_track(self, fault_free):
        tr = Tracer()
        router = FleetRouter([FakeEngine(), FakeEngine()], tracer=tr)
        rid = router.submit([3], 3)
        router.step()
        router.kill_replica(router.request(rid).replica)
        router.run_to_completion(max_steps=50)
        names = {e["name"] for e in tr.events if e.get("track") == "fleet"}
        assert {"submit", "dispatch", "replica_eject", "failover",
                "finish"} <= names


# ---------------------------------------------------------------------------
# real-model acceptance: chaos under load (slow/faults)
# ---------------------------------------------------------------------------

def _mk_engine(model, recorder=None, **kw):
    cfg = dict(num_pages=64, page_size=16, max_slots=4)
    cfg.update(kw)
    return ServingEngine(model, flight_recorder=recorder, **cfg)


class TestFleetRealModel:
    @pytest.mark.slow
    def test_kill_mid_stream_bitwise_parity(self, model, fault_free):
        prompts = [RNG.integers(1, 500, size=int(n)).tolist()
                   for n in (5, 9, 7, 12)]
        refs = [_reference(model, p, 8) for p in prompts]
        router = FleetRouter([_mk_engine(model), _mk_engine(model)])
        rids = [router.submit(p, 8) for p in prompts]
        for _ in range(3):
            router.step()
        router.kill_replica(router.request(rids[0]).replica)
        out = router.run_to_completion(max_steps=300)
        for rid, ref in zip(rids, refs):
            assert out[rid] == ref
        for h in router.stats()["replica_health"]:
            if h["state"] != DEAD:
                assert router.engines[h["replica"]] \
                    .decode_program_count() == 1

    @pytest.mark.slow
    def test_kill_at_every_k_real_engine(self, model, fault_free):
        """Real-engine version of the property sweep (short stream)."""
        prompt = RNG.integers(1, 500, size=6).tolist()
        max_new = 5
        ref = _reference(model, prompt, max_new)
        for k in range(max_new):
            router = FleetRouter([_mk_engine(model), _mk_engine(model)])
            rid = router.submit(prompt, max_new)
            guard = 0
            while router.request(rid).emitted < k:
                router.step()
                guard += 1
                assert guard < 50
            # a fresh request can emit 2 tokens in its first engine step
            # (prefill + decode) — assert against the count actually
            # delivered when the kill lands, not the loop target
            at_kill = router.request(rid).emitted
            victim = router.request(rid).replica
            router.kill_replica(0 if victim is None else victim)
            out = router.run_to_completion(max_steps=200)
            assert out[rid] == ref, f"k={k}"
            assert router.fleet_metrics.counters["replayed_tokens"] \
                == at_kill

    @pytest.mark.faults
    def test_chaos_acceptance_kill_stall_poison(self, model, fault_free,
                                                tmp_path):
        """ISSUE acceptance: 3 replicas, >= 24 requests, one replica
        killed, one stalled (pinned alloc storm), one request
        NaN-poisoned — every request is bitwise-exact or classified,
        zero dup/lost tokens, no hangs, 1 decode program per survivor."""
        n_req = 24
        max_new = 6
        prompts = [RNG.integers(1, 500, size=int(RNG.integers(4, 12)))
                   .tolist() for _ in range(n_req)]
        refs = [_reference(model, p, max_new) for p in prompts]
        recorders = [FlightRecorder(dump_dir=str(tmp_path))
                     for _ in range(3)]
        engines = [_mk_engine(model, recorder=recorders[i])
                   for i in range(3)]
        router = FleetRouter(engines, max_queue_depth=64)
        poisoned_rid = "fleet-req-5"
        fault.activate(fault.FaultPlan([
            # kill replica 1 mid-run
            fault.FaultSpec(site="fleet.replica_kill", action="raise",
                            step=4, match=r"^2$"),
            # permanent alloc storm pinned to replica 0 -> it stalls and
            # is ejected with its in-flight requests replayed elsewhere
            fault.FaultSpec(site="serving.alloc", action="raise",
                            once=False, match=r"^0$"),
            # NaN-poison one request's KV wherever it runs
            fault.FaultSpec(site="serving.decode", action="poison",
                            match=rf"^{poisoned_rid}$"),
        ]))
        rids = []
        events = []
        for i, p in enumerate(prompts):
            rids.append(router.submit(p, max_new))
            events.extend(router.step())    # staggered arrivals
        while router.has_work():
            events.extend(router.step())
            assert router.stats()["steps"] < 2000, "router hang"
        # exactly-once: the event stream carries each delivered token
        # once, and it equals the per-request record
        seen: dict[str, list] = {r: [] for r in rids}
        for ev in events:
            if ev["token"] is not None:
                seen[ev["rid"]].append(ev["token"])
        classified = 0
        for rid, ref in zip(rids, refs):
            rec = router.request(rid)
            assert rec.finished
            assert seen[rid] == rec.tokens      # no dup, no gap
            if rec.finish_reason in ("stop", "length"):
                assert rec.tokens == ref        # bitwise single-engine
            else:
                classified += 1
                assert rec.finish_reason in (
                    "nonfinite", "injected", "shed", "preempted",
                    "timeout", "preempted_limit")
        assert classified >= 1                  # the poisoned one
        assert router.request(poisoned_rid).finish_reason in (
            "nonfinite", "injected")
        st = router.stats()
        assert st["replicas_ejected"] == 2      # killed + stalled
        dead = {h["dead_reason"] for h in st["replica_health"]
                if h["state"] == DEAD}
        assert dead == {"killed", "stalled"}
        assert st["fleet"]["failovers"] >= 1
        # flight recorder dumped on every ejection
        for h in st["replica_health"]:
            if h["state"] == DEAD:
                assert h["flight_recorder"] is not None
        for h in st["replica_health"]:
            if h["state"] != DEAD:
                assert router.engines[h["replica"]] \
                    .decode_program_count() == 1
                # chaos left the pool's bookkeeping invariants intact
                router.engines[h["replica"]].audit_pool()
