"""paddle_tpu.observability — tracing, flight recorder, SLO export.

The contracts (OBSERVABILITY.md):

1. ZERO-COST OFF — the NULL_TRACER hot path records nothing and
   allocates nothing; tracing ON must not perturb the engine either:
   token streams stay bitwise identical to ``model.generate()`` and the
   decode step stays ONE compiled program.
2. LOADABLE TRACES — ``chrome_trace()`` emits Chrome trace-event JSON
   (every event has ph/ts/pid/tid, durations carry dur, instants carry
   scope) with one thread per track so requests render as rows.
3. STATE AT DEATH — the FlightRecorder is a bounded ring over the event
   stream, auto-dumped to rank-annotated JSON (ONE schema) when the
   engine hits a terminal condition; a stall snapshot points at the
   file.
"""

import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed import fault
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability import (NULL_TRACER, PROFILE_TRACER,
                                      FlightRecorder, MetricsServer, Tracer,
                                      parse_prometheus)
from paddle_tpu.observability.recorder import SCHEMA
from paddle_tpu.serving import (SchedulerStalledError, ServingEngine,
                                ServingMetrics)

import jax.numpy as jnp

RNG = np.random.default_rng(11)


@pytest.fixture(scope="module")
def model():
    pt.seed(123)
    m = LlamaForCausalLM(llama_tiny(dtype="float32",
                                    mp_axis=None, fsdp_axis=None))
    m.eval()
    return m


def _reference(model, prompt, max_new):
    out = model.generate(jnp.asarray([prompt]), max_new_tokens=max_new)
    return np.asarray(out)[0, len(prompt):].tolist()


@pytest.fixture
def fault_free(monkeypatch):
    """No FaultPlan leaks out of a chaos test, no rank env leaks in."""
    fault.deactivate()
    monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
    monkeypatch.delenv("PROCESS_ID", raising=False)
    monkeypatch.delenv("PADDLE_RESTART_EPOCH", raising=False)
    yield
    fault.deactivate()


def _vclock():
    t = [0.0]
    return t, (lambda: t[0])


# ---------------------------------------------------------------------------
# tracer: virtual-clock timelines, zero-cost off
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_records_measured_duration(self):
        t, clock = _vclock()
        tr = Tracer(clock=clock)
        with tr.span("decode_dispatch", slots=2):
            t[0] = 0.5
        (ev,) = tr.events
        assert ev["ph"] == "X" and ev["name"] == "decode_dispatch"
        assert ev["ts"] == 0.0 and ev["dur"] == 0.5
        assert ev["track"] == "engine" and ev["args"] == {"slots": 2}

    def test_lifecycle_timeline_on_a_request_track(self):
        t, clock = _vclock()
        tr = Tracer(clock=clock)
        tr.begin("queued", track="r-0", prompt=3)
        t[0] = 1.0
        tr.instant("admit", track="r-0", slot=0)
        tr.end("queued", track="r-0")
        t[0] = 2.5
        tr.instant("finish", track="r-0", reason="stop")
        assert [(e["ph"], e["name"], e["ts"]) for e in tr.events] == [
            ("B", "queued", 0.0), ("i", "admit", 1.0),
            ("E", "queued", 1.0), ("i", "finish", 2.5)]
        assert all(e["track"] == "r-0" for e in tr.events)

    def test_bump_accumulates_and_records_counter_events(self):
        tr = Tracer(clock=lambda: 0.0)
        tr.bump("compiles")
        tr.bump("compiles", 2)
        tr.bump("tokens", track="engine")
        assert tr.counters == {"compiles": 3, "tokens": 1}
        c0, c1, _ = tr.events
        assert c0["ph"] == "C" and c0["args"] == {"compiles": 1}
        assert c1["args"] == {"compiles": 3}

    def test_disabled_tracer_is_a_noop(self):
        tr = Tracer(enabled=False)
        with tr.span("x"):
            pass
        tr.begin("b")
        tr.end("b")
        tr.instant("i")
        tr.bump("c")
        assert tr.events == [] and tr.counters == {}
        # the null span context is shared — no per-call allocation
        assert NULL_TRACER.span("a") is NULL_TRACER.span("b")
        assert NULL_TRACER.events == []

    def test_sink_subscription_is_idempotent(self):
        tr = Tracer(clock=lambda: 0.0)
        seen = []
        tr.add_sink(seen.append)
        tr.add_sink(seen.append)  # engine re-attach must not double-record
        tr.instant("once")
        assert len(seen) == 1


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

class TestChromeTrace:
    def _traced(self):
        t, clock = _vclock()
        tr = Tracer(clock=clock)
        with tr.span("step", steps=1):
            t[0] = 0.001
        tr.begin("queued", track="r-0")
        tr.end("queued", track="r-0")
        tr.instant("quarantine", track="pool", pages=1)
        tr.bump("compiles")
        return tr

    def test_every_event_carries_the_required_schema_keys(self):
        tr = self._traced()
        doc = json.loads(json.dumps(tr.chrome_trace()))  # round-trips
        events = doc["traceEvents"]
        assert events, "empty trace"
        for ev in events:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev), ev
            if ev["ph"] == "X":
                assert "dur" in ev, ev
            if ev["ph"] == "i":
                assert ev["s"] == "t", ev
        # timestamps are scaled to microseconds at dump time
        span = next(e for e in events if e["ph"] == "X")
        assert span["dur"] == pytest.approx(1000.0)  # 0.001 s

    def test_tracks_become_named_threads(self):
        doc = self._traced().chrome_trace()
        names = {e["args"]["name"]: e["tid"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert set(names) == {"engine", "r-0", "pool"}
        assert names["engine"] == 0  # engine registered first: row 0
        assert len(set(names.values())) == 3  # one distinct row per track
        by_tid = {names["r-0"]: "r-0", names["pool"]: "pool"}
        for ev in doc["traceEvents"]:
            if ev["ph"] in ("B", "E"):
                assert by_tid[ev["tid"]] == "r-0"

    def test_dump_is_atomic_and_loadable(self, tmp_path):
        path = str(tmp_path / "traces" / "serve.trace.json")
        out = self._traced().dump_chrome_trace(path)
        assert out == path
        with open(path) as f:
            doc = json.load(f)
        assert doc["displayTimeUnit"] == "ms"
        assert not (tmp_path / "traces" / "serve.trace.json.tmp").exists()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_keeps_only_the_last_capacity_events(self):
        tr = Tracer(clock=lambda: 0.0)
        rec = FlightRecorder(capacity=8, tracer=tr)
        for i in range(20):
            tr.instant(f"e{i}")
        assert len(rec) == 8
        names = [e["name"] for e in rec.events()]
        assert names == [f"e{i}" for i in range(12, 20)]  # oldest dropped
        assert sum(rec.histogram().values()) == 8

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError, match="capacity"):
            FlightRecorder(capacity=0)

    def test_dump_writes_rank_annotated_schema(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
        tr = Tracer(clock=lambda: 0.0)
        rec = FlightRecorder(capacity=16, tracer=tr,
                             dump_dir=str(tmp_path))
        tr.instant("stall", queue=2)
        path = rec.dump("scheduler stalled!", snapshot={"idle_steps": 3})
        assert path.endswith("flight_recorder.rank3.scheduler_stalled_.json")
        with open(path) as f:
            payload = json.load(f)
        assert payload["schema"] == SCHEMA
        assert payload["rank"] == 3
        assert payload["reason"] == "scheduler stalled!"
        assert payload["snapshot"] == {"idle_steps": 3}
        assert payload["n_events"] == 1
        assert payload["histogram"] == {"stall": 1}
        assert payload["events"][0]["name"] == "stall"
        assert rec.last_dump_path == path and rec.dumps == 1


# ---------------------------------------------------------------------------
# engine integration: tracing must not perturb serving
# ---------------------------------------------------------------------------

class TestEngineTracing:
    def test_tracing_off_by_default(self, model):
        # the default tracer follows the profiler: off outside a session
        eng = ServingEngine(model, num_pages=16, page_size=4, max_slots=2)
        assert eng.tracer is PROFILE_TRACER
        assert eng.scheduler.tracer is eng.pool.tracer is PROFILE_TRACER
        assert eng.stats()["tracing"] is False

    def test_tracing_on_bitwise_parity_single_decode_program(self, model):
        prompts = [list(RNG.integers(0, 512, n)) for n in (5, 9, 3)]
        max_new = 8
        refs = [_reference(model, p, max_new) for p in prompts]
        tr = Tracer()
        eng = ServingEngine(model, num_pages=64, page_size=4, max_slots=4,
                            max_pages_per_slot=8, tracer=tr)
        assert eng.stats()["tracing"] is True
        rids = [eng.add_request(prompts[0], max_new),
                eng.add_request(prompts[1], max_new)]
        eng.step()
        rids.append(eng.add_request(prompts[2], max_new))
        res = eng.run_to_completion(max_steps=200)
        for rid, ref in zip(rids, refs):
            assert res[rid] == ref  # bitwise: tracing observes, not alters
        assert eng.decode_program_count() == 1
        assert "decode_retraces" not in tr.counters
        # the step phases, lifecycle events and compile markers all
        # landed (chunked default: prompts stream through the mixed
        # program, so chunk instants replace prefill_dispatch spans)
        names = {e["name"] for e in tr.events}
        assert {"deadline_sweep", "admission", "mixed_dispatch",
                "chunk", "decode_dispatch", "device_sync", "sample_emit",
                "queued", "running", "admit", "finish",
                "compile"} <= names, names
        assert tr.counters["tokens"] == sum(len(r) for r in refs)
        assert tr.counters["finishes"] == 3
        assert tr.counters["compiles"] >= 2  # mixed program + decode
        # every request track's B/E durations are balanced — the Chrome
        # B/E stack per tid corrupts if the scheduler mislays one side
        for rid in rids:
            evs = [e for e in tr.events if e["track"] == rid]
            for phase in ("queued", "running"):
                b = sum(1 for e in evs
                        if e["name"] == phase and e["ph"] == "B")
                e_ = sum(1 for e in evs
                         if e["name"] == phase and e["ph"] == "E")
                assert b == e_ > 0, (rid, phase, b, e_)

    @pytest.mark.faults
    def test_stall_dumps_the_flight_recorder(self, model, tmp_path,
                                             fault_free):
        # every pool alloc fails -> zero admission progress -> the stall
        # backstop fires; the snapshot must point at the dump file
        fault.activate(fault.FaultPlan([
            fault.FaultSpec(site="serving.alloc", action="raise",
                            prob=1.0, once=False)]))
        tr = Tracer()
        rec = FlightRecorder(capacity=64, tracer=tr,
                             dump_dir=str(tmp_path))
        eng = ServingEngine(model, num_pages=32, page_size=4, max_slots=2,
                            tracer=tr, flight_recorder=rec)
        eng.add_request([1, 2, 3], 4)
        with pytest.raises(SchedulerStalledError) as ei:
            eng.run_to_completion(max_steps=50)
        path = ei.value.snapshot["flight_recorder"]
        assert path == rec.last_dump_path
        with open(path) as f:
            payload = json.load(f)
        assert payload["schema"] == SCHEMA
        assert payload["reason"] == "scheduler_stalled"
        assert payload["histogram"]["admit_rollback"] >= 1
        assert payload["snapshot"]["idle_steps"] >= 1
        eng.audit_pool()

    def test_drain_dumps_outcomes(self, model, tmp_path):
        tr = Tracer()
        rec = FlightRecorder(capacity=64, tracer=tr,
                             dump_dir=str(tmp_path))
        eng = ServingEngine(model, num_pages=32, page_size=4, max_slots=2,
                            tracer=tr, flight_recorder=rec)
        rid = eng.add_request(list(RNG.integers(0, 512, 4)), 16)
        eng.step()
        eng.step()
        eng.drain(timeout_s=0.0)
        with open(rec.last_dump_path) as f:
            payload = json.load(f)
        assert payload["reason"] == "drain"
        assert payload["snapshot"]["outcomes"] == {rid: "preempted"}

    def test_metrics_server_scrapes_a_live_engine(self, model):
        tr = Tracer()
        eng = ServingEngine(model, num_pages=32, page_size=4, max_slots=2,
                            tracer=tr)
        eng.add_request(list(RNG.integers(0, 512, 5)), 6)
        eng.run_to_completion(max_steps=100)
        srv = MetricsServer(engine=eng)
        port = srv.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                body = r.read().decode()
            metrics = parse_prometheus(body)
            assert metrics["paddle_serving_requests_finished"] == 1
            assert metrics["paddle_serving_tokens_generated"] == 6
            assert "paddle_serving_goodput_at_slo" in metrics
            assert "paddle_serving_pool_peak_in_use" in metrics
            assert metrics["paddle_serving_trace_tokens_total"] == 6
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
                health = json.loads(r.read().decode())
            assert health["status"] == "ok"
            assert health["running"] == 0
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# goodput under SLO
# ---------------------------------------------------------------------------

class TestGoodput:
    def _metrics(self):
        t, clock = _vclock()
        m = ServingMetrics(clock=clock)
        # r-good: ttft 0.5s, itl gaps 0.1s, normal finish
        m.on_arrival("r-good")
        t[0] = 0.5
        m.on_token("r-good")
        t[0] = 0.6
        m.on_token("r-good")
        t[0] = 0.7
        m.on_token("r-good")
        m.on_finish("r-good", "stop")
        # r-slow: normal finish but ttft 3s blows the SLO
        m.on_arrival("r-slow")
        t[0] = 3.0
        m.on_token("r-slow")
        m.on_finish("r-slow", "length")
        # r-dead: fast but abnormal finish — never good
        m.on_arrival("r-dead")
        t[0] = 3.1
        m.on_token("r-dead")
        t[0] = 4.0
        m.on_finish("r-dead", "nonfinite")
        return m  # wall = 4.0s

    def test_goodput_counts_only_slo_meeting_normal_finishes(self):
        m = self._metrics()
        # unconstrained: both normal finishes count, the abnormal never
        assert m.goodput_at_slo() == pytest.approx(2 / 4.0)
        # TTFT SLO of 1s drops r-slow
        assert m.goodput_at_slo(ttft_p99_s=1.0) == pytest.approx(1 / 4.0)
        # ITL SLO below r-good's 0.1s gaps drops it too
        assert m.goodput_at_slo(ttft_p99_s=1.0,
                                itl_p99_s=0.05) == 0.0
        assert m.goodput_at_slo(ttft_p99_s=1.0,
                                itl_p99_s=0.2) == pytest.approx(1 / 4.0)

    def test_summary_carries_goodput_at_the_configured_slo(self):
        m = self._metrics()
        s = m.summary()
        assert s["goodput_at_slo"] == pytest.approx(2 / 4.0)  # no SLO set
        m.set_slo(ttft_p99_s=1.0, itl_p99_s=0.25)
        assert m.summary()["goodput_at_slo"] == pytest.approx(1 / 4.0)


# ---------------------------------------------------------------------------
# /metrics without an engine
# ---------------------------------------------------------------------------

def test_metrics_endpoint_serves_parseable_prometheus_text():
    """Tier-1-safe /metrics smoke: a MetricsServer on an ephemeral port
    fed by an explicit render callable (no engine, no jax) must serve
    text every strict Prometheus parser accepts, plus /healthz JSON."""
    import urllib.request

    from paddle_tpu.observability import (MetricsServer, parse_prometheus,
                                          render_prometheus)

    text_src = render_prometheus(
        {"tokens_per_s": 12.5, "ttft_p99_s": 0.25, "goodput_at_slo": 3.0,
         "note": "non-numeric values are skipped"},
        {"in_use": 7, "utilization": 0.5},
        {"compiles": 2})
    srv = MetricsServer(render=lambda: text_src,
                        health=lambda: {"status": "ok"})
    port = srv.start()
    try:
        assert port != 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        metrics = parse_prometheus(body)  # raises on any malformed line
        assert metrics["paddle_serving_tokens_per_seconds"] == 12.5
        assert metrics["paddle_serving_ttft_p99_seconds"] == 0.25
        assert metrics["paddle_serving_goodput_at_slo"] == 3.0
        assert metrics["paddle_serving_pool_in_use"] == 7
        assert metrics["paddle_serving_trace_compiles_total"] == 2
        assert "paddle_serving_note" not in metrics
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            assert json.loads(r.read().decode()) == {"status": "ok"}
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10) as r:
            raise AssertionError("unknown path must 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404
    finally:
        srv.stop()
