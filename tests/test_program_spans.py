"""The program's one span path (OBSERVABILITY.md "Tracer"): the
process-wide ``PROFILE_TRACER`` that ``ServingEngine`` and
``jit.TrainStep`` hold records exactly while a JAX profiler session is
on, every span with its parent and step index and also as a
``serve.*`` / ``train.*`` annotation in the profiler's own trace;
outside a session nothing is recorded. The step programs carry the
names of their layer parts in every operation's ``op_name``.
"""

import gc
import glob
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability import NULL_TRACER, PROFILE_TRACER
from paddle_tpu.profiler import RecordEvent
from paddle_tpu.serving import (BrownoutConfig, DraftProposer, ServingEngine,
                                SnapshotStore, SpeculativeConfig)

SLOTS, CHUNK, PAGES_PER_SLOT, PAGE = 2, 8, 8, 4
PROMPT_LENS, MAX_NEW = (11, 5, 3), 6

# every phase of ``ServingEngine.step`` and what it is a child of
ENGINE_PHASES = ("deadline_sweep", "admission", "ensure_pages", "plan",
                 "build_inputs", "decode_dispatch", "mixed_dispatch",
                 "watchdog_arm", "device_sync", "sample_emit", "bookkeeping")
OPTIONAL_PHASES = ("brownout", "draft", "snapshot_capture")
TRAIN_PHASES = ("step_args", "dispatch", "writeback")


class _session:
    """A profiler session as the harness opens one: host annotations on,
    Python's own tracer off. Afterwards ``events`` and ``counters`` hold
    what PROFILE_TRACER recorded in it: the events by their ``ts``, the
    counters' growth by the counter events' increments, which is how a
    reader takes a window out of the process-wide ring."""

    def __init__(self, path):
        self.path = str(path)

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self._t0 = PROFILE_TRACER.now()
        jax.profiler.start_trace(self.path, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        self.events = [e for e in PROFILE_TRACER.events
                       if e["ts"] >= self._t0]
        self.counters = {}
        for e in self.events:
            if e["ph"] == "C":
                self.counters[e["name"]] = (
                    self.counters.get(e["name"], 0) + e["n"])
        return False

    def host_event_names(self) -> set:
        (path,) = glob.glob(os.path.join(
            self.path, "plugins", "profile", "*", "*.xplane.pb"))
        pd = jax.profiler.ProfileData.from_file(path)
        return {e.name for plane in pd.planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events}


@pytest.fixture(scope="module")
def model():
    pt.seed(123)
    m = LlamaForCausalLM(llama_tiny(dtype="float32",
                                    mp_axis=None, fsdp_axis=None))
    m.eval()
    return m


def _prompts():
    rng = np.random.default_rng(5)
    return [list(rng.integers(0, 512, n)) for n in PROMPT_LENS]


def _scripted_run(model, **kw):
    """Three requests through two slots: chunked prefills, decode steps,
    a queue. Returns the engine and the token streams by rid."""
    eng = ServingEngine(model, num_pages=64, page_size=PAGE, max_slots=SLOTS,
                        max_pages_per_slot=PAGES_PER_SLOT,
                        prefill_chunk=CHUNK, **kw)
    for p in _prompts():
        eng.add_request(p, MAX_NEW)
    return eng, eng.run_to_completion(max_steps=200)


class _Repeat(DraftProposer):
    def propose(self, req, k):
        return [int((req.tokens or req.prompt)[-1])] * k


@pytest.fixture(scope="module")
def untraced(model):
    before = len(PROFILE_TRACER.events)
    eng, streams = _scripted_run(model)
    return {"streams": streams,
            "events": len(PROFILE_TRACER.events) - before,
            "enabled": eng.stats()["tracing"],
            "programs": eng.step_program_counts()}


@pytest.fixture(scope="module")
def traced(model, untraced, tmp_path_factory):
    """The same scripted run inside a profiler session."""
    with _session(tmp_path_factory.mktemp("serve_trace")) as ses:
        eng, streams = _scripted_run(model)
    return {"streams": streams, "events": ses.events,
            "counters": ses.counters,
            "host_names": ses.host_event_names(), "steps": eng._steps,
            "programs": eng.step_program_counts()}


@pytest.fixture(scope="module")
def traced_waits(model, tmp_path_factory):
    """Two runs in one session whose first tokens wait. A budget of one
    chunk and two prompts of two chunks each: the younger prompt sits
    out both steps of the older. A pool of six pages: the younger
    prompt, left out of step 0, is preempted in step 1 when the older
    request's decode needs a page, and waits in the queue until the
    older one finishes."""
    rng = np.random.default_rng(9)
    with _session(tmp_path_factory.mktemp("serve_trace_waits")) as ses:
        tight = ServingEngine(model, num_pages=64, page_size=PAGE,
                              max_slots=SLOTS,
                              max_pages_per_slot=PAGES_PER_SLOT,
                              prefill_chunk=CHUNK,
                              prefill_token_budget=CHUNK)
        rids = [tight.add_request(list(rng.integers(0, 512, 2 * CHUNK)),
                                  MAX_NEW) for _ in range(2)]
        tight.run_to_completion(max_steps=200)
        small = ServingEngine(model, num_pages=7, page_size=PAGE,
                              max_slots=SLOTS,
                              max_pages_per_slot=PAGES_PER_SLOT,
                              prefill_chunk=CHUNK,
                              prefill_token_budget=CHUNK)
        old = small.add_request(list(rng.integers(0, 512, CHUNK)), 8,
                                rid="old")
        young = small.add_request(list(rng.integers(0, 512, 2 * CHUNK)), 4,
                                  rid="young")
        small.run_to_completion(max_steps=200)
    points = {e["track"]: e for e in _first_tokens(ses.events)}
    return {"tight": [points[r] for r in rids],
            "old": points[old], "young": points[young],
            "preemptions": small.scheduler.num_preemptions}


@pytest.fixture(scope="module")
def traced_optional(model, tmp_path_factory):
    """An engine with the ladder, a drafter and a snapshot store, so
    that the phases only they have are recorded too."""
    with _session(tmp_path_factory.mktemp("serve_trace_opt")) as ses:
        _scripted_run(model,
                      speculative=SpeculativeConfig(k=3, drafter=_Repeat()),
                      brownout=BrownoutConfig(high_queue=8, low_queue=2),
                      snapshot_store=SnapshotStore(), snapshot_interval=2)
    return {"events": ses.events, "counters": ses.counters}


def _tiny_train_step():
    from paddle_tpu import nn
    import paddle_tpu.nn.functional as F
    pt.seed(7)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 3))
    opt = pt.optimizer.AdamW(learning_rate=1e-2, parameters=net)
    return pt.jit.TrainStep(net, opt, lambda out, y: F.cross_entropy(out, y))


@pytest.fixture(scope="module")
def traced_train(tmp_path_factory):
    rng = np.random.default_rng(3)
    x = rng.standard_normal((16, 8)).astype("float32")
    y = rng.integers(0, 3, 16)
    step = _tiny_train_step()
    before = len(PROFILE_TRACER.events)
    losses = [float(step(x, y))]                 # compiles, no session
    assert len(PROFILE_TRACER.events) == before
    with _session(tmp_path_factory.mktemp("train_trace")) as ses:
        losses += [float(step(x, y)) for _ in range(3)]
        step(x[:8], y[:8])                       # a new shape: compiles
    ref = _tiny_train_step()
    ref_losses = [float(ref(x, y)) for _ in range(4)]
    return {"events": ses.events, "counters": ses.counters,
            "host_names": ses.host_event_names(),
            "losses": losses, "ref_losses": ref_losses}


def _spans(events, name, track=None):
    return [e for e in events if e["ph"] == "X" and e["name"] == name
            and (track is None or e["track"] == track)]


def _first_tokens(events):
    return [e for e in events if e["ph"] == "i" and e["name"] == "first_token"]


# ---------------------------------------------------------------------------
# off outside a session
# ---------------------------------------------------------------------------

def test_outside_a_session_nothing_is_recorded(untraced):
    assert untraced["events"] == 0 and untraced["enabled"] is False
    assert PROFILE_TRACER.enabled is False
    # the shared null context: no allocation on the hot path
    assert PROFILE_TRACER.span("step", step=1) is NULL_TRACER.span("x")
    before = len(PROFILE_TRACER.events), dict(PROFILE_TRACER.counters)
    PROFILE_TRACER.bump("tokens")
    PROFILE_TRACER.instant("compile")
    assert (len(PROFILE_TRACER.events),
            dict(PROFILE_TRACER.counters)) == before


def test_token_streams_are_bitwise_the_same_in_a_session(untraced, traced):
    assert traced["streams"] == untraced["streams"]
    assert all(len(t) == MAX_NEW for t in traced["streams"].values())
    # and the session compiled nothing: one program of each shape
    assert traced["programs"] == untraced["programs"] == {"decode": 1,
                                                          "mixed": 1}


def test_the_buffer_is_a_ring_and_the_counters_run_on(traced, tmp_path):
    assert PROFILE_TRACER.events.maxlen >= 1 << 16
    tokens = PROFILE_TRACER.counters["tokens"]
    assert tokens >= traced["counters"]["tokens"]
    with _session(tmp_path) as ses:
        assert PROFILE_TRACER.enabled
        PROFILE_TRACER.bump("tokens", 3)
    assert not PROFILE_TRACER.enabled
    assert PROFILE_TRACER.counters["tokens"] == tokens + 3
    # a window's share of a counter: the increments of its events
    # (a collection the session's own set-up set off is on the host track)
    assert ses.counters == {"tokens": 3}
    assert len([e for e in ses.events if e["track"] != "host"]) == 1
    from paddle_tpu.observability import Tracer
    ring = Tracer(clock=lambda: 0.0, capacity=4)
    for i in range(10):
        ring.instant(f"e{i}")
    assert [e["name"] for e in ring.events] == ["e6", "e7", "e8", "e9"]


# ---------------------------------------------------------------------------
# engine step phases
# ---------------------------------------------------------------------------

def test_one_step_span_per_engine_step_with_its_program(traced):
    steps = _spans(traced["events"], "step", "engine")
    assert [s["step"] for s in steps] == list(range(traced["steps"]))
    assert all(s["parent"] is None for s in steps)
    for s in steps:
        assert s["args"]["program"] in ("decode", "mixed")
        assert 1 <= s["args"]["slots"] <= SLOTS
        assert (s["args"]["chunk_tokens"] > 0) <= (
            s["args"]["program"] == "mixed")
    assert {s["args"]["program"] for s in steps} == {"decode", "mixed"}


@pytest.mark.parametrize("name", ENGINE_PHASES)
def test_engine_phase_is_a_child_of_its_step(traced, name):
    spans = _spans(traced["events"], name, "engine")
    assert spans, name
    steps = {s["step"]: s for s in _spans(traced["events"], "step")}
    for sp in spans:
        assert sp["parent"] == "step" and sp["step"] in steps, sp
        parent = steps[sp["step"]]
        assert parent["ts"] <= sp["ts"]
        assert sp["ts"] + sp["dur"] <= parent["ts"] + parent["dur"]
    if name not in ("decode_dispatch", "mixed_dispatch"):
        assert len(spans) == len(steps)      # once in every step


@pytest.mark.parametrize("name", OPTIONAL_PHASES)
def test_optional_engine_phase_is_recorded(traced_optional, name):
    spans = _spans(traced_optional["events"], name, "engine")
    assert spans and all(s["parent"] == "step" and s["step"] is not None
                         for s in spans)


def _uncovered_shares(events, track):
    """Per step of ``track``: the share of the ``step`` span that its
    child spans leave uncovered."""
    kids = {}
    for e in events:
        if e["ph"] == "X" and e["parent"] == "step" and e["track"] == track:
            kids[e["step"]] = kids.get(e["step"], 0.0) + e["dur"]
    shares = [1.0 - kids.get(s["step"], 0.0) / s["dur"]
              for s in _spans(events, "step", track)]
    assert all(0.0 <= x <= 1.0 for x in shares)
    return shares


def test_children_cover_their_step_to_within_a_quarter(traced):
    """What a step's phases leave uncovered is the Python between them
    (two fault hooks, two clock reads, disarming the watchdog, freeing
    the step's inputs, the span objects themselves): a tenth of a
    millisecond or two, which at this toy size, where a decode step
    takes two milliseconds, is about a tenth of the step (on the chip:
    0.5 of 38 ms, PERF.md). The median, so that one collector pause does
    not decide it."""
    shares = _uncovered_shares(traced["events"], "engine")
    assert np.median(shares) < 0.25, sorted(shares)


def test_both_dispatch_spans_cover_the_compiled_call_only(traced):
    """``build_inputs`` precedes the dispatch in both paths; the
    dispatch span ends before the watchdog is armed and the sync begins."""
    ev = [e for e in traced["events"] if e["ph"] == "X"
          and e["parent"] == "step" and e["track"] == "engine"]
    by_step = {}
    for e in ev:
        by_step.setdefault(e["step"], []).append(e)
    for step, spans in by_step.items():
        spans.sort(key=lambda e: e["ts"])
        names = [e["name"] for e in spans]
        i = names.index("build_inputs")
        assert names[i + 1] in ("decode_dispatch", "mixed_dispatch")
        assert names[i + 2:i + 5] == ["watchdog_arm", "device_sync",
                                      "sample_emit"]
        assert names[-1] == "bookkeeping"


def test_request_events_keep_their_track_and_gain_the_step(traced):
    ev = traced["events"]
    rids = sorted(traced["streams"])
    for rid in rids:
        mine = [e for e in ev if e["track"] == rid]
        assert [e["name"] for e in mine if e["ph"] in "BE"] == [
            "queued", "queued", "running", "running"]
        queued_b = next(e for e in mine if e["ph"] == "B")
        assert queued_b["parent"] == "add_request"
        admit = next(e for e in mine if e["name"] == "admit")
        assert admit["parent"] == "admission" and admit["step"] is not None
    adds = _spans(ev, "add_request")
    assert len(adds) == len(rids) and all(a["parent"] is None for a in adds)
    # the third request waited for a slot: admitted in a later step
    last = [e for e in ev if e["track"] == rids[-1] and e["name"] == "admit"]
    assert last[0]["step"] > 0


# ---------------------------------------------------------------------------
# counters at the same boundaries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("run, spec_k", [("traced", 1),
                                         ("traced_optional", 3)])
def test_rows_sampled_and_tokens_are_exact_for_the_scripted_run(
        request, run, spec_k):
    """The mixed program is handed ``spec_k`` rows a slot, the decode
    program one: ``rows_sampled`` counts what each was handed."""
    traced = request.getfixturevalue(run)
    c = traced["counters"]
    steps = _spans(traced["events"], "step", "engine")
    n_mixed = sum(1 for s in steps if s["args"]["program"] == "mixed")
    n_decode = len(steps) - n_mixed
    assert n_mixed and c["mixed_steps"] == n_mixed
    assert c.get("decode_steps", 0) == n_decode
    assert c["rows_sampled"] == n_decode * SLOTS + n_mixed * SLOTS * spec_k
    assert c["tokens"] == len(PROMPT_LENS) * MAX_NEW
    assert c["finishes"] == len(PROMPT_LENS)
    assert "compiles" not in c or c["compiles"] <= 2


def test_block_table_entries_dispatched_and_live(traced):
    c = traced["counters"]
    decode = [s for s in _spans(traced["events"], "step", "engine")
              if s["args"]["program"] == "decode"]
    assert c["table_entries_dispatched"] == PAGES_PER_SLOT * sum(
        s["args"]["slots"] for s in decode)
    # a decoding slot of this run holds 4-17 tokens: 2 to 5 pages of 4
    n_rows = sum(s["args"]["slots"] for s in decode)
    assert 2 * n_rows <= c["table_entries_live"] <= 5 * n_rows
    assert c["table_entries_live"] < c["table_entries_dispatched"]


# ---------------------------------------------------------------------------
# what a first token waited on, and the host's collections
# ---------------------------------------------------------------------------

def test_one_first_token_per_request_with_the_steps_it_took(untraced, traced):
    """Once per request, at the step that emits it, and never outside a
    session. No budget or preemption holds these prompts back: every
    step from admission to first token ran a chunk of the prompt."""
    assert untraced["events"] == 0
    ev = traced["events"]
    points = _first_tokens(ev)
    assert sorted(p["track"] for p in points) == sorted(traced["streams"])
    for p in points:
        rid, a = p["track"], p["args"]
        prompt = next(e["args"]["prompt"] for e in ev if e["track"] == rid
                      and e["name"] == "queued" and e["ph"] == "B")
        admit = next(e for e in ev if e["track"] == rid
                     and e["name"] == "admit")
        assert a["chunks"] == math.ceil((prompt - a["cached"]) / CHUNK)
        assert (a["waits"], a["preemptions"]) == (0, 0)
        assert a["steps"] == a["chunks"] + a["waits"]
        assert a["steps"] == p["step"] - admit["step"] + 1
        assert p["parent"] == "sample_emit"


def test_a_budget_of_one_chunk_makes_the_younger_prompt_wait(traced_waits):
    older, younger = (p["args"] for p in traced_waits["tight"])
    assert (older["steps"], older["chunks"], older["waits"]) == (2, 2, 0)
    assert (younger["steps"], younger["chunks"], younger["waits"]) == (4, 2, 2)
    assert younger["preemptions"] == 0


def test_a_preempted_prompt_counts_its_preemption(traced_waits):
    old, young = traced_waits["old"]["args"], traced_waits["young"]["args"]
    assert traced_waits["preemptions"] == 1
    assert (old["steps"], old["chunks"], old["waits"]) == (1, 1, 0)
    assert (young["chunks"], young["waits"], young["preemptions"]) == (2, 1, 1)
    # the step it was preempted in and those it spent queued again
    # ran no chunk and were no wait of a prefilling slot
    assert young["steps"] > young["chunks"] + young["waits"] + 1


def test_a_collection_in_a_session_is_a_gc_span_of_the_open_phase(tmp_path):
    t0 = PROFILE_TRACER.now()
    gc.collect()                                 # outside a session
    assert not [e for e in PROFILE_TRACER.events if e["ts"] >= t0]
    with _session(tmp_path) as ses:
        with PROFILE_TRACER.span("bookkeeping", step=7):
            gc.collect()
    forced = [e for e in _spans(ses.events, "gc", "host")
              if e["args"]["generation"] == 2]
    assert len(forced) == 1, forced
    (f,) = forced
    assert (f["parent"], f["step"]) == ("bookkeeping", 7) and f["dur"] > 0
    assert set(f["args"]) == {"generation", "collected", "uncollectable"}
    assert "host.gc" in ses.host_event_names()
    # the phase was neither pushed over nor closed early by the callback
    (phase,) = _spans(ses.events, "bookkeeping")
    assert phase["ts"] <= f["ts"]
    assert f["ts"] + f["dur"] <= phase["ts"] + phase["dur"]


# ---------------------------------------------------------------------------
# the profiler's own trace
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["serve.step", "serve.build_inputs",
                                  "serve.decode_dispatch",
                                  "serve.mixed_dispatch",
                                  "serve.device_sync", "serve.add_request"])
def test_engine_spans_are_in_the_profilers_trace(traced, name):
    assert name in traced["host_names"]


@pytest.mark.parametrize("name", ["train.step", "train.step_args",
                                  "train.dispatch", "train.writeback"])
def test_train_spans_are_in_the_profilers_trace(traced_train, name):
    assert name in traced_train["host_names"]
    assert not any(n.startswith("serve.") for n in traced_train["host_names"])


def test_record_event_shares_the_annotation_helper(tmp_path):
    with RecordEvent("outside_a_session") as ev:
        assert ev._ta is NULL_TRACER.span("x")       # the shared null context
    with _session(tmp_path) as ses:
        with RecordEvent("my_region"):
            pass
    assert "my_region" in ses.host_event_names()


# ---------------------------------------------------------------------------
# TrainStep phases
# ---------------------------------------------------------------------------

def test_train_step_spans_with_parent_and_step(traced_train):
    ev = traced_train["events"]
    steps = _spans(ev, "step", "train")
    assert [s["step"] for s in steps] == [1, 2, 3, 4]
    assert all(s["parent"] is None for s in steps)
    for name in TRAIN_PHASES:
        kids = _spans(ev, name, "train")
        assert [k["step"] for k in kids] == [1, 2, 3, 4]
        for k, s in zip(kids, steps):
            assert k["parent"] == "step"
            assert s["ts"] <= k["ts"]
            assert k["ts"] + k["dur"] <= s["ts"] + s["dur"]
    # a toy step is a fifth of a millisecond: the span objects count
    assert np.median(_uncovered_shares(ev, "train")) < 0.25


def test_train_step_reports_a_compile_where_one_happened(traced_train):
    compiles = [e for e in traced_train["events"] if e["name"] == "compile"
                and e["ph"] == "i"]
    # the program compiled before the session is not reported in it;
    # the new batch shape of step 4 is
    assert [(e["step"], e["args"]["programs"]) for e in compiles] == [(4, 2)]
    assert traced_train["counters"]["compiles"] == 1


def test_train_losses_are_the_same_in_a_session(traced_train):
    assert traced_train["losses"] == traced_train["ref_losses"]


# ---------------------------------------------------------------------------
# names inside the step programs
# ---------------------------------------------------------------------------

def _op_names(lowered) -> list[tuple[str, str]]:
    """(instruction text, op_name) of every instruction of the compiled
    program (where calls are inlined, so that an operation's ``op_name``
    is its whole path of scopes)."""
    out = []
    for line in lowered.compile().as_text().splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m:
            out.append((line, m.group(1)))
    return out


@pytest.fixture(scope="module")
def step_program_names(model):
    eng = ServingEngine(model, num_pages=16, page_size=PAGE, max_slots=SLOTS,
                        max_pages_per_slot=PAGES_PER_SLOT,
                        prefill_chunk=CHUNK)
    return {k: _op_names(v) for k, v in eng.lower_step_programs().items()}


@pytest.fixture(scope="module")
def train_program_names():
    pt.seed(11)
    m = LlamaForCausalLM(llama_tiny(dtype="float32", mp_axis=None,
                                    fsdp_axis=None))
    opt = pt.optimizer.AdamW(learning_rate=1e-3, parameters=m)
    step = pt.jit.TrainStep(m, opt, lambda lg, y: m.loss(lg, y))
    ids = jnp.zeros((2, 16), jnp.int32)
    return _op_names(step.lower(ids, ids))


def _has_scope(names, scope):
    """Is some operation under ``scope`` (``attn/core``), bare or as
    autodiff wraps it (``jvp(attn)/core``, ``transpose(jvp(attn))/core``)?"""
    path = r"\)*/".join(re.escape(part) for part in scope.split("/"))
    pattern = re.compile(rf"(^|/|\(){path}(/|\)|$)")
    return any(pattern.search(n) for _, n in names)


@pytest.mark.parametrize("scope", ["embed", "norm", "attn", "attn/core",
                                   "mlp", "lm_head", "sampler"])
@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_step_program_names_its_layer_parts(step_program_names, program,
                                            scope):
    assert _has_scope(step_program_names[program], scope)


def test_mixed_program_names_its_rollback_and_sorts_under_the_sampler(
        step_program_names):
    names = step_program_names["mixed"]
    assert _has_scope(names, "rollback")
    sorts = [n for line, n in names if re.search(r"\bsort\(", line)]
    assert sorts and all("/sampler/" in n for n in sorts), sorts
    # no layer index in a scope: the same part of every layer merges
    assert not any(re.search(r"/(attn|mlp|norm)[_.]?\d", n) for _, n in names)


@pytest.mark.parametrize("scope", ["embed", "norm", "attn", "attn/core",
                                   "mlp", "lm_head", "loss", "optimizer"])
def test_train_program_names_its_layer_parts(train_program_names, scope):
    assert _has_scope(train_program_names, scope)
    # autodiff wraps the scopes itself
    assert any("transpose(jvp(" in n for _, n in train_program_names)
