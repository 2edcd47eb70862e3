"""The per-layer metrics that read the program's own spans and counters:
the self-time arithmetic on a hand-made event list, and each reader on
the tiny traced rehearsals (a number where the CPU can give one,
``None`` where the program recorded nothing)."""

import json
import os

import pytest

from benchmarks import program_spans as ps
from benchmarks import run
from benchmarks.tests.conftest import ROOT, TINY_BENCH

SERVE_METRICS = ("engine_host_ms_per_step", "engine_schedule_ms_per_step",
                 "engine_dispatch_ms_per_step", "engine_emit_ms_per_step",
                 "admission_wait_p95_ms", "sampler_useful_row_share")


def _x(name, ts, dur, parent=None, step=0, track="engine"):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "track": track,
            "parent": parent, "step": step, "args": {}}


def test_covered_is_the_length_of_the_union():
    assert ps.covered([]) == 0.0
    assert ps.covered([(0, 2), (1, 3), (5, 6), (5.5, 5.75)]) == 4.0


def test_self_time_is_duration_minus_what_children_cover():
    step = _x("step", 10.0, 1.0)
    events = [
        step,
        _x("plan", 10.0, 0.25, "step"),
        _x("build_inputs", 10.25, 0.25, "step"),
        _x("device_sync", 10.5, 0.375, "step"),
        _x("inner", 10.5, 0.125, "device_sync"),    # a grandchild: not counted
        _x("plan", 10.0, 0.25, "step", step=1),     # another step's child
        _x("plan", 11.5, 0.25, "step"),             # outside the parent
    ]
    kids = ps.children(events, step)
    assert [k["name"] for k in kids] == ["plan", "build_inputs", "device_sync"]
    assert ps.self_time(step, kids) == pytest.approx(0.125)
    # children that overlap are not counted twice
    overlapping = kids + [_x("extra", 10.125, 0.25, "step")]
    assert ps.self_time(step, overlapping) == pytest.approx(0.125)


def _ctx(events, t0=0.0, t1=100.0):
    """A reader's context over a hand-made event list."""
    from paddle_tpu.observability import PROFILE_TRACER
    PROFILE_TRACER.events.clear()
    PROFILE_TRACER.events.extend(events)
    return {"record": {"t_open": t0, "t_last": t1}}


def test_phase_means_add_up_to_the_step_over_a_hand_made_window():
    events = []
    for i, t in enumerate((1.0, 2.0)):
        events += [
            _x("step", t, 0.5, step=i),
            _x("admission", t, 0.0625, "step", i),
            _x("build_inputs", t + 0.0625, 0.0625, "step", i),
            _x("decode_dispatch", t + 0.125, 0.125, "step", i),
            _x("device_sync", t + 0.25, 0.125, "step", i),
            _x("sample_emit", t + 0.375, 0.0625, "step", i),
        ]
    events.append(_x("step", 200.0, 9.0, step=9))      # after the window
    ctx = _ctx(events)
    phases = ps.engine_step_phases(ctx)
    assert phases["step"] == 0.5 and phases["self"] == pytest.approx(0.0625)
    assert run.read_layer_metric("engine_host_ms_per_step", ctx) == 375.0
    assert run.read_layer_metric("engine_schedule_ms_per_step", ctx) == 62.5
    assert run.read_layer_metric("engine_dispatch_ms_per_step", ctx) == 187.5
    assert run.read_layer_metric("engine_emit_ms_per_step", ctx) == 62.5
    assert run.read_layer_metric("train_host_ms_per_step", ctx) is None


def test_counters_and_waits_of_a_hand_made_window():
    def c(name, ts, n):
        return {"name": name, "ph": "C", "ts": ts, "track": "engine",
                "n": n, "args": {}, "parent": None, "step": None}

    def q(ph, ts, rid):
        return {"name": "queued", "ph": ph, "ts": ts, "track": rid,
                "args": {}, "parent": None, "step": None}

    ctx = _ctx([c("rows_sampled", 1.0, 16), c("tokens", 1.5, 3),
                c("rows_sampled", 2.0, 1024), c("tokens", 2.5, 10),
                c("tokens", 300.0, 99),
                q("B", 1.0, "a"), q("E", 1.5, "a"),
                q("B", 2.0, "b"), q("E", 4.0, "b"),
                q("E", 5.0, "c")])                  # begun before the window
    assert ps.counter_growth(ctx, "rows_sampled") == 1040
    assert run.read_layer_metric("sampler_useful_row_share", ctx) == 1.25
    assert ps.queued_waits(ctx) == [0.5, 2.0]
    assert run.read_layer_metric("admission_wait_p95_ms", ctx) == pytest.approx(
        1e3 * (0.5 + 0.95 * 1.5))


@pytest.mark.parametrize("name", SERVE_METRICS + ("train_host_ms_per_step",))
def test_every_reader_returns_none_from_an_empty_tracer(name):
    assert run.read_layer_metric(name, _ctx([])) is None


def _run_tiny(workload, seconds, tmp_path):
    """A traced rehearsal of a tiny cell that reports, besides the tiny
    benchmark's own metrics, the seven that ``BENCHMARK.json`` has for
    the program's spans, on the tiny cells of the same kind."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        ours = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(TINY_BENCH) as f:
        tiny = json.load(f)
    cells = [w["name"] for w in tiny["workloads"]]
    for name in SERVE_METRICS + ("train_host_ms_per_step",):
        kind = "train" if "train" in ours[name]["workloads"][0] else "serve"
        tiny["per_layer"].append(dict(
            ours[name], workloads=[c for c in cells if kind in c]))
    path = tmp_path / "tiny_bench_spans.json"
    path.write_text(json.dumps(tiny))
    bench, cell, cfg, spec = run.load_cell(workload, str(path))
    return run.run_cell(bench, cell, cfg, spec, 2 ** 31 + 11, seconds, True,
                        require_tpu=False)


def test_serving_readers_on_the_traced_rehearsal(tmp_path):
    r = _run_tiny("tiny_serve.decode", 1.5, tmp_path)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"] and set(SERVE_METRICS) <= set(m)
    # the phases and the parent's self time add up to the host time
    from paddle_tpu.observability import PROFILE_TRACER
    steps = [e for e in PROFILE_TRACER.events
             if e["ph"] == "X" and e["name"] == "step"]
    assert steps
    parts = (m["engine_schedule_ms_per_step"]
             + m["engine_dispatch_ms_per_step"] + m["engine_emit_ms_per_step"])
    assert 0 < parts <= m["engine_host_ms_per_step"]
    assert parts >= 0.8 * m["engine_host_ms_per_step"]
    # inside the harness's own clock around step() (on the CPU the
    # dispatch runs the program, so host time is most of a step)
    assert m["engine_host_ms_per_step"] < max(m["decode_step_ms"],
                                              m["mixed_step_ms"])
    assert m["admission_wait_p95_ms"] >= 0
    # exact: every token of the window over every row handed to the sampler
    n_tokens = sum(e["n"] for e in PROFILE_TRACER.events
                   if e["ph"] == "C" and e["name"] == "tokens")
    n_rows = sum(e["n"] for e in PROFILE_TRACER.events
                 if e["ph"] == "C" and e["name"] == "rows_sampled")
    assert m["sampler_useful_row_share"] == pytest.approx(
        100.0 * n_tokens / n_rows)
    assert 0 < m["sampler_useful_row_share"] < 100


def test_training_reader_on_the_traced_rehearsal(tmp_path):
    r = _run_tiny("tiny_train.seq", 1.0, tmp_path)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"] and m["train_host_ms_per_step"] > 0
    assert "engine_host_ms_per_step" not in m
