"""``first_token_steps_p95``, ``ttft_engine_p95_ms`` and
``host_gc_ms_per_step`` (``benchmarks/layer_metrics``, over
``benchmarks/request_spans.py``): each reader over a hand-made window,
silent where the program records no ``first_token`` point or ``gc``
span, and each on a traced rehearsal of the tiny serving cell on the
CPU."""

import gc
import json
import os

import pytest

from benchmarks import request_spans, run
from benchmarks.tests.conftest import ROOT, TINY_BENCH, _from_root  # noqa: F401
from paddle_tpu.observability import PROFILE_TRACER

TTFT_CELLS = ["mistral_7b_serve.decode", "nemotron3_super_serve.decode_c64",
              "jamba2_3b_serve.decode_c64"]
SERVE_CELLS = ["mistral_7b_serve.decode", "nemotron3_super_serve.decode_c64",
               "openpangu_ultra_moe_serve.conv_c32",
               "jamba2_3b_serve.decode_c64"]
METRICS = {
    "first_token_steps_p95": ("steps", "ttft_p95_ms", TTFT_CELLS),
    "ttft_engine_p95_ms": ("ms", "ttft_p95_ms", TTFT_CELLS),
    "host_gc_ms_per_step": ("ms", "serve_out_tokens_per_s", SERVE_CELLS),
}


def _ctx(events, t0=10.0, t1=100.0):
    """A reader's context over a hand-made event list."""
    PROFILE_TRACER.events.clear()
    PROFILE_TRACER.events.extend(events)
    return {"record": {"t_open": t0, "t_last": t1}}


def _ev(name, ph, ts, track, dur=None, **args):
    e = {"name": name, "ph": ph, "ts": ts, "track": track, "args": args,
         "parent": None, "step": None}
    if dur is not None:
        e["dur"] = dur
    return e


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_is_listed_for_its_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}[name]
    unit, moves, cells = METRICS[name]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            m["workloads"]) == (unit, "lower", "program_span",
                                "engine host code", moves, cells)
    # every cell listed reports the end-to-end metric it moves
    e2e = {e["name"]: e for e in bench["end_to_end"]}[moves]
    assert set(cells) <= set(e2e["workloads"])


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_program_without_the_points_reads_nothing(name):
    # an empty window, and one with engine steps but no point or span
    assert run.read_layer_metric(name, _ctx([])) is None
    steps = [_ev("step", "X", 20.0, "engine", 1.0),
             _ev("queued", "B", 20.5, "r1")]
    if name != "host_gc_ms_per_step":
        assert run.read_layer_metric(name, _ctx(steps)) is None


def test_a_program_that_does_not_time_collections_reads_nothing(monkeypatch):
    ctx = _ctx([_ev("step", "X", 20.0, "engine", 1.0)])
    assert run.read_layer_metric("host_gc_ms_per_step", ctx) == 0.0
    monkeypatch.setattr(gc, "callbacks", [])
    assert not request_spans.times_collections()
    assert run.read_layer_metric("host_gc_ms_per_step", ctx) is None


def test_first_token_steps_over_a_hand_made_window():
    ctx = _ctx([_ev("first_token", "i", 20.0 + i, f"r{i}", steps=s)
                for i, s in enumerate((8, 9, 7, 12))]
               + [_ev("first_token", "i", 200.0, "late", steps=99)])
    # p95 of 7, 8, 9, 12 by linear interpolation
    assert run.read_layer_metric("first_token_steps_p95", ctx) == (
        pytest.approx(9 + 0.85 * 3))


def test_engine_ttft_over_a_hand_made_window():
    ctx = _ctx([
        _ev("queued", "B", 5.0, "early"),         # queued before the window
        _ev("first_token", "i", 12.0, "early", steps=1),
        _ev("queued", "B", 20.0, "a"), _ev("queued", "E", 20.1, "a"),
        _ev("preempt", "i", 20.5, "a"),
        _ev("queued", "B", 20.5, "a"),            # requeued: the first counts
        _ev("first_token", "i", 21.0, "a", steps=5),
        _ev("queued", "B", 30.0, "b"),
        _ev("first_token", "i", 30.5, "b", steps=2),
        _ev("finish", "i", 31.0, "b"),
        _ev("queued", "B", 40.0, "b"),            # the name again, afresh
        _ev("first_token", "i", 40.25, "b", steps=1),
    ])
    assert sorted(request_spans.engine_ttfts(ctx)) == [0.25, 0.5, 1.0]
    assert run.read_layer_metric("ttft_engine_p95_ms", ctx) == (
        pytest.approx(1e3 * (0.5 + 0.9 * 0.5)))


def test_gc_milliseconds_per_step_over_a_hand_made_window():
    ctx = _ctx([_ev("step", "X", 20.0, "engine", 0.5),
                _ev("step", "X", 21.0, "engine", 0.5),
                _ev("gc", "X", 20.2, "host", 0.003, generation=0),
                _ev("gc", "X", 20.7, "host", 0.001, generation=2),
                _ev("gc", "X", 300.0, "host", 9.0, generation=2)])
    assert run.read_layer_metric("host_gc_ms_per_step", ctx) == (
        pytest.approx(2.0))


def test_the_readers_on_a_traced_rehearsal(tmp_path):
    """The tiny serving cell: prompts of 8 to 40 tokens in chunks of 16
    under the default budget, so a first token takes one to three steps,
    each of which ran a chunk of its prompt."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        ours = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(TINY_BENCH) as f:
        tiny = json.load(f)
    for name in METRICS:
        tiny["per_layer"].append(dict(ours[name],
                                      workloads=["tiny_serve.decode"]))
    path = tmp_path / "tiny_bench_first_token.json"
    path.write_text(json.dumps(tiny))
    bench, cell, cfg, spec = run.load_cell("tiny_serve.decode", str(path))
    r = run.run_cell(bench, cell, cfg, spec, 2 ** 31 + 11, 1.5, True,
                     require_tpu=False)
    assert r["correct"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 1 <= m["first_token_steps_p95"] <= 3
    assert 0 < m["ttft_engine_p95_ms"]
    assert m["host_gc_ms_per_step"] >= 0
    points = [e for e in PROFILE_TRACER.events if e["name"] == "first_token"]
    assert points and all(
        e["args"]["steps"] == e["args"]["chunks"] and not e["args"]["waits"]
        for e in points)
