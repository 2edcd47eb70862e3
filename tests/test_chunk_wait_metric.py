"""``chunk_wait_share`` (``benchmarks/layer_metrics``): the reader of the
program's counters ``chunk_waits`` / ``chunks`` over a hand-made window,
silent where the program has no ``chunk_waits`` counter (an engine from
before the counter), and on a traced rehearsal of the Jamba cell at a
tiny size on the CPU (4 slots under the budget the engine derives, one
chunk of 16 a step)."""

import json
import os

import pytest

from benchmarks import run
from benchmarks.tests.conftest import DATA, ROOT, _from_root  # noqa: F401
from paddle_tpu.observability import PROFILE_TRACER

TINY = "tiny_jamba_serve.decode"
TTFT_CELLS = ["mistral_7b_serve.decode", "nemotron3_super_serve.decode_c64",
              "jamba2_3b_serve.decode_c64"]


def _ctx(bumps, t0=10.0, t1=100.0):
    """A reader's context over hand-made counter events: (ts, name, n)."""
    PROFILE_TRACER.events.clear()
    PROFILE_TRACER.events.extend(
        {"name": name, "ph": "C", "ts": ts, "track": "engine", "n": n,
         "args": {}, "parent": None, "step": None}
        for ts, name, n in bumps)
    return {"record": {"t_open": t0, "t_last": t1}}


def test_the_metric_is_listed_for_the_three_ttft_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}["chunk_wait_share"]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            m["workloads"]) == ("%", "lower", "program_counter",
                                "engine host code", "ttft_p95_ms",
                                TTFT_CELLS)
    e2e = {e["name"]: e for e in bench["end_to_end"]}["ttft_p95_ms"]
    assert set(TTFT_CELLS) <= set(e2e["workloads"])


@pytest.mark.parametrize("bumps", [[], [(20.0, "chunks", 3)],
                                   [(5.0, "chunk_waits", 2)]],
                         ids=["empty", "chunks_alone", "before_the_window"])
def test_a_program_without_the_counter_reads_nothing(bumps):
    assert run.read_layer_metric("chunk_wait_share", _ctx(bumps)) is None


@pytest.mark.parametrize("bumps,share", [
    ([(20.0, "chunks", 3), (20.0, "chunk_waits", 0)], 0.0),
    ([(20.0, "chunks", 3), (20.0, "chunk_waits", 1),
      (30.0, "chunks", 4), (30.0, "chunk_waits", 0),
      (200.0, "chunk_waits", 9)], 12.5),
], ids=["no_wait", "one_in_eight"])
def test_the_share_over_a_hand_made_window(bumps, share):
    assert run.read_layer_metric("chunk_wait_share", _ctx(bumps)) == (
        pytest.approx(share))


def test_a_traced_rehearsal_reads_one_bump_a_mixed_dispatch(tmp_path):
    with open(os.path.join(DATA, "tiny_bench_jamba.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        ours = {m["name"]: m for m in json.load(f)["per_layer"]}
    bench["per_layer"].append(dict(ours["chunk_wait_share"],
                                   workloads=[TINY]))
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(bench))
    b, cell, cfg, spec = run.load_cell(TINY, str(path))
    PROFILE_TRACER.events.clear()   # what the hand-made windows left
    r = run.run_cell(b, cell, cfg, spec, 2 ** 31 + 13, 1.5, True,
                     require_tpu=False, control=False)
    assert r["correct"]
    share = r["metrics"]["chunk_wait_share"]["value"]
    assert 0 <= share < 100
    # every mixed dispatch bumps the counter, a dispatch with no wait too
    bumps = [e for e in PROFILE_TRACER.events if e["ph"] == "C"]
    assert (sum(e["name"] == "chunk_waits" for e in bumps)
            == sum(e["name"] == "mixed_steps" for e in bumps) > 0)
