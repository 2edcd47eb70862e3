"""``mixed_rows_live_share`` (``benchmarks/layer_metrics``): the reader
of the program's counters ``mixed_rows_live`` / ``mixed_rows_computed``,
in a traced rehearsal of the Jamba cell at a tiny size on the CPU (the
tiny engine's 4 slots x 16 rows under the budget it derives, one chunk
of 16, pack into T = 4 + 16 -> 24 rows), and silent where the program
has no such counter (an engine from before the counters)."""

import importlib.util
import json
import os

import pytest

from benchmarks import run
from benchmarks.tests.conftest import DATA, ROOT, _from_root  # noqa: F401

TINY = "tiny_jamba_serve.decode"


def reader():
    path = os.path.join(ROOT, "benchmarks", "layer_metrics",
                        "mixed_rows_live_share.py")
    spec = importlib.util.spec_from_file_location("mixed_rows", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_metric_is_listed_for_the_four_serving_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}["mixed_rows_live_share"]
    serving = [w["name"] for w in bench["workloads"]
               if w["config"].endswith("_serve")]
    assert m["workloads"] == serving and len(serving) == 4
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "higher", "program_counter", "step programs",
        "serve_out_tokens_per_s")


def test_a_program_without_the_counters_reads_nothing():
    ctx = {"record": {"t_open": 0.0, "t_last": -1.0}}
    assert reader().read(ctx) is None


def test_a_traced_rehearsal_reads_the_share_of_the_rectangle(tmp_path):
    with open(os.path.join(DATA, "tiny_bench_jamba.json")) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "mixed_rows_live_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "step programs",
        "moves": "serve_out_tokens_per_s", "workloads": [TINY]})
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(bench))
    b, cell, cfg, spec = run.load_cell(TINY, str(path))
    r = run.run_cell(b, cell, cfg, spec, 2 ** 31 + 11, 1.5, True,
                     require_tpu=False, control=False)
    assert r["correct"]
    m = r["metrics"]
    share = m["mixed_rows_live_share"]["value"]
    # 24 packed rows a mixed dispatch, some of them live: the same live
    # rows the scan walks, over T and not over the 4 x 16 rectangle
    assert 0 < share < 100
    assert share == pytest.approx(
        m["scan_rows_live_share"]["value"] * 64 / 24)
