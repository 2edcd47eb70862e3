"""Run by hand, on the CPU, from the root of the checkout:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

Not part of the repo's tier-1 suite (``tests/``)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
TINY_BENCH = os.path.join(DATA, "tiny_bench.json")


@pytest.fixture(autouse=True)
def _from_root(monkeypatch):
    monkeypatch.chdir(ROOT)


@pytest.fixture
def mistral_serve():
    with open(os.path.join(ROOT, "benchmarks/configs/mistral_7b_serve.json")) as f:
        return json.load(f)


@pytest.fixture
def mistral_train():
    with open(os.path.join(ROOT, "benchmarks/configs/mistral_7b_train.json")) as f:
        return json.load(f)


def run_tiny(workload, seed=2 ** 31 + 11, seconds=2.0, trace=False,
             control=False, limits=None):
    """Everything of a run but the look for a chip, at a tiny size."""
    from benchmarks import run
    bench, cell, cfg, spec = run.load_cell(workload, TINY_BENCH)
    if limits is not None:
        cfg["limits"] = limits
    return run.run_cell(bench, cell, cfg, spec, seed, seconds, trace,
                        require_tpu=False, control=control)
