"""The rest of a run with the timed path broken underneath
(``faults.py``): ``correct`` has to come out false, once for each fault
a cell can have."""

import pytest

from benchmarks.tests import faults
from benchmarks.tests.conftest import run_tiny


def test_serving_token_altered_where_it_is_produced():
    with faults.token_altered():
        r = run_tiny("tiny_serve.decode", seconds=2.0)
    assert not r["correct"]
    c = r["compared"]["max_gap"]
    assert c["value"] > c["limit"]


def test_serving_request_cut_short_counts_as_failed():
    with faults.request_cut_short():
        r = run_tiny("tiny_serve.decode", seconds=1.5)
    assert not r["correct"] and r["failed"] > 0


def test_training_step_returns_its_state_unchanged():
    with faults.state_unchanged():
        r = run_tiny("tiny_train.seq", seconds=1.0)
    assert not r["correct"]
    c = r["compared"]["delta_norm_gap"]
    assert c["value"] == pytest.approx(1.0) and c["value"] > c["limit"]


def test_training_half_of_the_batch_left_out():
    with faults.half_batch():
        r = run_tiny("tiny_train.seq", seconds=1.0)
    assert not r["correct"]
    c = r["compared"]["grad_norm_gap"]
    assert c["value"] > c["limit"]
