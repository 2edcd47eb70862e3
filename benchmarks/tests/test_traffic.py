"""The generators: the same seed gives the same requests; every seed
gets the same multiset of sizes; the mix's lengths are its source's."""

import numpy as np
import pytest

from benchmarks import run, traffic
from benchmarks.drivers import closed_loop, train_steps


def test_closed_loop_is_deterministic_and_every_seed_has_the_same_work():
    spec = traffic.load("decode")
    a = closed_loop.make_traffic(spec, 2 ** 31 + 5, 32768)
    b = closed_loop.make_traffic(spec, 2 ** 31 + 5, 32768)
    c = closed_loop.make_traffic(spec, 7, 32768)
    for client, k in ((0, 0), (3, 1), (15, 6)):
        assert a.request(client, k) == b.request(client, k)
    assert a.request(3, 1)[0] != c.request(3, 1)[0]      # other token ids

    def sizes(g, k):         # what round k asks of the engine, as a set
        return sorted((len(p), n) for p, n in
                      (g.request(c_, k) for c_ in range(16)))

    for k in range(5):
        assert sizes(a, k) == sizes(c, k)
    prompt, n_out = a.request(0, 1)
    assert all(0 <= t < 32768 for t in prompt)
    # first requests are the rest of a request in flight, on a fixed grid
    firsts = [a.request(c_, 0)[1] for c_ in range(16)]
    full = [int(a._outputs[(c_ + a._turn) % 16]) for c_ in range(16)]
    assert all(f <= n for f, n in zip(firsts, full)) and firsts != full


def test_decode_mix_has_its_sources_lengths():
    """ShareGPT as the vLLM paper serves it: means 161.31 in, 337.99
    out; at least 4 tokens each, prompt <= 1024, total <= 2048."""
    spec = traffic.load("decode")
    g = closed_loop.make_traffic(spec, 0, 32768)
    assert abs(np.mean(g._prompts) - 161.31) < 2
    assert abs(np.mean(g._outputs) - 337.99) < 3
    assert g._prompts.min() >= 4 and g._prompts.max() <= 1024
    assert g._outputs.min() >= 4
    assert g.max_tokens <= 2048
    assert np.median(g._prompts) < np.mean(g._prompts)    # a tail


def test_quantile_grid():
    g = traffic.quantile_grid({"dist": "uniform", "low": 0, "high": 100}, 4)
    assert g == [12, 38, 62, 88]
    lg = traffic.quantile_grid({"dist": "log_uniform", "low": 64,
                                "high": 256}, 64)
    assert abs(np.mean(np.ceil(np.asarray(lg) / 64)) - 2.7) < 0.1
    ex = traffic.quantile_grid({"dist": "exponential", "mean": 100.0,
                                "low": 10, "high": 400}, 1000)
    assert abs(np.mean(ex) - 100.0) < 0.5 and 10 <= min(ex) and max(ex) <= 400
    with pytest.raises(ValueError):     # no such cut distribution
        traffic.quantile_grid({"dist": "exponential", "mean": 300.0,
                               "low": 10, "high": 400}, 8)


def test_total_tokens_are_capped():
    spec = dict(traffic.load("decode"), max_total_tokens=900)
    g = closed_loop.make_traffic(spec, 0, 32768)
    assert g.max_tokens <= 900 and g._outputs.min() >= 2


def test_train_batches_differ_and_repeat():
    spec = traffic.load("seq4k")
    g = train_steps.make_traffic(spec, 2 ** 31 + 1, 32768)
    assert g.ids.shape == (4, 1, 4096)
    assert not np.array_equal(g.batch_of(0), g.batch_of(1))
    assert np.array_equal(g.batch_of(1), g.batch_of(5))
    assert np.array_equal(
        g.ids, train_steps.make_traffic(spec, 2 ** 31 + 1, 32768).ids)


def test_a_kind_is_its_driver_file_and_open_loop_has_none_yet():
    assert run.find_driver("closed_loop") is closed_loop
    assert run.find_driver("train_steps") is train_steps
    with pytest.raises(run.Refused):
        run.find_driver("open_loop")
