"""What every traffic kind shares: a mix is a data file under
``benchmarks/traffic/`` (parameters only, with the public source of its
numbers), and lengths are drawn as a FIXED multiset so that every seed
gets the same work.

The generator of a kind lives with the kind's driver,
``benchmarks/drivers/<kind>.py`` (``make_traffic(spec, seed, vocab)``),
which the harness finds by the name in the mix's ``kind``: a new kind is
a new file.

Why a fixed multiset: lengths are the quantiles of the stated
distribution on a grid of ``pool`` points, laid out over the clients by
one constant permutation; ``--seed`` draws the token ids and turns the
clients round, which changes no step. A first version let the seed
permute the lengths: a 51 s window holds a third of the pool, so which
lengths it drew moved the rate by 17% between seeds (PERF.md, PR 26).
"""

from __future__ import annotations

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))
LAYOUT_SEED = 0          # the one layout of every mix


def load(name: str) -> dict:
    """The mix ``name``: ``benchmarks/traffic/<name>.json`` (a test may
    give a path that ends in .json instead)."""
    path = (name if name.endswith(".json")
            else os.path.join(HERE, "traffic", f"{name}.json"))
    with open(path) as f:
        spec = json.load(f)
    if not isinstance(spec.get("kind"), str):
        raise ValueError(f"traffic {name!r}: no kind")
    return spec


def _exponential_quantiles(mean: float, lo: float, hi: float, us):
    """Quantiles of ``lo`` + an exponential, cut at ``hi``, whose scale is
    chosen (by bisection) so that the CUT distribution has the stated
    mean: the source gives the mean of the lengths it kept."""
    if not lo < mean < (lo + hi) / 2:
        raise ValueError(f"no cut exponential on [{lo}, {hi}] has mean {mean}")
    span = hi - lo

    def cut_mean(scale):
        z = span / scale
        return lo + scale - span / math.expm1(z) if z < 700 else lo + scale

    a, b = 1e-6 * span, 1e6 * span
    for _ in range(200):
        mid = math.sqrt(a * b)
        a, b = (mid, b) if cut_mean(mid) < mean else (a, mid)
    scale = math.sqrt(a * b)
    mass = -math.expm1(-span / scale)
    return [lo - scale * math.log1p(-u * mass) for u in us]


def quantile_grid(dist: dict, n: int) -> list[int]:
    """n whole-number lengths: the distribution's quantiles at
    (j + 0.5) / n."""
    lo, hi = dist["low"], dist["high"]
    us = [(j + 0.5) / n for j in range(n)]
    if dist["dist"] == "uniform":
        vals = [lo + u * (hi - lo) for u in us]
    elif dist["dist"] == "log_uniform":
        vals = [math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
                for u in us]
    elif dist["dist"] == "exponential":
        vals = _exponential_quantiles(dist["mean"], lo, hi, us)
    elif dist["dist"] == "constant":
        vals = [dist["low"]] * n
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [max(1, int(round(v))) for v in vals]
