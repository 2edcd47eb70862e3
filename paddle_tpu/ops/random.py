"""Random sampling ops (parity: python/paddle/tensor/random.py).

Eager calls draw from the process-global threefry stream (``paddle.seed``
semantics via core.rng); under ``nn.functional_call``/jit they draw from the
scoped deterministic stream so compiled steps stay pure — the TPU-native
replacement for the reference's per-device ``phi::Generator`` state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import rng
from ..core.dtypes import canonical_dtype, get_default_dtype
from ..core.registry import register_op

__all__ = [
    "rand", "randn", "standard_normal", "normal", "uniform", "randint",
    "randint_like", "randperm", "bernoulli", "poisson", "multinomial",
    "exponential_", "standard_gamma", "binomial", "uniform_", "gumbel_softmax",
]


def _key(key):
    return key if key is not None else rng.next_key()


def rand(shape, dtype=None, key=None, name=None):
    return jax.random.uniform(_key(key), tuple(shape),
                              canonical_dtype(dtype) or get_default_dtype())


def randn(shape, dtype=None, key=None, name=None):
    return jax.random.normal(_key(key), tuple(shape),
                             canonical_dtype(dtype) or get_default_dtype())


standard_normal = randn


def normal(mean=0.0, std=1.0, shape=None, key=None, name=None):
    if shape is None:
        shape = jnp.shape(mean) if hasattr(mean, "shape") else ()
    return jnp.asarray(mean) + jnp.asarray(std) * jax.random.normal(
        _key(key), tuple(shape), get_default_dtype())


def uniform(shape, dtype=None, min=-1.0, max=1.0, key=None, name=None):
    return jax.random.uniform(_key(key), tuple(shape),
                              canonical_dtype(dtype) or get_default_dtype(),
                              minval=min, maxval=max)


def uniform_(x, min=-1.0, max=1.0, key=None, name=None):
    return jax.random.uniform(_key(key), x.shape, x.dtype, minval=min, maxval=max)


def randint(low=0, high=None, shape=(1,), dtype="int64", key=None, name=None):
    if high is None:
        low, high = 0, low
    return jax.random.randint(_key(key), tuple(shape), low, high,
                              dtype=canonical_dtype(dtype))


def randint_like(x, low=0, high=None, dtype=None, key=None, name=None):
    if high is None:
        low, high = 0, low
    return jax.random.randint(_key(key), x.shape, low, high,
                              dtype=canonical_dtype(dtype) or x.dtype)


def randperm(n, dtype="int64", key=None, name=None):
    return jax.random.permutation(_key(key), n).astype(canonical_dtype(dtype))


def bernoulli(x, key=None, name=None):
    x = jnp.asarray(x)
    return jax.random.bernoulli(_key(key), x).astype(x.dtype)


def poisson(x, key=None, name=None):
    x = jnp.asarray(x)
    return jax.random.poisson(_key(key), x).astype(x.dtype)


def binomial(count, prob, key=None, name=None):
    count, prob = jnp.asarray(count), jnp.asarray(prob)
    return jax.random.binomial(_key(key), count, prob).astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32)


def multinomial(x, num_samples=1, replacement=False, key=None, name=None):
    x = jnp.asarray(x)
    p = x / jnp.sum(x, -1, keepdims=True)
    squeeze = x.ndim == 1
    if squeeze:
        p = p[None]
    k = _key(key)
    if replacement:
        keys = jax.random.split(k, p.shape[0])
        out = jax.vmap(lambda kk, pp: jax.random.categorical(
            kk, jnp.log(jnp.clip(pp, 1e-30)), shape=(num_samples,)))(keys, p)
    else:
        # Gumbel top-k: draws without replacement with probabilities p
        g = jax.random.gumbel(k, p.shape)
        scores = jnp.log(jnp.clip(p, 1e-30)) + g
        out = jax.lax.top_k(scores, num_samples)[1]
    return out[0] if squeeze else out


def exponential_(x, lam=1.0, key=None, name=None):
    return jax.random.exponential(_key(key), x.shape, x.dtype) / lam


def standard_gamma(x, key=None, name=None):
    x = jnp.asarray(x)
    return jax.random.gamma(_key(key), x)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, key=None, name=None):
    x = jnp.asarray(x)
    g = jax.random.gumbel(_key(key), x.shape, x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis)
        onehot = jax.nn.one_hot(idx, y.shape[axis], axis=axis, dtype=y.dtype)
        # straight-through estimator: forward = onehot, backward = soft
        y = onehot - jax.lax.stop_gradient(y) + y
    return y


@register_op("top_p_sampling", category="random", grad_ref=False)
def top_p_sampling(x, ps, threshold=None, seed=None, key=None, name=None):
    """Nucleus (top-p) sampling (parity: tensor/search.py:1235 over the
    top_p_sampling CUDA kernel).

    x: [B, V] probabilities (rows should sum to 1 — e.g. softmax output);
    ps: [B] cumulative-probability thresholds; threshold: optional [B]
    absolute per-token floor. Returns (values [B,1], indices [B,1] int32):
    one token per row sampled from the renormalised nucleus. The top-1 token
    is always kept (reference kernel contract), so ps<=0 is greedy decode.

    The descending order and the sorted probabilities come from ONE stable
    key-value sort of ``(-x, iota)``: the negated keys are the sorted
    probabilities (the bits a ``take_along_axis(x, order)`` would read) and
    the carried iota is ``argsort(-x)``, ties in index order. Nothing
    gathers over the vocabulary.
    """
    x = jnp.asarray(x)
    ps = jnp.asarray(ps).reshape(-1, 1)
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    neg_sorted, order = jax.lax.sort_key_val(-x, iota, dimension=-1,
                                             is_stable=True)
    sorted_p = -neg_sorted
    prefix = jnp.cumsum(sorted_p, axis=-1) - sorted_p  # exclusive cumsum
    keep = prefix < ps
    keep = keep.at[:, 0].set(True)  # always keep the argmax
    if threshold is not None:
        thr = jnp.asarray(threshold).reshape(-1, 1)
        keep = keep & (sorted_p >= thr)
        keep = keep.at[:, 0].set(True)
    probs = jnp.where(keep, sorted_p, 0.0)
    probs = probs / jnp.maximum(jnp.sum(probs, -1, keepdims=True), 1e-9)
    if key is None:
        key = (jax.random.key(seed) if seed is not None and seed >= 0
               else rng.next_key())
    pick = jax.random.categorical(key, jnp.log(jnp.maximum(probs, 1e-38)), -1)
    idx = jnp.take_along_axis(order, pick[:, None], axis=-1)
    val = jnp.take_along_axis(x, idx, axis=-1)
    return val, idx.astype(jnp.int32)


__all__ += ["top_p_sampling"]
