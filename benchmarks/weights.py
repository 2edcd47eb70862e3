"""Weights from ``--seed``: a counter-based integer hash, so that one
jitted call makes every leaf on the device in the type it is served in,
any single leaf can be made again alone (the reference makes its own,
layer by layer), and a CPU run makes the same values as the chip.

A leaf's values depend only on (seed, leaf name, shape): bell-shaped (the
sum of the hash's four bytes: mean 0, the stated std, support +-3.46 std,
so that a per-channel absmax sits where a trained matrix's does and int8
rounding costs what it costs there); one-dimensional leaves (norm gains)
are 1 + the same noise, so that no gain is exactly 1 and a missed gain
shows.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02


def _mix(x):
    """lowbias32 (Chris Wellons): a bijective avalanche of uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def leaf_salt(seed: int, name: str) -> int:
    """32 bits from the seed (any whole number) and the leaf's name."""
    return zlib.crc32(f"{int(seed)}/{name}".encode()) & 0xFFFFFFFF


def make_leaf(salt, shape, dtype, std: float = STD):
    """One leaf; ``salt`` may be traced. Traceable under jit."""
    n = int(np.prod(shape))
    if n >= 2 ** 32:
        raise ValueError(f"leaf of {n} elements overflows the 32-bit counter")
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape)
    bits = _mix(_mix(idx + jnp.uint32(salt)) ^ jnp.uint32(salt))
    four = ((bits & 0xFF) + ((bits >> 8) & 0xFF) + ((bits >> 16) & 0xFF)
            + (bits >> 24))                       # 0..1020, mean 510
    sigma = (4 * (256.0 ** 2 - 1) / 12) ** 0.5     # of that sum
    v = (four.astype(jnp.float32) - 510.0) * jnp.float32(std / sigma)
    if len(shape) == 1:
        v = 1.0 + v
    # round HERE, by an operation the compiler may not drop: a bare
    # ``astype(bfloat16).astype(float32)`` inside a jit is elided on the
    # TPU (excess precision), and the reference would then hold weights
    # that the program, which stores real bfloat16, does not (PR 26)
    info = jnp.finfo(dtype)
    v = jax.lax.reduce_precision(v, exponent_bits=info.nexp,
                                 mantissa_bits=info.nmant)
    return v.astype(dtype)


def make_weights(seed: int, shapes: dict[str, tuple], dtype) -> dict:
    """Every leaf of ``shapes`` (name -> shape) in one jitted call."""
    names = sorted(shapes)
    salts = np.asarray([leaf_salt(seed, k) for k in names], np.uint32)

    @jax.jit
    def build(salts):
        return {k: make_leaf(salts[i], tuple(shapes[k]), dtype)
                for i, k in enumerate(names)}

    return build(salts)
