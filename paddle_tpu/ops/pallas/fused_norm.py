"""Fused RMS / Layer norm as Pallas TPU kernels.

Parity: the reference's fused norm surface (incubate
``functional/fused_rms_norm.py``, ``fused_layer_norm.py`` over
``phi/kernels/fusion/gpu`` kernels). On TPU the payoff is one HBM pass:
read x, compute the row statistic in VMEM, scale, write y — instead of
relying on XLA to fuse the mean/rsqrt/mul chain across op boundaries.

The backward is a closed-form XLA composition (two row-reductions + an
elementwise chain) that XLA fuses into ~one pass by itself; a Pallas
backward would buy nothing (measured parity on v5e) — documented collapse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _interpret, _scratch

__all__ = ["fused_rms_norm", "fused_layer_norm"]


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps) * w[None, :]).astype(o_ref.dtype)


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    b = b_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    o_ref[:] = (xc * jax.lax.rsqrt(var + eps) * w[None, :]
                + b[None, :]).astype(o_ref.dtype)


def _rows_block(n_rows: int) -> int:
    br = 256
    while br > 8 and n_rows % br:
        br //= 2
    return min(br, n_rows)


def _rms_fwd_pallas(x2, w, eps):
    n0, d = x2.shape
    br = _rows_block(n0)
    pad = (-n0) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n = n0 + pad
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2.dtype),
        interpret=_interpret(),
        name="fused_rms_norm",
    )(x2, w)
    return out[:n0] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms(x2, w, eps):
    return _rms_fwd_pallas(x2, w, eps)


def _rms_fwd(x2, w, eps):
    return _rms_fwd_pallas(x2, w, eps), (x2, w)


def _rms_bwd(eps, res, g):
    x2, w = res
    x = x2.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    d = x.shape[-1]
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    xhat = x * inv
    gw = gf * wf[None, :]
    dx = (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True)) * inv
    dw = jnp.sum(gf * xhat, axis=0)
    return dx.astype(x2.dtype), dw.astype(w.dtype)


_rms.defvjp(_rms_fwd, _rms_bwd)


def _pallas_ok(x) -> bool:
    """Pallas route gate: lane-aligned feature dim and no multi-device mesh
    (pallas_call carries no GSPMD sharding rule — under a mesh the XLA
    composition partitions correctly and fuses nearly as well)."""
    from ..._mesh_gate import no_mesh_active
    return x.shape[-1] % 128 == 0 and x.ndim >= 2 and no_mesh_active()


def fused_rms_norm(x, weight, epsilon: float = 1e-6):
    """One-pass RMS norm: y = x * rsqrt(mean(x^2) + eps) * weight.
    x: [..., d]; weight: [d]. Differentiable. Falls back to the XLA-fused
    composition when the Pallas route is unavailable (mesh active or
    unaligned d)."""
    d = x.shape[-1]
    if not _pallas_ok(x):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + epsilon)
                * weight.astype(jnp.float32)).astype(x.dtype)
    x2 = x.reshape(-1, d)
    return _rms(x2, weight, float(epsilon)).reshape(x.shape)


def _ln_fwd_pallas(x2, w, b, eps):
    n0, d = x2.shape
    br = _rows_block(n0)
    pad = (-n0) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n = n0 + pad
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2.dtype),
        interpret=_interpret(),
        name="fused_layer_norm",
    )(x2, w, b)
    return out[:n0] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln(x2, w, b, eps):
    return _ln_fwd_pallas(x2, w, b, eps)


def _ln_fwd(x2, w, b, eps):
    return _ln_fwd_pallas(x2, w, b, eps), (x2, w)


def _ln_bwd(eps, res, g):
    x2, w = res
    x = x2.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    inv = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    xhat = xc * inv
    gw = gf * wf[None, :]
    dx = (gw - jnp.mean(gw, axis=-1, keepdims=True)
          - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True)) * inv
    dw = jnp.sum(gf * xhat, axis=0)
    db = jnp.sum(gf, axis=0)
    return dx.astype(x2.dtype), dw.astype(w.dtype), db.astype(w.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


def fused_layer_norm(x, weight, bias, epsilon: float = 1e-5):
    """One-pass layer norm with scale+shift. x: [..., d]. Same fallback
    policy as fused_rms_norm."""
    d = x.shape[-1]
    if not _pallas_ok(x):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        xc = xf - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        return (xc * jax.lax.rsqrt(var + epsilon)
                * weight.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(x.dtype)
    x2 = x.reshape(-1, d)
    return _ln(x2, weight, bias, float(epsilon)).reshape(x.shape)
