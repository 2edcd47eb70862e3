"""Deployment-grade low-bit serving quantization (ISSUE 7 tentpole).

Two halves, both aimed at the decode bandwidth wall PERF.md measured:

* **Int8 KV cache** — :class:`QuantizedKV` is the storage format the paged
  :class:`~paddle_tpu.serving.kv_cache.KVCachePool` (and the contiguous
  ``init_kv_caches(dtype="int8")`` caches) hold when quantized mode is on:
  per-token-per-head symmetric absmax int8 codes plus an fp32 scale per
  ``[..., head_dim]`` row. Quantization happens exactly once, at
  cache-WRITE time (prefill scatter and decode append); every attention
  read dequantizes to fp32 inside the one shared GQA decode core, so the
  engine's two-program contract (decode + mixed step) is untouched.

* **Int8 weight streaming** — :func:`quantize_for_serving` converts a
  model's decode matmul weights (attention projections + MLP; the lm_head
  stays fp unless asked) into :class:`Int8ServingLinear` layers that keep
  the int8 codes + per-channel fp32 scales as buffers and fold the dequant
  into the matmul epilogue, so XLA streams int8 bytes from HBM, not fp.

Error model (documented in SERVING.md "Quantized KV & weights"): with
``scale = absmax/127`` per row, the per-element quantization error is
bounded by ``scale/2`` — rows that are exactly zero get scale 0 and
dequantize to exact 0, which preserves the pool's masked-garbage-is-zero
invariant and the NaN-scrub contract.
"""

from __future__ import annotations

import copy
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..nn.module import Layer, Parameter
from . import _dequantize_weight, quantize_weight

__all__ = ["QuantizedKV", "KV_QMAX", "kv_quantize", "kv_dequantize",
           "Int8ServingLinear", "quantize_for_serving",
           "serving_state_bytes"]

# symmetric int8 grid: codes in [-127, 127] (the -128 code is unused so
# the grid is symmetric and scale*code round-trips without bias)
KV_QMAX = 127.0


class QuantizedKV(NamedTuple):
    """Int8 KV storage: ``q`` int8 codes ``[..., head_dim]`` and ``scale``
    fp32 ``[...]`` (one absmax scale per token-per-head row). NamedTuples
    are automatic jax pytrees, so a QuantizedKV rides through jit/scan
    carries and functional_call state exactly like the fp array it
    replaces; ``shape``/``dtype``/``ndim`` delegate to the codes so shape
    probes in the serving engine work unchanged."""

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def nbytes(self):
        return self.q.nbytes + self.scale.nbytes


def kv_quantize(x) -> QuantizedKV:
    """Symmetric absmax int8 quantization over the LAST axis (head_dim):
    ``scale = amax/127`` per row, codes clipped to [-127, 127]. The max
    reduction is order-exact, so quantizing a token at prefill-scatter
    time and at decode-append time produces bitwise-identical codes —
    the engine==generate parity tests rely on this. Zero rows get scale
    0 and a guarded divide, so they dequantize to exact 0."""
    xf = jnp.asarray(x).astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / KV_QMAX
    denom = jnp.where(scale > 0, scale, 1.0)[..., None]
    q = jnp.clip(jnp.round(xf / denom), -KV_QMAX, KV_QMAX).astype(jnp.int8)
    return QuantizedKV(q, scale)


def kv_dequantize(c: QuantizedKV, dtype=jnp.float32):
    """Inverse of :func:`kv_quantize`: ``q * scale`` per row. fp32 by
    default — the decode core's einsums accumulate in fp32 anyway, and a
    bf16 round-trip would stack a second rounding on the int8 one."""
    return (c.q.astype(jnp.float32) * c.scale[..., None]).astype(dtype)


class Int8ServingLinear(Layer):
    """Weight-streaming deploy form of ``nn.Linear``: int8 codes + fp32
    per-out-channel (or groupwise) scales as buffers, with the dequant
    folded into the matmul epilogue. Per-channel scales factor out of the
    contraction — ``x @ (q * s/127) == (x @ q) * (s/127)`` — so XLA
    streams the int8 weight bytes and applies one fused scale multiply on
    the [..., out] result. Groupwise scales do not factor out and fall
    back to dequantize-then-matmul (still int8 in HBM; the dequant fuses
    into the matmul's operand read)."""

    def __init__(self, weight_q, weight_scale, bias=None, bits: int = 8):
        super().__init__()
        self.bits = bits
        self.in_features = int(weight_q.shape[0])
        self.out_features = int(weight_q.shape[1])
        self.register_buffer("weight_q", jnp.asarray(weight_q, jnp.int8))
        self.register_buffer("weight_scale",
                             jnp.asarray(weight_scale, jnp.float32))
        if bias is not None:
            self.bias = Parameter(jnp.asarray(bias))
        else:
            self.bias = None

    @classmethod
    def from_linear(cls, linear, group_size: int | None = None):
        q, scales = quantize_weight(linear.weight, 8, group_size)
        return cls(q, scales, linear.bias)

    def forward(self, x):
        x = jnp.asarray(x)
        if self.weight_scale.ndim == 2:   # groupwise [in/gs, out]
            w = _dequantize_weight(self.weight_q, self.weight_scale,
                                   self.bits, dtype=x.dtype)
            out = x @ w
        else:                              # per-out-channel [out]
            qmax = 2.0 ** (self.bits - 1) - 1
            acc = jnp.einsum("...i,io->...o", x,
                             self.weight_q.astype(x.dtype),
                             preferred_element_type=jnp.float32)
            s = jnp.maximum(self.weight_scale, 1e-8) / qmax
            out = (acc * s).astype(x.dtype)
        if self.bias is not None:
            out = out + self.bias.astype(out.dtype)
        return out

    def extra_repr(self):
        kind = ("groupwise" if self.weight_scale.ndim == 2
                else "per-channel")
        return f"in={self.in_features}, out={self.out_features}, {kind}"


def quantize_for_serving(model: Layer, group_size: int | None = None,
                         quantize_lm_head: bool = False,
                         inplace: bool = False) -> Layer:
    """Convert every ``nn.Linear`` in ``model`` to an
    :class:`Int8ServingLinear` (attention projections + MLP — the decode
    streaming set). The ``lm_head`` keeps fp weights unless
    ``quantize_lm_head=True``: its logits feed sampling directly, and the
    reference deployments keep the output head in higher precision.
    Returns the converted model in eval mode (a deepcopy unless
    ``inplace``)."""
    from .. import nn
    if not inplace:
        model = copy.deepcopy(model)

    def _convert(layer: Layer):
        for name, sub in list(layer._sub_layers.items()):
            if isinstance(sub, nn.Linear):
                if name == "lm_head" and not quantize_lm_head:
                    continue
                layer._sub_layers[name] = Int8ServingLinear.from_linear(
                    sub, group_size)
            else:
                _convert(sub)

    _convert(model)
    # drop any compiled decode-program cache carried over from the source
    # model: deepcopy shares the cached closures, which are still bound to
    # the UNQUANTIZED module tree — a stale hit would functional_call the
    # old model with the new weight_q/weight_scale state and KeyError
    model.__dict__.pop("_decode_prog_cache", None)
    model.eval()
    return model


def serving_state_bytes(model: Layer) -> int:
    """Bytes the decode step must stream for the model's weights+buffers
    (the numerator of the weights-only MBU): sum of ``nbytes`` over the
    full serving state. For a :func:`quantize_for_serving` model this
    counts 1 byte per int8 weight element plus the fp32 scale vectors —
    the *necessary* bytes a weights-only MBU is scored against."""
    state = model.state_dict(include_non_persistable_buffer=True)
    return int(sum(int(v.nbytes) for v in state.values()))
