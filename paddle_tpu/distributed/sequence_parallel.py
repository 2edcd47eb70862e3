"""Sequence/context parallelism (parity: SURVEY §5.7's three mechanisms).

1. **Megatron-SP** (fleet/utils/sequence_parallel_utils.py): activations
   sequence-sharded outside attention. TPU-native: sharding constraints on
   the seq axis; Column/RowSequenceParallelLinear are annotation shims whose
   allgather/reduce-scatter GSPMD inserts.
2. **SEP / Ulysses** (meta_parallel/segment_parallel.py:26): all-to-all
   reshard between seq-sharded and head-sharded layouts around attention —
   here an explicit ``lax.all_to_all`` inside shard_map over the 'sep' axis.
3. **Ring attention** (capability the reference lacks — included for
   long-context parity): sequence-sharded flash attention with K/V blocks
   rotating over ``ppermute``, partial results merged in log-sum-exp space
   using the Pallas kernel's stored LSE. Fully differentiable (scan +
   ppermute + custom-vjp flash).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import mesh as mesh_lib
from ..nn.module import Layer
from ..ops.pallas.flash_attention import flash_attention_with_lse
from .fleet.mp_layers import ColumnParallelLinear, RowParallelLinear, mark_sharding

__all__ = ["ulysses_attention", "ring_attention", "scatter_to_sequence_parallel",
           "gather_from_sequence_parallel", "ColumnSequenceParallelLinear",
           "RowSequenceParallelLinear", "sep_reshard_qkv", "sep_reshard_out",
           "manual_sep_region", "current_manual_sep", "ring_attention_manual"]

# Trace-time flag: set while tracing code that is INSIDE a shard_map manual
# over the sep axis (e.g. the 1F1B pipeline body), so seq-sharded-aware
# layers (LlamaAttention) switch to ring attention + offset rope positions.
_MANUAL_SEP: list[str | None] = [None]


import contextlib


@contextlib.contextmanager
def manual_sep_region(axis: str | None):
    """Mark the enclosed trace as running inside a manual-sep shard_map."""
    prev = _MANUAL_SEP[0]
    _MANUAL_SEP[0] = axis
    try:
        yield
    finally:
        _MANUAL_SEP[0] = prev


def current_manual_sep() -> str | None:
    return _MANUAL_SEP[0]


# ---------- Megatron-SP annotation shims ----------

def scatter_to_sequence_parallel(x, axis="sep"):
    """Parity: sequence_parallel_utils.ScatterOp — constrain seq dim sharded."""
    return mark_sharding(x, None, axis, *([None] * (x.ndim - 2)))


def gather_from_sequence_parallel(x, axis="sep"):
    """Parity: GatherOp — constrain seq dim replicated (allgather)."""
    return mark_sharding(x, *([None] * x.ndim))


class ColumnSequenceParallelLinear(ColumnParallelLinear):
    """Parity: sequence_parallel_utils.py:395 — allgather(seq) then column
    matmul; GSPMD derives it from input seq-sharded + output head-sharded."""

    def forward(self, x):
        x = gather_from_sequence_parallel(x)
        return super().forward(x)


class RowSequenceParallelLinear(RowParallelLinear):
    """Row matmul then reduce-scatter onto the seq axis."""

    def forward(self, x):
        y = super().forward(x)
        return scatter_to_sequence_parallel(y)


# ---------- Ulysses (SEP all-to-all) ----------

def sep_reshard_qkv(t, axis_name="sep"):
    """Inside shard_map: [b, s/P, h, d] -> [b, s, h/P, d] via all-to-all
    (parity: the reshard around attention in segment_parallel / Ulysses)."""
    return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1, tiled=True)


def sep_reshard_out(t, axis_name="sep"):
    """Inverse: [b, s, h/P, d] -> [b, s/P, h, d]."""
    return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh | None = None, axis: str = "sep",
                      causal: bool = True, attention_fn=None):
    """Ulysses sequence parallelism: inputs seq-sharded [b, S, h, d] (global
    view), attention computed head-sharded after all-to-all. Requires
    num_heads % sep_degree == 0."""
    from ..nn.functional.attention import _xla_attention
    mesh = mesh or mesh_lib.current_mesh()
    if mesh is None or mesh_lib.axis_size(axis, mesh) == 1:
        fn = attention_fn or (lambda q, k, v: _xla_attention(q, k, v, is_causal=causal))
        return fn(q, k, v)
    inner_attn = attention_fn or (lambda q, k, v: _xla_attention(q, k, v,
                                                                 is_causal=causal))

    def local_fn(q, k, v):
        qh = sep_reshard_qkv(q, axis)
        kh = sep_reshard_qkv(k, axis)
        vh = sep_reshard_qkv(v, axis)
        oh = inner_attn(qh, kh, vh)
        return sep_reshard_out(oh, axis)

    spec = P(None, axis, None, None)
    return shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


# ---------- Ring attention ----------

def _merge_lse(o1, lse1, o2, lse2):
    """Combine two attention partials in log-sum-exp space.
    o: [b, sq, h, d]; lse: [b, h, sq]."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    lse = m + jnp.log(w1 + w2)
    w1n = (w1 / (w1 + w2)).transpose(0, 2, 1)[..., None]  # [b, sq, h, 1]
    w2n = (w2 / (w1 + w2)).transpose(0, 2, 1)[..., None]
    return o1 * w1n + o2 * w2n, lse


def _ring_rotate(t, axis, nsteps):
    # send to the next rank: rank r's block moves to r+1, so after i steps
    # rank r holds the block owned by (r - i) mod P
    perm = [(r, (r + 1) % nsteps) for r in range(nsteps)]
    return lax.ppermute(t, axis, perm)


def _rep_kv(t, rep):
    """GQA: expand kvh key/value heads to the query head count. Done
    per-ring-step so the rotating buffers (and their backward accumulators)
    stay at kvh heads — h/kvh less ICI traffic than pre-repeating."""
    return t if rep == 1 else jnp.repeat(t, rep, axis=2)


def _reduce_kv_heads(g, rep):
    """Fold gradient heads back onto the kvh grouped heads."""
    if rep == 1:
        return g
    b, s, h, d = g.shape
    return g.reshape(b, s, h // rep, rep, d).sum(3)


def _ring_fwd_loop(q, k, v, axis, nsteps, causal, scale):
    my = lax.axis_index(axis)
    NEG = jnp.float32(-1e30)
    b, sl, h, d = q.shape
    rep = h // k.shape[2]

    def step(carry, i):
        o, lse, kb, vb = carry
        src = jnp.mod(my - i, nsteps)  # owner of the block we currently hold

        def do_skip(_):
            return (jnp.zeros_like(q, jnp.float32),
                    jnp.full((b, h, sl), NEG, jnp.float32))

        def do_full(_):
            ob, lseb = flash_attention_with_lse(q, _rep_kv(kb, rep),
                                                _rep_kv(vb, rep),
                                                causal=False, scale=scale)
            return ob.astype(jnp.float32), lseb

        def do_causal(_):
            ob, lseb = flash_attention_with_lse(q, _rep_kv(kb, rep),
                                                _rep_kv(vb, rep),
                                                causal=True, scale=scale)
            return ob.astype(jnp.float32), lseb

        if causal:
            case = jnp.where(src == my, 2, jnp.where(src < my, 1, 0))
            ob, lseb = lax.switch(case, [do_skip, do_full, do_causal], None)
        else:
            ob, lseb = do_full(None)
        o, lse = _merge_lse(o, lse, ob, lseb)
        return (o, lse, _ring_rotate(kb, axis, nsteps),
                _ring_rotate(vb, axis, nsteps)), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((b, h, sl), NEG, jnp.float32)
    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v), jnp.arange(nsteps))
    return o.astype(q.dtype), lse


def _ring_core_impl(q, k, v, axis, nsteps, causal, scale):
    out, _ = _ring_fwd_loop(q, k, v, axis, nsteps, causal, scale)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_core(q, k, v, axis, nsteps, causal, scale):
    return _ring_core_impl(q, k, v, axis, nsteps, causal, scale)


def _ring_core_fwd(q, k, v, axis, nsteps, causal, scale):
    out, lse = _ring_fwd_loop(q, k, v, axis, nsteps, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_core_bwd(axis, nsteps, causal, scale, res, do):
    """Ring backward: dk/dv accumulators travel WITH their k/v block around
    the ring, arriving home after a full revolution; dq accumulates locally.
    Uses the global LSE + delta trick (delta computed once from the merged
    output is valid for every block's partial gradient)."""
    from ..ops.pallas.flash_attention import flash_block_grads
    q, k, v, out, lse = res
    my = lax.axis_index(axis)
    rep = q.shape[2] // k.shape[2]
    delta = jnp.moveaxis(
        jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1), 2, 1)

    def step(carry, i):
        dq, kb, vb, dkb, dvb = carry
        src = jnp.mod(my - i, nsteps)

        def do_skip(_):
            return (jnp.zeros_like(q, jnp.float32),
                    jnp.zeros_like(kb, jnp.float32),
                    jnp.zeros_like(vb, jnp.float32))

        def grads(causal_flag):
            def f(_):
                a, b_, c = flash_block_grads(q, _rep_kv(kb, rep),
                                             _rep_kv(vb, rep), do, lse, delta,
                                             scale=scale, causal=causal_flag)
                return (a.astype(jnp.float32),
                        _reduce_kv_heads(b_.astype(jnp.float32), rep),
                        _reduce_kv_heads(c.astype(jnp.float32), rep))
            return f

        if causal:
            case = jnp.where(src == my, 2, jnp.where(src < my, 1, 0))
            dqp, dkp, dvp = lax.switch(case, [do_skip, grads(False), grads(True)],
                                       None)
        else:
            dqp, dkp, dvp = grads(False)(None)
        dq = dq + dqp
        dkb = dkb + dkp
        dvb = dvb + dvp
        return (dq, _ring_rotate(kb, axis, nsteps), _ring_rotate(vb, axis, nsteps),
                _ring_rotate(dkb, axis, nsteps), _ring_rotate(dvb, axis, nsteps)), None

    init = (jnp.zeros_like(q, jnp.float32), k, v,
            jnp.zeros_like(k, jnp.float32), jnp.zeros_like(v, jnp.float32))
    (dq, _, _, dk, dv), _ = lax.scan(step, init, jnp.arange(nsteps))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention_manual(q, k, v, axis: str = "sep", causal: bool = True,
                          scale: float | None = None):
    """Ring attention for callers ALREADY inside a shard_map manual over
    ``axis`` (e.g. the 1F1B pipeline body): q/k/v are local seq shards
    [b, S/P, h, d]; GQA (fewer k/v heads) is supported — k/v blocks rotate
    at kv-head width. Public entry point for model code."""
    import math
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    nsteps = mesh_lib.axis_size(axis)
    return _ring_core(q, k, v, axis, nsteps, causal, scale)


def ring_attention(q, k, v, mesh: Mesh | None = None, axis: str = "sep",
                   causal: bool = True, scale: float | None = None):
    """Ring (blockwise) attention over the 'sep' mesh axis: memory O(S/P)
    per device, K/V streamed over ICI. Inputs [b, S, h, d] seq-sharded."""
    import math
    mesh = mesh or mesh_lib.current_mesh()
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    nsteps = mesh_lib.axis_size(axis, mesh) if mesh else 1
    if mesh is None or nsteps == 1:
        from ..ops.pallas.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale)
    spec = P(None, axis, None, None)

    def fn(q, k, v):
        return _ring_core(q, k, v, axis, nsteps, causal, scale)

    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                     check_vma=False)(q, k, v)
