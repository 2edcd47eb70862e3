"""Pipeline parallelism (parity: fleet/meta_parallel/ — PipelineLayer
pp_layers.py:257, 1F1B scheduler pipeline_parallel.py:148/455, p2p handoff
p2p_communication.py:559; behavioral spec SURVEY §B.1).

TPU-native architecture: no per-rank interpreter or message bus. The whole
pipeline is ONE SPMD program under shard_map over the 'pp' mesh axis:

- homogeneous stage layers are STACKED — params get a leading layer axis
  sharded on pp (each device owns L/P layers, applied with lax.scan);
- the microbatch schedule is a lax.scan over T = M + P - 1 ticks; at tick t
  stage r computes microbatch t - r, then hands its activation to stage r+1
  with a single ring ppermute (the p2p send/recv pair);
- reverse pass: jax.grad differentiates through scan + ppermute, yielding
  the mirrored backward pipeline automatically (GPipe fill-drain schedule;
  activation memory bounded by remat of the stage body).

``pipeline_forward`` keeps the forward-only GPipe schedule (inference);
training uses ``pipeline_train_1f1b`` — a lockstep SPMD 1F1B schedule
(parity: pipeline_parallel.py:455, behavioral spec SURVEY §B.1) where each
tick runs one forward and one rematerialised backward per stage, so peak
activation memory is O(pp) stage inputs instead of O(num_micro), and
heterogeneous first/last stages (embedding source, loss sink) are expressed
as ``first_fn``/``last_fn`` with shared-parameter gradients merged by one
psum over the pp axis (parity: PipelineLayer shared embeddings,
pp_layers.py:257).
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import mesh as mesh_lib
from ..nn.module import Layer, functional_call

__all__ = ["pipeline_forward", "stack_layer_params", "PipelineStagedLayers",
           "pipeline_train_1f1b"]


def stack_layer_params(layers: Sequence[Layer]) -> dict[str, jax.Array]:
    """Stack the path-keyed params of homogeneous layers along a new leading
    axis: list of L layers -> {path: [L, ...]} (the PipelineLayer
    LayerDesc-list collapses into one stacked tensor per weight)."""
    dicts = [l.state_dict(include_non_persistable_buffer=True) for l in layers]
    keys = dicts[0].keys()
    for d in dicts[1:]:
        if d.keys() != keys:
            raise ValueError("pipeline stages must be homogeneous")
    return {k: jnp.stack([d[k] for d in dicts]) for k in keys}


def pipeline_forward(stacked: dict[str, jax.Array], x: jax.Array,
                     layer_apply: Callable, *, mesh: Mesh | None = None,
                     axis: str = "pp", num_micro: int = 1,
                     remat: bool = True) -> jax.Array:
    """Run x through L stacked layers pipelined over the pp axis.

    stacked: {path: [L, ...]} (sharded or not — shard_map partitions by spec)
    x: [batch, ...] global batch; split into num_micro microbatches.
    layer_apply(params_slice, h) -> h : applies ONE layer.
    """
    mesh = mesh or mesh_lib.current_mesh()
    pp = mesh_lib.axis_size(axis, mesh) if mesh else 1
    if mesh is None or pp == 1:
        def body(h, sl):
            return layer_apply(sl, h), None
        out, _ = lax.scan(body, x, stacked)
        return out
    if x.shape[0] % num_micro:
        raise ValueError(f"batch {x.shape[0]} not divisible by {num_micro} microbatches")
    mb = x.shape[0] // num_micro
    xs = x.reshape(num_micro, mb, *x.shape[1:])

    apply_one = jax.checkpoint(layer_apply) if remat else layer_apply

    def stage_fn(local_params, h):
        # local_params leaves: [L/P, ...]; scan them over the microbatch act
        def body(carry, sl):
            return apply_one(sl, carry), None
        out, _ = lax.scan(body, h, local_params)
        return out

    T = num_micro + pp - 1
    perm_fwd = [(r, (r + 1) % pp) for r in range(pp)]

    def per_device(local_params, xs_local):
        r = lax.axis_index(axis)
        h0 = jnp.zeros((mb,) + xs_local.shape[2:], xs_local.dtype)
        outs0 = jnp.zeros_like(xs_local)

        def tick(carry, t):
            h_in, b_in, outs = carry
            m_idx = t - r  # microbatch this stage handles at tick t
            valid = (m_idx >= 0) & (m_idx < num_micro)
            # stage 0 reads from the input queue; others use the received act
            src = lax.cond(r == 0,
                           lambda _: lax.dynamic_index_in_dim(
                               xs_local, jnp.clip(m_idx, 0, num_micro - 1), 0,
                               keepdims=False),
                           lambda _: h_in, None)
            y = stage_fn(local_params, src)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # last stage banks its finished microbatch locally
            outs = lax.cond(
                (r == pp - 1) & valid,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(m_idx, 0, num_micro - 1), 0),
                lambda o: o, outs)
            # streamed replication: finished microbatches ride a second ring
            # channel (last stage injects, everyone else forwards), so each
            # travels every link exactly once overlapped with compute —
            # half the ICI bytes of the old post-loop whole-buffer psum.
            # rank r holds the microbatch the last stage emitted r+1 hops
            # (= ticks) ago: m_b = (t - (r+1)) - (pp-1)
            m_b = t - r - pp
            outs = lax.cond(
                (r != pp - 1) & (m_b >= 0) & (m_b < num_micro),
                lambda o: lax.dynamic_update_index_in_dim(
                    o, b_in, jnp.clip(m_b, 0, num_micro - 1), 0),
                lambda o: o, outs)
            b_out = jnp.where(r == pp - 1, y, b_in)
            # hand off to the next stage (ring; stage P-1 -> 0 is ignored on
            # the h channel, it IS the injection point of the b channel)
            h_next, b_next = lax.ppermute((y, b_out), axis, perm_fwd)
            return (h_next, b_next, outs), None

        (_, b_last, outs), _ = lax.scan(tick, (h0, h0, outs0),
                                        jnp.arange(T))
        # drain: every microbatch was injected during the T main ticks —
        # the remaining hops only FORWARD the b ring (no stage compute)
        # until the furthest rank (pp-2) has banked the last microbatch

        def drain(carry, t):
            b_in, outs = carry
            m_b = t - r - pp
            outs = lax.cond(
                (r != pp - 1) & (m_b >= 0) & (m_b < num_micro),
                lambda o: lax.dynamic_update_index_in_dim(
                    o, b_in, jnp.clip(m_b, 0, num_micro - 1), 0),
                lambda o: o, outs)
            return (lax.ppermute(b_in, axis, perm_fwd), outs), None

        if pp > 1:
            (_, outs), _ = lax.scan(drain, (b_last, outs),
                                    jnp.arange(T, T + pp - 1))
        return outs

    pspec = jax.tree.map(lambda v: P(axis, *([None] * (v.ndim - 1))), stacked)
    # partial-manual shard_map (manual pp, auto dp/fsdp/mp) requires jit;
    # nested jit is inlined so this is free inside a compiled train step
    out = jax.jit(shard_map(per_device, mesh=mesh,
                            in_specs=(pspec, P()), out_specs=P(),
                            axis_names=frozenset({axis}),
                            check_vma=False))(stacked, xs)
    return out.reshape(x.shape[0], *out.shape[2:])


def pipeline_train_1f1b(stage_params, extra_params, micro_inputs,
                        first_fn: Callable, layer_apply: Callable,
                        last_fn: Callable, *, mesh: Mesh | None = None,
                        axis: str = "pp", remat: bool = True,
                        extra_manual_axes: Sequence[str] = (),
                        micro_in_specs=None, vpp: int = 1):
    """One pipelined forward+backward over microbatches with the 1F1B
    schedule (parity: PipelineParallel.forward_backward_pipeline,
    pipeline_parallel.py:455; spec SURVEY §B.1).

    The whole schedule is ONE SPMD program: shard_map manual over ``axis``
    (plus ``extra_manual_axes``, e.g. 'sep' for ring attention inside the
    stage body); every other mesh axis (dp/fsdp/mp) stays a GSPMD auto axis,
    so batch sharding and ZeRO/TP weight shardings compose untouched.

    Schedule: T = M + 2P - 2 lockstep ticks. At tick t stage r runs the
    forward of microbatch ``t - r`` and the backward of microbatch
    ``t - (2P - 2 - r)`` (the classic 1F1B interleaving: the last stage
    folds loss forward+backward into one tick, grads stream back one stage
    per tick). Backward rematerialises the stage from its saved *input*, so
    only O(P) stage inputs are alive — the reference's "one in-flight
    activation per stage depth" property — vs O(M) for fill-drain GPipe.

    Args:
      stage_params: pytree with leading stacked-layer dim on every leaf,
        sharded ``P(axis, ...)``.
      extra_params: pytree used by ``first_fn``/``last_fn`` (embedding, final
        norm, lm head). A param referenced by both (tied embeddings) gets its
        two gradient contributions summed by the final psum over ``axis`` —
        the reference's shared-embedding allreduce (pp_layers.py:257).
      micro_inputs: pytree, every leaf ``[M, ...]`` (microbatch-major).
      first_fn(extra, micro_in) -> h:        stage-0 source (embedding).
      layer_apply(param_slice, h) -> h:      one stacked layer.
      last_fn(extra, h, micro_in) -> (num, den): loss numerator/denominator
        (sum & token count); total loss = Σnum/Σden, gradients are of the
        total loss.
      vpp: virtual-pipeline chunks per device (parity: interleaved
        PipelineParallelWithInterleave, pipeline_parallel.py:942). With
        V = vpp > 1 each device owns V NON-adjacent stage chunks
        (stage s = c*P + r): forward of microbatch m = g*P + i runs at tick
        ``i + s + g*V*P`` and its backward at
        ``(S-1) + i + (S-1-s) + g*V*P`` — a closed-form interleaved
        timetable where every stage handoff is produced exactly one tick
        before its consumption on the adjacent device, so the same two ring
        ppermutes serve all chunks with NO in-transit buffering, and the
        warm-up/cool-down bubble shrinks from 2P to (1+1/V)P ticks.
        vpp=1 reduces to the plain 1F1B schedule.

    Returns (loss, d_stage_params, d_extra_params); d_stage stays sharded on
    ``axis`` like the params, d_extra is replicated over ``axis``.
    """
    mesh = mesh or mesh_lib.current_mesh()
    pp = mesh_lib.axis_size(axis, mesh) if mesh else 1
    V = int(vpp)
    apply_one = jax.checkpoint(layer_apply) if remat else layer_apply

    def stage_fn(local_params, h):
        def body(carry, sl):
            return apply_one(sl, carry), None
        out, _ = lax.scan(body, h, local_params)
        return out

    M = jax.tree.leaves(micro_inputs)[0].shape[0]

    if mesh is None or pp == 1:
        # degenerate: plain grad-accumulation over microbatches
        def total_loss(sp, ep):
            def mb(carry, mi):
                num, den = carry
                h = first_fn(ep, mi)
                h = stage_fn(sp, h)
                n, d = last_fn(ep, h, mi)
                return (num + n, den + d), None
            (num, den), _ = lax.scan(mb, (jnp.float32(0), jnp.float32(0)),
                                     micro_inputs)
            return num / den
        loss, grads = jax.value_and_grad(total_loss, argnums=(0, 1))(
            stage_params, extra_params)
        return loss, grads[0], grads[1]

    S = pp * V                       # virtual stages
    L_total = jax.tree.leaves(stage_params)[0].shape[0]
    if L_total % S:
        raise ValueError(f"stacked layer dim {L_total} must divide over "
                         f"{S} virtual stages (pp={pp} x vpp={V})")
    Lc = L_total // S                # layers per chunk
    if V > 1:
        # reorder stages so each device's V chunks are CONTIGUOUS under the
        # P(axis) leading-dim sharding: position (r, c, j) <- stage c*P+r
        import numpy as _np
        perm = _np.concatenate([
            _np.arange(Lc) + (c * pp + r) * Lc
            for r in range(pp) for c in range(V)])
        stage_params = jax.tree.map(lambda a: jnp.take(a, perm, axis=0),
                                    stage_params)
    # last tick = backward of stage 0 for the last microbatch:
    # b(0, M-1) = 2(S-1) + (M-1)%P + ((M-1)//P)*V*P  (partial groups still
    # advance a full V*P ticks, so ceil-group accounting, not M*V)
    T = 2 * (S - 1) + (M - 1) % pp + ((M - 1) // pp) * V * pp + 1
    B = 2 * pp + 1       # per-chunk input ring buffer; slot B-1 is trash
    perm_fwd = [(r, (r + 1) % pp) for r in range(pp)]
    perm_bwd = [(r, (r - 1) % pp) for r in range(pp)]
    manual = {axis, *extra_manual_axes}

    def per_device(sp_local, extra, micros):
        r = lax.axis_index(axis)
        m0 = jax.tree.map(lambda a: a[0], micros)
        h_struct = jax.eval_shape(first_fn, extra, m0)
        zero_h = jnp.zeros(h_struct.shape, h_struct.dtype)
        # local stacked params as [V, Lc, ...] chunk-major
        sp_ch = jax.tree.map(
            lambda a: a.reshape((V, Lc) + a.shape[1:]), sp_local)
        zeros_sp = jax.tree.map(jnp.zeros_like, sp_ch)
        zeros_ex = jax.tree.map(jnp.zeros_like, extra)

        def tick(carry, t):
            # NO lax.cond anywhere in this body: collectives (ring-attention
            # ppermutes in the stage, GSPMD-inserted psums for mp/dp/fsdp)
            # must be reached by EVERY device in lockstep — stage-dependent
            # work is expressed through masked VJP cotangents instead, so
            # masked contributions are exactly zero without divergent control
            # flow (the SPMD-safe formulation of the 1F1B/VPP schedule).
            h_in, g_in, buf, gsp, gex, num_acc, den_acc = carry

            # ---- decode the forward item: tick t = i + s + g*V*P with
            # s = c*P + r  =>  q = t - r = i + (c + g*V)*P
            qf = t - r
            i_f = jnp.mod(qf, pp)
            c_f = jnp.mod(qf // pp, V)
            g_f = qf // (V * pp)
            mf = g_f * pp + i_f
            valid_f = (qf >= 0) & (mf >= 0) & (mf < M)
            mf_c = jnp.clip(mf, 0, M - 1)

            # ---- decode the backward item: t = 2(S-1) - c*P - r + i + g*V*P
            # =>  u = t + r - 2(S-1) + (V-1)*P = i + (V-1-c)*P + g*V*P
            u = t + r - 2 * (S - 1) + (V - 1) * pp
            i_b = jnp.mod(u, pp)
            cb = V - 1 - jnp.mod(u // pp, V)
            g_b = u // (V * pp)
            mb_ = g_b * pp + i_b
            valid_b = (u >= 0) & (mb_ >= 0) & (mb_ < M)
            mb_c = jnp.clip(mb_, 0, M - 1)
            cb_c = jnp.clip(cb, 0, V - 1)
            is_last_b = (r == pp - 1) & (cb_c == V - 1)

            mi_f = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                a, mf_c, 0, keepdims=False), micros)
            mi_b = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                a, mb_c, 0, keepdims=False), micros)

            # ---- forward: stage 0 (chunk 0 on device 0) sources from the
            # embedding, every other stage from the act received on the ring
            emb = first_fn(extra, mi_f)
            src = jnp.where((r == 0) & (c_f == 0), emb, h_in)
            slot_f = jnp.where(valid_f, mf_c % (B - 1), B - 1)
            buf = buf.at[c_f, slot_f].set(src)
            sp_f = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                a, c_f, 0, keepdims=False), sp_ch)
            y = stage_fn(sp_f, src)

            # ---- backward: ONE vjp serves both roles. The last stage
            # differentiates loss(stage(src_f)) seeded with cot_n=1; other
            # stages differentiate stage(saved input) seeded with the grad
            # received from downstream (cot_y). The unused cotangent is
            # zero, so the unused path contributes exactly 0 everywhere.
            slot_b = jnp.where(valid_b, mb_c % (B - 1), B - 1)
            src_saved = buf[cb_c, slot_b]
            src_bwd = jnp.where(is_last_b, src, src_saved)
            sp_b = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                a, cb_c, 0, keepdims=False), sp_ch)
            mi_bwd = jax.tree.map(
                lambda a, b_: jnp.where(is_last_b, a, b_), mi_f, mi_b)

            def composite(sp, s, ex):
                y2 = stage_fn(sp, s)
                n, d = last_fn(ex, y2, mi_bwd)
                return (y2, n), d

            (_, n), vjp_fn, d = jax.vjp(composite, sp_b, src_bwd, extra,
                                        has_aux=True)
            cot_n = jnp.where(is_last_b & valid_b, jnp.float32(1),
                              jnp.float32(0))
            cot_y = jnp.where((~is_last_b) & valid_b, g_in,
                              jnp.zeros_like(g_in))
            dsp, dsrc, dex = vjp_fn((cot_y, cot_n))

            # ---- stage-0 embedding backward (masked seed => exact zeros
            # elsewhere); shared (tied) params get both contributions summed
            seed = jnp.where((r == 0) & (cb_c == 0) & valid_b, dsrc,
                             jnp.zeros_like(dsrc))
            _, evjp = jax.vjp(lambda ex: first_fn(ex, mi_b), extra)
            (dex0,) = evjp(seed)

            # ---- accumulate (into the bwd item's chunk) + hand off
            gsp = jax.tree.map(
                lambda G, dd: G.at[cb_c].add(dd), gsp, dsp)
            gex = jax.tree.map(lambda a, x, yy: a + x + yy, gex, dex, dex0)
            num_acc = num_acc + jnp.where(is_last_b & valid_b, n, 0.0)
            den_acc = den_acc + jnp.where(is_last_b & valid_b, d, 0.0)
            y_send = jnp.where(valid_f, y, jnp.zeros_like(y))
            h_next = lax.ppermute(y_send, axis, perm_fwd)
            g_next = lax.ppermute(dsrc, axis, perm_bwd)
            return (h_next, g_next, buf, gsp, gex, num_acc, den_acc), None

        buf0 = jnp.zeros((V, B) + h_struct.shape, h_struct.dtype)
        carry0 = (zero_h, jnp.zeros_like(zero_h), buf0, zeros_sp, zeros_ex,
                  jnp.float32(0), jnp.float32(0))
        (_, _, _, gsp, gex, num, den), _ = lax.scan(tick, carry0,
                                                    jnp.arange(T))
        gsp = jax.tree.map(
            lambda G: G.reshape((V * Lc,) + G.shape[2:]), gsp)
        axes = tuple(manual)
        num = lax.psum(num, axes)
        den = lax.psum(den, axes)
        gex = jax.tree.map(lambda a: lax.psum(a, axes), gex)
        inv = jnp.where(den > 0, 1.0 / den, 0.0)
        # stage grads: psum over the extra manual axes only (they stay
        # sharded over `axis`); scale everything by 1/Σden so the gradients
        # are of the mean loss
        if extra_manual_axes:
            gsp = jax.tree.map(lambda a: lax.psum(a, tuple(extra_manual_axes)),
                               gsp)
        gsp = jax.tree.map(lambda a: (a * inv).astype(a.dtype), gsp)
        gex = jax.tree.map(lambda a: (a * inv).astype(a.dtype), gex)
        return num * inv, gsp, gex

    sp_spec = jax.tree.map(lambda v: P(axis, *([None] * (v.ndim - 1))),
                           stage_params)
    if micro_in_specs is None:
        micro_in_specs = jax.tree.map(lambda v: P(), micro_inputs)
    ex_spec = jax.tree.map(lambda v: P(), extra_params)
    out_specs = (P(), sp_spec, ex_spec)
    # partial-manual shard_map (manual pp/sep, auto dp/fsdp/mp) requires jit;
    # nested jit is inlined so this is free inside a compiled train step
    fn = jax.jit(shard_map(per_device, mesh=mesh,
                           in_specs=(sp_spec, ex_spec, micro_in_specs),
                           out_specs=out_specs, axis_names=frozenset(manual),
                           check_vma=False))
    loss, d_stage, d_extra = fn(stage_params, extra_params, micro_inputs)
    if V > 1:
        # undo the chunk-contiguous reorder so grads match the caller's
        # original layer order
        import numpy as _np
        inv = _np.argsort(perm)
        d_stage = jax.tree.map(lambda a: jnp.take(a, inv, axis=0), d_stage)
    return loss, d_stage, d_extra


class PipelineStagedLayers(Layer):
    """Module owning stacked homogeneous layers, executed pipelined.

    Parity: PipelineLayer(pp_layers.py:257) — but the segmentation is
    "stack + shard leading axis" instead of per-rank layer assignment.

    Example (Llama middle):
        staged = PipelineStagedLayers([LlamaDecoderLayer(cfg) for _ in range(L)],
                                      lambda layer, params, h: ...,)
    """

    def __init__(self, layers: Sequence[Layer], num_micro: int = 1,
                 axis: str = "pp", remat: bool = True):
        super().__init__()
        # the template is used only to re-apply one layer functionally; keep
        # it OUT of the registries so its (stage-0) weights are not duplicated
        # as trainable params next to the stacked copies
        object.__setattr__(self, "template", layers[0])
        from ..nn.module import Parameter
        param_keys = set(layers[0].param_dict())
        stacked = stack_layer_params(layers)
        for k, v in stacked.items():
            name = "s__" + k.replace(".", "__")
            spec = (axis,) + (None,) * (v.ndim - 1)
            if k in param_keys:
                self.add_parameter(name, Parameter(v, spec=spec))
            else:
                # stage buffers (BN stats, rope caches) stay buffers
                self.register_buffer(name, v)
        self._stacked_keys = list(stacked.keys())
        self.num_micro = num_micro
        self.axis = axis
        self.remat = remat

    def _stacked(self):
        out = {}
        for k in self._stacked_keys:
            name = "s__" + k.replace(".", "__")
            out[k] = (self._parameters.get(name)
                      if name in self._parameters else self._buffers[name])
        return out

    def layer_apply(self, params_slice, h, *extra):
        out, _ = functional_call(self.template, params_slice, h, *extra,
                                 training=self.training)
        return out

    def forward(self, x, *extra):
        def apply_fn(sl, h):
            return self.layer_apply(sl, h, *extra)
        return pipeline_forward(self._stacked(), x, apply_fn,
                                axis=self.axis, num_micro=self.num_micro,
                                remat=self.remat)
