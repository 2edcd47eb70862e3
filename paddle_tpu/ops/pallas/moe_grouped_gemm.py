"""Fused grouped-GEMM MoE dispatch: expert FFNs that consume routed tokens
in place (parity: the reference's cutlass grouped GEMM,
``fusion/cutlass/moe/`` — routing + dispatch fused into kernels whose
expert GEMMs read dispatched tokens directly).

Why this kernel exists (the round-5 profile of July): after the
gating chain was exonerated by the round-5 A/B, the sparse block's residual
sink is the `[E, capacity, h]` packed buffer the grouped path materializes
on BOTH sides of the expert FFN (pack-gather -> batched GEMMs ->
unpack-scatter) plus the per-copy combine. This kernel removes both
buffers:

- LHS load GATHERS tokens by routing index straight out of the `[T, h]`
  activations: per capacity-block of slots, the kernel DMAs the assigned
  token rows from HBM into VMEM (slot -> source-token map rides as a
  scalar-prefetch array in SMEM). No packed input buffer exists.
- The per-expert GEMM tiles run over a grouped (expert-segmented) grid
  ``(E, capacity/BC)`` — slot block (e, ci) multiplies against expert e's
  weight block, which the pipeline keeps resident across that expert's
  capacity blocks.
- The epilogue applies the per-slot combine (gate) weights and
  SCATTER-ADDS the weighted rows into the `[T, h]` combine output in HBM
  (read-modify-write row DMAs; the TPU grid is sequential, so cross-expert
  accumulation into the same token row is race-free). No packed output
  buffer exists either. Empty capacity slots carry a sentinel row id T
  pointing at a trash row beyond the real tokens (and combine weight 0),
  so they burn padding FLOPs — exactly like the packed path — but cannot
  corrupt real rows.

Custom VJP (autodiff would otherwise re-materialize both buffers):
- dX pass: gathers the output cotangent rows through the SAME slot->token
  index map, recomputes the expert FFN forward (remat — cheaper than
  storing [slots, H] activations), backprops to the token rows and
  scatter-accumulates dX via the same read-modify-write epilogue. Also
  emits the per-slot gate-weight gradient <g[row_s], y_s> (the combine
  weights carry gradient back into the router).
- dW pass: reuses the grouped grid with per-expert `[D, H]`/`[H, D]`
  fp32 accumulator blocks that stay in VMEM across an expert's capacity
  blocks (zeroed at ci == 0, accumulated, written back on expert change).
  At large D*H (the qwen2_moe bench shapes) the three fp32 accumulators
  plus the weight blocks exceed VMEM in one pass, so the pass splits into
  two pallas calls — (dw_in, dw_gate) and (dw_out) — each re-gathering
  rows and re-running the cheap forward GEMMs it needs (remat again:
  ~1.5x dW FLOPs buys back ~5 MB of VMEM headroom).

Differentiability contract matches ``moe_grouped_compute``: x, the combine
weights, and the three expert weight tensors carry gradients; the slot
row-id map is integer (float0).

Interpret mode (CPU tests): every mechanism used here — scalar-prefetch
grid, ``pl.ANY`` HBM refs, ``make_async_copy`` row DMAs, semaphores —
has an interpret-mode lowering, so the parity suite runs the real kernel
logic on the CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret, _scratch

__all__ = ["fused_grouped_moe", "fused_dispatch_applicable", "slot_maps"]

_BC = 128          # capacity-block rows per grid step (MXU-friendly)
_VMEM_BUDGET = 14 * 2 ** 20   # leave headroom under the ~16 MB VMEM
_SMEM_BUDGET = 256 * 2 ** 10  # slot->row map lives in SMEM (scalar prefetch)


def _act(name, v):
    if name == "silu":
        return v * jax.nn.sigmoid(v)
    if name == "relu":
        return jnp.maximum(v, 0.0)
    raise ValueError(name)  # pragma: no cover - gated by applicability


def _dact(name, v):
    if name == "silu":
        s = jax.nn.sigmoid(v)
        return s * (1.0 + v * (1.0 - s))
    if name == "relu":
        return (v > 0).astype(v.dtype)
    raise ValueError(name)  # pragma: no cover


def act_name_of(activation) -> str | None:
    """Resolve an activation callable to the kernel's static table (the
    backward needs the analytic derivative, so only known activations are
    fusable; others fall back to the packed grouped path)."""
    name = getattr(activation, "__name__", None)
    return name if name in ("silu", "relu") else None


def _block_c(capacity: int) -> int:
    if capacity >= _BC:
        return _BC
    return max(8, -(-int(capacity) // 8) * 8)  # small caps: multiple of 8


def padded_capacity(capacity: int) -> int:
    bc = _block_c(capacity)
    return -(-int(capacity) // bc) * bc


def fused_dispatch_applicable(T, D, H, E, capacity, dtype, activation,
                              gated) -> bool:
    """Shape/dtype gate for the fused dispatch. Conservative: anything
    outside falls back to ``moe_grouped_compute`` (identical semantics).

    - D % 128: the gather/scatter row DMAs and the [BC, D] VMEM tiles want
      lane-aligned rows;
    - SMEM budget: the slot->row map is scalar-prefetched;
    - VMEM budget: per-expert weight blocks + dW accumulators (fp32) +
      row blocks must fit next to the pipeline's double buffers.
    """
    if act_name_of(activation) is None:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    if D % 128 or D <= 0 or H <= 0 or T <= 0:
        return False
    cpad = padded_capacity(capacity)
    if E * cpad * 4 > _SMEM_BUDGET:
        return False
    wbytes = jnp.dtype(dtype).itemsize
    n_w = 3 if gated else 2
    bc = _block_c(capacity)
    # dW pass is the high-water mark; when one pass doesn't fit it splits
    # into (dw_in[, dw_gate]) and (dw_out) calls, so gate on the larger
    # piece: weight blocks + that piece's fp32 accumulators + row blocks.
    acc = (2 if gated else 1) * D * H * 4
    vmem = n_w * D * H * wbytes + acc + 2 * bc * D * 4
    return vmem <= _VMEM_BUDGET


def slot_maps(slot, fill_copy, occupied, w_flat, T, E, cpad, K):
    """Build the kernel's two per-slot arrays from the router's capacity
    packing (``_slot_structures`` with the PADDED capacity as stride):

    - row_id [E, cpad] int32: source token per slot; sentinel T (the trash
      row past the real tokens) for empty slots;
    - gate_w [E, cpad] f32: combine weight per slot; 0 for empty slots.
      Built by differentiable scatter, so autodiff of this map alone
      routes the kernel's per-slot gate gradient back to the per-copy
      combine weights (dropped copies get exact 0).
    """
    ec = E * cpad
    row_id = jnp.where(occupied, fill_copy // K, T).astype(jnp.int32)
    gate_w = jnp.zeros((ec + 1,), jnp.float32).at[slot].set(
        w_flat.astype(jnp.float32), mode="drop")[:ec]
    return row_id.reshape(E, cpad), gate_w.reshape(E, cpad)


# ---------------- forward ----------------

def _row_loop(n, start_fn, sem, probe_src, probe_dst):
    """Issue ``n`` same-shaped row DMAs then drain the semaphore: every
    completion decrements by the same byte count, so one wait per copy."""
    lax.fori_loop(0, n, lambda i, _: (start_fn(i), 0)[1], 0)

    def _wait(i, _):
        pltpu.make_async_copy(probe_src, probe_dst, sem).wait()
        return 0
    lax.fori_loop(0, n, _wait, 0)


def _fwd_kernel(row_ref, x_any, gw_ref, w_in_ref, *rest, T, tpad, bc, nc,
                has_gate, act_name):
    if has_gate:
        w_gate_ref, w_out_ref, o_any, xg, acc, sem_in, sem_out = rest
    else:
        w_out_ref, o_any, xg, acc, sem_in, sem_out = rest
        w_gate_ref = None
    e, ci = pl.program_id(0), pl.program_id(1)
    base = (e * nc + ci) * bc

    @pl.when((e == 0) & (ci == 0))
    def _zero_out():
        acc[...] = jnp.zeros_like(acc)

        def _z(i):
            pltpu.make_async_copy(acc, o_any.at[pl.ds(i * bc, bc)],
                                  sem_out).start()
        _row_loop(tpad // bc, _z, sem_out, acc, o_any.at[pl.ds(0, bc)])

    # LHS gather: token rows by routing index, straight from HBM
    def _g(i):
        r = jnp.minimum(row_ref[base + i], T - 1)  # sentinel gathers row T-1
        pltpu.make_async_copy(x_any.at[pl.ds(r, 1)], xg.at[pl.ds(i, 1)],
                              sem_in).start()
    _row_loop(bc, _g, sem_in, x_any.at[pl.ds(0, 1)], xg.at[pl.ds(0, 1)])

    xb = xg[...]
    h1 = lax.dot(xb, w_in_ref[0], preferred_element_type=jnp.float32)
    if has_gate:
        hg = lax.dot(xb, w_gate_ref[0], preferred_element_type=jnp.float32)
        h = _act(act_name, hg) * h1
    else:
        h = _act(act_name, h1)
    y = lax.dot(h.astype(xb.dtype), w_out_ref[0],
                preferred_element_type=jnp.float32)
    y = y * gw_ref[0, :][:, None]  # combine weight epilogue (0 kills pads)

    # scatter-add into the combine output: read-modify-write row DMAs;
    # the sequential grid orders cross-expert contributions to one token
    def _r(i):
        pltpu.make_async_copy(o_any.at[pl.ds(row_ref[base + i], 1)],
                              acc.at[pl.ds(i, 1)], sem_out).start()
    _row_loop(bc, _r, sem_out, o_any.at[pl.ds(0, 1)], acc.at[pl.ds(0, 1)])
    acc[...] = acc[...] + y

    def _w(i):
        pltpu.make_async_copy(acc.at[pl.ds(i, 1)],
                              o_any.at[pl.ds(row_ref[base + i], 1)],
                              sem_out).start()
    _row_loop(bc, _w, sem_out, acc.at[pl.ds(0, 1)], o_any.at[pl.ds(0, 1)])


def _grid_spec(E, cpad, bc, nc, n_extra_in, out_specs, scratch):
    """PrefetchScalarGridSpec shared by the three passes: scalar slot map,
    x in HBM (ANY), per-slot gate weights, per-expert weight blocks."""
    def _e0(e, ci, row_ref):
        return (e, 0, 0)

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]  # x
    in_specs += [pl.BlockSpec((1, bc), lambda e, ci, row_ref: (e, ci))]  # gw
    in_specs += [pl.BlockSpec((1, None, None), _e0)] * n_extra_in  # weights
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(E, nc), in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch)


def _weight_specs(shapes):
    """Per-expert weight BlockSpecs: block (1, d0, d1), resident per e."""
    return [pl.BlockSpec((1, s[1], s[2]), lambda e, ci, row_ref: (e, 0, 0))
            for s in shapes]


def _fwd_call(x, row_id, gate_w, w_in, w_gate, w_out, act_name):
    T, D = x.shape
    E, cpad = row_id.shape
    H = w_in.shape[2]
    bc = cpad if cpad < _BC else _BC
    nc = cpad // bc
    tpad = (T // bc + 1) * bc  # >= T+1: row T is the sentinel trash row
    has_gate = w_gate is not None
    weights = [w_in] + ([w_gate] if has_gate else []) + [w_out]
    in_specs = ([pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec((1, bc), lambda e, ci, row_ref: (e, ci))]
                + _weight_specs([w.shape for w in weights]))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, T=T, tpad=tpad, bc=bc, nc=nc,
                          has_gate=has_gate, act_name=act_name),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(E, nc), in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((bc, D), x.dtype), _scratch((bc, D)),
                pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((tpad, D), jnp.float32),
        interpret=_interpret(),
    )(row_id.reshape(-1), x, gate_w, *weights)
    return out[:T]


# ---------------- backward: dX + d(gate_w) ----------------

def _dx_kernel(row_ref, x_any, gw_ref, g_any, w_in_ref, *rest, T, tpad, bc,
               nc, has_gate, act_name):
    if has_gate:
        (w_gate_ref, w_out_ref, dx_any, dgw_ref,
         xg, gg, acc, sem_in, sem_out) = rest
    else:
        w_out_ref, dx_any, dgw_ref, xg, gg, acc, sem_in, sem_out = rest
        w_gate_ref = None
    e, ci = pl.program_id(0), pl.program_id(1)
    base = (e * nc + ci) * bc

    @pl.when((e == 0) & (ci == 0))
    def _zero_dx():
        acc[...] = jnp.zeros_like(acc)

        def _z(i):
            pltpu.make_async_copy(acc, dx_any.at[pl.ds(i * bc, bc)],
                                  sem_out).start()
        _row_loop(tpad // bc, _z, sem_out, acc, dx_any.at[pl.ds(0, bc)])

    def _g(i):
        r = jnp.minimum(row_ref[base + i], T - 1)
        pltpu.make_async_copy(x_any.at[pl.ds(r, 1)], xg.at[pl.ds(i, 1)],
                              sem_in).start()
    _row_loop(bc, _g, sem_in, x_any.at[pl.ds(0, 1)], xg.at[pl.ds(0, 1)])

    def _gy(i):
        r = jnp.minimum(row_ref[base + i], T - 1)
        pltpu.make_async_copy(g_any.at[pl.ds(r, 1)], gg.at[pl.ds(i, 1)],
                              sem_in).start()
    _row_loop(bc, _gy, sem_in, g_any.at[pl.ds(0, 1)], gg.at[pl.ds(0, 1)])

    xb = xg[...]
    wi = w_in_ref[0]
    wo = w_out_ref[0]
    h1 = lax.dot(xb, wi, preferred_element_type=jnp.float32)
    if has_gate:
        hg = lax.dot(xb, w_gate_ref[0], preferred_element_type=jnp.float32)
        ag = _act(act_name, hg)
        h = ag * h1
    else:
        h = _act(act_name, h1)
    y = lax.dot(h.astype(xb.dtype), wo, preferred_element_type=jnp.float32)
    gf = gg[...].astype(jnp.float32)
    # gate-weight gradient: <dOut[row_s], y_s> per slot (pads yield garbage
    # here, but no token copy maps to a pad slot so it is never gathered)
    dgw_ref[0, :] = jnp.sum(gf * y, axis=1)
    dy = gf * gw_ref[0, :][:, None]
    dh = lax.dot_general(dy.astype(xb.dtype), wo,
                         (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    if has_gate:
        dh1 = dh * ag
        dhg = dh * h1 * _dact(act_name, hg)
        dxr = lax.dot_general(dh1.astype(xb.dtype), wi,
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        dxr = dxr + lax.dot_general(dhg.astype(xb.dtype), w_gate_ref[0],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    else:
        dh1 = dh * _dact(act_name, h1)
        dxr = lax.dot_general(dh1.astype(xb.dtype), wi,
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)

    def _r(i):
        pltpu.make_async_copy(dx_any.at[pl.ds(row_ref[base + i], 1)],
                              acc.at[pl.ds(i, 1)], sem_out).start()
    _row_loop(bc, _r, sem_out, dx_any.at[pl.ds(0, 1)], acc.at[pl.ds(0, 1)])
    acc[...] = acc[...] + dxr

    def _w(i):
        pltpu.make_async_copy(acc.at[pl.ds(i, 1)],
                              dx_any.at[pl.ds(row_ref[base + i], 1)],
                              sem_out).start()
    _row_loop(bc, _w, sem_out, acc.at[pl.ds(0, 1)], dx_any.at[pl.ds(0, 1)])


def _dx_call(x, row_id, gate_w, w_in, w_gate, w_out, g, act_name):
    T, D = x.shape
    E, cpad = row_id.shape
    bc = cpad if cpad < _BC else _BC
    nc = cpad // bc
    tpad = (T // bc + 1) * bc
    has_gate = w_gate is not None
    weights = [w_in] + ([w_gate] if has_gate else []) + [w_out]
    in_specs = ([pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec((1, bc), lambda e, ci, row_ref: (e, ci)),
                 pl.BlockSpec(memory_space=pl.ANY)]
                + _weight_specs([w.shape for w in weights]))
    dx, dgw = pl.pallas_call(
        functools.partial(_dx_kernel, T=T, tpad=tpad, bc=bc, nc=nc,
                          has_gate=has_gate, act_name=act_name),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(E, nc), in_specs=in_specs,
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec((1, bc),
                                    lambda e, ci, row_ref: (e, ci))),
            scratch_shapes=[
                pltpu.VMEM((bc, D), x.dtype), pltpu.VMEM((bc, D), g.dtype),
                _scratch((bc, D)),
                pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA]),
        out_shape=(jax.ShapeDtypeStruct((tpad, D), jnp.float32),
                   jax.ShapeDtypeStruct((E, cpad), jnp.float32)),
        interpret=_interpret(),
    )(row_id.reshape(-1), x, gate_w, g, *weights)
    return dx[:T], dgw


# ---------------- backward: dW (grouped-grid accumulation) ----------------

def _dw_kernel(row_ref, x_any, gw_ref, g_any, w_in_ref, *rest, T, bc, nc,
               has_gate, act_name, want_in, want_out):
    rest = list(rest)
    w_gate_ref = rest.pop(0) if has_gate else None
    w_out_ref = rest.pop(0)
    dwi_ref = rest.pop(0) if want_in else None
    dwg_ref = rest.pop(0) if (want_in and has_gate) else None
    dwo_ref = rest.pop(0) if want_out else None
    xg, gg, sem_in = rest
    e, ci = pl.program_id(0), pl.program_id(1)
    base = (e * nc + ci) * bc

    @pl.when(ci == 0)
    def _zero_acc():
        if want_in:
            dwi_ref[...] = jnp.zeros_like(dwi_ref)
            if has_gate:
                dwg_ref[...] = jnp.zeros_like(dwg_ref)
        if want_out:
            dwo_ref[...] = jnp.zeros_like(dwo_ref)

    def _g(i):
        r = jnp.minimum(row_ref[base + i], T - 1)
        pltpu.make_async_copy(x_any.at[pl.ds(r, 1)], xg.at[pl.ds(i, 1)],
                              sem_in).start()
    _row_loop(bc, _g, sem_in, x_any.at[pl.ds(0, 1)], xg.at[pl.ds(0, 1)])

    def _gy(i):
        r = jnp.minimum(row_ref[base + i], T - 1)
        pltpu.make_async_copy(g_any.at[pl.ds(r, 1)], gg.at[pl.ds(i, 1)],
                              sem_in).start()
    _row_loop(bc, _gy, sem_in, g_any.at[pl.ds(0, 1)], gg.at[pl.ds(0, 1)])

    xb = xg[...]
    wi = w_in_ref[0]
    wo = w_out_ref[0]
    h1 = lax.dot(xb, wi, preferred_element_type=jnp.float32)
    if has_gate:
        hg = lax.dot(xb, w_gate_ref[0], preferred_element_type=jnp.float32)
        ag = _act(act_name, hg)
        h = ag * h1
    else:
        h = _act(act_name, h1)
    dy = gg[...].astype(jnp.float32) * gw_ref[0, :][:, None]
    # per-expert fp32 accumulators, resident in VMEM across ci
    if want_out:
        dwo_ref[0] += lax.dot_general(h.astype(xb.dtype),
                                      dy.astype(xb.dtype),
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    if want_in:
        dh = lax.dot_general(dy.astype(xb.dtype), wo,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        if has_gate:
            dh1 = dh * ag
            dhg = dh * h1 * _dact(act_name, hg)
        else:
            dh1 = dh * _dact(act_name, h1)
        dwi_ref[0] += lax.dot_general(xb, dh1.astype(xb.dtype),
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        if has_gate:
            dwg_ref[0] += lax.dot_general(xb, dhg.astype(xb.dtype),
                                          (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)


def _dw_call(x, row_id, gate_w, w_in, w_gate, w_out, g, act_name):
    T, D = x.shape
    E, cpad = row_id.shape
    H = w_in.shape[2]
    bc = cpad if cpad < _BC else _BC
    nc = cpad // bc
    has_gate = w_gate is not None
    weights = [w_in] + ([w_gate] if has_gate else []) + [w_out]
    in_specs = ([pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec((1, bc), lambda e, ci, row_ref: (e, ci)),
                 pl.BlockSpec(memory_space=pl.ANY)]
                + _weight_specs([w.shape for w in weights]))

    def _acc_spec(d0, d1):
        return pl.BlockSpec((1, d0, d1), lambda e, ci, row_ref: (e, 0, 0))

    def _one_call(want_in, want_out):
        out_specs, out_shapes = [], []
        if want_in:
            n = 2 if has_gate else 1
            out_specs += [_acc_spec(D, H)] * n
            out_shapes += [jax.ShapeDtypeStruct((E, D, H), jnp.float32)] * n
        if want_out:
            out_specs.append(_acc_spec(H, D))
            out_shapes.append(jax.ShapeDtypeStruct((E, H, D), jnp.float32))
        return pl.pallas_call(
            functools.partial(_dw_kernel, T=T, bc=bc, nc=nc,
                              has_gate=has_gate, act_name=act_name,
                              want_in=want_in, want_out=want_out),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(E, nc), in_specs=in_specs,
                out_specs=tuple(out_specs),
                scratch_shapes=[
                    pltpu.VMEM((bc, D), x.dtype),
                    pltpu.VMEM((bc, D), g.dtype),
                    pltpu.SemaphoreType.DMA]),
            out_shape=tuple(out_shapes),
            interpret=_interpret(),
        )(row_id.reshape(-1), x, gate_w, g, *weights)

    # One pass holds every fp32 accumulator in VMEM at once; when that
    # overflows the budget, split into (dw_in[, dw_gate]) then (dw_out) —
    # each call re-gathers rows and recomputes the cheap forward GEMMs.
    wbytes = jnp.dtype(x.dtype).itemsize
    one_pass = (len(weights) * D * H * wbytes
                + (3 if has_gate else 2) * D * H * 4 + 2 * bc * D * 4)
    if one_pass <= _VMEM_BUDGET:
        outs = _one_call(True, True)
        if has_gate:
            dwi, dwg, dwo = outs
        else:
            (dwi, dwo), dwg = outs, None
    else:
        ins = _one_call(True, False)
        dwi, dwg = ins if has_gate else (ins[0], None)
        dwo, = _one_call(False, True)
    return dwi, dwg, dwo


# ---------------- custom VJP wrapper ----------------

def _float0(shape):
    return np.zeros(shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def fused_grouped_moe(x, row_id, gate_w, w_in, w_gate, w_out, act_name):
    """Routed-expert output [T, D] from x [T, D] and the per-slot maps
    (``slot_maps``): gather -> grouped GEMMs -> gate-weighted scatter-add,
    with no [E, capacity, D] buffer on either side. ``w_gate`` may be
    None (ungated FFN). ``act_name`` comes from :func:`act_name_of`."""
    return _fused_fwd(x, row_id, gate_w, w_in, w_gate, w_out, act_name)[0]


def _fused_fwd(x, row_id, gate_w, w_in, w_gate, w_out, act_name):
    out = _fwd_call(x, row_id, gate_w, w_in, w_gate, w_out,
                    act_name).astype(x.dtype)
    return out, (x, row_id, gate_w, w_in, w_gate, w_out)


def _fused_bwd(act_name, res, g):
    x, row_id, gate_w, w_in, w_gate, w_out = res
    dx, dgw = _dx_call(x, row_id, gate_w, w_in, w_gate, w_out, g, act_name)
    dwi, dwg, dwo = _dw_call(x, row_id, gate_w, w_in, w_gate, w_out, g,
                             act_name)
    return (dx.astype(x.dtype), _float0(row_id.shape), dgw,
            dwi.astype(w_in.dtype),
            None if w_gate is None else dwg.astype(w_gate.dtype),
            dwo.astype(w_out.dtype))


fused_grouped_moe.defvjp(_fused_fwd, _fused_bwd)
