"""Fused top-2 MoE routing kernel — the routing FRONT-END of the fused
grouped-GEMM dispatch (``dispatch="fused"``: this kernel decides, then
ops/pallas/moe_grouped_gemm.py gathers/computes/scatters). Selected via
``_top2_parts(..., impl="fused")``; there is no standalone flag — the
round-5 A/B showed the in-situ routing cost is too small (~0.1-0.2 ms) to
justify an independent switch, so it rides with the dispatch that needs
its sparse outputs anyway.

The round-5 profile of July named routing/gating as a suspected sink:
the XLA lowering of ``_top2_parts`` is ~30 small serially-dependent
kernels over a [T, E] logits tile (softmax, two argmaxes, one-hots,
position cumsums, renorm) — latency-bound on the VPU, ~1.2 ms forward at
bench shapes where the expert GEMMs themselves take ~0.95 ms.

This kernel computes the whole routing decision in ONE sequential-grid
Pallas pass (parity: the reference fuses the same chain into two CUDA
kernels — ``fusion/cutlass/moe_kernel.cu`` topk + aligned scatter):

  per block of BT tokens
    softmax -> top-1/top-2 indices and probs -> random second-expert keep
    (uniforms PASSED IN so decisions are bitwise-identical to the XLA
    path under the same PRNG key) -> first-come-first-served position
    assignment via an in-kernel [BT, BT] tril matmul (MXU) with running
    per-expert counts carried across blocks in scratch.

The capacity/renormalization epilogue and the analytic backward (softmax
VJP with scatter of dW into the two chosen experts + the dense aux-loss
term) are a handful of fused XLA elementwise ops — the custom VJP
replaces autodiff's long small-op backward chain.

Differentiability contract matches ``_top2_parts``: w1/w2 and aux carry
gradients to the logits; indices, positions and keep flags are integer
(float0). The random-keep threshold comparison is non-differentiable in
both implementations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .flash_attention import _interpret, _scratch

_BT = 1024  # token block; grid is sequential so counts carry across blocks


def _routing_kernel(logits_ref, u_ref, g1i_ref, g2i_ref, g1_ref, g2_ref,
                    p1_ref, c2_ref, keep2_ref, count1_ref, me_ref,
                    run1_ref, run2_ref, me_acc_ref, *,
                    blocks, random_keep2):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        run1_ref[...] = jnp.zeros_like(run1_ref)
        run2_ref[...] = jnp.zeros_like(run2_ref)
        me_acc_ref[...] = jnp.zeros_like(me_acc_ref)

    l = logits_ref[...].astype(jnp.float32)          # [BT, E]
    bt, e = l.shape
    mx = jnp.max(l, axis=1, keepdims=True)
    ex = jnp.exp(l - mx)
    probs = ex / jnp.sum(ex, axis=1, keepdims=True)

    iota = lax.broadcasted_iota(jnp.int32, (bt, e), 1)
    g1v = jnp.max(probs, axis=1, keepdims=True)
    g1i = jnp.min(jnp.where(probs >= g1v, iota, e), axis=1)  # first-tie argmax
    m1 = iota == g1i[:, None]
    pw = jnp.where(m1, 0.0, probs)
    g2v = jnp.max(pw, axis=1, keepdims=True)
    g2i = jnp.min(jnp.where(pw >= g2v, iota, e), axis=1)
    m2 = iota == g2i[:, None]
    g1 = jnp.sum(jnp.where(m1, probs, 0.0), axis=1)
    g2 = jnp.sum(jnp.where(m2, pw, 0.0), axis=1)

    if random_keep2:
        u = u_ref[b, :].astype(jnp.float32)
        keep2 = u < (2.0 * g2 / jnp.maximum(g1 + g2, 1e-9))
    else:
        keep2 = jnp.ones((bt,), jnp.bool_)

    mask1 = m1.astype(jnp.float32)
    # cast BEFORE the [:, None] broadcast: Mosaic only supports minor-dim
    # insertion on 32-bit types (bool is 1-bit)
    mask2 = m2.astype(jnp.float32) * keep2.astype(jnp.float32)[:, None]

    # inclusive within-block cumsum as ONE MXU matmul (0/1 values, sums
    # <= BT: exact in fp32)
    r = lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    c = lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    tril = (r >= c).astype(jnp.float32)
    c1 = jnp.dot(tril, mask1, preferred_element_type=jnp.float32)
    c2 = jnp.dot(tril, mask2, preferred_element_type=jnp.float32)
    pos1 = run1_ref[0, :][None, :] + c1              # inclusive global
    pos2 = run2_ref[0, :][None, :] + c2
    # 0-based claimed-slot position of each token (0 when no claim)
    p1 = jnp.sum((pos1 - 1.0) * mask1, axis=1)
    c2tok = jnp.sum((pos2 - 1.0) * mask2, axis=1)

    row = pl.dslice(b, 1)
    g1i_ref[row, :] = g1i.astype(jnp.int32)[None]
    g2i_ref[row, :] = g2i.astype(jnp.int32)[None]
    g1_ref[row, :] = g1[None]
    g2_ref[row, :] = g2[None]
    p1_ref[row, :] = p1.astype(jnp.int32)[None]
    c2_ref[row, :] = c2tok.astype(jnp.int32)[None]
    keep2_ref[row, :] = keep2.astype(jnp.int32)[None]

    run1_ref[0, :] += jnp.sum(mask1, axis=0)
    run2_ref[0, :] += jnp.sum(mask2, axis=0)
    me_acc_ref[0, :] += jnp.sum(probs, axis=0)

    @pl.when(b == blocks - 1)
    def _fin():
        count1_ref[0, :] = run1_ref[0, :]            # == sum of one-hot(g1)
        me_ref[0, :] = me_acc_ref[0, :]


def _run_kernel(logits, u, random_keep2):
    """Per-token vectors ride as 2-D [blocks, BT] arrays (1-D f32 arrays
    get size-dependent XLA tilings that Mosaic block shapes cannot match);
    reshaped back to [T] on return."""
    T, E = logits.shape
    blocks = T // _BT
    # per-token vectors live as [blocks, BT] arrays held ENTIRELY in VMEM
    # (constant index map; 32 KB each at bench shapes) — satisfies the
    # (8, 128)-divisibility rule via full-dimension blocks, and the
    # sequential grid writes one row per step
    vec = lambda: pl.BlockSpec((blocks, _BT), lambda b: (0, 0))
    erow = pl.BlockSpec((1, E), lambda b: (0, 0))
    out_shapes = (
        jax.ShapeDtypeStruct((blocks, _BT), jnp.int32),    # g1_idx
        jax.ShapeDtypeStruct((blocks, _BT), jnp.int32),    # g2_idx
        jax.ShapeDtypeStruct((blocks, _BT), jnp.float32),  # g1
        jax.ShapeDtypeStruct((blocks, _BT), jnp.float32),  # g2
        jax.ShapeDtypeStruct((blocks, _BT), jnp.int32),    # p1
        jax.ShapeDtypeStruct((blocks, _BT), jnp.int32),    # c2 (pre-offset)
        jax.ShapeDtypeStruct((blocks, _BT), jnp.int32),    # keep2
        jax.ShapeDtypeStruct((1, E), jnp.float32),         # count1
        jax.ShapeDtypeStruct((1, E), jnp.float32),         # me_sum
    )
    uin = (u if u is not None else jnp.zeros((T,), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_routing_kernel, blocks=blocks,
                          random_keep2=random_keep2),
        grid=(blocks,),
        in_specs=[pl.BlockSpec((_BT, E), lambda b: (b, 0)), vec()],
        out_specs=(vec(), vec(), vec(), vec(), vec(), vec(), vec(),
                   erow, erow),
        out_shape=out_shapes,
        scratch_shapes=[_scratch((1, E)), _scratch((1, E)),
                        _scratch((1, E))],
        interpret=_interpret(),
        name="moe_top2_routing",
    )(logits.astype(jnp.float32), uin.reshape(blocks, _BT))
    flat = tuple(o.reshape(T) for o in outs[:7])
    return flat + (outs[7].reshape(E), outs[8].reshape(E))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_top2_routing(logits, u, capacity, random_keep2,
                       balance_loss_weight):
    """Fused ``_top2_parts``: same 9-tuple
    (g1_idx, g2_idx, w1, w2, keep1, keep2f, p1, p2, aux)."""
    out, _ = _fused_fwd(logits, u, capacity, random_keep2,
                        balance_loss_weight)
    return out


def _fused_fwd(logits, u, capacity, random_keep2, balance_loss_weight):
    T, E = logits.shape
    g1i, g2i, g1, g2, p1, c2, keep2, count1, me_sum = _run_kernel(
        logits, u, random_keep2)
    # epilogue: capacity + renorm + aux (a few fused elementwise XLA ops);
    # the renorm is the SHARED contract — the XLA chain uses the same
    # function, so the two implementations cannot drift on drop semantics
    from ...distributed.moe import _top2_epilogue
    keep1 = p1 < capacity
    claimed2 = keep2 > 0
    p2 = jnp.where(claimed2, c2 + count1[g2i].astype(jnp.int32), 0)
    keep2f = (p2 < capacity) & claimed2
    w1, w2 = _top2_epilogue(g1, g2, keep1, keep2f)
    ce = count1 / T
    aux = jnp.sum((me_sum / T) * ce) * E * balance_loss_weight
    out = (g1i, g2i, w1, w2, keep1, keep2f, p1, p2, aux)
    res = (logits, g1i, g2i, g1, g2, keep1, keep2f, ce)
    return out, res


def _fused_bwd(capacity, random_keep2, balance_loss_weight, res, cots):
    logits, g1i, g2i, g1, g2, keep1, keep2f, ce = res
    _, _, dw1, dw2, _, _, _, _, daux = cots
    T, E = logits.shape
    k1 = keep1.astype(jnp.float32)
    k2 = keep2f.astype(jnp.float32)
    s = k1 * g1 + k2 * g2
    live = (s >= 1e-9).astype(jnp.float32)   # max(s, eps) subgradient
    d = jnp.maximum(s, 1e-9)
    d2 = d * d
    # w1 = k1*g1/d, w2 = k2*g2/d, d = max(k1 g1 + k2 g2, eps)
    dg1 = dw1 * (k1 / d - k1 * k1 * g1 * live / d2) \
        + dw2 * (-k2 * g2 * k1 * live / d2)
    dg2 = dw2 * (k2 / d - k2 * k2 * g2 * live / d2) \
        + dw1 * (-k1 * g1 * k2 * live / d2)
    # scatter into the two chosen experts + dense aux term; then softmax VJP
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    oh1 = jax.nn.one_hot(g1i, E, dtype=jnp.float32)
    oh2 = jax.nn.one_hot(g2i, E, dtype=jnp.float32)
    dprobs = dg1[:, None] * oh1 + dg2[:, None] * oh2
    dprobs = dprobs + (daux * balance_loss_weight * E / T) * ce[None, :]
    dlogits = probs * (dprobs - jnp.sum(dprobs * probs, axis=-1,
                                        keepdims=True))
    return dlogits.astype(logits.dtype), None


fused_top2_routing.defvjp(
    lambda logits, u, capacity, random_keep2, w:
        _fused_fwd(logits, u, capacity, random_keep2, w),
    _fused_bwd)


def fused_routing_applicable(T, E) -> bool:
    """Shape gate: sequential-grid blocks need T % BT == 0; E must fit one
    lane tile; T is capped because the eight per-token output arrays live
    ENTIRELY in VMEM (constant index map) next to the 4 MB tril — past
    ~64k tokens the kernel would fail Mosaic compilation instead of
    falling back, breaking the fall-back-on-unsupported-shapes contract."""
    return T % _BT == 0 and _BT <= T <= 65536 and E <= 128
