"""Mixture-of-Experts with expert parallelism
(parity: python/paddle/incubate/distributed/models/moe/ — MoELayer
moe_layer.py:263, GShardGate gshard_gate.py:31, SwitchGate switch_gate.py:31,
token dispatch via global_scatter/global_gather all-to-all
distributed/utils/moe_utils.py:20,153 and the CUDA routing kernels
number_count/limit_by_capacity/prune_gate_by_capacity).

TPU-native design (GShard-style dense dispatch):
- routing, capacity limiting and combine are einsums over a one-hot dispatch
  tensor — XLA turns these into the same all-to-all the reference launches
  explicitly when the expert dim is sharded on the 'ep'/'mp' mesh axis;
- expert FFNs are ONE batched weight tensor [E, d_in, d_out] sharded on the
  expert axis — the grouped GEMM the reference implements in cutlass
  (fused_moe) is a single einsum on the MXU;
- capacity enforcement via position-in-expert cumsum (the reference's
  limit_by_capacity/prune_gate kernels collapse into a cumsum + mask).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core import rng
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.module import Layer, Parameter

__all__ = ["MoELayer", "HeldExpertsMoE", "TopKGate", "SwitchGate",
           "GShardGate", "ExpertFFN", "relu2", "moe_held_dense_compute",
           "moe_held_tiles_compute", "moe_dispatch_combine", "moe_ragged_compute", "moe_grouped_compute",
           "moe_fused_compute", "global_scatter", "global_gather"]


def global_scatter(x, local_count, global_count, axis: str = "mp"):
    """Explicit expert-parallel token dispatch (parity:
    distributed/utils/moe_utils.py:20 ``global_scatter`` over the
    global_scatter_op all-to-all).

    Call INSIDE a shard_map manual over ``axis`` (the EP group). Each rank
    holds ``x`` = its local tokens grouped by destination expert in
    capacity-padded expert-major layout [E, C, d] (E = total experts =
    P * experts_per_rank). The all-to-all reshapes so every rank receives
    the slots bound for ITS experts from every peer:
    [E, C, d] -> [P, E/P, C, d] -all_to_all-> [P, E/P, C, d]
    = per-source-rank slots for my local experts.
    Returns [E/P_local_experts, P*C, d] — each local expert's inbox.
    """
    from jax import lax
    E, C, d = x.shape
    P = lax.psum(1, axis)
    xr = x.reshape(P, E // P, C, d)
    recv = lax.all_to_all(xr, axis, split_axis=0, concat_axis=0, tiled=False)
    # recv: [P(source), E/P(my experts), C, d] -> inbox per local expert
    return jnp.moveaxis(recv, 0, 1).reshape(E // P, P * C, d)


def global_gather(y, local_count, global_count, axis: str = "mp"):
    """Inverse of global_scatter (parity: moe_utils.py:153): expert outputs
    [E/P, P*C, d] return to their source ranks as [E, C, d]."""
    from jax import lax
    Elocal, PC, d = y.shape
    P = lax.psum(1, axis)
    C = PC // P
    yr = jnp.moveaxis(y.reshape(Elocal, P, C, d), 1, 0)  # [P, E/P, C, d]
    back = lax.all_to_all(yr, axis, split_axis=0, concat_axis=0, tiled=False)
    return back.reshape(P * Elocal, C, d)


def _fcfs_cumsum(mask, block: int = 512):
    """Inclusive cumsum of a 0/1 int mask over axis 0 (the FCFS
    position-in-expert assignment), computed as a blocked tril-matmul on
    the MXU plus a tiny per-block offset cumsum.

    Why: ``jnp.cumsum`` over T=8k tokens lowers to a log-depth chain of
    ~13 dependent kernels over [T, E] — latency-bound, ~1 ms per cumsum
    on a v5e (a July profile named routing as the MoE block's top
    sink). One [B, B] @ [B, E] matmul per block does the same work in a
    single MXU pass. Exact: 0/1 values, block sums <= block <= 512, fp32
    accumulation — integer-exact far beyond these counts."""
    T, E = mask.shape
    if T % block or T <= block:
        return jnp.cumsum(mask, axis=0)
    nb = T // block
    m = mask.astype(jnp.float32).reshape(nb, block, E)
    tril = jnp.tril(jnp.ones((block, block), jnp.float32))
    within = jax.lax.dot_general(
        tril, m, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)  # [block, nb, E]
    within = jnp.moveaxis(within, 0, 1)  # [nb, block, E] inclusive-in-block
    totals = within[:, -1, :]
    offsets = jnp.concatenate(
        [jnp.zeros((1, E), jnp.float32), jnp.cumsum(totals, axis=0)[:-1]],
        axis=0)
    out = within + offsets[:, None, :]
    return out.reshape(T, E).astype(mask.dtype)


def _kernel_path_ok() -> bool:
    """Pallas MoE kernels (routing front-end and fused dispatch) carry no
    GSPMD partitioning rule, so they only run meshless or inside a manual
    shard_map region (local shapes — the all-to-all EP path). Under
    auto-GSPMD meshes the XLA chain keeps the dense path partitionable."""
    from .._mesh_gate import no_mesh_active
    from ..nn.functional.attention import _in_manual_trace
    return no_mesh_active() or _in_manual_trace()


def _top2_epilogue(g1, g2, keep1, keep2f):
    """THE capacity/renormalization contract: combine weights are the raw
    top-2 probs, zeroed for capacity-dropped copies, renormalized over the
    kept experts (GShard). Single definition shared by the XLA chain, the
    fused routing kernel's epilogue (ops/pallas/moe_routing.py) and — via
    the w arrays the sparse form hands over — the fused dispatch, so the
    paths cannot drift on what a 'dropped' copy contributes."""
    denom = jnp.maximum(g1 * keep1 + g2 * keep2f, 1e-9)
    w1 = jnp.where(keep1, g1, 0.0) / denom
    w2 = jnp.where(keep2f, g2, 0.0) / denom
    return w1, w2


def _top2_parts(logits, capacity, *, second_policy="random", key=None,
                balance_loss_weight=1.0, impl="xla"):
    """GShard top-2 gating core. logits: [tokens, E]. Returns the routing
    decision pieces shared by the dense (one-hot) and sparse (sorted/ragged/
    fused) dispatch builders so every path shares one set of gating rules:
    (g1_idx, g2_idx, w1, w2, keep1, keep2f, p1, p2, aux) — w1/w2 are already
    zeroed for capacity-dropped slots and renormalized over kept experts
    (the shared ``_top2_epilogue``).

    ``impl`` selects the implementation: "xla" is the dense chain below;
    "fused" routes through the one-pass Pallas kernel
    (ops/pallas/moe_routing.py — the fused dispatch's routing front-end),
    falling back to the XLA chain when shapes or mesh state don't fit.
    Identical up to float tie-breaks: the random second-expert keep draws
    its uniforms OUTSIDE both paths from the same key, so the compared
    randomness is shared — but each path computes its OWN softmax, and
    argmax ties or keep2 threshold comparisons that land exactly on
    differently-rounded probabilities can resolve differently between the
    two."""
    T, E = logits.shape
    if second_policy == "random":
        k = key if key is not None else rng.next_key()
        u = jax.random.uniform(k, (T,))
    else:
        u = None
    if impl == "fused":
        from ..ops.pallas.moe_routing import (fused_routing_applicable,
                                              fused_top2_routing)
        if fused_routing_applicable(T, E) and _kernel_path_ok():
            return fused_top2_routing(logits, u, int(capacity),
                                      second_policy == "random",
                                      float(balance_loss_weight))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    g1_idx = jnp.argmax(probs, axis=-1)
    g1 = jnp.take_along_axis(probs, g1_idx[:, None], axis=1)[:, 0]
    probs_wo1 = probs * (1 - jax.nn.one_hot(g1_idx, E))
    g2_idx = jnp.argmax(probs_wo1, axis=-1)
    g2 = jnp.take_along_axis(probs_wo1, g2_idx[:, None], axis=1)[:, 0]
    # load-balance aux loss (GShard eq.4): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(g1_idx, E, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * E * balance_loss_weight
    # second-expert random drop (gshard: keep with prob proportional to g2)
    if second_policy == "random":
        keep2 = u < (2.0 * g2 / jnp.maximum(g1 + g2, 1e-9))
    else:
        keep2 = jnp.ones((T,), bool)
    # positions within each expert, first-come-first-served, top1 before top2
    mask1 = jax.nn.one_hot(g1_idx, E, dtype=jnp.int32)
    pos1 = _fcfs_cumsum(mask1) * mask1 - mask1  # 0-based
    count1 = jnp.sum(mask1, axis=0)  # tokens claimed by top1 per expert
    mask2 = jax.nn.one_hot(g2_idx, E, dtype=jnp.int32) * keep2[:, None].astype(jnp.int32)
    pos2 = (_fcfs_cumsum(mask2) * mask2 - mask2) + count1[None, :]
    keep1 = jnp.sum(pos1 * mask1, axis=1) < capacity
    keep2f = (jnp.sum(pos2 * mask2, axis=1) < capacity) & (jnp.sum(mask2, 1) > 0)
    p1 = jnp.sum(pos1 * mask1, axis=1)
    p2 = jnp.sum(pos2 * mask2, axis=1)
    w1, w2 = _top2_epilogue(g1, g2, keep1, keep2f)
    return g1_idx, g2_idx, w1, w2, keep1, keep2f, p1, p2, aux


def _top2_gating(logits, capacity, *, second_policy="random", key=None,
                 balance_loss_weight=1.0):
    """GShard top-2 gating. logits: [tokens, E]. Returns (dispatch [T,E,C],
    combine [T,E,C], aux_loss)."""
    E = logits.shape[1]
    g1_idx, g2_idx, w1, w2, keep1, keep2f, p1, p2, aux = _top2_parts(
        logits, capacity, second_policy=second_policy, key=key,
        balance_loss_weight=balance_loss_weight)
    disp1 = (jax.nn.one_hot(g1_idx, E, dtype=jnp.float32)[:, :, None] *
             jax.nn.one_hot(p1, capacity, dtype=jnp.float32)[:, None, :] *
             keep1[:, None, None])
    disp2 = (jax.nn.one_hot(g2_idx, E, dtype=jnp.float32)[:, :, None] *
             jax.nn.one_hot(p2, capacity, dtype=jnp.float32)[:, None, :] *
             keep2f[:, None, None])
    dispatch = disp1 + disp2
    combine = disp1 * w1[:, None, None] + disp2 * w2[:, None, None]
    return dispatch, combine, aux


def _top1_parts(logits, capacity, *, balance_loss_weight=1.0, jitter_eps=0.0,
                key=None, training=True):
    """Switch top-1 gating core (see _top2_parts): returns
    (idx, gate, keep, p, aux)."""
    T, E = logits.shape
    if jitter_eps > 0 and training:
        k = key if key is not None else rng.next_key()
        logits = logits * jax.random.uniform(k, logits.shape, jnp.float32,
                                             1 - jitter_eps, 1 + jitter_eps)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, idx[:, None], axis=1)[:, 0]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * E * balance_loss_weight
    mask = jax.nn.one_hot(idx, E, dtype=jnp.int32)
    pos = _fcfs_cumsum(mask) * mask - mask
    p = jnp.sum(pos * mask, axis=1)
    keep = p < capacity
    return idx, gate, keep, p, aux


def _top1_gating(logits, capacity, *, balance_loss_weight=1.0, jitter_eps=0.0,
                 key=None, training=True):
    """Switch-transformer top-1 gating."""
    E = logits.shape[1]
    idx, gate, keep, p, aux = _top1_parts(
        logits, capacity, balance_loss_weight=balance_loss_weight,
        jitter_eps=jitter_eps, key=key, training=training)
    dispatch = (jax.nn.one_hot(idx, E, dtype=jnp.float32)[:, :, None] *
                jax.nn.one_hot(p, capacity, dtype=jnp.float32)[:, None, :] *
                keep[:, None, None])
    combine = dispatch * gate[:, None, None]
    return dispatch, combine, aux


class TopKGate(Layer):
    """Router: linear gate + top-k dispatch (base for GShard/Switch)."""

    def __init__(self, d_model, num_experts, top_k=2, capacity_factor=1.25,
                 eval_capacity_factor=2.0, balance_loss_weight=1.0,
                 jitter_eps=0.0, name=None):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.balance_loss_weight = balance_loss_weight
        self.jitter_eps = jitter_eps
        self.weight = Parameter(I.XavierUniform()((d_model, num_experts),
                                                  "float32"))

    def capacity(self, num_tokens):
        f = self.capacity_factor if self.training else self.eval_capacity_factor
        cap = int(f * num_tokens * self.top_k / self.num_experts)
        return max(cap, 4)

    def logits(self, x):
        """Router logits — the extension point custom gates override; every
        dispatch mode (dense forward, sorted forward_sparse, all-to-all)
        routes through it."""
        return x.astype(jnp.float32) @ self.weight

    def forward(self, x):
        return self._route(self.logits(x), self.capacity(x.shape[0]))

    def forward_sparse(self, x, impl="xla"):
        """Sparse-form routing for the sorted grouped-GEMM dispatch modes:
        (idx, w, pos, keep, aux, capacity) — same logits/capacity as
        forward. ``impl="fused"`` asks for the Pallas routing front-end
        (falls back to the XLA chain when shapes/mesh don't fit)."""
        cap = self.capacity(x.shape[0])
        return (*self._route_sparse(self.logits(x), cap, impl=impl), cap)

    def _route(self, logits, cap):
        """Post-logits routing policy — the single definition used by both
        the dense einsum path (via forward) and the all-to-all path, so the
        two dispatch modes can never diverge on gating rules."""
        if self.top_k == 1:
            return _top1_gating(logits, cap,
                                balance_loss_weight=self.balance_loss_weight,
                                jitter_eps=self.jitter_eps, training=self.training)
        return _top2_gating(logits, cap,
                            balance_loss_weight=self.balance_loss_weight,
                            second_policy="random" if self.training else "all")

    def _route_sparse(self, logits, cap, impl="xla"):
        """Same routing decisions as _route, in sparse form for the sorted
        grouped-GEMM paths: (idx, w, pos, keep, aux), each [T, k] — w is
        zero for capacity-dropped slots and pos/keep are the SAME
        position-in-expert/drop decisions the dense one-hot builder encodes
        (top-1 claims before top-2; both builders consume the same
        _top*_parts core, so the dispatch modes cannot diverge)."""
        if self.top_k == 1:
            idx, gate, keep, p, aux = _top1_parts(
                logits, cap, balance_loss_weight=self.balance_loss_weight,
                jitter_eps=self.jitter_eps, training=self.training)
            return (idx[:, None], (gate * keep)[:, None], p[:, None],
                    keep[:, None], aux)
        g1_idx, g2_idx, w1, w2, keep1, keep2f, p1, p2, aux = _top2_parts(
            logits, cap, balance_loss_weight=self.balance_loss_weight,
            second_policy="random" if self.training else "all", impl=impl)
        return (jnp.stack([g1_idx, g2_idx], axis=1),
                jnp.stack([w1, w2], axis=1),
                jnp.stack([p1, p2], axis=1),
                jnp.stack([keep1, keep2f], axis=1), aux)


class SwitchGate(TopKGate):
    def __init__(self, d_model, num_experts, capacity_factor=1.25, **kw):
        super().__init__(d_model, num_experts, top_k=1,
                         capacity_factor=capacity_factor, jitter_eps=0.01, **kw)


class GShardGate(TopKGate):
    def __init__(self, d_model, num_experts, capacity_factor=1.25, **kw):
        super().__init__(d_model, num_experts, top_k=2,
                         capacity_factor=capacity_factor, **kw)


class ExpertFFN(Layer):
    """Batched expert FFNs: weights [E, ...] sharded on the expert axis —
    one einsum = the reference's cutlass grouped GEMM."""

    def __init__(self, num_experts, d_model, d_hidden, activation=F.silu,
                 ep_axis="mp", gated=True):
        super().__init__()
        self.activation = activation
        self.gated = gated
        init = I.XavierNormal()
        self.w_in = Parameter(init((num_experts, d_model, d_hidden), self._dtype),
                              spec=(ep_axis, None, None))
        if gated:
            self.w_gate = Parameter(init((num_experts, d_model, d_hidden),
                                         self._dtype), spec=(ep_axis, None, None))
        self.w_out = Parameter(init((num_experts, d_hidden, d_model), self._dtype),
                               spec=(ep_axis, None, None))

    def forward(self, x):
        # x: [E, C, d_model]
        w_gate = self.w_gate if self.gated else None
        return self.apply(x, self.w_in, w_gate, self.w_out, self.activation)

    @staticmethod
    def apply(x, w_in, w_gate, w_out, activation):
        """Pure form of forward — used by the all-to-all dispatch path, which
        must compute with per-rank weight SLICES handed in by shard_map rather
        than the captured global parameters."""
        h = jnp.einsum("ecd,edh->ech", x, w_in)
        if w_gate is not None:
            h = activation(jnp.einsum("ecd,edh->ech", x, w_gate)) * h
        else:
            h = activation(h)
        return jnp.einsum("ech,ehd->ecd", h, w_out)


def moe_dispatch_combine(x, dispatch, combine, expert_fn):
    """Dense GShard dispatch: x [T, D], dispatch/combine [T, E, C]."""
    expert_in = jnp.einsum("td,tec->ecd", x.astype(jnp.float32), dispatch)
    expert_out = expert_fn(expert_in.astype(x.dtype))
    return jnp.einsum("ecd,tec->td", expert_out.astype(jnp.float32),
                      combine).astype(x.dtype)


def moe_ragged_compute(x, idx, w, w_in, w_gate, w_out, activation):
    """Sorted grouped-GEMM expert compute — the TPU answer to the
    reference's cutlass grouped GEMM (fusion/cutlass/moe_kernel.cu:647
    ``MoeKernel``: sort tokens by expert, run one GEMM per contiguous
    expert group, scatter back).

    x: [T, D]; idx/w: [T, k] expert assignments and combine weights
    (capacity-dropped slots carry w == 0). Token copies are sorted by
    expert id and every expert runs over its contiguous group via
    ``jax.lax.ragged_dot`` on the MXU — no [T, E, C] one-hot dispatch
    tensors (the round-3 einsum path spent as much time building them as
    computing the experts). The combine inverts the sort with a gather
    (argsort of the permutation) instead of a scatter-add.
    """
    T, D = x.shape
    K = idx.shape[1]
    E = w_in.shape[0]
    e_flat = idx.reshape(-1)                       # [T*K], slot t*K+k
    order = jnp.argsort(e_flat)                    # stable: expert-major
    tok = order // K                               # source token per slot
    xs = jnp.take(x, tok, axis=0)                  # [T*K, D] sorted inputs
    group_sizes = jnp.bincount(e_flat, length=E).astype(jnp.int32)
    h = jax.lax.ragged_dot(xs, w_in, group_sizes)
    if w_gate is not None:
        h = activation(jax.lax.ragged_dot(xs, w_gate, group_sizes)) * h
    else:
        h = activation(h)
    y = jax.lax.ragged_dot(h, w_out, group_sizes)  # [T*K, D]
    ws = w.reshape(-1)[order].astype(jnp.float32)
    y = y.astype(jnp.float32) * ws[:, None]
    # inverse of a known permutation: O(n) iota scatter, not a second sort
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    return jnp.take(y, inv, axis=0).reshape(T, K, D).sum(axis=1).astype(x.dtype)


def _float0(shape):
    return np.zeros(shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _pack_rows(x, fill_tok, occupied, slot, keep, K):
    """xe[s] = x[fill_tok[s]] for occupied slots, else 0. The backward is a
    GATHER through the inverse mapping (slot/keep), not the scatter-add XLA
    autodiff would emit for a gather — measured 1.3x end-to-end on v5e."""
    xe = jnp.take(x, fill_tok, axis=0)
    return jnp.where(occupied[:, None], xe, 0)


def _pack_rows_fwd(x, fill_tok, occupied, slot, keep, K):
    return _pack_rows(x, fill_tok, occupied, slot, keep, K), (slot, keep)


def _pack_rows_bwd(K, res, g):
    slot, keep = res
    ec = g.shape[0]
    d_copy = jnp.where(keep[:, None],
                       jnp.take(g, jnp.minimum(slot, ec - 1), axis=0), 0)
    dx = d_copy.reshape(-1, K, g.shape[-1]).sum(axis=1)
    return (dx.astype(g.dtype), _float0((ec,)), _float0((ec,)),
            _float0(slot.shape), _float0(keep.shape))


_pack_rows.defvjp(_pack_rows_fwd, _pack_rows_bwd)


@jax.custom_vjp
def _unpack_rows(ye, slot, keep, fill_copy, occupied):
    """Per-copy readback: out[i] = ye[slot[i]] for kept copies, else 0.
    Backward gathers through fill_copy/occupied (see _pack_rows)."""
    ec = ye.shape[0]
    out = jnp.take(ye, jnp.minimum(slot, ec - 1), axis=0)
    return jnp.where(keep[:, None], out, 0)


def _unpack_rows_fwd(ye, slot, keep, fill_copy, occupied):
    return _unpack_rows(ye, slot, keep, fill_copy, occupied), (fill_copy,
                                                               occupied)


def _unpack_rows_bwd(res, g):
    fill_copy, occupied = res
    tk = g.shape[0]
    d_ye = jnp.where(occupied[:, None], jnp.take(g, fill_copy, axis=0), 0)
    return (d_ye.astype(g.dtype), _float0((tk,)), _float0((tk,)),
            _float0(fill_copy.shape), _float0(occupied.shape))


_unpack_rows.defvjp(_unpack_rows_fwd, _unpack_rows_bwd)


def moe_grouped_compute(x, idx, w, pos, keep, capacity, w_in, w_gate, w_out,
                        activation):
    """Capacity-packed grouped GEMM — the fastest measured TPU form of the
    reference's cutlass grouped GEMM (fusion/cutlass/moe_kernel.cu:647):
    token copies are placed into per-expert capacity slots by GATHER (no
    [T, E, C] one-hot dispatch tensors), experts run as one dense batched
    matmul over [E, C, D] on the MXU, and the combine reads each copy's slot
    back by gather. Both pack and unpack carry custom VJPs whose backwards
    are again gathers (v5e sweep 2026-07: 1.3x over the one-hot einsum path
    end-to-end; jax.lax.ragged_dot fwd is equally fast but its dRHS
    backward loses the advantage — see moe_ragged_compute).

    Capacity semantics come from the router's pos/keep (the oracle's own
    position-in-expert assignment, top-1 before top-2): a copy lands in slot
    (e, pos) when keep, else it is dropped (zero contribution).
    """
    T, D = x.shape
    K = idx.shape[1]
    E = w_in.shape[0]
    C = int(capacity)
    slot, keep_f, fill_copy, occupied = _slot_structures(idx, pos, keep, E, C)
    xe = _pack_rows(x, fill_copy // K, occupied, slot, keep_f, K)
    ye = ExpertFFN.apply(xe.reshape(E, C, D), w_in, w_gate, w_out,
                         activation).reshape(E * C, D)
    back = _unpack_rows(ye, slot, keep_f, fill_copy, occupied)
    out = back.astype(jnp.float32) * w.reshape(-1).astype(jnp.float32)[:, None]
    return out.reshape(T, K, D).sum(axis=1).astype(x.dtype)


def _slot_structures(idx, pos, keep, E, C):
    """Capacity-packed dispatch indexing shared by the single-device
    grouped path and the all-to-all per-rank dispatch: flat copy i of
    token i//K goes to slot e*C + pos (or the dropped sentinel E*C).
    Returns (slot [T*K], keep [T*K], fill_copy [E*C], occupied [E*C])."""
    ec = E * C
    e_flat = idx.reshape(-1)
    keep_f = keep.reshape(-1)
    slot = jnp.where(keep_f, e_flat * C + pos.reshape(-1), ec)
    fill_copy = jnp.zeros((ec + 1,), jnp.int32).at[slot].set(
        jnp.arange(slot.shape[0], dtype=jnp.int32), mode="drop")
    occupied = jnp.zeros((ec + 1,), bool).at[slot].set(True, mode="drop")
    return slot, keep_f, fill_copy[:ec], occupied[:ec]


def moe_fused_compute(x, idx, w, pos, keep, capacity, w_in, w_gate, w_out,
                      activation):
    """Fused grouped-GEMM dispatch (ops/pallas/moe_grouped_gemm.py): same
    contract as ``moe_grouped_compute`` but WITHOUT the [E, capacity, D]
    packed buffer on either side of the expert FFN — the Pallas kernel's
    LHS load gathers token rows by routing index straight from x, and its
    epilogue gate-weights and scatter-adds straight into the [T, D]
    combine output (parity: the reference's fusion/cutlass/moe kernels,
    which consume dispatched tokens directly).

    Routing semantics are byte-identical to the grouped path: the SAME
    router pos/keep decide slot assignment and drops; the capacity is only
    PADDED up to the kernel's block size, which widens each expert's slot
    segment without ever admitting a dropped copy (keep was decided
    against the real capacity).

    Callers must pre-check :func:`fused_dispatch_applicable`; see
    ``MoELayer._forward_sorted`` for the fallback policy."""
    from ..ops.pallas.moe_grouped_gemm import (act_name_of, fused_grouped_moe,
                                               padded_capacity, slot_maps)
    T = x.shape[0]
    K = idx.shape[1]
    E = w_in.shape[0]
    cpad = padded_capacity(int(capacity))
    slot, keep_f, fill_copy, occupied = _slot_structures(idx, pos, keep, E,
                                                         cpad)
    row_id, gate_w = slot_maps(slot, fill_copy, occupied, w.reshape(-1),
                               T, E, cpad, K)
    return fused_grouped_moe(x, row_id, gate_w, w_in, w_gate, w_out,
                             act_name_of(activation))


def _fused_inbox_ffn(inbox, w_in, w_gate, w_out, activation):
    """Run an EP inbox [E_local, slots, d] through the fused grouped-GEMM
    kernel in identity arrangement: each slot row gathers itself (row_id =
    iota, combine weight 1), so the all-to-all's output feeds the kernel's
    gather-LHS/scatter-epilogue machinery directly with the per-expert
    grouped grid intact. The EP transport itself REQUIRES the capacity-
    packed layout on the wire (see PERF.md), so unlike the local path this
    removes no buffer — it is the same batched FFN with the kernel's
    pipelining. Falls back to the einsum FFN when shapes don't fit."""
    from ..ops.pallas.moe_grouped_gemm import (act_name_of,
                                               fused_dispatch_applicable,
                                               fused_grouped_moe,
                                               padded_capacity)
    El, S, d = inbox.shape
    if not fused_dispatch_applicable(El * S, d, w_in.shape[2], El, S,
                                     inbox.dtype, activation,
                                     w_gate is not None):
        return ExpertFFN.apply(inbox, w_in, w_gate, w_out, activation)
    T = El * S
    cpad = padded_capacity(S)
    s_ids = jnp.arange(cpad, dtype=jnp.int32)[None, :]
    e_ids = jnp.arange(El, dtype=jnp.int32)[:, None]
    row_id = jnp.where(s_ids < S, e_ids * S + s_ids, T).astype(jnp.int32)
    gate_w = jnp.broadcast_to((s_ids < S).astype(jnp.float32), (El, cpad))
    out = fused_grouped_moe(inbox.reshape(T, d), row_id, gate_w,
                            w_in, w_gate, w_out, act_name_of(activation))
    return out.reshape(El, S, d)


class MoELayer(Layer):
    """Parity: paddle.incubate.distributed.models.moe.MoELayer(:263).

    ``gate`` may be a TopKGate instance or a string ('gshard'|'switch'|'naive').
    The aux (load-balance) loss accumulates in ``self.aux_loss`` each forward;
    training code adds it to the objective (same contract as the reference).
    """

    def __init__(self, d_model, experts=None, gate="gshard", num_experts=8,
                 d_hidden=None, recompute_interval=0, ep_axis="mp",
                 dispatch="einsum", name=None):
        super().__init__()
        d_hidden = d_hidden or 4 * d_model
        if isinstance(gate, str):
            gate = {"gshard": GShardGate, "switch": SwitchGate,
                    "naive": SwitchGate}[gate](d_model, num_experts)
        self.gate = gate
        self.ep_axis = ep_axis
        if dispatch not in ("einsum", "alltoall", "ragged", "grouped",
                            "fused"):
            raise ValueError(f"dispatch must be 'einsum', 'alltoall', "
                             f"'ragged', 'grouped' or 'fused', got "
                             f"{dispatch!r}")
        self.dispatch = dispatch
        self.experts = experts if experts is not None else ExpertFFN(
            num_experts, d_model, d_hidden, ep_axis=ep_axis)
        if dispatch in ("alltoall", "ragged", "grouped", "fused") and \
                not isinstance(self.experts, ExpertFFN):
            raise ValueError(f"dispatch={dispatch!r} requires ExpertFFN experts")
        self.register_buffer("aux_loss", jnp.zeros((), jnp.float32),
                             persistable=False)

    def forward(self, x):
        shape = x.shape
        t = x.reshape(-1, shape[-1])
        if self.dispatch == "alltoall":
            out, aux = self._forward_alltoall(t)
        elif self.dispatch in ("ragged", "grouped", "fused"):
            out, aux = self._forward_sorted(t)
        else:
            dispatch, combine, aux = self.gate(t)
            out = moe_dispatch_combine(t, dispatch, combine, self.experts)
        self.aux_loss = aux
        return out.reshape(shape)

    def _forward_sorted(self, t):
        """Single-device sorted dispatch: 'grouped' = capacity-packed dense
        batched GEMM with gather-VJP pack/unpack (moe_grouped_compute);
        'fused' = the Pallas grouped-GEMM kernel that removes the packed
        buffer entirely (moe_fused_compute; falls back to 'grouped' —
        identical semantics — when shapes/dtype/activation don't fit the
        kernel); 'ragged' = jax.lax.ragged_dot over sorted token copies
        (no capacity padding in the compute, but capacity DROPS still apply
        via zeroed combine weights — identical routing semantics to the
        einsum oracle). None carries a GSPMD partitioning rule, so under a
        multi-device mesh: 'fused' with the EP axis present hands off to
        the all-to-all path (whose inbox feeds the fused kernel), and the
        rest fall back to the dense einsum path (GSPMD partitions it;
        explicit EP uses dispatch='alltoall')."""
        from ..core import mesh as mesh_lib
        mesh = mesh_lib.current_mesh()
        if mesh is not None and any(s > 1 for s in mesh.shape.values()):
            if self.dispatch == "fused" and mesh.shape.get(self.ep_axis, 1) > 1:
                return self._forward_alltoall(t)
            dispatch, combine, aux = self.gate(t)
            return moe_dispatch_combine(t, dispatch, combine, self.experts), aux
        experts = self.experts
        w_gate = experts.w_gate if experts.gated else None
        fused = False
        if self.dispatch == "fused":
            from ..ops.pallas.moe_grouped_gemm import fused_dispatch_applicable
            fused = fused_dispatch_applicable(
                t.shape[0], t.shape[1], experts.w_in.shape[2],
                self.gate.num_experts, self.gate.capacity(t.shape[0]),
                t.dtype, experts.activation, experts.gated)
        idx, w, pos, keep, aux, cap = self.gate.forward_sparse(
            t, impl="fused" if fused else "xla")
        if fused:
            out = moe_fused_compute(t, idx, w, pos, keep, cap,
                                    experts.w_in, w_gate, experts.w_out,
                                    experts.activation)
        elif self.dispatch in ("grouped", "fused"):
            out = moe_grouped_compute(t, idx, w, pos, keep, cap,
                                      experts.w_in, w_gate, experts.w_out,
                                      experts.activation)
        else:
            out = moe_ragged_compute(t, idx, w, experts.w_in, w_gate,
                                     experts.w_out, experts.activation)
        return out, aux

    def _forward_alltoall(self, t):
        """Explicit EP dispatch (parity: moe_layer.py:263 dispatch path over
        moe_utils.py:20/:153 global_scatter/global_gather).

        shard_map over the EP axis: tokens sharded across the EP group, gate
        weight replicated, expert weights sharded on the expert dim. Each rank
        routes its local tokens into capacity-padded per-expert slots, the
        all-to-all delivers every expert its inbox, local expert FFNs run on
        per-rank weight slices, and the inverse all-to-all returns outputs for
        the local combine. Partial-manual shard_map requires an enclosing jit
        (TrainStep provides one; standalone callers must wrap in jax.jit).

        Falls back to the dense einsum path when no multi-device mesh with the
        EP axis is active (single-chip) so the same model code runs anywhere.
        """
        from functools import partial

        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from ..core import mesh as mesh_lib

        mesh = mesh_lib.current_mesh()
        axis = self.ep_axis
        if mesh is None or mesh.shape.get(axis, 1) == 1:
            dispatch, combine, aux = self.gate(t)
            return moe_dispatch_combine(t, dispatch, combine, self.experts), aux

        ep = mesh.shape[axis]
        T = t.shape[0]
        E = self.gate.num_experts
        if T % ep:
            raise ValueError(f"token count {T} not divisible by ep degree {ep}")
        if E % ep:
            raise ValueError(f"num_experts {E} not divisible by ep degree {ep}")
        cap = self.gate.capacity(T // ep)
        gate_layer = self.gate
        experts = self.experts
        w_gate = experts.w_gate if experts.gated else None
        use_fused = self.dispatch == "fused"

        def fn(t_local, gw, w_in, w_out, *rest):
            w_g = rest[0] if rest else None
            logits = t_local.astype(jnp.float32) @ gw
            # per-rank capacity packing by GATHER (same machinery as the
            # single-device grouped path — no [T, E, C] one-hot dispatch
            # tensors before/after the all-to-all)
            idx, w, pos, keep, aux = gate_layer._route_sparse(
                logits, cap, impl="fused" if use_fused else "xla")
            K = idx.shape[1]
            Tl, d = t_local.shape
            slot, keep_f, fill_copy, occupied = _slot_structures(
                idx, pos, keep, E, cap)
            expert_in = _pack_rows(t_local, fill_copy // K, occupied, slot,
                                   keep_f, K).reshape(E, cap, d)
            inbox = global_scatter(expert_in, None, None, axis)
            if use_fused:
                out = _fused_inbox_ffn(inbox, w_in, w_g, w_out,
                                       experts.activation)
            else:
                out = ExpertFFN.apply(inbox, w_in, w_g, w_out,
                                      experts.activation)
            back = global_gather(out, None, None, axis)  # [E, cap, d]
            per_copy = _unpack_rows(back.reshape(E * cap, d), slot, keep_f,
                                    fill_copy, occupied)
            y = (per_copy.astype(jnp.float32)
                 * w.reshape(-1).astype(jnp.float32)[:, None]) \
                .reshape(Tl, K, d).sum(axis=1).astype(t_local.dtype)
            return y, jax.lax.pmean(aux, axis)

        args = [t, gate_layer.weight, experts.w_in, experts.w_out]
        in_specs = [P(axis), P(), P(axis), P(axis)]
        if w_gate is not None:
            args.append(w_gate)
            in_specs.append(P(axis))
        # Partial-manual over ONLY the EP axis: other mesh axes (dp/fsdp)
        # stay auto so dp-sharded activations are not gathered/replicated —
        # each dp group runs only its own tokens' MoE.
        shmap = partial(shard_map, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=(P(axis), P()), check_vma=False,
                        axis_names={axis})
        y, aux = shmap(fn)(*args)
        return y, aux


# ---------------------------------------------------------------------------
# a chip's share of a routed-expert layer (serving)
# ---------------------------------------------------------------------------

def relu2(x):
    """Squared ReLU (``mlp_hidden_act = "relu2"``)."""
    return jnp.square(jax.nn.relu(x))


def _held_assignments(idx, live, first, n_held):
    """Which of the [T, k] assignments land on a held expert of a live
    row, and that expert's local index (``n_held`` where they do not)."""
    local = idx - first
    held = (local >= 0) & (local < n_held) & live[:, None]
    return jnp.where(held, local, n_held), held


def moe_held_dense_compute(x, local, w, w_in, w_gate, w_out, activation):
    """Held experts over a FEW rows, dropless by a capacity equal to the
    row count: every held expert computes every row (one batched matmul
    that streams each expert's weights once, which is all a decode step
    can do when nearly every expert has a row), and a row's combine
    weight is zero where it was not routed.

    x [T, D]; local, w [T, k] (local index ``n_held`` = not held);
    weights [n_held, ...]. Returns float32 [T, D]."""
    n_held = w_in.shape[0]
    comb = jnp.sum(jax.nn.one_hot(local, n_held, dtype=jnp.float32)
                   * w[..., None].astype(jnp.float32), axis=1)   # [T, E]
    h = jnp.einsum("td,edh->eth", x, w_in,
                   preferred_element_type=jnp.float32)
    if w_gate is not None:
        h = activation(jnp.einsum("td,edh->eth", x, w_gate,
                                  preferred_element_type=jnp.float32)) * h
    else:
        h = activation(h)
    h = (h * comb.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("eth,ehd->td", h, w_out,
                      preferred_element_type=jnp.float32)


def moe_held_tiles_compute(x, local, w, w_in, w_gate, w_out, activation,
                           tile: int = 64):
    """Held experts over MANY rows, dropless by sorted rows
    (``moe_ragged_compute``'s way, without ``ragged_dot``, which the TPU
    compiler expands to one dense product per group): the held
    assignments are sorted by expert, each expert's run is cut into
    tiles of ``tile`` rows, and a loop over the tiles multiplies each by
    its expert's matrices and adds the weighted result to its rows.
    Every held expert has one tile, empty or not, and a second only past
    ``tile`` rows: the trip count is the number of held experts whenever
    no expert has more than a tile of rows, so a step's time does not
    follow its routing or its live rows (steps of several durations
    under one latency percentile made it jump between runs), and an
    expert's weights stream once.

    Arguments as ``moe_held_dense_compute``. Returns float32 [T, D]."""
    T, D = x.shape
    K = local.shape[1]
    n_held = w_in.shape[0]
    key = local.reshape(-1)
    order = jnp.argsort(key).astype(jnp.int32)       # stable: expert-major
    sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes               # of each expert's run
    tiles = jnp.maximum(-(-sizes // tile), 1)
    tile_ends = jnp.cumsum(tiles)
    w_flat = w.reshape(-1).astype(jnp.float32)
    lane = jnp.arange(tile, dtype=jnp.int32)

    def body(t, out):
        e = jnp.searchsorted(tile_ends, t, side="right").astype(jnp.int32)
        j = t - (tile_ends[e] - tiles[e])            # tile within the run
        left = sizes[e] - j * tile
        pos = jnp.minimum(starts[e] + j * tile + lane, T * K - 1)
        a = order[pos]                               # assignment ids
        tok = a // K
        xs = jnp.take(x, tok, axis=0)
        h = jnp.dot(xs, w_in[e], preferred_element_type=jnp.float32)
        if w_gate is not None:
            h = activation(jnp.dot(xs, w_gate[e],
                                   preferred_element_type=jnp.float32)) * h
        else:
            h = activation(h)
        y = jnp.dot(h.astype(x.dtype), w_out[e],
                    preferred_element_type=jnp.float32)
        wt = jnp.where(lane < left, w_flat[a], 0.0)  # the run's tail: 0
        return out.at[tok].add(y * wt[:, None])

    return jax.lax.fori_loop(0, tile_ends[-1], body,
                             jnp.zeros((T, D), jnp.float32))


class HeldExpertsMoE(Layer):
    """One chip's share of a routed-expert layer and a shared expert,
    for SERVING: it is told which experts it holds (``experts_held =
    (first, count)``), keeps ``w_in`` / ``w_out`` (and ``w_gate``) for
    those only, routes every row over ALL ``num_experts`` (sigmoid
    scores, with ``score_bias`` a correction bias that chooses but does
    not weigh, top-k, weights normalised over the chosen and scaled),
    and returns the held experts' part of the routed sum plus the
    shared expert. What the absent experts would add is left out:
    summed over the chips that share the layer, the parts are the whole
    routed sum. No row is ever dropped (no capacity).

    The experts work in a latent width ``d_latent`` between two
    projections (LatentMoE), or with ``d_latent=None`` on ``d_model``
    itself; ``shared_gated`` gives the shared expert a gate,
    ``act(x G) * (x U)``, as ``gated`` gives the routed ones.

    ``forward(x, live)`` -> ``(out, counts)``; ``live`` [rows] marks the
    rows that count (dead rows get the shared expert only), ``counts``
    is int32 [3]: assignments routed (live rows x top_k), assignments
    that landed on a held expert, held experts with at least one row."""

    # a row count up to which every held expert computes every row
    DENSE_ROWS = 256

    def __init__(self, d_model, d_latent, d_hidden, num_experts, top_k,
                 experts_held=None, d_shared=0, activation=relu2,
                 gated=False, norm_topk_prob=True,
                 routed_scaling_factor=1.0, tile_rows: int = 64,
                 shared_gated=False, score_bias=True):
        super().__init__()
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and count >= 1
                and first + count <= num_experts):
            raise ValueError(f"experts_held={experts_held!r} is not a "
                             f"range of the {num_experts} experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.experts_held = (int(first), int(count))
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.activation = activation
        self.tile_rows = int(tile_rows)
        self.gate = nn.Linear(d_model, num_experts, bias_attr=False)
        self.e_score_correction_bias = (Parameter(
            I.Constant(0.0)((num_experts,), jnp.float32), trainable=False)
            if score_bias else None)
        self.fc1_latent_proj = self.fc2_latent_proj = None
        if d_latent is not None:
            self.fc1_latent_proj = nn.Linear(d_model, d_latent,
                                             bias_attr=False)
            self.fc2_latent_proj = nn.Linear(d_latent, d_model,
                                             bias_attr=False)
        self.experts = ExpertFFN(count, d_latent or d_model, d_hidden,
                                 activation=activation, ep_axis=None,
                                 gated=gated)
        self.shared_up = self.shared_gate = self.shared_down = None
        if d_shared:
            self.shared_up = nn.Linear(d_model, d_shared, bias_attr=False)
            if shared_gated:
                self.shared_gate = nn.Linear(d_model, d_shared,
                                             bias_attr=False)
            self.shared_down = nn.Linear(d_shared, d_model, bias_attr=False)

    def route(self, x):
        """x [T, d_model] -> (idx [T, k] int32, weights [T, k] float32),
        in float32 whatever the model's dtype."""
        logits = jnp.dot(x.astype(jnp.float32),
                         self.gate.weight.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        chooses = (s if self.e_score_correction_bias is None else
                   s + self.e_score_correction_bias.astype(jnp.float32))
        _, idx = jax.lax.top_k(chooses, self.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if self.norm_topk_prob:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), w * self.routed_scaling_factor

    def forward(self, x, live=None):
        shape = x.shape
        t = x.reshape(-1, shape[-1])
        T = t.shape[0]
        live = (jnp.ones((T,), bool) if live is None
                else live.reshape(-1))
        first, n_held = self.experts_held
        with jax.named_scope("router"):
            idx, w = self.route(t)
            local, held = _held_assignments(idx, live, first, n_held)
        lat = t
        if self.fc1_latent_proj is not None:
            with jax.named_scope("latent"):
                lat = self.fc1_latent_proj(t)
        ex = self.experts
        with jax.named_scope("experts"):
            compute = (moe_held_dense_compute if T <= self.DENSE_ROWS
                       else functools.partial(moe_held_tiles_compute,
                                              tile=self.tile_rows))
            routed = compute(lat, local, w, ex.w_in,
                             ex.w_gate if ex.gated else None, ex.w_out,
                             self.activation)
            touched = jnp.sum(jnp.bincount(
                local.reshape(-1), length=n_held + 1)[:n_held] > 0)
        out = routed.astype(t.dtype)
        if self.fc2_latent_proj is not None:
            with jax.named_scope("latent"):
                out = self.fc2_latent_proj(out)
        if self.shared_up is not None:
            with jax.named_scope("shared"):
                h = (self.activation(self.shared_up(t))
                     if self.shared_gate is None else
                     self.activation(self.shared_gate(t))
                     * self.shared_up(t))
                out = out + self.shared_down(h)
        counts = jnp.stack([jnp.sum(live) * self.top_k, jnp.sum(held),
                            touched]).astype(jnp.int32)
        return out.reshape(shape), counts
