"""Flash attention as a Pallas TPU kernel.

Parity contract (SURVEY §B.7, reference phi/kernels/gpu/flash_attn_kernel.cu:250
wrapping Dao FA2): inputs [batch, seqlen, num_heads, head_dim]; outputs
(out, softmax_lse); backward consumes (q, k, v, out, lse, d_out). Tiled
online-softmax — no O(S^2) materialization; LSE stored for the backward.

TPU mapping:
- grid (batch*heads, q_blocks, k_blocks), k innermost: K/V blocks stream
  HBM→VMEM via BlockSpec double-buffering while accumulators (acc, m, l)
  persist in VMEM scratch across the k dimension — the Pallas version of
  FA2's warp-level pipeline.
- all matmuls hit the MXU in fp32 accumulation; inputs may be bf16.
- causal masking by global row/col iota comparison; fully-masked blocks
  skip compute via pl.when AND their k/v DMAs: the BlockSpec index maps
  clamp dead block indices to the last live block, and Mosaic elides the
  copy when the index repeats (fwd kv_index, bwd kv_index/q_index_kv).
  Dead blocks cost only a grid step (~us at 1024-wide tiles).

The backward recomputes P per block from (q, k, lse) — the standard
flash-bwd — with separate dq and dkv kernels so each accumulator has a
clean grid-persistence story.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_with_lse", "flash_attn_unpadded"]

_LANES = 128  # VPU lane count; scratch row-stat tiles use full lanes


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _drop_mask(seed_ref, bh, i, j, bq, bk, dropout_p):
    """Deterministic per-block keep mask from (seed, offset, bh, qi, kj).

    Parity: flash_attn_kernel.cu:250 fixed_seed_offset — the same five-tuple
    reseeds the per-core PRNG in the forward AND both backward kernels, so
    the mask regenerates bit-identically without storing it (the reference
    stores philox seed/offset in the softmax_return state; here the seed
    rides in SMEM). TPU-only: pltpu.prng_* has no interpret-mode lowering.
    """
    # the core PRNG accepts at most 2 seed words on this libtpu — fold the
    # five-tuple into two via odd-constant mixing (wrapping int32 mults);
    # identical folding in fwd/bwd keeps masks bit-identical
    h1 = seed_ref[0] ^ (bh * jnp.int32(-1640531527))   # 0x9E3779B9
    h2 = seed_ref[1] ^ (i * jnp.int32(-2048144777)) ^ (j * jnp.int32(-1028477379))
    pltpu.prng_seed(h1, h2)
    bits = pltpu.bitcast(pltpu.prng_random_bits((bq, bk)), jnp.uint32)
    thresh = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return bits >= thresh


def _block_sizes(seq_q, seq_k, head_dim):
    """Tuned on v5e (sweep 2026-07): bq=bk=1024 is ~9% faster end-to-end
    than the round-1 512/256 at seq 2048 (fewer grid steps, larger MXU
    tiles); 2048-row blocks exceed VMEM. Overridable via
    FLAGS_flash_block_q / FLAGS_flash_block_k for autotuning sweeps."""
    from ...core import flags

    def pow2_floor(n):
        p = 8
        while p * 2 <= n:
            p *= 2
        return p

    # flag values are rounded down to a power of two so the halving loop
    # always lands on a valid >=8 tile (768 -> 512, never 6)
    bq = pow2_floor(max(int(flags.get_flag("flash_block_q") or 1024), 8))
    while bq > 8 and seq_q % bq:
        bq //= 2
    bk = pow2_floor(max(int(flags.get_flag("flash_block_k") or 1024), 8))
    while bk > 8 and seq_k % bk:
        bk //= 2
    return min(bq, seq_q), min(bk, seq_k)


# ---------------- forward ----------------

def _fwd_kernel(*refs, scale, causal, bq, bk, nk, off, k_valid, has_seg=False,
                has_bias=False, dropout_p=0.0):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    qs_ref = ks_ref = bias_ref = seed_ref = None
    if has_seg:
        qs_ref, ks_ref = refs[:2]
        refs = refs[2:]
    if has_bias:
        bias_ref = refs[0]
        refs = refs[1:]
    if dropout_p > 0.0:
        seed_ref = refs[0]
        refs = refs[1:]
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    j = pl.program_id(2)
    i = pl.program_id(1)

    NEG = jnp.float32(-1e30)  # finite mask value: avoids inf-inf NaN paths,
    # saving three VPU where-passes per [bq,bk] tile vs a -inf formulation

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    run = True
    if causal:
        # block is live unless its first col strictly exceeds the last row
        # (bottom-right aligned: row r sees cols <= r + off, off = sk - sq)
        run = (j * bk) <= (i * bq + bq - 1 + off)

    @pl.when(run if causal else (j >= 0))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # [bq, d] (one scale pass)
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)  # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal or k_valid is not None:
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(rows + off >= cols, s, NEG)
        if k_valid is not None:  # ragged non-causal: exclude padded keys
            s = jnp.where(cols < k_valid, s, NEG)
        if has_seg:  # varlen packing: tokens attend within their sequence
            s = jnp.where(qs_ref[0, :, 0][:, None] == ks_ref[0, :, 0][None, :],
                          s, NEG)
        if has_bias:  # additive attn_mask (reference flash attn_mask attr)
            s = s + bias_ref[0, 0].astype(jnp.float32)
        m_prev = m_ref[:, 0]  # [bq]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        # clamp the subtracted max so fully-masked rows (m_cur == NEG, possible
        # with bottom-right alignment when off < 0) give p == 0, not exp(0)
        p = jnp.exp(s - jnp.maximum(m_cur, jnp.float32(-1e25))[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        # softmax normalizer uses the UNDROPPED mass (dropout applies to the
        # normalized P); PV accumulation uses the dropped, rescaled p
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        if dropout_p > 0.0:
            keep = _drop_mask(seed_ref, pl.program_id(0), i, j, bq, bk,
                              dropout_p)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_cur

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, :, 0] = m_ref[:, 0] + jnp.log(l_safe)


def _fwd(q, k, v, scale, causal, seg=None, bias=None, dropout_p=0.0,
         seed_arr=None):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qh = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kh = jnp.moveaxis(k, 2, 1).reshape(b * h, sk, d)
    vh = jnp.moveaxis(v, 2, 1).reshape(b * h, sk, d)
    bq, bk = _block_sizes(sq, sk, d)
    if bias is not None:
        # the streamed bias block shares VMEM with the s/p tiles — cap at
        # the round-1-swept 512 blocks (1024 blocks fit only without bias)
        bq, bk = min(bq, 512), min(bk, 512)
    # pad seq dims to block multiples
    pq = (-sq) % bq
    pk = (-sk) % bk
    if pq:
        qh = jnp.pad(qh, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kh = jnp.pad(kh, ((0, 0), (0, pk), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pk), (0, 0)))
    SQ, SK = sq + pq, sk + pk
    nq, nk = SQ // bq, SK // bk
    off = sk - sq  # bottom-right causal alignment (FA2 convention)
    # Padded keys would otherwise join the softmax (zero-filled keys score 0,
    # not -inf). Under the causal mask they are provably excluded when
    # off >= 0; ragged shapes get an explicit in-kernel validity mask.
    # Segment (varlen) runs mask padded keys through the mismatched pad ids.
    k_valid = sk if (pk and not causal and seg is None and bias is None) \
        else None
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk, off=off, k_valid=k_valid,
                               has_seg=seg is not None,
                               has_bias=bias is not None,
                               dropout_p=dropout_p)

    if causal:
        # Clamp dead (fully masked) k blocks to the last live block index:
        # Mosaic elides the DMA when the block index is unchanged between
        # iterations, so the upper-triangular half costs neither bandwidth
        # nor compute (compute is skipped by pl.when in the kernel).
        def kv_index(b_, i, j):
            last_live = jnp.maximum((i * bq + bq - 1 + off) // bk, 0)
            return (b_, jnp.minimum(j, last_live), 0)
    else:
        def kv_index(b_, i, j):
            return (b_, j, 0)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0)),  # q
        pl.BlockSpec((1, bk, d), kv_index),  # k
        pl.BlockSpec((1, bk, d), kv_index),  # v
    ]
    inputs = [qh, kh, vh]
    if seg is not None:
        sq_arr, sk_arr = _pad_segments(seg, b * h, sq, sk, pq, pk)
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, bk, 1), kv_index),
        ]
        inputs += [sq_arr, sk_arr]
    if bias is not None:
        biasp = _pad_bias(bias, b, h, sq, sk, pq, pk)
        in_specs.append(_bias_spec(
            biasp, h, bq, bk,
            lambda b_, i, j: (i, kv_index(b_, i, j)[1])))
        inputs.append(biasp)
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(seed_arr)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b_, i, j: (b_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, SQ, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, SQ, 1), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((bq, d)),
            _scratch((bq, _LANES)),
            _scratch((bq, _LANES)),
        ],
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="flash_attention_fwd",
    )(*inputs)
    out = out[:, :sq].reshape(b, h, sq, d)
    lse = lse[:, :sq, 0].reshape(b, h, sq)
    return jnp.moveaxis(out, 1, 2), lse


def _pad_bias(bias, b, h, sq, sk, pq, pk):
    """Normalize an additive mask to [B, H, SQ, SK] f32 with B in {1, b}
    and H in {1, h} — broadcast dims stay size 1 (the BlockSpec index map
    clamps them), so a shared [sq, sk] mask costs O(S^2), not O(b*h*S^2).
    Padded key columns get -1e30 so they never join a softmax."""
    bias = jnp.asarray(bias, jnp.float32)
    if bias.ndim == 2:          # [sq, sk]
        bias = bias[None, None]
    elif bias.ndim == 3:        # [b, sq, sk] (paddle-style)
        bias = bias[:, None]
    elif bias.ndim != 4:        # [b|1, h|1, sq, sk]
        raise ValueError(f"attn_mask rank {bias.ndim} unsupported: expected "
                         f"[sq,sk], [b,sq,sk] or [b,h|1,sq,sk]")
    B, H = bias.shape[:2]
    if B not in (1, b) or H not in (1, h) or bias.shape[2:] != (sq, sk):
        raise ValueError(f"attn_mask shape {bias.shape} does not broadcast "
                         f"to [{b}, {h}, {sq}, {sk}]")
    if pq or pk:
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pq), (0, pk)),
                       constant_values=jnp.float32(-1e30))
    return bias


def _bias_spec(bias, h, bq, bk, qj_index):
    """BlockSpec for the [B, H, SQ, SK] bias under a (b*h, x, y) grid —
    broadcast dims (B or H == 1) index 0; ``qj_index(b_, x, y) -> (qi, kj)``
    maps grid coords to (q-block, k-block) indices, letting callers reuse
    their dead-block clamping (causal DMA elision) for the bias operand."""
    B, H = bias.shape[:2]

    def im(b_, x, y):
        qi, kj = qj_index(b_, x, y)
        return ((b_ // h) if B > 1 else 0, (b_ % h) if H > 1 else 0, qi, kj)

    return pl.BlockSpec((1, 1, bq, bk), im)


def _pad_segments(seg, bh, sq, sk, pq, pk):
    """Broadcast per-token segment ids to [b*h, S, 1] with mismatching pad
    ids (-1 for q, -2 for k) so padded rows/cols never join a softmax."""
    import numpy as np
    seg_q, seg_k = seg
    sq_arr = np.full((sq + pq,), -1, np.int32)
    sq_arr[:sq] = np.asarray(seg_q, np.int32)
    sk_arr = np.full((sk + pk,), -2, np.int32)
    sk_arr[:sk] = np.asarray(seg_k, np.int32)
    sq_b = jnp.broadcast_to(jnp.asarray(sq_arr)[None, :, None],
                            (bh, sq + pq, 1))
    sk_b = jnp.broadcast_to(jnp.asarray(sk_arr)[None, :, None],
                            (bh, sk + pk, 1))
    return sq_b, sk_b


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


# ---------------- backward ----------------

def _bwd_dq_kernel(*refs, scale, causal, bq, bk, nk, off, has_seg=False,
                   has_bias=False, dropout_p=0.0):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    refs = refs[6:]
    qs_ref = ks_ref = bias_ref = seed_ref = None
    if has_seg:
        qs_ref, ks_ref = refs[:2]
        refs = refs[2:]
    if has_bias:
        bias_ref = refs[0]
        refs = refs[1:]
    if dropout_p > 0.0:
        seed_ref = refs[0]
        refs = refs[1:]
    dq_ref, dq_acc = refs
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = (j * bk) <= (i * bq + bq - 1 + off)

    @pl.when(run if causal else (j >= 0))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows + off >= cols, s, jnp.float32(-1e30))
        if has_seg:
            s = jnp.where(qs_ref[0, :, 0][:, None] == ks_ref[0, :, 0][None, :],
                          s, jnp.float32(-1e30))
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        # clamped so fully-masked rows (lse == -1e30 sentinel) give p == 0
        p = jnp.exp(s - jnp.maximum(lse, jnp.float32(-1e25))[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:  # dS = P ∘ (mask∘dP/(1-p) − D): same FA2 chain
            keep = _drop_mask(seed_ref, pl.program_id(0), i, j, bq, bk,
                              dropout_p)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _fin():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, bq, bk, nq, off, has_seg=False,
                    has_bias=False, dropout_p=0.0):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    refs = refs[6:]
    qs_ref = ks_ref = bias_ref = seed_ref = None
    if has_seg:
        qs_ref, ks_ref = refs[:2]
        refs = refs[2:]
    if has_bias:
        bias_ref = refs[0]
        refs = refs[1:]
    if dropout_p > 0.0:
        seed_ref = refs[0]
        refs = refs[1:]
    dk_ref, dv_ref, dk_acc, dv_acc = refs
    i = pl.program_id(2)  # q block (innermost)
    j = pl.program_id(1)  # k block

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (i * bq + bq - 1 + off) >= (j * bk)

    @pl.when(run if causal else (i >= 0))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows + off >= cols, s, jnp.float32(-1e30))
        if has_seg:
            s = jnp.where(qs_ref[0, :, 0][:, None] == ks_ref[0, :, 0][None, :],
                          s, jnp.float32(-1e30))
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        # clamped so fully-masked rows (lse == -1e30 sentinel) give p == 0
        p = jnp.exp(s - jnp.maximum(lse, jnp.float32(-1e25))[:, None])
        if dropout_p > 0.0:
            # dV = (mask∘P/(1-p))^T dO; dS = P ∘ (mask∘dP/(1-p) − D).
            # Seed tuple (bh, qi, kj) matches the forward bit-for-bit.
            keep = _drop_mask(seed_ref, pl.program_id(0), i, j, bq, bk,
                              dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            p_drop = jnp.where(keep, p * inv, 0.0)
        else:
            p_drop = p
        dv_acc[:] += jax.lax.dot_general(p_drop, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _fin():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _delta(do, out):
    """delta = rowsum(do * out) in [b, h, sq] — shared by every backward."""
    d = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return jnp.moveaxis(d, 2, 1)


def _bwd(scale, causal, res, g):
    q, k, v, out, lse = res
    return flash_block_grads(q, k, v, g, lse, _delta(g, out), scale=scale,
                             causal=causal)


def flash_block_grads(q, k, v, do, lse, delta, *, scale, causal, seg=None,
                      bias=None, dropout_p=0.0, seed_arr=None):
    """Gradient building block given precomputed row stats.

    Inputs: q/do [b,sq,h,d]; k/v [b,sk,h,d]; lse/delta [b,h,sq] where lse is
    the GLOBAL log-sum-exp of the full attention row and delta = rowsum(do *
    out_full). Returns (dq, dk, dv) contributions of THIS k/v block — the
    primitive ring attention's backward rotates over (SURVEY §5.7 ring plan).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qh = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kh = jnp.moveaxis(k, 2, 1).reshape(b * h, sk, d)
    vh = jnp.moveaxis(v, 2, 1).reshape(b * h, sk, d)
    doh = jnp.moveaxis(do, 2, 1).reshape(b * h, sq, d)
    lseh = lse.reshape(b * h, sq, 1)
    deltah = delta.reshape(b * h, sq, 1)
    bq, bk = _block_sizes(sq, sk, d)
    if bias is not None:
        bq, bk = min(bq, 512), min(bk, 512)  # see _fwd VMEM note
    off = sk - sq  # bottom-right causal alignment, matching the forward
    # Mirror the forward's padding to block multiples. Padded q rows carry
    # lse=+big so p == 0 there (no pollution of dk/dv); padded k rows are
    # zero so their dq contribution is exactly zero; padded dk/dv/dq rows
    # are sliced off below.
    pq_ = (-sq) % bq
    pk_ = (-sk) % bk
    if pq_:
        qh = jnp.pad(qh, ((0, 0), (0, pq_), (0, 0)))
        doh = jnp.pad(doh, ((0, 0), (0, pq_), (0, 0)))
        lseh = jnp.pad(lseh, ((0, 0), (0, pq_), (0, 0)),
                       constant_values=jnp.float32(1e30))
        deltah = jnp.pad(deltah, ((0, 0), (0, pq_), (0, 0)))
    if pk_:
        kh = jnp.pad(kh, ((0, 0), (0, pk_), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pk_), (0, 0)))
    SQ, SK = sq + pq_, sk + pk_
    nq, nk = SQ // bq, SK // bk
    common_in = [qh, kh, vh, doh, lseh, deltah]
    if seg is not None:
        sq_arr, sk_arr = _pad_segments(seg, b * h, sq, sk, pq_, pk_)
        common_in += [sq_arr, sk_arr]
    biasp = None
    if bias is not None:
        biasp = _pad_bias(bias, b, h, sq, sk, pq_, pk_)
        common_in.append(biasp)
    if dropout_p > 0.0:
        common_in.append(seed_arr)
    if causal:
        def kv_index(b_, i, j):  # dead k blocks re-use the last live index (no DMA)
            last_live = jnp.maximum((i * bq + bq - 1 + off) // bk, 0)
            return (b_, jnp.minimum(j, last_live), 0)

        def q_index_kv(b_, j, i):  # dead q blocks before the diagonal
            return (b_, jnp.maximum(i, (j * bk - off) // bq), 0)
    else:
        def kv_index(b_, i, j):
            return (b_, j, 0)

        def q_index_kv(b_, j, i):
            return (b_, i, 0)
    in_specs_q = [
        pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0)),
        pl.BlockSpec((1, bk, d), kv_index),
        pl.BlockSpec((1, bk, d), kv_index),
        pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0)),
        pl.BlockSpec((1, bq, 1), lambda b_, i, j: (b_, i, 0)),
        pl.BlockSpec((1, bq, 1), lambda b_, i, j: (b_, i, 0)),
    ]
    if seg is not None:
        in_specs_q += [
            pl.BlockSpec((1, bq, 1), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, bk, 1), kv_index),
        ]
    if bias is not None:
        in_specs_q.append(_bias_spec(
            biasp, h, bq, bk,
            lambda b_, i, j: (i, kv_index(b_, i, j)[1])))
    if dropout_p > 0.0:
        in_specs_q.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, off=off,
                          has_seg=seg is not None,
                          has_bias=bias is not None,
                          dropout_p=dropout_p),
        grid=(b * h, nq, nk),
        in_specs=in_specs_q,
        out_specs=pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, SQ, d), q.dtype),
        scratch_shapes=[_scratch((bq, d))],
        interpret=_interpret(),
        name="flash_attention_bwd_dq",
    )(*common_in)
    in_specs_kv = [
        pl.BlockSpec((1, bq, d), q_index_kv),
        pl.BlockSpec((1, bk, d), lambda b_, j, i: (b_, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b_, j, i: (b_, j, 0)),
        pl.BlockSpec((1, bq, d), q_index_kv),
        pl.BlockSpec((1, bq, 1), q_index_kv),
        pl.BlockSpec((1, bq, 1), q_index_kv),
    ]
    if seg is not None:
        in_specs_kv += [
            pl.BlockSpec((1, bq, 1), q_index_kv),
            pl.BlockSpec((1, bk, 1), lambda b_, j, i: (b_, j, 0)),
        ]
    if bias is not None:
        in_specs_kv.append(_bias_spec(
            biasp, h, bq, bk,
            lambda b_, j, i: (q_index_kv(b_, j, i)[1], j)))
    if dropout_p > 0.0:
        in_specs_kv.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, off=off,
                          has_seg=seg is not None,
                          has_bias=bias is not None,
                          dropout_p=dropout_p),
        grid=(b * h, nk, nq),
        in_specs=in_specs_kv,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b_, j, i: (b_, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b_, j, i: (b_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, SK, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, SK, d), v.dtype),
        ],
        scratch_shapes=[_scratch((bk, d)), _scratch((bk, d))],
        interpret=_interpret(),
        name="flash_attention_bwd_dkv",
    )(*common_in)
    dq = jnp.moveaxis(dq[:, :sq].reshape(b, h, sq, d), 1, 2)
    dk = jnp.moveaxis(dk[:, :sk].reshape(b, h, sk, d), 1, 2)
    dv = jnp.moveaxis(dv[:, :sk].reshape(b, h, sk, d), 1, 2)
    return dq, dk, dv


# ---------------- public API ----------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, scale, causal):
    out, _ = _fwd(q, k, v, scale, causal)
    return out


def _flash_fwd(q, k, v, scale, causal):
    out, lse = _fwd(q, k, v, scale, causal)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, res, g):
    return _bwd(scale, causal, res, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_bias(q, k, v, bias, scale, causal):
    out, _ = _fwd(q, k, v, scale, causal, bias=bias)
    return out


def _flash_bias_fwd(q, k, v, bias, scale, causal):
    out, lse = _fwd(q, k, v, scale, causal, bias=bias)
    return out, (q, k, v, bias, out, lse)


def _flash_bias_bwd(scale, causal, res, g):
    q, k, v, bias, out, lse = res
    dq, dk, dv = flash_block_grads(q, k, v, g, lse, _delta(g, out),
                                   scale=scale, causal=causal, bias=bias)
    # attn_mask is non-differentiable on the flash path, matching the
    # reference kernel (flash_attn_bwd emits no dmask); the wrapper also
    # stop_gradients the mask so this is explicit, not silent
    return dq, dk, dv, jnp.zeros_like(bias)


_flash_bias.defvjp(_flash_bias_fwd, _flash_bias_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_drop(q, k, v, seed_arr, scale, causal, dropout_p):
    out, _ = _fwd(q, k, v, scale, causal, dropout_p=dropout_p,
                  seed_arr=seed_arr)
    return out


def _flash_drop_fwd(q, k, v, seed_arr, scale, causal, dropout_p):
    out, lse = _fwd(q, k, v, scale, causal, dropout_p=dropout_p,
                    seed_arr=seed_arr)
    return out, (q, k, v, seed_arr, out, lse)


def _flash_drop_bwd(scale, causal, dropout_p, res, g):
    q, k, v, seed_arr, out, lse = res
    dq, dk, dv = flash_block_grads(q, k, v, g, lse, _delta(g, out),
                                   scale=scale, causal=causal,
                                   dropout_p=dropout_p, seed_arr=seed_arr)
    return dq, dk, dv, jnp.zeros_like(seed_arr)


_flash_drop.defvjp(_flash_drop_fwd, _flash_drop_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_bias_drop(q, k, v, bias, seed_arr, scale, causal, dropout_p):
    out, _ = _fwd(q, k, v, scale, causal, bias=bias, dropout_p=dropout_p,
                  seed_arr=seed_arr)
    return out


def _flash_bias_drop_fwd(q, k, v, bias, seed_arr, scale, causal, dropout_p):
    out, lse = _fwd(q, k, v, scale, causal, bias=bias, dropout_p=dropout_p,
                    seed_arr=seed_arr)
    return out, (q, k, v, bias, seed_arr, out, lse)


def _flash_bias_drop_bwd(scale, causal, dropout_p, res, g):
    q, k, v, bias, seed_arr, out, lse = res
    dq, dk, dv = flash_block_grads(q, k, v, g, lse, _delta(g, out),
                                   scale=scale, causal=causal, bias=bias,
                                   dropout_p=dropout_p, seed_arr=seed_arr)
    # mask non-differentiable on the flash path (see _flash_bias_bwd)
    return dq, dk, dv, jnp.zeros_like(bias), jnp.zeros_like(seed_arr)


_flash_bias_drop.defvjp(_flash_bias_drop_fwd, _flash_bias_drop_bwd)


def flash_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    attn_mask=None, dropout_p: float = 0.0,
                    fixed_seed_offset=None):
    """Differentiable flash attention; layout [batch, seq, heads, head_dim].
    ``attn_mask``: optional additive mask (bool masks converted to 0/-1e30),
    broadcastable [sq, sk], [b, sq, sk] or [b, h|1, sq, sk] — the reference
    kernel's attn_mask attr, applied INSIDE the tiled kernel. Like the
    reference kernel the mask is NON-differentiable here (stop_gradient
    applied); learned additive biases (ALiBi/T5) must use the XLA path.

    ``dropout_p`` > 0 enables IN-KERNEL seeded attention dropout (parity:
    flash_attn_kernel.cu:250 dropout + fixed_seed_offset): the mask is
    generated by the TPU core PRNG keyed on (seed, offset, head, q-block,
    k-block) and regenerated identically in the backward — nothing is
    stored. ``fixed_seed_offset``: optional (seed, offset) int pair for
    reproducible replays; defaults to a fresh seed from the framework RNG
    stream. TPU-only (pltpu PRNG has no interpret lowering); CPU callers
    must use the XLA path (nn.functional routes this automatically).
    Dropout composes with ``causal`` AND with ``attn_mask`` (both ride the
    same tiled kernel; the mask stays non-differentiable)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    m = None
    if attn_mask is not None:
        m = jnp.asarray(attn_mask)
        if m.dtype == jnp.bool_:
            m = jnp.where(m, jnp.float32(0), jnp.float32(-1e30))
        m = jax.lax.stop_gradient(m)
    if dropout_p > 0.0:
        if _interpret():
            raise NotImplementedError(
                "in-kernel flash dropout is TPU-only; use the XLA attention "
                "path (nn.functional.scaled_dot_product_attention) on CPU")
        if fixed_seed_offset is None:
            from ...core import rng as _rng
            bits = jax.random.key_data(_rng.next_key()).reshape(-1)[:2]
            seed_arr = jnp.asarray(bits, jnp.int32)
        else:
            seed_arr = jnp.asarray(fixed_seed_offset, jnp.int32).reshape(2)
        if m is not None:
            return _flash_bias_drop(q, k, v, m, seed_arr, scale, causal,
                                    float(dropout_p))
        return _flash_drop(q, k, v, seed_arr, scale, causal, float(dropout_p))
    if m is not None:
        return _flash_bias(q, k, v, m, scale, causal)
    return _flash(q, k, v, scale, causal)


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: float | None = None):
    """Forward-only variant returning (out, lse) — the reference kernel's
    full output contract (lse needed by ring attention)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    return _fwd(q, k, v, scale, causal)


def flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k,
                        causal: bool = False, scale: float | None = None):
    """Varlen flash attention over PACKED sequences (parity:
    FlashAttnUnpaddedKernel, phi/kernels/gpu/flash_attn_kernel.cu:27).

    q: [total_q, num_heads, head_dim] — b sequences packed along dim 0;
    cu_seqlens_q/k: HOST-known cumulative lengths [b+1] (list/np array; they
    define the segment structure of the kernel, so they are static — jit
    callers treat them like shapes). Tokens attend only within their own
    sequence; ``causal`` additionally applies per-sequence causal masking
    (sequences must have seqlen_q == seqlen_k when causal).

    Implementation: segment-ids threaded into the tiled flash kernel — one
    kernel launch for the whole packed batch, no per-sequence padding.
    """
    import numpy as np
    cu_q = np.asarray(cu_seqlens_q, np.int64)
    cu_k = np.asarray(cu_seqlens_k, np.int64)
    if causal and not np.array_equal(np.diff(cu_q), np.diff(cu_k)):
        raise ValueError("causal varlen requires seqlen_q == seqlen_k "
                         "per sequence")
    total_q, h, d = q.shape
    total_k = k.shape[0]
    if total_q != cu_q[-1] or total_k != cu_k[-1]:
        raise ValueError("cu_seqlens totals do not match packed lengths")
    seg_q = np.searchsorted(cu_q, np.arange(total_q), side="right") - 1
    seg_k = np.searchsorted(cu_k, np.arange(total_k), side="right") - 1
    # causal note: with equal per-sequence q/k lengths the packings align, so
    # the kernel's GLOBAL causal mask restricted to same-segment pairs is
    # exactly per-sequence causal — no per-segment offset needed.
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    seg = (seg_q, seg_k)

    @jax.custom_vjp
    def run(q, k, v):
        out, _ = _fwd(q[None], k[None], v[None], scale, causal, seg=seg)
        return out[0]

    def run_fwd(q, k, v):
        out, lse = _fwd(q[None], k[None], v[None], scale, causal, seg=seg)
        return out[0], (q, k, v, out[0], lse)

    def run_bwd(res, g):
        q, k, v, out, lse = res
        delta = jnp.moveaxis(
            jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[None], 2, 1)
        dq, dk, dv = flash_block_grads(q[None], k[None], v[None], g[None],
                                       lse, delta, scale=scale,
                                       causal=causal, seg=seg)
        return dq[0], dk[0], dv[0]

    run.defvjp(run_fwd, run_bwd)
    return run(q, k, v)
