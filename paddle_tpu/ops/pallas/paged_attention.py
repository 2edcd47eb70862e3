"""Paged-attention decode as a Pallas TPU kernel (block-table gather).

The serving engine's decode attention: one query token per slot attends a
KV cache scattered across fixed-size pages of a shared pool (vLLM /
PagedAttention, SOSP '23; parity target: the reference's incubate
block_multihead_attention decode kernel). The XLA fallback in
nn/functional/attention.py materializes the gathered cache
[b, max_pages*page_size, kvh, d] in HBM before attending; this kernel
never does — pages stream HBM→VMEM directly by block-table lookup.

TPU mapping:
- grid (slots, pages), pages innermost: the page id for step (s, j)
  comes from the scalar-prefetched block table in SMEM via the BlockSpec
  index map, so the K/V page DMA is issued ahead of compute (the Pallas
  analogue of the CUDA kernel's per-block table fetch).
- one WHOLE page per grid step, all its kv heads: the block is
  (1, page_size, kvh, d), whose last two dims are the pool array's own —
  the only block of this layout the TPU lowering accepts (a one-head
  block (1, page_size, 1, d) is refused: the last two block dims must be
  the array's or multiples of (8, 128)). The kernel walks the kv heads
  in a static loop, reading head n's [page_size, d] rows out of the page
  block.
- online softmax over pages: fp32 accumulators (acc, m, l), one row set
  per kv head, persist in VMEM scratch across the page dimension — same
  stored-stats scheme as the flash kernel.
- dead pages (j past the slot's last live page, seq_lens[s] // page_size)
  skip compute via pl.when AND their DMAs: the index map clamps dead j to
  the last live page id, and Mosaic elides the repeated copy.
- GQA: the g = h/kvh query heads of one kv head attend together as a
  [g, page_size] score tile; the cache is never head-repeated.

Masking matches the XLA path exactly: position <= seq_lens[s] keeps a
score, others take -1e30 (finite, so a fully-padded tail underflows to
exactly 0 probability in fp32).

The kernel is HEAD-LOCAL: heads never mix inside a grid step, so under
tensor parallelism
(serving/parallel.py) each shard runs this same kernel unchanged on its
``kvh/tp`` heads of the sharded pool — head counts are derived from the
array shapes, and no collective ever appears inside attention.

``paged_latent_attention_tpu`` is the same walk over a LATENT pool (one
``[num_pages, page_size, width]`` array, a row a token: multi-head
latent attention in the absorbed form, ``serving/kv_cache.py``): K is
the cached row and V its first columns, the same for every head, so all
the heads of a slot attend together as one ``[heads, keys]`` score tile
and a grid step takes ``_LATENT_PAGES`` pages at once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_tpu", "kernel_applicable", "KERNEL_NAME",
           "paged_latent_attention_tpu", "latent_kernel_applicable",
           "LATENT_KERNEL_NAME"]

_LANES = 128
# the pallas_call's name: how a compiled program's text (and a profiler
# trace) shows that this kernel, and not the XLA gather path, is in it
KERNEL_NAME = "paged_attention_decode"
# the same kernel over a latent pool (one array, one row a token, every
# query head against the same row): its own name, so that a trace tells
# the two apart
LATENT_KERNEL_NAME = "paged_latent_attention_decode"
# pages a grid step of the latent kernel: a page of 16 rows is 18 KB,
# far under what a grid step costs, so a step takes this many (the pool
# is handed to the call that many times, each with its own block-table
# index map) and its scores are one [heads, 128] tile
_LATENT_PAGES = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_applicable(q_shape, pool_shape) -> bool:
    """Shape gate for the kernel route (the caller falls back to the XLA
    gather path otherwise): head_dim must fill the lanes, the page the
    sublanes, and q heads must group evenly over the cache kv heads."""
    b, s, h, d = q_shape
    _, ps, kvh, _ = pool_shape
    return (s == 1 and d % _LANES == 0 and ps % 8 == 0
            and h % kvh == 0)


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                   page_size, n_pages, kv_heads, scale, quant):
    # quant mode rides two extra inputs (the per-row fp32 absmax scales,
    # DMA'd by the SAME block-table index map as their pages) between the
    # K/V refs and the output ref
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = lens_ref[s]
    live = seq_len // page_size  # page holding position seq_len

    @pl.when(j <= live)
    def _compute():
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        for n in range(kv_heads):
            q = q_ref[0, n].astype(jnp.float32)            # [g, d]
            k = k_ref[0, :, n, :].astype(jnp.float32)      # [page_size, d]
            v = v_ref[0, :, n, :].astype(jnp.float32)
            if quant:
                # dequantize inside the page loop: int8 codes stream from
                # HBM, the fp32 page materializes only in VMEM
                k = k * ks_ref[0, :, n:n + 1]
                v = v * vs_ref[0, :, n:n + 1]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [g, page_size]
            sc = jnp.where(pos <= seq_len, sc, jnp.float32(-1e30))
            # every computed page holds >= 1 live position (j <= live), so
            # the running max is finite and -1e30 pads underflow to exact 0
            m_prev = m_ref[n, :, 0:1]
            l_prev = l_ref[n, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)                        # [g, page_size]
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[n] = acc_ref[n] * alpha + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)  # [g, d]
            m_ref[n] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[n] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[:, :, 0:1]).astype(o_ref.dtype)


def paged_attention_tpu(q, pool_k, pool_v, block_tables, seq_lens,
                        scale: float | None = None,
                        k_scale=None, v_scale=None):
    """q: [b, 1, h, d]; pool_k/v: [num_pages, page_size, kvh, d];
    block_tables: [b, max_pages] int32; seq_lens: [b] int32 (attends
    positions <= seq_lens). Returns [b, 1, h, d].

    Int8 KV mode: pass the pools' int8 code arrays as pool_k/v plus
    their fp32 absmax scales ``k_scale``/``v_scale``
    [num_pages, page_size, kvh]; the scales ride the same block-table
    index map as their pages and the dequant (codes * scale per row)
    happens inside the page loop, in VMEM — HBM only ever streams int8
    KV bytes."""
    b, s, h, d = q.shape
    _, ps, kvh, _ = pool_k.shape
    M = block_tables.shape[1]
    g = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    quant = k_scale is not None
    q4 = q.reshape(b, kvh, g, d)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)

    def q_index(s_, j, tables_ref, lens_ref):
        return (s_, 0, 0, 0)

    def kv_index(s_, j, tables_ref, lens_ref):
        # clamp dead page steps to the last live page: the repeated block
        # index lets Mosaic elide the DMA (flash-kernel dead-block idiom)
        jj = jnp.minimum(j, lens_ref[s_] // ps)
        return (tables_ref[s_, jj], 0, 0, 0)

    def scale_index(s_, j, tables_ref, lens_ref):
        return kv_index(s_, j, tables_ref, lens_ref)[:3]

    kernel = functools.partial(_decode_kernel, page_size=ps, n_pages=M,
                               kv_heads=kvh, scale=scale, quant=quant)
    in_specs = [
        pl.BlockSpec((1, kvh, g, d), q_index),
        pl.BlockSpec((1, ps, kvh, d), kv_index),
        pl.BlockSpec((1, ps, kvh, d), kv_index),
    ]
    operands = [tables, lens, q4, pool_k, pool_v]
    if quant:
        in_specs += [pl.BlockSpec((1, ps, kvh), scale_index),
                     pl.BlockSpec((1, ps, kvh), scale_index)]
        operands += [jnp.asarray(k_scale, jnp.float32),
                     jnp.asarray(v_scale, jnp.float32)]
    scratch = [pltpu.VMEM((kvh, g, d), jnp.float32),
               pltpu.VMEM((kvh, g, _LANES), jnp.float32),
               pltpu.VMEM((kvh, g, _LANES), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, M),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kvh, g, d), q_index),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(*operands)
    return out.reshape(b, 1, h, d)


# ---------------------------------------------------------------------------
# the latent pool (multi-head latent attention, absorbed form)
# ---------------------------------------------------------------------------

def latent_kernel_applicable(q_shape, pool_shape, v_width) -> bool:
    """Shape gate of the latent kernel: one query row a slot, a page
    that fills the sublanes of either dtype, rows and value columns
    that fill the lanes (``KVCachePool`` pads a row to whole lanes)."""
    _, s, h, w = q_shape
    _, ps, pw = pool_shape
    return (s == 1 and w == pw and w % _LANES == 0 and ps % 16 == 0
            and h % 8 == 0 and 0 < v_width <= w and v_width % _LANES == 0)


def _latent_decode_kernel(tables_ref, lens_ref, q_ref, *rest, page_size,
                          n_groups, pages, v_width, scale):
    row_refs = rest[:pages]
    o_ref, acc_ref, m_ref, l_ref = rest[pages:]
    s = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = lens_ref[s]
    span = pages * page_size

    @pl.when(g * span <= seq_len)       # the group holds a live position
    def _compute():
        # the group's rows, [span, width]; a dead page of a live group
        # is the last live page again (the index map clamps) and masked
        # below by its nominal position
        rows = jnp.concatenate([r[0] for r in row_refs], axis=0)
        q = q_ref[0]                                      # [h, width]
        # operands in the pool's dtype, float32 accumulation: what the
        # XLA path does (``_latent_attend``)
        sc = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [h, span]
        pos = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        sc = jnp.where(pos <= seq_len, sc, jnp.float32(-1e30))
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # V is the row's first ``v_width`` columns, already in VMEM
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(rows.dtype), rows[:, :v_width],
            preferred_element_type=jnp.float32)            # [h, v_width]
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(g == n_groups - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)


def paged_latent_attention_tpu(q, pool, block_tables, seq_lens, v_width,
                               scale: float):
    """Decode attention against a latent pool, in the absorbed form.

    q: [b, 1, h, width], each head's query carried into the row's
    space (latent part | rotary part); pool: [num_pages, page_size,
    width], one row a token: K is the row, V its first ``v_width``
    columns, the same for every head, so the h heads of a slot attend
    together as one [h, keys] score tile. block_tables [b, max_pages],
    seq_lens [b] (attends positions <= seq_lens). Returns
    [b, 1, h, v_width] in q's dtype: the mix of latent rows, which the
    caller carries through the value up-projection."""
    b, _, h, w = q.shape
    _, ps, _ = pool.shape
    M = block_tables.shape[1]
    P = _LATENT_PAGES
    n_groups = -(-M // P)
    q3 = q.reshape(b, h, w).astype(pool.dtype)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)

    def q_index(s_, g, tables_ref, lens_ref):
        return (s_, 0, 0)

    def row_index(i):
        def index(s_, g, tables_ref, lens_ref):
            # a dead page is the last live page again: in a dead GROUP
            # every index repeats the step before and no DMA is issued
            jj = jnp.minimum(g * P + i, lens_ref[s_] // ps)
            return (tables_ref[s_, jj], 0, 0)
        return index

    kernel = functools.partial(_latent_decode_kernel, page_size=ps,
                               n_groups=n_groups, pages=P, v_width=v_width,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_groups),
            in_specs=[pl.BlockSpec((1, h, w), q_index)]
            + [pl.BlockSpec((1, ps, w), row_index(i)) for i in range(P)],
            out_specs=pl.BlockSpec((1, h, v_width), q_index),
            scratch_shapes=[pltpu.VMEM((h, v_width), jnp.float32),
                            pltpu.VMEM((h, _LANES), jnp.float32),
                            pltpu.VMEM((h, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, v_width), q.dtype),
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name=LATENT_KERNEL_NAME,
    )(tables, lens, q3, *([pool] * P))
    return out.reshape(b, 1, h, v_width)
