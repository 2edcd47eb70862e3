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
and a grid step takes ``_LATENT_PAGES`` pages at once. It takes one
query row a slot (the decode program) or many (the mixed program's
chunk and verify rows, each causal up to its own position): the rows of
a slot share the cached rows too, so a block of them is one taller
score tile, and the rows and page groups that are not live are skipped.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_tpu", "kernel_applicable", "KERNEL_NAME",
           "paged_latent_attention_tpu", "latent_kernel_applicable",
           "latent_rows_tile", "latent_last_positions", "latent_step_live",
           "latent_grid_steps",
           "LATENT_KERNEL_NAME", "LATENT_ROWS_KERNEL_NAME"]

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
# the same kernel handed more than one query row a slot (the mixed
# program's chunk and verify rows): its own name, because
# ``paged_latent_attention_decode_roofline`` divides the DECODE
# program's live keys by the device time of every event of that name
LATENT_ROWS_KERNEL_NAME = "paged_latent_attention_rows"
# query rows (slot rows x heads) of one grid step of the latent kernel,
# and of one product inside it: a tile's float32 accumulator, its query
# and its output block live in VMEM for the whole walk over the page
# groups; a product's scores are one [_LATENT_SUB_ROWS, 128] tile (16
# rows of 128 heads: a bfloat16 tile's sublanes, so that a block of a
# heads-major tile reads as one matrix)
_LATENT_TILE_ROWS = 8192
_LATENT_SUB_ROWS = 2048
# what Mosaic gives a kernel that asks for nothing
_VMEM_DEFAULT = 16 << 20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_applicable(q_shape, pool_shape) -> bool:
    """Shape gate for the kernel route (the caller falls back to the XLA
    gather path otherwise): head_dim must fill the lanes, the page the
    sublanes, and q heads must group evenly over the cache kv heads.
    Nothing is asked of the size of a query group: its ``[g, d]`` tile
    is the whole of the last two dimensions of the query block, which
    Mosaic takes at any ``g`` (20 heads on ONE kv head compile and run:
    multi-query attention; the wrapper hands that pool over as
    ``[pages, page_size, d]``, see ``paged_attention_tpu``)."""
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
            # [page_size, d]; a one-head pool comes without its head axis
            head = (0,) if len(k_ref.shape) == 3 else (0, slice(None), n)
            k = k_ref[head].astype(jnp.float32)
            v = v_ref[head].astype(jnp.float32)
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
    page = (1, ps, kvh, d)
    page_index = kv_index
    if kvh == 1 and not quant:
        # ONE kv head (multi-query attention): the compiler lays
        # ``[pages, page_size, 1, d]`` out with the size-1 axis outside
        # the tiles, a page being ``page_size x d`` in one piece, while a
        # block whose last two dimensions are (1, d) asks for rows tiled
        # alone: the operand would be a copy of the whole pool, every
        # call. Without the axis the reshape is a bitcast and the block
        # a whole page.
        pool_k, pool_v = (p.reshape(-1, ps, d) for p in (pool_k, pool_v))
        page, page_index = (1, ps, d), scale_index
    in_specs = [
        pl.BlockSpec((1, kvh, g, d), q_index),
        pl.BlockSpec(page, page_index),
        pl.BlockSpec(page, page_index),
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

def latent_rows_tile(rows: int, heads: int) -> tuple[int, int]:
    """``(tile, sub)``: the rows of a slot that one grid step of the
    latent kernel holds, and the rows of one product inside it. The
    ``rows x heads`` query rows of a slot all attend the same cached
    rows, so ``sub`` rows are ONE ``[sub * heads, width]`` product; a
    step walks its live ``sub``-blocks in a loop, so that a tile can be
    as tall as its scratch allows and the grid as short. One row a slot
    (the decode program) is one tile of one row."""
    def divisor(n, query_rows):     # the largest that fits; at least 1
        return max(d for d in range(1, n + 1)
                   if n % d == 0 and d <= max(1, query_rows // heads))

    tile = divisor(rows, _LATENT_TILE_ROWS)
    return tile, divisor(tile, _LATENT_SUB_ROWS)


def latent_kernel_applicable(q_shape, pool_shape, v_width) -> bool:
    """Shape gate of the latent kernel: a page that fills the sublanes
    of either dtype, rows and value columns that fill the lanes
    (``KVCachePool`` pads a row to whole lanes), heads that fill the
    sublanes and, with more than one row a slot, blocks of rows that
    fill them in either dtype too."""
    _, s, h, w = q_shape
    _, ps, pw = pool_shape
    return (w == pw and w % _LANES == 0 and ps % 16 == 0 and h % 8 == 0
            and (s == 1 or latent_rows_tile(s, h)[1] % 16 == 0)
            and 0 < v_width <= w and v_width % _LANES == 0)


def latent_last_positions(seq_lens, n_live, rows, tile, xp=jnp):
    """``[slots, rows // tile]``: the last position that each tile of
    the latent kernel attends, -1 for a tile with no live row. Tile
    ``i`` holds a slot's rows ``[i * tile, (i + 1) * tile)``, of which
    those ``< n_live`` are live; row ``j`` sits at position ``seq_lens +
    j`` and attends positions up to its own. ``xp``: ``jnp`` for the
    kernel's scalars, ``numpy`` for the engine's count."""
    i = xp.arange(rows // tile)[None, :]
    n = n_live[:, None]
    last = seq_lens[:, None] + xp.minimum((i + 1) * tile, n) - 1
    return xp.where(i * tile < n, last, -1)


def latent_step_live(last_pos, g, span):
    """The latent kernel's predicate: grid step ``(slot, tile, g)``
    computes when page group ``g``, which holds the positions from ``g *
    span``, has one that the tile's last live row attends
    (``latent_last_positions``). The kernel and ``latent_grid_steps``
    both ask here."""
    return g * span <= last_pos


def latent_grid_steps(seq_lens, n_live, *, rows, heads, max_pages,
                      page_size) -> tuple[int, int]:
    """``(live, dispatched)`` grid steps of ONE call of the latent
    kernel (one layer) with ``rows`` query rows a slot: every slot of
    the lanes ``seq_lens`` / ``n_live`` (an inactive slot has ``n_live``
    0) walks ``rows / tile`` tiles x ``ceil(max_pages / 8)`` page
    groups; live are the steps whose ``latent_step_live`` holds."""
    tile, _ = latent_rows_tile(rows, heads)
    last = latent_last_positions(np.asarray(seq_lens, np.int64),
                                 np.asarray(n_live, np.int64), rows, tile,
                                 np)
    groups = np.arange(-(-max_pages // _LATENT_PAGES))
    computes = latent_step_live(last[..., None], groups,
                                _LATENT_PAGES * page_size)
    return int(computes.sum()), int(computes.size)


def _latent_kernel(tables_ref, lens_ref, last_ref, q_ref, *rest, page_size,
                   n_tiles, n_groups, pages, heads, tile, sub, v_width,
                   scale):
    """One grid step ``(slot, tile, page group)``. One row a slot: q
    and the output are ``[1, heads, .]`` blocks. More:
    ``[heads, 1, tile, .]`` blocks of heads-major arrays, a block of
    ``sub`` rows is read as ``[heads * sub, .]`` (row r of it is the
    slot's row ``r % sub``), and a tile's ONE live row (a decode lane of
    the mixed program) goes through its ``heads`` query rows alone."""
    row_refs = rest[:pages]
    o_ref, acc_ref, m_ref, l_ref = rest[pages:]
    s = pl.program_id(0)
    i = pl.program_id(1)
    g = pl.program_id(2)
    span = pages * page_size
    block = sub * heads                    # query rows of one product
    blocks = tile // sub
    first = lens_ref[s] + i * tile         # position of the tile's row 0
    last_pos = last_ref[s * n_tiles + i]
    live_rows = jnp.maximum(last_pos + 1 - first, 0)
    n_sub = (live_rows + sub - 1) // sub   # blocks that hold a live row

    def part(k, unit, n):
        start = k * unit
        if not isinstance(k, int):
            start = pl.multiple_of(start, unit)
        return pl.ds(start, n)

    def state_of(k, n=block):
        """The scratch rows of block k (the one live row: the first
        ``heads`` of them)."""
        return part(k, block, n)

    def rows_of(k):
        return part(k, sub, sub)

    def clear(k, _=None):
        at = state_of(k)
        acc_ref[at, :] = jnp.zeros((block, v_width), jnp.float32)
        m_ref[at, :] = jnp.full((block, _LANES), -1e30, jnp.float32)
        l_ref[at, :] = jnp.zeros((block, _LANES), jnp.float32)

    @pl.when(g == 0)
    def _init():
        if blocks == 1:
            clear(0)
        else:
            jax.lax.fori_loop(0, n_sub, clear, None)

    @pl.when(latent_step_live(last_pos, g, span))
    def _compute():
        # the group's rows, [span, width]; a dead page of a live group
        # is the last live page again (the index map clamps) and masked
        # below by its nominal position
        rows = jnp.concatenate([r[0] for r in row_refs], axis=0)
        pos = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)

        def attend(q, at, limit):
            """Query rows q [n, width], whose state is the scratch
            rows ``at``, against the group: row r keeps positions
            ``<= limit`` ([n, 1], or a scalar)."""
            n = q.shape[0]
            # operands in the pool's dtype, float32 accumulation: what
            # the XLA path does (``_latent_attend``)
            sc = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [n, span]
            sc = jnp.where(pos <= limit, sc, jnp.float32(-1e30))
            # group 0 holds position 0, which every row attends, and
            # the groups come in order: the running maximum is finite
            # from a row's first step on, so -1e30 underflows to exact 0
            m_prev = m_ref[at, 0:1]
            l_prev = l_ref[at, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            # V is the row's first ``v_width`` columns, already in VMEM
            acc_ref[at, :] = acc_ref[at, :] * alpha + jax.lax.dot(
                p.astype(rows.dtype), rows[:, :v_width],
                preferred_element_type=jnp.float32)        # [n, v_width]
            m_ref[at, :] = jnp.broadcast_to(m_new, (n, _LANES))
            l_ref[at, :] = jnp.broadcast_to(l_new, (n, _LANES))

        if q_ref.ndim == 3:     # one row a slot: the decode program
            attend(q_ref[0], state_of(0), first)
        else:
            @pl.when(live_rows == 1)
            def _one_row():
                attend(q_ref[:, 0, 0, :], state_of(0, heads), first)

            @pl.when(live_rows > 1)
            def _blocks():
                row = jax.lax.broadcasted_iota(
                    jnp.int32, (block, 1), 0) % sub

                def body(k, _):
                    attend(q_ref[:, 0, rows_of(k), :].reshape(block, -1),
                           state_of(k), first + k * sub + row)
                # the blocks whose last row sits before the group's
                # first position have nothing to attend in it
                jax.lax.fori_loop(
                    jnp.maximum(g * span - first, 0) // sub, n_sub, body,
                    None)

    def normalised(at, live):
        """The scratch rows ``at`` over their sums; a row that is not
        ``live`` ([n, 1] or a scalar) or was never attended comes out
        as zero, not 0/0."""
        l = l_ref[at, 0:1]
        inv = jnp.where(live & (l > 0), 1.0 / jnp.where(l > 0, l, 1.0), 0.0)
        return (acc_ref[at, :] * inv).astype(o_ref.dtype)

    @pl.when(g == n_groups - 1)
    def _finish():
        if o_ref.ndim == 3:
            o_ref[0] = normalised(state_of(0), live_rows > 0)
            return

        @pl.when(live_rows == 1)
        def _one_row():
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
            o_ref[:, 0, 0, :] = normalised(state_of(0, heads), True)

        @pl.when(live_rows != 1)
        def _blocks():
            row = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0) % sub
            for k in range(blocks):
                @pl.when(k < n_sub)
                def _live():
                    o_ref[:, 0, rows_of(k), :] = normalised(
                        state_of(k), k * sub + row < live_rows
                    ).reshape(heads, sub, v_width)

                @pl.when(k >= n_sub)
                def _dead():
                    o_ref[:, 0, rows_of(k), :] = jnp.zeros(
                        (heads, sub, v_width), o_ref.dtype)


def paged_latent_attention_tpu(q, pool, block_tables, seq_lens, v_width,
                               scale: float, n_live=None):
    """Attention against a latent pool, in the absorbed form.

    q: [b, t, h, width], each head's query carried into the row's
    space (latent part | rotary part); pool: [num_pages, page_size,
    width], one row a token: K is the row, V its first ``v_width``
    columns, the same for every head, so the t x h query rows of a slot
    attend the same rows and ``sub`` of the t are one
    ``[sub * h, keys]`` score tile. block_tables [b, max_pages];
    seq_lens [b]: row j of a slot sits at ``seq_lens + j`` and attends
    positions up to its own; n_live [b]: the slot's live rows (``None``:
    all t; 0: an inactive slot). Rows ``>= n_live`` come out as zeros.
    Returns [b, t, h, v_width] in q's dtype: the mix of latent rows,
    which the caller carries through the value up-projection.

    Grid (slot, row tile, page group), the groups innermost with the
    online softmax's state in VMEM. A tile with no live row and a group
    past what the tile's last live row attends compute nothing
    (``latent_step_live``), and the dead groups, their index maps
    repeating the step before, move nothing. The kernel is named by ``t``:
    ``paged_latent_attention_decode`` for one row a slot,
    ``paged_latent_attention_rows`` for more."""
    b, t, h, w = q.shape
    _, ps, _ = pool.shape
    M = block_tables.shape[1]
    P = _LATENT_PAGES
    n_groups = -(-M // P)
    tile, sub = latent_rows_tile(t, h)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)
    n_tiles = t // tile
    last = latent_last_positions(
        lens, (jnp.full((b,), t, jnp.int32) if n_live is None
               else jnp.asarray(n_live, jnp.int32)), t, tile).reshape(-1)
    if t == 1:
        qk = q.reshape(b, h, w).astype(pool.dtype)
        q_block, o_block = (1, h, w), (1, h, v_width)
        out_shape = (b, h, v_width)

        def tile_index(s_, i, g, tables_ref, lens_ref, last_ref):
            return (s_, 0, 0)
    else:
        # heads-major: what the absorbing product before and the value
        # up-projection after (one matrix a head) hold their operands
        # in, so that neither transpose is a pass over the queries
        qk = q.transpose(2, 0, 1, 3).astype(pool.dtype)
        q_block, o_block = (h, 1, tile, w), (h, 1, tile, v_width)
        out_shape = (h, b, t, v_width)

        def tile_index(s_, i, g, tables_ref, lens_ref, last_ref):
            return (0, s_, i, 0)

    def row_index(p):
        def index(s_, i, g, tables_ref, lens_ref, last_ref):
            # a dead page is the last live page again: in a dead GROUP
            # every index repeats the step before and no DMA is issued
            # (a tile with no live row stays on the slot's first page)
            last = jnp.maximum(last_ref[s_ * n_tiles + i], 0) // ps
            return (tables_ref[s_, jnp.minimum(g * P + p, last)], 0, 0)
        return index

    kernel = functools.partial(
        _latent_kernel, page_size=ps, n_tiles=n_tiles, n_groups=n_groups,
        pages=P, heads=h, tile=tile, sub=sub, v_width=v_width, scale=scale)
    size = jnp.dtype(pool.dtype).itemsize
    # the pipeline's two buffers of a query and an output tile, the
    # scratch, and room for a block's scores and products
    vmem = (tile * h * (2 * w * size + 2 * v_width * q.dtype.itemsize
                        + 4 * (v_width + 2 * _LANES))
            + sub * h * 4 * (2 * v_width + 4 * P * ps) + (4 << 20))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_tiles, n_groups),
            in_specs=[pl.BlockSpec(q_block, tile_index)]
            + [pl.BlockSpec((1, ps, w), row_index(p)) for p in range(P)],
            out_specs=pl.BlockSpec(o_block, tile_index),
            scratch_shapes=[pltpu.VMEM((tile * h, v_width), jnp.float32),
                            pltpu.VMEM((tile * h, _LANES), jnp.float32),
                            pltpu.VMEM((tile * h, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=vmem if vmem > _VMEM_DEFAULT else None),
        interpret=_interpret(),
        name=LATENT_KERNEL_NAME if t == 1 else LATENT_ROWS_KERNEL_NAME,
    )(tables, lens, last, qk, *([pool] * P))
    if t == 1:
        return out.reshape(b, 1, h, v_width)
    return out.transpose(1, 2, 0, 3)
