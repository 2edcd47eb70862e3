"""The Mamba-1 selective scan over the rows a serving step brings, as a
Pallas TPU kernel (``nn.functional.ssm.selective_scan_rows`` is the
contract and the plain form).

Per slot, from a carried state ``h`` ``[d_state, d_inner]`` and for each
LIVE row ``t``: ``h = exp(dt_t * A) * h + B_t (dt_t * x_t)`` and ``y_t =
sum_j h[j] C_t[j] + D x_t``: a decay for every (state index, channel),
so there is no matrix form and the rows are walked one by one. In plain
XLA the closed form over a chunk materialises ``[slots, rows, d_inner,
d_state]`` float32 tensors (1.3 GB each at 64 x 64 x 5120 x 16); here
the state never leaves VMEM while a slot's rows are walked.

TPU mapping:
- grid (slots,): one grid step holds a slot's whole ``[rows, d_inner]``
  blocks of ``x``, ``dt`` and ``y`` and its ``[d_state, d_inner]``
  state, the channels on the lanes.
- inside, a static loop over tiles of ``_TILE`` channels: a state tile
  ``[16, 512]`` float32 is 8 vector registers and stays in them through
  a ``fori_loop`` over the slot's LIVE rows (``n_live`` by scalar
  prefetch: a dead row costs nothing).
- ``B_t`` and ``C_t`` are needed as columns ``[d_state, 1]`` that
  broadcast over the lanes; they arrive transposed ``[d_state, rows]``
  and each live row's column is picked once a slot by a masked sum over
  the lanes into VMEM scratch.
- the state operand is aliased to its result. A slot with NO live row
  names, by a second scalar-prefetch lane (``dead_slot_blocks``), the
  blocks of the live slot before it (or the first live slot): the
  pipeline sees an unchanged block index, fetches nothing and writes
  nothing back, so such a slot costs a skipped grid step and a block of
  zeros for its ``y``. Its state in HBM is never touched.

The decode program's one row a slot stays XLA's fused
``selective_scan_step``: a one-row kernel (grid over tiles of channels,
every slot's state tile walked inside) ran 0.075 ms a call inside the
program but made the decode step 2 ms SLOWER, by the copies that lay
its operands out (PERF.md, PR 37).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan_tpu", "kernel_applicable", "dead_slot_blocks",
           "KERNEL_NAME"]

_LANES = 128
# the pallas_call's name: how a compiled program's text and a profiler
# trace show this kernel (``selective_scan_roofline`` sums the device
# time of every event of this name)
KERNEL_NAME = "selective_scan_rows"
# channels of one register-resident state tile
_TILE = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tile(d: int) -> int:
    return _TILE if d % _TILE == 0 else _LANES


def kernel_applicable(x_shape, state_shape) -> bool:
    """Shape gate of the kernel route (``selective_scan_rows`` takes the
    ``lax.scan`` otherwise): more than one row a slot, rows and state
    indices that fill the sublanes, channels that fill the lanes."""
    _, k, d = x_shape
    _, n, _ = state_shape
    return k > 1 and k % 8 == 0 and n % 8 == 0 and d % _LANES == 0


def dead_slot_blocks(n_live):
    """``[slots]`` int32: the slot whose blocks each grid step names. A
    live slot names its own; a slot with no live row names the last live
    slot before it, or the first live slot if none came before, or slot
    0 if no slot is live (the kernel then copies slot 0's state
    through)."""
    idx = jnp.arange(n_live.shape[0], dtype=jnp.int32)
    live = n_live > 0
    before = jax.lax.cummax(jnp.where(live, idx, -1))
    first = jnp.argmax(live).astype(jnp.int32)      # 0 where none is live
    return jnp.where(before >= 0, before, first)


def _scan_kernel(live_ref, src_ref, x_ref, dt_ref, a_ref, bt_ref, ct_ref,
                 d_ref, h_ref, y_ref, ho_ref, bcol_ref, ccol_ref, *, tile):
    s = pl.program_id(0)
    n_live = live_ref[s]
    n, k = bt_ref.shape[1], bt_ref.shape[2]
    d = x_ref.shape[2]
    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n_live > 0)
    def _scan():
        bt, ct = bt_ref[0], ct_ref[0]                          # [n, k]
        lane = jax.lax.broadcasted_iota(jnp.int32, (n, k), 1)

        def column(t, carry):           # [n, 1]: a masked sum over lanes
            at = lane == t
            bcol_ref[t] = jnp.sum(jnp.where(at, bt, 0.0), axis=1,
                                  keepdims=True)
            ccol_ref[t] = jnp.sum(jnp.where(at, ct, 0.0), axis=1,
                                  keepdims=True)
            return carry

        jax.lax.fori_loop(0, n_live, column, 0)
        for j in range(d // tile):
            cols = slice(j * tile, (j + 1) * tile)
            a, skip = a_ref[:, cols], d_ref[:, cols]

            def row(t, h):
                x_t = x_ref[0, pl.ds(t, 1), cols]              # [1, tile]
                dt_t = dt_ref[0, pl.ds(t, 1), cols]
                h = jnp.exp(dt_t * a) * h + bcol_ref[t] * (dt_t * x_t)
                y_ref[0, pl.ds(t, 1), cols] = (
                    jnp.sum(h * ccol_ref[t], axis=0, keepdims=True)
                    + skip * x_t)
                return h

            ho_ref[0, :, cols] = jax.lax.fori_loop(
                0, n_live, row, h_ref[0, :, cols])

    # no slot is live: every step names slot 0, whose state block goes
    # back as it came
    @pl.when((n_live == 0) & (live_ref[src_ref[s]] == 0))
    def _through():
        ho_ref[...] = h_ref[...]


def selective_scan_tpu(x, dt, A, B, C, D, state, n_live):
    """x, dt [b, k, d]; A [d, n]; B, C [b, k, n]; D [d]; state [b, n, d];
    all float32; n_live [b] int32. Returns y [b, k, d] float32 (zeros on
    dead rows) and the new state, which takes the place of ``state``
    (donate it: the operand is aliased to the result)."""
    b, k, d = x.shape
    n = state.shape[1]
    tile = _tile(d)
    src = dead_slot_blocks(n_live)

    def own(s, live, src):
        return (s, 0, 0)

    def named(s, live, src):
        return (src[s], 0, 0)

    def whole(s, live, src):
        return (0, 0)

    rows = pl.BlockSpec((1, k, d), named)
    cols = pl.BlockSpec((1, n, k), named)
    held = pl.BlockSpec((1, n, d), named)
    # two buffers of each block: x, dt, y, the state in and out, A
    vmem = 2 * 4 * (3 * k * d + 3 * n * d) + (8 << 20)
    y, new = pl.pallas_call(
        functools.partial(_scan_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[rows, rows, pl.BlockSpec((n, d), whole), cols, cols,
                      pl.BlockSpec((1, d), whole), held],
            out_specs=[pl.BlockSpec((1, k, d), own), held],
            scratch_shapes=[pltpu.VMEM((k, n, 1), jnp.float32),
                            pltpu.VMEM((k, n, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, k, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the two scalar lanes) is the state
        input_output_aliases={8: 1},
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(n_live, src, x, dt, A.T, B.transpose(0, 2, 1), C.transpose(0, 2, 1),
      D[None, :], state)
    return y, new

