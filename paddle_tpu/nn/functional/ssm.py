"""State-space primitives, in the two forms a serving engine needs: a
scan over the rows a step brings, which starts from a carried state and
returns the state after the last LIVE row, and the one-row recurrence of
a decode step. The state and every sum over it are float32.

Mamba-2 / SSD (``ssd_chunk_scan``, ``ssd_step``; plain XLA). Per head,
with ``a_t = dt_t * A`` (``A < 0``): ``S_t = exp(a_t) S_{t-1} + dt_t x_t
B_t^T`` and ``y_t = S_t C_t + D x_t``. A row with ``dt = 0`` leaves
``S`` exactly as it was (``exp(0) * S + 0``): that is how a dead row
(padding beyond a slot's ``n_live``, or every row of an inactive slot)
is kept out of the state.

Mamba-1 (``selective_scan_rows``, ``selective_scan_step``): a decay for
every channel ``c`` and state index ``j``, ``h_t[j, c] = exp(dt_t[c]
A[c, j]) h_{t-1}[j, c] + dt_t[c] x_t[c] B_t[j]`` and ``y_t[c] = sum_j
h_t[j, c] C_t[j] + D[c] x_t[c]``. It has no matrix form: the rows are
walked one by one, on the TPU by the Pallas kernel of
``ops/pallas/selective_scan.py``, elsewhere by a ``lax.scan``. The state
is kept ``[slots, d_state, d_inner]``: the channels fill the lanes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv1d_window", "fresh_slots", "selective_scan_rows",
           "selective_scan_step", "ssd_chunk_scan", "ssd_step"]

_HI = jax.lax.Precision.HIGHEST


def fresh_slots(seq_lens, active):
    """The slots whose recurrent state starts from zero in this step:
    those that start at position 0. A request is always (re)admitted at
    position 0 (the engine keeps the prefix cache off for a model with
    recurrent state), so whatever the slot's last tenant left is never
    read."""
    return active & (seq_lens == 0)


def causal_conv1d_window(x, window, weight, bias, n_live):
    """Depthwise causal convolution of the rows ``x`` [b, k, c] that
    follow ``window`` [b, w - 1, c] (the rows before them), and the
    window the NEXT call needs.

    ``weight`` is [c, w] with tap ``w - 1`` on the current row (the
    layout of a ``Conv1d(groups=c, padding=w - 1)`` cut to its causal
    part), ``bias`` [c]. Returns the convolution in float32 [b, k, c]
    and the last ``w - 1`` rows that end at each slot's last live row:
    rows ``n_live - w + 1 .. n_live - 1`` of ``x``, reaching back into
    ``window`` where ``n_live < w - 1`` (``n_live = 0`` returns
    ``window`` itself)."""
    k, w = x.shape[1], weight.shape[1]
    cat = jnp.concatenate([window.astype(x.dtype), x], axis=1)
    wf = weight.astype(jnp.float32)
    y = jnp.broadcast_to(bias.astype(jnp.float32), x.shape)
    for i in range(w):
        y = y + cat[:, i:i + k].astype(jnp.float32) * wf[:, i]
    idx = n_live[:, None] + jnp.arange(w - 1)[None, :]
    new_window = jnp.take_along_axis(cat, idx[:, :, None], axis=1)
    return y, new_window.astype(window.dtype)


def ssd_chunk_scan(x, dt, A, B, C, D, state):
    """The recurrence over ``k`` rows at once (the chunked form of
    Mamba-2's state-space duality: within the chunk a masked
    ``[k, k]`` decay matrix, across it the carried state).

    x [b, k, h, p], dt [b, k, h] (0 on dead rows, which must follow the
    live ones), A [h] (negative), B, C [b, k, g, n] (head ``i`` uses
    group ``i // (h // g)``), D [h], state [b, h, p, n]; all float32.
    Returns y [b, k, h, p] and the state after the last live row."""
    b, k, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    r = h // g
    cum = jnp.cumsum(dt * A, axis=1)                           # [b, k, h]
    seg = cum[:, :, None, :] - cum[:, None, :, :]              # t, s
    tri = jnp.tril(jnp.ones((k, k), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))             # [b, t, s, h]
    cb = jnp.einsum("btgn,bsgn->btsg", C, B, precision=_HI)
    m = (decay.reshape(b, k, k, g, r) * cb[..., None]
         * dt.reshape(b, 1, k, g, r))
    xg = x.reshape(b, k, g, r, p)
    y = jnp.einsum("btsgr,bsgrp->btgrp", m, xg, precision=_HI)
    sg = state.reshape(b, g, r, p, n)
    y = y + (jnp.einsum("btgn,bgrpn->btgrp", C, sg, precision=_HI)
             * jnp.exp(cum).reshape(b, k, g, r, 1))
    to_end = jnp.exp(cum[:, -1:] - cum)                        # [b, k, h]
    wx = xg * (dt * to_end).reshape(b, k, g, r, 1)
    new = (sg * jnp.exp(cum[:, -1]).reshape(b, g, r, 1, 1)
           + jnp.einsum("bsgrp,bsgn->bgrpn", wx, B, precision=_HI))
    y = y.reshape(b, k, h, p) + x * D[None, None, :, None]
    return y, new.reshape(b, h, p, n)


def ssd_step(x, dt, A, B, C, D, state):
    """One row of the recurrence. x [b, h, p], dt [b, h] (0 leaves the
    state as it was), B, C [b, g, n], state [b, h, p, n]; float32.
    Returns y [b, h, p] and the new state."""
    b, h, p = x.shape
    g, n = B.shape[1], B.shape[2]
    r = h // g
    sg = state.reshape(b, g, r, p, n)
    da = jnp.exp(dt * A).reshape(b, g, r, 1, 1)
    dx = (dt[..., None] * x).reshape(b, g, r, p, 1)
    new = sg * da + dx * B[:, :, None, None, :]
    y = jnp.sum(new * C[:, :, None, None, :], axis=-1)         # [b, g, r, p]
    return (y.reshape(b, h, p) + x * D[None, :, None],
            new.reshape(b, h, p, n))


def _kernel_backend_ok() -> bool:
    """The Pallas route needs a real TPU backend; apart so that a test
    can steer it (as ``attention._flash_backend_ok``)."""
    return jax.default_backend() == "tpu"


def selective_scan_step(x, dt, A, B, C, D, state):
    """One row of the Mamba-1 recurrence. x, dt [b, d] (``dt = 0``
    leaves the state as it was), A [d, n] (negative), B, C [b, n], D
    [d], state [b, n, d]; float32. Returns y [b, d] and the new state.
    One elementwise pass over a donated state, which XLA fuses with the
    sum over ``n``; a Pallas kernel of the same pass made the decode
    program slower (PERF.md, PR 37)."""
    new = (jnp.exp(dt[:, None, :] * A.T[None]) * state
           + (dt * x)[:, None, :] * B[:, :, None])
    return jnp.sum(new * C[:, :, None], axis=1) + D * x, new


def _selective_scan_rows_xla(x, dt, A, B, C, D, state, n_live):
    """``selective_scan_rows`` as a ``lax.scan`` over the rows: never
    the ``[b, k, d, n]`` tensors of the closed form."""
    k = x.shape[1]
    live = jnp.arange(k)[:, None] < n_live[None, :]             # [k, b]

    def row(h, t):
        x_t, dt_t, B_t, C_t, live_t = t
        y, new = selective_scan_step(x_t, dt_t, A, B_t, C_t, D, h)
        return (jnp.where(live_t[:, None, None], new, h),
                jnp.where(live_t[:, None], y, 0.0))

    h, y = jax.lax.scan(row, state, (
        *(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)), live))
    return jnp.moveaxis(y, 0, 1), h


def selective_scan_rows(x, dt, A, B, C, D, state, n_live):
    """The Mamba-1 recurrence over the ``k`` rows a step brings, from a
    carried state.

    x, dt [b, k, d], A [d, n] (negative), B, C [b, k, n], D [d], state
    [b, n, d] float32, n_live [b] int32: the first ``n_live`` rows of a
    slot are live. Returns y [b, k, d] float32, exact zeros on dead
    rows, and the state after each slot's last live row; a slot with no
    live row keeps its state bit for bit. On the TPU the Pallas kernel
    (``selective_scan_rows`` in a trace), elsewhere the ``lax.scan``."""
    from ...ops.pallas import selective_scan as kernel
    f32 = jnp.float32
    args = (x.astype(f32), dt.astype(f32), A.astype(f32), B.astype(f32),
            C.astype(f32), D.astype(f32), state.astype(f32),
            n_live.astype(jnp.int32))
    if _kernel_backend_ok() and kernel.kernel_applicable(x.shape,
                                                         state.shape):
        return kernel.selective_scan_tpu(*args)
    return _selective_scan_rows_xla(*args)
