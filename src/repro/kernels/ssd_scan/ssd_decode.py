"""Pallas TPU kernel: the Mamba-2 decode state update, in place.

One token per slot, per head, over the [P, N] state h (P = head dim,
N = state dim):

    h' = exp(dt * A) * h + (dt * x) outer B
    y  = h' @ C

This is the whole recurrent cost of a decode tick: every slot's fp32
state is read and written once (75.5 MB a slot for Granite-4.0-H-Micro),
against a few KB of x / B / C / dt, so the kernel is bound by HBM.  The
state arrives as the model's stack of layer groups ``[G, B, H, P, N]``
with the group to update, and is aliased to the new state
(``input_output_aliases``): group ``g``'s rows are written in place.
The compiled decode tick holds no copy, slice or write-back of the
state (``tests/test_tpu_compile.py`` checks it at Granite's widths).  Slots whose ``mask`` entry is 0
(idle, or parked mid-admission) keep their state bit for bit.

Grid (slot, head block) over state blocks ``[hb, P, N]`` viewed as
``[hb*P, N]`` rows: N lies on the lanes, so B and C are rows and the
per-row factors exp(dt*A) and dt*x are columns.  x and dt (repeated
over P) arrive as lane rows ``[1, hb*P]`` and are turned into columns
by one transpose each; y leaves by the reverse transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_BYTES = 1 << 20          # state bytes of one grid step, at most


def _column(row, n: int):
    """[1, R] lane row -> [R, n], row r holding row[r] in every lane."""
    t = jnp.broadcast_to(row, (LANES, row.shape[1])).T          # [R, 128]
    return jnp.broadcast_to(t[:, :1], (row.shape[1], n))


def _row(col):
    """[R, 1] column -> [1, R] lane row."""
    t = jnp.broadcast_to(col, (col.shape[0], LANES)).T          # [128, R]
    return t[:1]


def _kernel(m_ref, g_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, h_ref, y_ref,
            o_ref):
    del g_ref                       # read by the index maps
    hb, p, n = h_ref.shape[1:]
    r = hb * p
    h = h_ref[0].reshape(r, n)

    @pl.when(m_ref[pl.program_id(0)] != 0)
    def _update():
        dt = dt_ref[0]                                          # [1, R]
        decay = jnp.exp(dt * a_ref[...])
        u = dt * x_ref[0]
        hn = h * _column(decay, n) + _column(u, n) * b_ref[0, 0]   # [R, N]
        o_ref[0] = hn.reshape(hb, p, n)
        y_ref[0] = _row(jnp.sum(hn * c_ref[0, 0], axis=-1, keepdims=True))

    @pl.when(m_ref[pl.program_id(0)] == 0)
    def _keep():
        o_ref[0] = h.reshape(hb, p, n)
        y_ref[...] = jnp.zeros_like(y_ref)


def head_block(h: int, g: int, p: int, n: int) -> int:
    """Heads per grid step: a divisor of the heads per group (a block
    reads one group's B and C), its state within ``BLOCK_BYTES``,
    preferring a lane-aligned row count (``hb * P`` a multiple of 128)."""
    hpg = h // g
    fits = [d for d in range(hpg, 0, -1)
            if hpg % d == 0 and d * p * n * 4 <= BLOCK_BYTES] or [1]
    aligned = [d for d in fits if (d * p) % LANES == 0]
    return (aligned or fits)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_step(state, x, dt, a, b, c, mask, group, *,
                    interpret: bool = False):
    """One decode step of every slot, in layer group ``group``.

    state: [L, B, H, P, N] f32 (the stacked layer groups); group: int32
    scalar; x: [B, H, P]; dt: [B, H] (post-softplus); a: [H] (negative);
    b, c: [B, G, N]; mask: [B] bool or int (0: the slot's state is kept
    and its y is 0).  Returns (new stacked state, y [B, H, P]), both
    f32; the new state reuses ``state``'s buffer.
    """
    _, bsz, h, p, n = state.shape
    g = b.shape[1]
    hb = head_block(h, g, p, n)
    r, hpg = hb * p, h // g
    f32 = jnp.float32
    xr = x.astype(f32).reshape(bsz, 1, h * p)
    dtr = jnp.repeat(dt.astype(f32), p, axis=-1).reshape(bsz, 1, h * p)
    ar = jnp.repeat(a.astype(f32), p).reshape(1, h * p)
    br = b.astype(f32).reshape(bsz, g, 1, n)
    cr = c.astype(f32).reshape(bsz, g, 1, n)
    row = pl.BlockSpec((1, 1, r), lambda i, j, m, g: (i, 0, j))
    grp = pl.BlockSpec((1, 1, 1, n),
                       lambda i, j, m, g: (i, j * hb // hpg, 0, 0))
    st = pl.BlockSpec((None, 1, hb, p, n),
                      lambda i, j, m, g: (g[0], i, j, 0, 0))
    y, new = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bsz, h // hb),
            in_specs=[row, row,
                      pl.BlockSpec((1, r), lambda i, j, m, g: (0, j)),
                      grp, grp, st],
            out_specs=[row, st]),
        out_shape=[jax.ShapeDtypeStruct((bsz, 1, h * p), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssd_decode_step",
    )(mask.astype(jnp.int32), jnp.reshape(group, (1,)).astype(jnp.int32),
      xr, dtr, ar, br, cr, state.astype(f32))
    return new, y.reshape(bsz, h, p)
