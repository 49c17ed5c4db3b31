"""Pallas TPU kernel: Mamba-2 SSD (state-space duality) chunked scan.

Per head, the SSD recurrence over a [P, N] state h (P = head dim,
N = state dim):

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * (x_t outer B_t)
    y_t = h_t @ C_t

The chunked (quadratic-within-chunk, linear-across-chunks) algorithm of
the Mamba-2 paper maps onto the MXU as three matmuls per chunk:

    intra:  y += ((C B^T) * decay * dt_j  masked-causal) @ X
    inter:  y += (C @ h_prev^T) * exp(cum)
    state:  h  = exp(cum_Q) h_prev + X^T @ (B * w_j)

Grid (batch, heads, chunks) with the [P, N] state carried in VMEM
scratch across the sequential chunk axis.  B/C are group-shared
(``ngroups`` divides heads) via the BlockSpec index map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *, nchunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)       # [Q, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)     # [Q]
    a = a_ref[0].astype(jnp.float32)             # scalar A (negative)
    bmat = b_ref[0, :, 0].astype(jnp.float32)    # [Q, N]
    cmat = c_ref[0, :, 0].astype(jnp.float32)    # [Q, N]

    da = dt * a                                  # [Q]
    cum = jnp.cumsum(da)                         # inclusive within-chunk
    q = x.shape[0]

    # intra-chunk: S_ij = (C_i . B_j) * exp(cum_i - cum_j) * dt_j,  j <= i
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())))  # [Q, Q]
    li = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # exp(cum_i - cum_j) can overflow for i<j (masked anyway): clamp first
    ldecay = jnp.where(li >= lj, cum[:, None] - cum[None, :], -jnp.inf)
    scores = scores * jnp.exp(ldecay) * dt[None, :]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())))        # [Q, P]

    # inter-chunk: contribution of the carried state
    h_prev = h_ref[...]                          # [P, N]
    y += jax.lax.dot_general(cmat, h_prev, (((1,), (1,)), ((), ()))) * jnp.exp(cum)[:, None]

    # state update for the next chunk
    wj = jnp.exp(cum[-1] - cum) * dt             # [Q]
    h_ref[...] = jnp.exp(cum[-1]) * h_prev + jax.lax.dot_general(
        x, bmat * wj[:, None], (((0,), (0,)), ((), ())))               # [P, N]

    y_ref[0, :, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,     # [B, L, H, P]
    dt: jax.Array,    # [B, L, H]  (post-softplus, > 0)
    a: jax.Array,     # [H]        (negative)
    b: jax.Array,     # [B, L, G, N]
    c: jax.Array,     # [B, L, G, N]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bsz, l, h, p = x.shape
    _, _, g, n = b.shape
    assert h % g == 0, (h, g)
    hpg = h // g
    chunk = min(chunk, l)
    assert l % chunk == 0, (l, chunk)
    nchunks = l // chunk
    grid = (bsz, h, nchunks)
    return pl.pallas_call(
        functools.partial(_kernel, nchunks=nchunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, chunk, 1, n), lambda bi, hi, ci: (bi, ci, hi // hpg, 0)),
            pl.BlockSpec((1, chunk, 1, n), lambda bi, hi, ci: (bi, ci, hi // hpg, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, a, b, c)
