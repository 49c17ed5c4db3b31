"""Pallas TPU kernel: w8a8 quantized matmul with fused dequant scales.

This is the production serving matmul of the framework — the op whose
silicon the EN-T architecture shrinks.  int8 x int8 -> int32 on the MXU,
with per-row activation scales and per-channel weight scales fused into
the epilogue (one VMEM round trip instead of three).

Grid (m, n, k) with a VMEM int32 accumulator carried across the k steps;
blocks are MXU-aligned (multiples of 128 on the minor dims).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 runs natively on the MXU
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * sx_ref[...] * sw_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def int8_matmul(
    x: jax.Array,           # [M, K] int8 activations
    w: jax.Array,           # [K, N] int8 weights
    scale_x: jax.Array,     # [M, 1] f32 per-row activation scale
    scale_w: jax.Array,     # [1, N] f32 per-channel weight scale
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert scale_x.shape == (m, 1) and scale_w.shape == (1, n)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        "pad operands to block multiples", (m, n, k), (block_m, block_n, block_k))
    nk = k // block_k
    grid = (m // block_m, n // block_n, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        name="int8_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, t: (i, t)),
            pl.BlockSpec((block_k, block_n), lambda i, j, t: (t, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, scale_x, scale_w)
