"""Pallas TPU kernel: EN-T digit-plane int8 matmul (the paper's technique).

The software twin of the EN-T array: weights arrive PRE-ENCODED as four
signed digit planes p_i in {-2,...,2} with W = sum_i p_i 4^i (the hoisted
edge encoder of paper §3.1 runs once, at quantization time — see
repro.core.multiplier.ent_digit_planes).  The kernel computes

    acc = sum_i ( X @ p_i ) << 2i          (bit-exact int32)

i.e. the partial-product-plane accumulation the EN-T PEs perform, with
the 4^i combine done as shift-adds.  Per-channel dequant scales are fused
in the epilogue, making this a drop-in for the serving matmul.

Grid (m, n, k) with an int32 VMEM accumulator carried across k; the four
plane matmuls are unrolled inside the kernel so each X block is read once
from VMEM for all four planes (the in-kernel form of the paper's reuse).

PACKED VARIANTS (the serving fast path): since plane values live in
{-2,...,2}, adjacent plane pairs fuse into one int8 operand
packed_j = p_2j + 4 p_{2j+1} in [-10, 10] (repro.core.multiplier), so

    acc = (X @ packed_0) + (X @ packed_1) << 4     (bit-exact int32)

does the same matmul with HALF the MXU work and half the encoded-weight
bytes.  ``ent_matmul_packed`` consumes pre-quantized int8 activations;
``ent_matmul_packed_fused`` additionally fuses the per-row activation
quantization into the kernel prologue — the f32/bf16 X block is quantized
in VMEM against a precomputed per-row scale, so the separate
``quantize_acts`` pass (an f32 read + int8 write + int8 re-read of X
through HBM) disappears entirely.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.multiplier import NUM_PACKED_PLANES, PACKED_MAX_K

NUM_PLANES = 4  # int8 -> 4 radix-4 digit planes (carry provably dead)


def _kernel(x_ref, p_ref, sx_ref, sw_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    contrib = None
    for i in range(NUM_PLANES):  # unrolled: X stays resident in VMEM
        term = jax.lax.dot_general(
            x, p_ref[i], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        term = term << (2 * i)   # 4**i combine: pure shift-add
        contrib = term if contrib is None else contrib + term
    acc_ref[...] += contrib

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * sx_ref[...] * sw_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def ent_matmul(
    x: jax.Array,           # [M, K] int8 activations
    planes: jax.Array,      # [4, K, N] int8 EN-T digit planes of the weight
    scale_x: jax.Array,     # [M, 1] f32
    scale_w: jax.Array,     # [1, N] f32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    p, k2, n = planes.shape
    assert p == NUM_PLANES and k == k2, (x.shape, planes.shape)
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
        name="ent_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, t: (i, t)),
            pl.BlockSpec((NUM_PLANES, block_k, block_n), lambda i, j, t: (0, t, j)),
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
    )(x, planes, scale_x, scale_w)


# ----------------------------------------------------------------------------
# Packed 2-plane kernels.
# ----------------------------------------------------------------------------

def _packed_contrib(x_i32, p_ref):
    """(X @ packed_0) + (X @ packed_1) << 4 for one k-block (int32)."""
    acc = jax.lax.dot_general(
        x_i32, p_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    hi = jax.lax.dot_general(
        x_i32, p_ref[1], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc + (hi << 4)


def _packed_kernel(x_ref, p_ref, sx_ref, sw_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _packed_contrib(x_ref[...], p_ref)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * sx_ref[...] * sw_ref[...]).astype(o_ref.dtype)


def _packed_fused_kernel(x_ref, p_ref, sx_ref, inv_ref, sw_ref, o_ref,
                         acc_ref, *, nk: int):
    """Fused prologue: quantize the float X block in VMEM, then matmul.
    ``inv_ref`` holds 1/scale per row, computed outside the kernel by the
    same op the oracle uses, so the int8 X is bit-identical to it."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    xq = jnp.clip(jnp.round(x * inv_ref[...]), -127, 127).astype(jnp.int8)
    acc_ref[...] += _packed_contrib(xq, p_ref)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * sx_ref[...] * sw_ref[...]).astype(o_ref.dtype)


def _packed_call(kernel_body, x, packed, scale_x, scale_w, *, block_m,
                 block_n, block_k, out_dtype, interpret, name, inv_x=None):
    m, k = x.shape
    p, k2, n = packed.shape
    assert p == NUM_PACKED_PLANES and k == k2, (x.shape, packed.shape)
    assert k <= PACKED_MAX_K, (
        "K too large for a provably overflow-free int32 packed accumulator",
        k, PACKED_MAX_K)
    assert scale_x.shape == (m, 1) and scale_w.shape == (1, n)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        "pad operands to block multiples", (m, n, k), (block_m, block_n, block_k))
    nk = k // block_k
    grid = (m // block_m, n // block_n, nk)
    rows = [scale_x] if inv_x is None else [scale_x, inv_x]   # [M, 1] each
    row_spec = pl.BlockSpec((block_m, 1), lambda i, j, t: (i, 0))
    return pl.pallas_call(
        functools.partial(kernel_body, nk=nk),
        name=name,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, t: (i, t)),
            pl.BlockSpec((NUM_PACKED_PLANES, block_k, block_n),
                         lambda i, j, t: (0, t, j)),
            *[row_spec] * len(rows),
            pl.BlockSpec((1, block_n), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, packed, *rows, scale_w)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def ent_matmul_packed(
    x: jax.Array,           # [M, K] int8 activations
    packed: jax.Array,      # [2, K, N] int8 packed EN-T planes
    scale_x: jax.Array,     # [M, 1] f32
    scale_w: jax.Array,     # [1, N] f32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Packed 2-plane EN-T matmul: half the plane matmuls of ent_matmul."""
    return _packed_call(_packed_kernel, x, packed, scale_x, scale_w,
                        block_m=block_m, block_n=block_n, block_k=block_k,
                        out_dtype=out_dtype, interpret=interpret,
                        name="ent_matmul_packed")


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def ent_matmul_packed_fused(
    x: jax.Array,           # [M, K] f32/bf16 UNquantized activations
    packed: jax.Array,      # [2, K, N] int8 packed EN-T planes
    scale_x: jax.Array,     # [M, 1] f32 per-row quant scale (amax/127)
    scale_w: jax.Array,     # [1, N] f32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Packed matmul with the per-row activation quant fused in-kernel.

    ``scale_x`` is the per-row quantization scale (a cheap [M] amax
    reduction computed by the caller); the int8 X never touches HBM.
    """
    return _packed_call(_packed_fused_kernel, x, packed, scale_x, scale_w,
                        block_m=block_m, block_n=block_n, block_k=block_k,
                        out_dtype=out_dtype, interpret=interpret,
                        name="ent_matmul_packed_fused",
                        inv_x=1.0 / scale_x)
