"""Pure-jnp oracle for the EN-T digit-plane matmul."""

from __future__ import annotations

import jax.numpy as jnp


def ent_matmul_ref(x, planes, scale_x, scale_w, out_dtype=jnp.float32):
    """Reference: reconstruct W from planes, matmul in int32, dequant."""
    n_planes = planes.shape[0]
    weights = jnp.asarray([4**i for i in range(n_planes)], jnp.int32)
    w = jnp.sum(planes.astype(jnp.int32) * weights[:, None, None], axis=0)
    acc = jnp.matmul(x.astype(jnp.int32), w)
    return (acc.astype(jnp.float32) * scale_x * scale_w).astype(out_dtype)


def ent_matmul_int32_ref(x, planes):
    """Bit-exactness oracle (no scales): int32 accumulator."""
    n_planes = planes.shape[0]
    weights = jnp.asarray([4**i for i in range(n_planes)], jnp.int32)
    w = jnp.sum(planes.astype(jnp.int32) * weights[:, None, None], axis=0)
    return jnp.matmul(x.astype(jnp.int32), w)


def quantize_rows(x):
    """Per-row symmetric int8 activation quant: (q int8, scale f32 [.., 1]).

    Rows are scaled by the reciprocal ``1 / scale`` — the exact product
    the fused kernel's prologue forms from the same reciprocal — so the
    int8 rows agree bit for bit on every backend."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x32 * (1.0 / scale)), -127, 127).astype(jnp.int8)
    return q, scale


def ent_packed_matmul_ref(x, packed, scale_x, scale_w, out_dtype=jnp.float32):
    """Packed 2-plane oracle: 2 int8 matmuls + shift-add, fused dequant.

    This is also the CPU serving fast path — two int matmuls instead of
    the seed's four.
    """
    xi = x.astype(jnp.int32)
    acc = jnp.matmul(xi, packed[0].astype(jnp.int32))
    acc = acc + (jnp.matmul(xi, packed[1].astype(jnp.int32)) << 4)
    return (acc.astype(jnp.float32) * scale_x * scale_w).astype(out_dtype)


def ent_packed_matmul_int32_ref(x, packed):
    """Bit-exactness oracle for the packed kernel (no scales)."""
    xi = x.astype(jnp.int32)
    acc = jnp.matmul(xi, packed[0].astype(jnp.int32))
    return acc + (jnp.matmul(xi, packed[1].astype(jnp.int32)) << 4)


def ent_packed_fused_ref(x_float, packed, scale_w, out_dtype=jnp.float32):
    """Oracle of the fused-quant packed matmul: quantize rows, then packed
    matmul with fused dequant — numerically identical to the Pallas kernel
    (same round/clip, same int32 accumulation order per plane)."""
    xq, sx = quantize_rows(x_float)
    return ent_packed_matmul_ref(xq, packed, sx, scale_w, out_dtype)
