"""Public op: EN-T encoded matmul with backend dispatch + weight pre-encoding.

Three entry points, slowest to fastest serving path:

* ``ent_quantized_matmul``        — seed 4-plane path (kept for parity tests)
* ``ent_quantized_matmul_packed`` — packed 2-plane path, int8 activations
* ``ent_quantized_matmul_fused``  — packed planes + in-kernel activation
  quantization from f32/bf16 X (the serving default via quant.qdense_apply)

Block sizes default from the shared shape-keyed table in
``repro.kernels.tuning``; explicit ``block_*`` kwargs always win.  Kernel
launches pad M to the 8-row sublane tile (``tuning.padded_matmul``), so
any row count compiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.multiplier import ent_digit_planes, ent_packed_planes
from repro.kernels import tuning
from repro.kernels.ent_matmul.ent_matmul import (ent_matmul, ent_matmul_packed,
                                                 ent_matmul_packed_fused)
from repro.kernels.ent_matmul.ref import (ent_matmul_ref, ent_packed_fused_ref,
                                          ent_packed_matmul_ref, quantize_rows)

__all__ = ["encode_weights", "encode_weights_packed", "ent_quantized_matmul",
           "ent_quantized_matmul_packed", "ent_quantized_matmul_fused"]


def encode_weights(w_int8: jax.Array) -> jax.Array:
    """Hoisted edge encoder: int8 weights -> [4, K, N] digit planes.

    Runs ONCE per weight (checkpoint load / quantization time); every
    subsequent matmul reuses the encoded form — the paper's computation
    reuse, amortized across the whole serving lifetime.
    """
    return ent_digit_planes(w_int8)


def encode_weights_packed(w_int8: jax.Array) -> jax.Array:
    """Edge encoder, packed form: int8 weights -> [2, K, N] packed planes.

    Same one-time cost, but every subsequent matmul needs only TWO int8
    matmuls (and the encoded weights take half the bytes of the 4-plane
    form).
    """
    return ent_packed_planes(w_int8)


def _resolve(use_kernel: str) -> str:
    if use_kernel == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return use_kernel


def ent_quantized_matmul(x, planes, scale_x, scale_w, *,
                         out_dtype=jnp.float32, use_kernel: str = "auto",
                         **block_kw):
    use_kernel = _resolve(use_kernel)
    if use_kernel == "ref":
        return ent_matmul_ref(x, planes, scale_x, scale_w, out_dtype)
    return tuning.padded_matmul(
        ent_matmul, "ent_matmul", x, planes, scale_x, scale_w, block_kw,
        out_dtype=out_dtype, interpret=(use_kernel == "interpret"))


def ent_quantized_matmul_packed(x, packed, scale_x, scale_w, *,
                                out_dtype=jnp.float32,
                                use_kernel: str = "auto", **block_kw):
    """Packed 2-plane matmul over pre-quantized int8 activations."""
    use_kernel = _resolve(use_kernel)
    if use_kernel == "ref":
        return ent_packed_matmul_ref(x, packed, scale_x, scale_w, out_dtype)
    return tuning.padded_matmul(
        ent_matmul_packed, "ent_matmul", x, packed, scale_x, scale_w,
        block_kw, out_dtype=out_dtype, interpret=(use_kernel == "interpret"))


def ent_quantized_matmul_fused(x, packed, scale_w, *, out_dtype=jnp.float32,
                               use_kernel: str = "auto", **block_kw):
    """Fused path from UNquantized f32/bf16 activations.

    ``packed`` may hold more columns than ``scale_w`` (lane-tiled planes,
    ``quant.quantize_weight``): the result has ``scale_w``'s width.
    The per-row quant scale is a cheap [M] amax reduction here; the int8
    X itself is produced inside the kernel (never written to HBM).  On
    non-TPU backends the jnp oracle fuses the same way under jit.
    """
    use_kernel = _resolve(use_kernel)
    n = scale_w.shape[-1]
    if packed.shape[-1] != n:   # lane-tiled planes: zero columns past n
        scale_w = jnp.pad(scale_w, ((0, 0), (0, packed.shape[-1] - n)),
                          constant_values=1)
    if use_kernel == "ref":
        return ent_packed_fused_ref(x, packed, scale_w, out_dtype)[:, :n]
    x32 = x.astype(jnp.float32)
    _, sx = quantize_rows(x32)   # the int8 q is unused -> DCE'd under jit
    return tuning.padded_matmul(
        ent_matmul_packed_fused, "ent_matmul", x32, packed, sx, scale_w,
        block_kw, out_dtype=out_dtype,
        interpret=(use_kernel == "interpret"))[:, :n]
