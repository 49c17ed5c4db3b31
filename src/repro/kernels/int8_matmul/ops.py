"""Public op: quantized matmul that dispatches Pallas-on-TPU / oracle-on-CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.int8_matmul.int8_matmul import int8_matmul
from repro.kernels.int8_matmul.ref import int8_matmul_ref


def quantized_matmul(x, w, scale_x, scale_w, *, out_dtype=jnp.bfloat16,
                     use_kernel: str = "auto", **block_kw):
    """w8a8 matmul with fused dequant.

    use_kernel: "auto" (Pallas on TPU, jnp oracle elsewhere), "pallas",
    "interpret" (Pallas interpret mode — CPU-correct, slow), or "ref".
    Block sizes default from the shared tuning table (repro.kernels.tuning).
    """
    if use_kernel == "auto":
        use_kernel = "pallas" if jax.default_backend() == "tpu" else "ref"
    if use_kernel == "ref":
        return int8_matmul_ref(x, w, scale_x, scale_w, out_dtype)
    return tuning.padded_matmul(
        int8_matmul, "int8_matmul", x, w, scale_x, scale_w, block_kw,
        out_dtype=out_dtype, interpret=(use_kernel == "interpret"))
