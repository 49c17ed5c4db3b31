"""EN-T w8a8 serving quantization.

``quantize_params`` walks a float param tree and replaces every matmul
kernel (minus skip patterns) with a quantized record:

    {"planes_packed": int8 [2, I, O'], "scale": f32 [1, O]}  # EN-T
    {"q": int8 [I, O], "scale": f32 [1, O]}                  # plain int8

(per-out-channel scales).  An EN-T record carries the planes only: they
decode exactly to the int8 weight (``dequantize_weight``), so a second
int8 copy would be bytes nothing reads.  Its planes are lane-tiled: a
width O past one 128-lane tile that is no multiple of it (Mamba-2's
in_proj, 8,512 columns) gets zero columns up to the next multiple O',
so every block of the kernel's column tiling is lane-aligned; the
scale keeps the true width, which the matmul's result is cut back to.

The packed planes are produced ONCE here by the hoisted edge encoder
(repro.core.multiplier.ent_packed_planes) — the paper's computation reuse
amortized over the serving lifetime, at HALF the encoded-weight bytes and
half the per-matmul MXU work of the seed 4-plane form (adjacent digit
planes fuse as packed_j = p_2j + 4 p_{2j+1}, still bit-exact).

``qdense_apply`` is the quantized counterpart of layers.dense_apply: the
float activations go straight into the FUSED packed matmul
(repro.kernels.ent_matmul.ops.ent_quantized_matmul_fused), which performs
the per-row int8 activation quantization inside the kernel — no separate
``quantize_acts`` pass, no f32->int8 HBM round trip.  ``layers.dense_apply``
dispatches here when it sees a quantized record, so the whole model zoo serves
quantized without code changes.  Legacy records carrying 4-plane
``planes`` (old checkpoints) still work via the unpacked path.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

from repro.configs.base import QuantConfig
from repro.core.multiplier import (ent_packed_planes, packed_to_weight,
                                   planes_to_weight)
from repro.kernels.ent_matmul import ops as ent_ops
from repro.kernels.ent_matmul.ref import quantize_rows
from repro.kernels.int8_matmul import ops as int8_ops

__all__ = ["quantize_weight", "quantize_params", "quantize_acts",
           "qdense_apply", "dequantize_weight"]

LANES = 128     # the TPU's lane tile: EN-T plane columns are padded to it


def quantize_weight(w, *, ent_encode: bool = True, per_channel: bool = True):
    """Symmetric int8 quantization of a [I, O] kernel: packed EN-T planes
    (``ent_encode``) or the plain int8 weight, plus per-channel scales."""
    w32 = w.astype(jnp.float32)
    if per_channel:
        amax = jnp.max(jnp.abs(w32), axis=0, keepdims=True)     # [1, O]
    else:
        amax = jnp.max(jnp.abs(w32)).reshape(1, 1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    if ent_encode:
        o = q.shape[-1]
        if o > LANES and o % LANES:
            q = jnp.pad(q, ((0, 0), (0, -o % LANES)))
        return {"planes_packed": ent_packed_planes(q),
                "scale": scale.astype(jnp.float32)}
    return {"q": q, "scale": scale.astype(jnp.float32)}


def dequantize_weight(rec):
    """Float weight of a record (stacked records keep their lead axes)."""
    if "planes_packed" in rec:
        w = packed_to_weight(jnp.moveaxis(rec["planes_packed"], -3, 0))
        w = w[..., :rec["scale"].shape[-1]]       # lane-tile padding
    elif "planes" in rec:
        w = planes_to_weight(jnp.moveaxis(rec["planes"], -3, 0))
    else:
        w = rec["q"]
    return w.astype(jnp.float32) * rec["scale"]


def quantize_acts(x):
    """Dynamic symmetric per-row int8 activation quantization.

    x: [..., K] float -> (q int8, scale f32 [..., 1]).  Kept for the plain
    int8 path and external callers — the EN-T serving path quantizes
    activations INSIDE the fused packed kernel instead, with the same
    rounding (``ent_matmul.ref.quantize_rows``)."""
    return quantize_rows(x)


def qdense_apply(rec, x, out_dtype=jnp.bfloat16, use_kernel: str = "auto"):
    """Quantized matmul: x [..., K] float x rec -> [..., O]."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    if "planes_packed" in rec:
        # fused path: per-row act-quant happens inside the packed kernel
        y = ent_ops.ent_quantized_matmul_fused(
            x2, rec["planes_packed"], rec["scale"],
            out_dtype=jnp.float32, use_kernel=use_kernel)
    elif "planes" in rec:   # legacy 4-plane records
        xq, sx = quantize_acts(x2)
        y = ent_ops.ent_quantized_matmul(
            xq, rec["planes"], sx, rec["scale"],
            out_dtype=jnp.float32, use_kernel=use_kernel)
    else:
        xq, sx = quantize_acts(x2)
        y = int8_ops.quantized_matmul(
            xq, rec["q"], sx, rec["scale"],
            out_dtype=jnp.float32, use_kernel=use_kernel)
    y = y.astype(out_dtype).reshape(*lead, -1)
    if "bias" in rec:
        y = y + rec["bias"].astype(out_dtype)
    return y


def _should_skip(path: str, qcfg: QuantConfig) -> bool:
    return any(re.search(p, path) for p in qcfg.skip_patterns)


def quantize_params(params, qcfg: QuantConfig):
    """Quantize every 2D kernel leaf-dict not matching skip patterns.

    Returns a new tree where {"kernel": w[, "bias": b]} records become
    quantized records; everything else passes through unchanged.
    MoE expert stacks ([..., E, I, O]) and scanned stacks ([G, I, O]) are
    quantized along their trailing [I, O], ONE slice at a time
    (``lax.map`` under ``jit``): the encoder's int32 digit temporaries
    exist for one [I, O] slice, never for a whole stack — a full-width
    [36, 2048, 11008] MLP stack would otherwise need ~13 GB of them.
    """
    encode = functools.partial(quantize_weight, ent_encode=qcfg.ent_encode,
                               per_channel=qcfg.per_channel)

    @jax.jit
    def encode_stack(kern):
        lead = kern.shape[:-2]
        flat = kern.reshape((-1,) + kern.shape[-2:])
        rec = jax.lax.map(encode, flat)
        return jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), rec)

    def walk(node, path):
        if isinstance(node, dict):
            if "kernel" in node and not _should_skip(path, qcfg):
                kern = node["kernel"]
                if kern.ndim >= 2:
                    rec = (jax.jit(encode)(kern) if kern.ndim == 2
                           else encode_stack(kern))
                    if "bias" in node:
                        rec["bias"] = node["bias"]
                    return rec
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, f"{path}/{i}") for i, v in enumerate(node))
        return node

    return walk(params, "")
