"""Public op: attention with backend dispatch (Pallas on TPU, oracle on CPU)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.flash_attention.flash_attention import (
    flash_attention, flash_attention_masked)
from repro.kernels.flash_attention.ref import (
    attention_blockwise, attention_ref, masked_attention_ref)

# sequences at or above this use the O(chunk)-memory blockwise path when
# the Pallas kernel is unavailable (CPU dry-run / tests)
BLOCKWISE_THRESHOLD = 2048


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, scale, interpret, block_q, block_kv):
    return flash_attention(q, k, v, causal=causal, window=window,
                           scale=scale, interpret=interpret,
                           block_q=block_q, block_kv=block_kv)


def _flash_fwd(q, k, v, causal, window, scale, interpret, block_q,
               block_kv):
    return (_flash(q, k, v, causal, window, scale, interpret, block_q,
                   block_kv), (q, k, v))


def _flash_bwd(causal, window, scale, interpret, block_q, block_kv, res, g):
    """The Pallas forward has no autodiff rule: differentiate the jnp
    oracle (same math) at the same inputs instead."""
    del interpret, block_q, block_kv
    q, k, v = res
    ref = (attention_blockwise if k.shape[2] >= BLOCKWISE_THRESHOLD
           else attention_ref)
    _, vjp = jax.vjp(functools.partial(ref, causal=causal, window=window,
                                       scale=scale), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention(q, k, v, *, causal=True, window=None, scale=None,
              use_kernel: str = "auto", **block_kw):
    """Full-sequence (train / prefill) attention.  The kernel path is
    differentiable: its backward pass is the oracle's."""
    if use_kernel == "auto":
        if jax.default_backend() == "tpu":
            use_kernel = "pallas"
        elif k.shape[2] >= BLOCKWISE_THRESHOLD:
            use_kernel = "blockwise"
        else:
            use_kernel = "ref"
    if use_kernel == "ref":
        return attention_ref(q, k, v, causal=causal, window=window, scale=scale)
    if use_kernel == "blockwise":
        return attention_blockwise(q, k, v, causal=causal, window=window,
                                   scale=scale)
    bk = tuning.get_block_config(
        "flash_attention", (q.shape[2], k.shape[2], q.shape[3]), block_kw)
    return _flash(q, k, v, causal, window, scale, use_kernel == "interpret",
                  int(bk["block_q"]), int(bk["block_kv"]))


# tuning._valid implements the min-clamp divisibility vetting shared by
# every kernel family; reuse it rather than growing a second copy
_tiles_divide = tuning._valid


def masked_attention(q, k, v, *, start=None, q_offset=0, causal=True,
                     window=None, scale=None, k_scale=None, v_scale=None,
                     valid=None, use_kernel: str = "auto", chunk=None,
                     **block_kw):
    """Serving attention: ragged/masked flash with backend dispatch.

    The one entry point behind ``attention.prefill_step`` and
    ``attention.decode_step`` (the deleted dense-einsum paths).  Shapes
    follow :func:`attention`: q [B, Hq, Sq, D], k/v [B, Hkv, Skv, D].

    * ``start`` ([B] int32): first attendable kv column per sequence
      (left padding); ``q_offset``: q row t sits at kv position
      ``q_offset + t`` (chunked prefill: queries are the stream suffix).
    * ``valid`` ([B, Sq, Skv] bool): explicit mask override for the
      ring-buffer decode and the speculative-verify burst, whose masks
      are position-scattered.  Runs the jnp core (a [B, Sq, W] mask is
      decode-sized, not O(S^2)).
    * ``k_scale``/``v_scale`` ([B, Hkv, Skv] f32): int8-KV dequant
      scales, folded exactly (K after the dot, V into the
      probabilities) on the jnp core.  The Pallas kernel consumes
      pre-dequantized operands instead (atol-level difference, CPU
      serving parity is what the tests pin).

    On the kernel path a shape with no legal tiling is an error naming
    the shape, never a silent fall back to the jnp core.

    Returns [B, Hq, Sq, D] float32.
    """
    sq, skv = q.shape[2], k.shape[2]
    if use_kernel == "auto":
        use_kernel = "pallas" if jax.default_backend() == "tpu" else "ref"
    if use_kernel in ("pallas", "interpret") and valid is None:
        bk = tuning.get_block_config(
            "flash_attention", (sq, skv, q.shape[3]), block_kw)
        if not _tiles_divide("flash_attention", (sq, skv), bk):
            raise ValueError(
                f"masked_attention: no legal flash tiling for q {q.shape} "
                f"x kv {k.shape} (blocks {bk}); Sq and Skv must be "
                "multiples of 8 or fit one block")
        if k_scale is not None:   # kernel takes dequantized operands
            k = k.astype(q.dtype) * k_scale[..., None].astype(q.dtype)
        if v_scale is not None:
            v = v.astype(q.dtype) * v_scale[..., None].astype(q.dtype)
        if start is None:
            start = jnp.zeros((q.shape[0],), jnp.int32)
        out = flash_attention_masked(
            q, k, v, start, q_offset=q_offset, causal=causal,
            window=window, scale=scale,
            interpret=(use_kernel == "interpret"),
            **{kk: min(int(vv), (sq if kk == "block_q" else skv))
               for kk, vv in bk.items()})
        return out.astype(jnp.float32)
    # chunk the kv axis only when the score tile is actually large:
    # decode (Sq=1) scores are [B, H, 1, W] — chunking there saves no
    # memory and would unroll W/chunk blocks into the jitted decode step
    if chunk is None and skv >= BLOCKWISE_THRESHOLD and sq > 1:
        chunk = BLOCKWISE_THRESHOLD // 2
    if chunk is not None and skv % chunk:
        chunk = None   # ragged tail: one block (serving shapes are small)
    return masked_attention_ref(
        q, k, v, start=start, q_offset=q_offset, causal=causal, window=window,
        scale=scale, k_scale=k_scale, v_scale=v_scale, valid=valid,
        chunk=chunk)
