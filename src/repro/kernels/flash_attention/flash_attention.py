"""Pallas TPU kernel: blockwise (flash) attention.

Online-softmax attention with q/kv tiling: grid (batch, q_heads,
q_blocks, kv_blocks), f32 running max / denominator / accumulator carried
in VMEM scratch across the kv dimension (sequential innermost grid axis).

Supports:
  * causal masking with a query offset (decode: q is the suffix of a
    longer kv stream),
  * sliding-window attention (Mixtral SWA) via ``window``,
  * GQA: kv heads indexed as q_head // (Hq // Hkv) in the BlockSpec,
  * ragged serving prefill (``flash_attention_masked``): a per-sequence
    ``start`` vector rides in as a scalar-prefetch operand and masks
    left-pad kv columns out of the attention forever; fully-masked query
    rows (pad-slot queries) emit exact zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(*refs, nkv: int, block_q: int, block_kv: int, scale: float,
            causal: bool, window: int | None, q_offset: int,
            has_start: bool):
    if has_start:
        start_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        start_ref = None
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)                    # [bkv, D]
    v = v_ref[0, 0].astype(jnp.float32)                    # [bkv, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bkv]

    iq = pl.program_id(2)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_offset
    kv_pos = ikv * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= kv_pos <= q_pos
    if window is not None:
        mask &= kv_pos > q_pos - window
    if has_start:
        mask &= kv_pos >= start_ref[pl.program_id(0)]
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                                    # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)                            # fully-masked rows stay 0
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(ikv == nkv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _blocks(sq, skv, d, block_q, block_kv):
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0, (sq, skv, block_q, block_kv)
    return block_q, block_kv, sq // block_q, skv // block_kv


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jax.Array,       # [B, Hq, Sq, D]
    k: jax.Array,       # [B, Hkv, Skv, D]
    v: jax.Array,       # [B, Hkv, Skv, D]
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if scale is None:
        scale = d**-0.5
    block_q, block_kv, nq, nkv = _blocks(sq, skv, d, block_q, block_kv)
    q_offset = skv - sq  # decode: queries are the stream suffix
    grid = (b, hq, nq, nkv)
    kv_spec = pl.BlockSpec(
        (1, 1, block_kv, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0))
    return pl.pallas_call(
        functools.partial(
            _kernel, nkv=nkv, block_q=block_q, block_kv=block_kv,
            scale=scale, causal=causal, window=window, q_offset=q_offset,
            has_start=False),
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)


@functools.partial(
    jax.jit,
    static_argnames=("q_offset", "causal", "window", "scale", "block_q",
                     "block_kv", "interpret"),
)
def flash_attention_masked(
    q: jax.Array,       # [B, Hq, Sq, D]
    k: jax.Array,       # [B, Hkv, Skv, D]
    v: jax.Array,       # [B, Hkv, Skv, D]
    start: jax.Array,   # [B] int32: first attendable kv column per sequence
    *,
    q_offset: int = 0,  # q row t sits at kv position q_offset + t
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Ragged serving prefill: flash attention with per-sequence start.

    The ``start`` vector is a scalar-prefetch operand (SMEM), so the
    mask costs one compare per tile — no [B, Sq, Skv] mask tensor ever
    exists.  Left-pad query rows (q_pos < start) are fully masked and
    emit exact zeros, matching the serving oracle.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if scale is None:
        scale = d**-0.5
    block_q, block_kv, nq, nkv = _blocks(sq, skv, d, block_q, block_kv)
    grid = (b, hq, nq, nkv)
    kv_spec = pl.BlockSpec(
        (1, 1, block_kv, d), lambda bi, hi, qi, ki, s_ref: (bi, hi // group, ki, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki, s_ref: (bi, hi, qi, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki, s_ref: (bi, hi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, nkv=nkv, block_q=block_q, block_kv=block_kv,
            scale=scale, causal=causal, window=window, q_offset=q_offset,
            has_start=True),
        name="flash_attention_masked",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(start.astype(jnp.int32), q, k, v)
