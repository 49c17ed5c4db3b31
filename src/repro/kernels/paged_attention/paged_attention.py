"""Pallas TPU kernel: in-place paged decode attention.

One-token (Sq=1) attention that consumes the :class:`PagedCache` pool
DIRECTLY: the per-slot int32 block-table row, the slot's position and
its left-pad ``start`` ride in as scalar-prefetch operands, and the
BlockSpec index maps use the prefetched table to stream each K/V page
HBM -> VMEM in page-table order — the logical [B, max_len] KV view is
never materialized (the ``PagedCache._gather`` copy this kernel
replaces was an O(B * max_len * H * D) HBM round trip per decode step).

Grid: ``(batch, kv_heads, kv_blocks)`` with the kv axis innermost and
sequential; each step covers ``block_kv`` columns of one page
(``block_kv`` divides ``page_size``; the within-page tile is the
kernel's autotunable block — family ``"paged_attention"`` in
``kernels.tuning``).  Per step the kernel

* resolves the page id ``table[b, j // tiles_per_page]`` (page 0 is the
  reserved null page: its columns are masked out entirely),
* masks column positions against the slot's ``pos`` (causality: pages
  past the write head hold stale/unwritten rows) and ``start``
  (left-pad slots, masked forever),
* for int8-KV caches, dequantizes the page tile IN KERNEL against its
  per-row scale column (K and V rows scaled before the dots — the same
  product as the oracle's after-the-dot fold, at f32 round-off),
* and runs the online-softmax flash reduction with f32 running
  max / denominator / accumulator in VMEM scratch, so a fully-masked
  slot (an idle serving slot whose table row is all null) emits exact
  zeros.

Layout: pools are ``[P, Hkv, page, D]``, so one (page, kv head) tile is
a contiguous ``[page, D]`` slab whose last two dims are what the TPU
block rule needs (a multiple of 8 / the full page, and the full head
dim).  GQA: the q heads of kv head ``h`` are the contiguous block
``h*G .. (h+1)*G - 1``; q arrives as ``[B, Hkv, G, D]`` so one grid step
loads a ``[G, D]`` q tile and scores it against the page tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(*refs, nkv: int, block_kv: int, tiles_per_page: int,
            scale: float, has_scale: bool):
    if has_scale:
        (table_ref, pos_ref, start_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (table_ref, pos_ref, start_ref, q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a null (page-0) table entry can contribute nothing — skip its dots
    # entirely (idle serving slots and the unmapped tail past a slot's
    # reservation cost zero MXU work)
    page = j // tiles_per_page
    pid = table_ref[b, page]

    @pl.when(pid != 0)
    def _block():
        q = q_ref[...].astype(jnp.float32) * scale             # [G, D]
        k = k_ref[...].astype(jnp.float32)                     # [bkv, D]
        v = v_ref[...].astype(jnp.float32)                     # [bkv, D]
        if has_scale:   # int8 KV: per-row dequant scales, [bkv, 1]
            k = k * ks_ref[...].astype(jnp.float32)
            v = v * vs_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, bkv]

        # column c of tile j sits at logical position j*block_kv + c
        # (table order IS position order)
        kv_pos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = (kv_pos <= pos_ref[b]) & (kv_pos >= start_ref[b])
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                                    # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)                        # masked rows stay 0
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(j == nkv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "block_kv", "interpret"),
)
def paged_attention_kernel(
    q: jax.Array,            # [B, Hq, 1, D]
    k_pages: jax.Array,      # [P, Hkv, page, D] page pool (page 0 = null)
    v_pages: jax.Array,      # [P, Hkv, page, D]
    block_table: jax.Array,  # [B, pages_per_slot] int32 page ids
    pos: jax.Array,          # [B] int32: last valid position per slot
    start: jax.Array,        # [B] int32: first attendable position
    k_scales: jax.Array | None = None,   # [P, Hkv, page, 1] per-row scales
    v_scales: jax.Array | None = None,
    *,
    page_size: int,
    scale: float | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hq, sq, d = q.shape
    assert sq == 1, "paged_attention is a decode (Sq=1) kernel"
    _, hkv, page, _ = k_pages.shape
    assert page == page_size, (page, page_size)
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    npages = block_table.shape[-1]
    if scale is None:
        scale = d**-0.5
    block_kv = page_size if block_kv is None else min(int(block_kv), page_size)
    assert page_size % block_kv == 0, (page_size, block_kv)
    tiles_per_page = page_size // block_kv
    nkv = npages * tiles_per_page
    has_scale = k_scales is not None
    grid = (b, hkv, nkv)

    # the scalar-prefetched table drives the page DMA: block j of the kv
    # axis maps to tile (j % tiles_per_page) of page table[b, j // tpp]
    def kv_idx(bi, hi, ji, table_ref, pos_ref, start_ref):
        del pos_ref, start_ref
        return (table_ref[bi, ji // tiles_per_page], hi,
                ji % tiles_per_page, 0)

    def q_idx(bi, hi, ji, *refs):
        return (bi, hi, 0, 0)

    kv_spec = pl.BlockSpec((None, None, block_kv, d), kv_idx)
    scale_spec = pl.BlockSpec((None, None, block_kv, 1), kv_idx)
    q_spec = pl.BlockSpec((None, None, group, d), q_idx)

    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q.reshape(b, hkv, group, d), k_pages, v_pages]
    if has_scale:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, group, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, nkv=nkv, block_kv=block_kv,
            tiles_per_page=tiles_per_page, scale=scale, has_scale=has_scale),
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_table.astype(jnp.int32), pos.astype(jnp.int32),
      start.astype(jnp.int32), *operands)
    return out.reshape(b, hq, sq, d)
