"""Public op: in-place paged decode attention with backend dispatch.

``paged_attention`` is the one entry point behind the PagedCache decode
path (``attention.decode_step``): it takes the page pools + block table
AS STORED — no gathered [B, max_len] KV view, no pre-dequantized int8
copy — and dispatches to the Pallas kernel on TPU (pages streamed
HBM -> VMEM through the scalar-prefetched table) or the blocked jnp
oracle elsewhere (bit-identical to the dense backend's decode — see
``ref.py`` for the reduction-order contract).

A decode tick's layers address the model's STACKED pools
``[G, P, Hkv, page, D]`` by their layer index (``layer``): the kernels
take the stack folded flat, ``[G*P, ..]``, and offset every page id by
``layer * P``, so no layer's pool is sliced out or written back.
``paged_write`` is the tick's row write, aliased in place on TPU.

Block sizes come from the shared shape-keyed table in
``kernels.tuning`` (family ``"paged_attention"``, keyed on
``(page_size, head_dim)``): ``pages_per_block`` is the kernel's kv
block in whole pages, ``block_pages`` the oracle's K-streaming
granularity — both swept by ``hillclimb --tune-kernels``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.paged_attention.paged_attention import (
    paged_attention_kernel)
from repro.kernels.paged_attention.paged_write import paged_write_kernel
from repro.kernels.paged_attention.ref import (
    paged_attention_ref, paged_write_ref)


def _backend(use_kernel: str) -> str:
    if use_kernel == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return use_kernel


def _flat(pool):
    """[G, P, ..] stacked pool -> [G*P, ..] (free in the default layout)."""
    return None if pool is None else pool.reshape((-1,) + pool.shape[2:])


def _pick(pool, layer):
    """Layer ``layer`` of a stacked pool (the oracle's read)."""
    return None if pool is None else jax.lax.dynamic_index_in_dim(
        pool, layer, 0, keepdims=False)


def paged_attention(q, k_pages, v_pages, block_table, pos, start=None, *,
                    page_size: int, k_scales=None, v_scales=None, scale=None,
                    layer=None, use_kernel: str = "auto",
                    score_mode: str = "auto", **block_kw):
    """Decode attention straight off the page pool.

    q: [B, Hq, 1, D]; k_pages/v_pages: [P, Hkv, page, D] pools (page 0 =
    reserved null page, masked); block_table: [B, pages_per_slot] int32;
    pos/start: [B] int32 (last valid / first attendable position per
    slot).  ``k_scales``/``v_scales`` ([P, Hkv, page, 1]) are the
    per-page int8-KV dequant scale pools, folded exactly where the
    gather path folded them (K after the q.k dot, V into the
    probabilities).  With ``layer`` (int32 scalar) the pools are the
    stack ``[G, P, Hkv, page, D]`` (scales ``[G, P, Hkv, page, 1]``) and
    layer ``layer`` is read, the table's ids being its own.  Returns
    [B, Hq, 1, D] float32.
    """
    if start is None:
        start = jnp.zeros((q.shape[0],), jnp.int32)
    use_kernel = _backend(use_kernel)
    bk = tuning.get_block_config(
        "paged_attention", (page_size, q.shape[3]), block_kw)
    if use_kernel in ("pallas", "interpret"):
        base = None
        if layer is not None:
            base = layer * k_pages.shape[1]
            k_pages, v_pages, k_scales, v_scales = map(
                _flat, (k_pages, v_pages, k_scales, v_scales))
        return paged_attention_kernel(
            q, k_pages, v_pages, block_table, pos, start,
            k_scales, v_scales, base, page_size=page_size, scale=scale,
            pages_per_block=int(bk["pages_per_block"]),
            interpret=(use_kernel == "interpret"))
    if layer is not None:
        k_pages, v_pages, k_scales, v_scales = (
            _pick(c, layer) for c in (k_pages, v_pages, k_scales, v_scales))
    return paged_attention_ref(
        q, k_pages, v_pages, block_table, pos, start=start,
        page_size=page_size, k_scales=k_scales, v_scales=v_scales,
        scale=scale, block_pages=int(bk.get("block_pages", 64)),
        score_mode=score_mode)


def paged_write(pools, rows, page, off, layer, *, use_kernel: str = "auto"):
    """A decode tick's row write into layer ``layer`` (int32 scalar) of
    stacked ``[G, P, H, page, X]`` pools: row ``b`` of each ``rows``
    entry ([B, H, X], in its pool's dtype) lands in row ``off[b]`` of
    the layer's page ``page[b]``.  Returns the new pools.  On TPU the
    Pallas kernel writes them in place (each output aliases its pool);
    elsewhere a jnp scatter, bit-identical."""
    use_kernel = _backend(use_kernel)
    pools, rows = tuple(pools), tuple(rows)
    if use_kernel not in ("pallas", "interpret"):
        return paged_write_ref(pools, rows, page, off, layer)
    flat = paged_write_kernel(
        tuple(map(_flat, pools)), rows, page + layer * pools[0].shape[1], off,
        interpret=(use_kernel == "interpret"))
    return tuple(f.reshape(c.shape) for f, c in zip(flat, pools))
