"""Public op: in-place paged decode attention with backend dispatch.

``paged_attention`` is the one entry point behind the PagedCache decode
path (``attention.decode_step``): it takes the page pools + block table
AS STORED — no gathered [B, max_len] KV view, no pre-dequantized int8
copy — and dispatches to the Pallas kernel on TPU (pages streamed
HBM -> VMEM through the scalar-prefetched table) or the blocked jnp
oracle elsewhere (bit-identical to the dense backend's decode — see
``ref.py`` for the reduction-order contract).

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
from repro.kernels.paged_attention.ref import paged_attention_ref


def paged_attention(q, k_pages, v_pages, block_table, pos, start=None, *,
                    page_size: int, k_scales=None, v_scales=None, scale=None,
                    use_kernel: str = "auto", score_mode: str = "auto",
                    **block_kw):
    """Decode attention straight off the page pool.

    q: [B, Hq, 1, D]; k_pages/v_pages: [P, Hkv, page, D] pools (page 0 =
    reserved null page, masked); block_table: [B, pages_per_slot] int32;
    pos/start: [B] int32 (last valid / first attendable position per
    slot).  ``k_scales``/``v_scales`` ([P, Hkv, page, 1]) are the
    per-page int8-KV dequant scale pools, folded exactly where the
    gather path folded them (K after the q.k dot, V into the
    probabilities).  Returns [B, Hq, 1, D] float32.
    """
    if start is None:
        start = jnp.zeros((q.shape[0],), jnp.int32)
    if use_kernel == "auto":
        use_kernel = "pallas" if jax.default_backend() == "tpu" else "ref"
    bk = tuning.get_block_config(
        "paged_attention", (page_size, q.shape[3]), block_kw)
    if use_kernel in ("pallas", "interpret"):
        return paged_attention_kernel(
            q, k_pages, v_pages, block_table, pos, start,
            k_scales, v_scales, page_size=page_size, scale=scale,
            pages_per_block=int(bk["pages_per_block"]),
            interpret=(use_kernel == "interpret"))
    return paged_attention_ref(
        q, k_pages, v_pages, block_table, pos, start=start,
        page_size=page_size, k_scales=k_scales, v_scales=v_scales,
        scale=scale, block_pages=int(bk.get("block_pages", 64)),
        score_mode=score_mode)
