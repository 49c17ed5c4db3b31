"""Blocked jnp oracle for in-place paged decode attention.

``paged_attention_ref`` computes one-token attention straight off the
:class:`PagedCache` page pool + block table — the CPU twin of the Pallas
kernel and the serving decode path on hosts without a TPU.  Contract:

* **Bit-identity with the dense backend.**  Scores are per-column q . k
  dots (stable under any block grouping — every formulation tested
  concatenates bitwise-equal to the one-einsum result), the softmax
  runs as single ops over the full [B, Hkv, G, W] score tensor
  (decode-sized: no D factor), and the value side is ONE
  position-ordered f32 contraction — the same reduction order the dense
  backend's single-block ``masked_attention_ref`` path uses.  That is
  what keeps paged decode bit-identical to DenseCache (bf16 and
  int8-KV), pinned by ``tests/test_paged_attention.py``.
* **Blocked or pool-wide K reads.**  ``score_mode="blocks"`` gathers
  ``block_pages`` pages per score block (peak extra memory O(block),
  not O(max_len); on CPU, XLA cannot fuse an indexed page read into a
  GEMM operand, so coarse blocks win — ``hillclimb --tune-kernels``
  sweeps the knob).  ``score_mode="pool"`` skips the K gather entirely:
  q scores against EVERY pool column as one regular GEMM and each
  slot's columns are selected out of the decode-sized score tensor —
  profitable when the pool is small relative to the extra flops
  (``generate()``'s fully-provisioned pool at long widths; the "auto"
  rule picks it there).  The Pallas kernel, which CAN stream pages
  HBM -> VMEM without an intermediate, always reads per page.
* **Masking.**  Column ``j`` (table order * page_size + offset — table
  order is position order) attends iff ``start[b] <= j <= pos[b]`` and
  its table entry is mapped (page 0 is the reserved null page).  A
  fully-masked slot (idle serving slot, all-null table row) returns
  exact zeros.
* int8-KV pools are cast to the compute dtype per block — never as a
  full-pool copy — and the per-page scales fold exactly where the
  gather path folded them (K after the dot, V into the probabilities).
"""

from __future__ import annotations

import jax.numpy as jnp


def _take_pages(pool, table):
    """[P, H, page, *] pool + [B, n] ids -> [B, H, n*page, *] operand.
    Built as position-ordered rows [B, n*page, H, *], then head-major —
    the same ops as ``PagedCache``'s gathered view, so both reads feed
    the contraction identically (the head transpose fuses into it)."""
    g = pool[table].swapaxes(2, 3)               # [B, n, page, H, *]
    g = g.reshape((g.shape[0], -1) + g.shape[3:])
    return g.transpose(0, 2, 1, 3)               # [B, H, C, *]


def _scale_cols(pool, table):
    """[P, H, page, 1] scale pool -> [B, H, C] f32 fold operand."""
    return _take_pages(pool, table)[..., 0].astype(jnp.float32)


def paged_attention_ref(q, k_pages, v_pages, block_table, pos, start=None,
                        *, page_size: int, k_scales=None, v_scales=None,
                        scale=None, block_pages: int | None = None,
                        score_mode: str = "auto"):
    """q: [B, Hq, 1, D]; pools: [P, Hkv, page, D]; table: [B, nP] int32;
    pos/start: [B] int32.  Returns [B, Hq, 1, D] float32.

    ``score_mode`` picks how K is read (both bit-exact):

    * ``"blocks"`` — gather ``block_pages`` pages per score block
      (O(block) extra memory; the general path).
    * ``"pool"`` — score q against EVERY pool column in one regular
      GEMM, then select each slot's columns out of the (decode-sized)
      score tensor: K is never gathered at all.  Pays pool/width extra
      score flops, so it only makes sense for small pools at long
      widths — exactly the ``generate()`` shape (full provisioning,
      pool = B * width).
    * ``"auto"`` — "pool" when the pool is <= 4x one slot's width, the
      width is >= 512 and K is not int8 (a pool-wide dequant cast would
      cost more than the gather it saves); else "blocks".
    """
    b, hq, sq, d = q.shape
    assert sq == 1, "paged_attention is a decode (Sq=1) op"
    _, hkv, page, _ = k_pages.shape
    assert page == page_size, (page, page_size)
    group = hq // hkv
    npages = block_table.shape[-1]
    w = npages * page_size
    ncols = k_pages.shape[0] * page_size   # incl. the reserved null page
    if scale is None:
        scale = d**-0.5
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    if block_pages is None:
        block_pages = npages
    if score_mode == "auto":
        score_mode = ("pool" if (ncols - page_size <= 4 * w and w >= 512
                                 and k_pages.dtype != jnp.int8) else "blocks")
    qg = q.reshape(b, hkv, group, sq, d)

    if score_mode == "pool":
        # one regular GEMM against the whole pool, then per-slot column
        # selection from the [B, Hkv, G, 1, ncols] scores — the K pages
        # are read once, in pool order, with no gather at all (each
        # selected score is the same q . k dot, so this is bit-exact)
        kcols = k_pages.swapaxes(1, 2).reshape(-1, hkv, d)   # pool order
        if kcols.dtype == jnp.int8:
            kcols = kcols.astype(q.dtype)
        s_all = jnp.einsum("bhgqd,khd->bhgqk", qg, kcols,
                           preferred_element_type=jnp.float32) * scale
        colid = (block_table[:, :, None] * page_size
                 + jnp.arange(page_size, dtype=block_table.dtype)).reshape(
                     b, -1)
        s = jnp.take_along_axis(s_all, colid[:, None, None, None, :],
                                axis=-1)
        if k_scales is not None:   # per-page K scale, folded after the dot
            s = s * _scale_cols(k_scales, block_table)[:, :, None, None, :]
    else:
        # per-block K reads: the same per-column dots as the one-shot
        # einsum (concatenation is bit-exact), O(block) extra memory
        ss = []
        for lo in range(0, npages, int(block_pages)):
            blk = block_table[:, lo:lo + int(block_pages)]
            kb = _take_pages(k_pages, blk)
            if kb.dtype == jnp.int8:
                kb = kb.astype(q.dtype)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kb,
                           preferred_element_type=jnp.float32) * scale
            if k_scales is not None:
                s = s * _scale_cols(k_scales, blk)[:, :, None, None, :]
            ss.append(s)
        s = jnp.concatenate(ss, -1) if len(ss) > 1 else ss[0]

    cols = jnp.arange(w, dtype=jnp.int32)[None, :]
    mapped = jnp.repeat(block_table != 0, page_size, axis=-1)   # [B, W]
    valid = ((cols <= pos[:, None]) & (cols >= start[:, None]) & mapped)
    mask = valid[:, None, None, None, :]
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)                  # fully-masked rows: 0
    l = jnp.sum(p, -1, keepdims=True)
    if v_scales is not None:   # per-page V scale, folded into the probs
        p = p * _scale_cols(v_scales, block_table)[:, :, None, None, :]

    # ONE position-ordered contraction: pins the f32 reduction order the
    # dense backend uses, hence the paged==dense bit-identity
    vb = _take_pages(v_pages, block_table)
    if vb.dtype == jnp.int8:
        vb = vb.astype(q.dtype)
    acc = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
                     preferred_element_type=jnp.float32)
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(b, hq, sq, d)


def paged_write_ref(pools, rows, page, off, layer):
    """jnp twin of the in-place row write: row ``b`` of each ``rows``
    entry ([B, H, X]) lands in row ``off[b]`` of page ``page[b]`` of
    layer ``layer`` of its stacked ``[G, P, H, page, X]`` pool — the
    scatter ``PagedCache.write_token`` makes on one layer's pool."""
    return tuple(c.at[layer, page, :, off].set(r.astype(c.dtype))
                 for c, r in zip(pools, rows))
