"""Pallas TPU kernel: in-place paged decode attention.

One-token (Sq=1) attention that consumes the :class:`PagedCache` pool
DIRECTLY: the per-slot int32 block-table row, the slot's position, its
left-pad ``start`` and its live extent (:func:`live_extent`) ride in as
scalar-prefetch operands, and K/V pages stream HBM -> VMEM in
page-table order — the logical [B, max_len] KV view is never
materialized.

The unit of work is a kv BLOCK of ``pages_per_block`` whole pages for
all kv heads (the kernel's autotunable block — family
``"paged_attention"`` in ``kernels.tuning``).  Each slot runs only the
blocks from the one holding its first live page (mapped, at or after
``start``) to the one holding its last (at or before ``pos``), so a slot
costs work in proportion to its length, not to ``max_len``.  Per block
the kernel

* masks column positions against the slot's ``pos`` (causality: rows
  past the write head are stale/unwritten), ``start`` (left-pad slots,
  masked forever) and the liveness of the column's page (a null
  (page-0) entry, or a page outside the live range, contributes nothing;
  its V rows are zeroed, since its buffer holds whatever was there),
* for int8-KV caches, dequantizes IN KERNEL against the per-row scales
  (K and V rows scaled before the dots — the same product as the
  oracle's after-the-dot fold, at f32 round-off),
* scores all ``Hkv x G`` query heads against the block in one batched
  dot and runs the online-softmax flash reduction with f32 running
  max / denominator / accumulator in VMEM scratch; a slot with no live
  page emits exact zeros.

Two ways of bringing the pages in, one per pool shape:

* **by hand** (``_dma_kernel``), where a page's ``[Hkv, page, D]`` slab is
  lane-aligned (``D % 128 == 0``) and there are no scale pools: the grid
  runs over slots, and a ``fori_loop`` over the slot's live blocks
  gathers each block with one async copy per live page (every kv head
  in one copy), double-buffered, the next slot's first block started
  before the current slot finishes;
* **by the grid's pipeline** (``_pipelined_kernel``) otherwise: Mosaic
  cannot slice an HBM array whose minor dim is under 128 lanes (a
  ``D = 64`` page, a ``[.., 1]`` int8 scale slab), so a flat grid runs
  over the call's (slot, block) steps and each page of a block is a
  block operand the pipeline fetches (:func:`_schedule`).

Layout: pools are ``[P, Hkv, page, D]``, so one page is one contiguous
slab.  A decode tick hands the model's stack of layer pools folded flat,
``[G*P, Hkv, page, D]``, with ``base = g * P``, the first page of layer
``g``: table ids are layer-local, and every page is read at ``base +
id`` (the DMA path's source, the pipelined path's fetch list), so no
layer's pool is sliced out of the stack.  GQA: the q heads of kv head
``h`` are the contiguous block ``h*G .. (h+1)*G - 1``; q arrives as
``[B, Hkv, G, D]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def live_extent(block_table, pos, start, *, page_size: int,
                pages_per_block: int) -> jax.Array:
    """[4, B] int32, per slot: its first and last live page (mapped, and
    holding a position in ``[start, pos]``), the kv block holding the
    first, and the number of blocks from there to the last — for a slot
    with no live page, the empty range 0..-1 and 0 blocks.  The number of blocks is the kernel's trip
    count: it follows ``pos``/``start``, not the table's width."""
    npages = block_table.shape[-1]
    idx = jnp.arange(npages, dtype=jnp.int32)
    live = ((block_table != 0)
            & (idx >= (start // page_size)[:, None])
            & (idx <= (pos // page_size)[:, None]))
    any_live = jnp.any(live, axis=-1)
    first = jnp.argmax(live, axis=-1).astype(jnp.int32)
    last = jnp.where(any_live, npages - 1 - jnp.argmax(live[:, ::-1], axis=-1),
                     -1).astype(jnp.int32)
    first_blk = first // pages_per_block
    nblk = jnp.where(any_live, last // pages_per_block - first_blk + 1, 0)
    return jnp.stack([first, last, first_blk, nblk]).astype(jnp.int32)


def _block_pages(table_ref, ext_ref, b, blk, *, ppb: int, npages: int):
    """[(id, live)] of the pages of kv block ``blk`` of slot ``b``
    (scalars): live is mapped and inside the slot's live range."""
    out = []
    for j in range(ppb):
        idx = blk * ppb + j
        pid = table_ref[b, jnp.minimum(idx, npages - 1)]
        out.append((pid, (pid != 0) & (idx >= ext_ref[0, b])
                    & (idx <= ext_ref[1, b])))
    return out


def _attend_block(q, k, v, lives, kv_pos0, pos, start, m_ref, l_ref,
                  acc_ref, *, page: int, scale: float):
    """One online-softmax update of the [Hkv, G, .] state with a block:
    q [Hkv, G, D] and k/v [Hkv, T, D] in f32, ``lives`` the scalar
    liveness of each of its pages, ``kv_pos0`` the logical position of
    its first column (table order IS position order).  The softmax scale
    multiplies the f32 scores, not q: at Mosaic's default precision the
    MXU rounds f32 operands to bf16, so a scaled bf16 q would round
    once more."""
    tokens = k.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tokens), 2)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, tokens, 1), 1)
    col_live = jnp.zeros(col.shape, jnp.bool_)
    row_live = jnp.zeros(row.shape, jnp.bool_)
    for j, live in enumerate(lives):
        lo, hi = j * page, (j + 1) * page
        col_live = col_live | ((col >= lo) & (col < hi) & live)
        row_live = row_live | ((row >= lo) & (row < hi) & live)
    v = jnp.where(row_live, v, 0.0)     # a dead page's rows may hold NaN

    s = jnp.einsum("hgd,htd->hgt", q, k,
                   preferred_element_type=jnp.float32) * scale  # [Hkv, G, T]
    kv_pos = kv_pos0 + col
    mask = col_live & (kv_pos <= pos) & (kv_pos >= start)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                                        # [Hkv, G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)                            # masked rows stay 0
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
        "hgt,htd->hgd", p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finish(o_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _dma_kernel(table_ref, pos_ref, start_ref, ext_ref, base_ref, q_ref,
                k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, cur_ref, m_ref, l_ref,
                acc_ref, *, page: int, ppb: int, npages: int, nslots: int,
                scale: float):
    b = pl.program_id(0)
    tokens = ppb * page

    def copy_block(s, blk, buf, wait: bool):
        pages = _block_pages(table_ref, ext_ref, s, blk, ppb=ppb,
                             npages=npages)
        for j, (pid, live) in enumerate(pages):
            rows = pl.ds(j * page, page)

            @pl.when(live)
            def _():
                for src, dst in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    c = pltpu.make_async_copy(
                        src.at[base_ref[0] + pid], dst.at[buf, :, rows],
                        sem.at[buf])
                    c.wait() if wait else c.start()

    _init_state(m_ref, l_ref, acc_ref)
    first_blk, nblk = ext_ref[2, b], ext_ref[3, b]
    # invariant at a step's start: slot b's first block is in flight in
    # buffer cur_ref[0] — started by the step before, so a slot boundary
    # pays no DMA latency
    nb = jnp.minimum(b + 1, nslots - 1)
    has_next = (b + 1 < nslots) & (ext_ref[3, nb] > 0)

    @pl.when(b == 0)
    def _prime():
        cur_ref[0] = 0

        @pl.when(nblk > 0)
        def _():
            copy_block(b, first_blk, 0, wait=False)

    cur = cur_ref[0]

    @pl.when((nblk == 0) & has_next)
    def _hand_on():
        copy_block(nb, ext_ref[2, nb], cur, wait=False)

    def body(i, buf):
        blk = first_blk + i
        nxt = 1 - buf

        @pl.when(i + 1 < nblk)
        def _():
            copy_block(b, blk + 1, nxt, wait=False)

        @pl.when((i + 1 == nblk) & has_next)
        def _():
            copy_block(nb, ext_ref[2, nb], nxt, wait=False)

        copy_block(b, blk, buf, wait=True)
        lives = [live for _, live in _block_pages(
            table_ref, ext_ref, b, blk, ppb=ppb, npages=npages)]
        _attend_block(q_ref[...].astype(jnp.float32),
                      k_buf[buf].astype(jnp.float32),
                      v_buf[buf].astype(jnp.float32), lives, blk * tokens,
                      pos_ref[b], start_ref[b], m_ref, l_ref, acc_ref,
                      page=page, scale=scale)
        return nxt

    cur_ref[0] = jax.lax.fori_loop(0, nblk, body, cur)
    _finish(o_ref, l_ref, acc_ref)


def _schedule(ext, block_table, *, pages_per_block: int):
    """The call's flat list of (slot, kv block) steps for the pipelined
    kernel, and the page each page operand fetches at each step.

    Each slot gets its live blocks, at least one (so an idle slot's
    output is written: one masked block of zeros); the grid runs the
    total, and the lists are padded to the most steps a call can take.
    A page that is not live at a step fetches what its operand fetched
    at the step before, which the pipeline does not copy again.
    Returns (steps [2, S]: slot, block; fetch [S * ppb]; total)."""
    b, npages = block_table.shape
    ppb = pages_per_block
    first_page, last_page, first_blk, nblk = ext
    n = jnp.maximum(nblk, 1)
    ends = jnp.cumsum(n)
    total = ends[-1]
    cap = b * pl.cdiv(npages, ppb)
    i = jnp.minimum(jnp.arange(cap, dtype=jnp.int32), total - 1)
    slot = jnp.searchsorted(ends, i, side="right").astype(jnp.int32)
    blk = first_blk[slot] + i - (ends - n)[slot]
    idx = blk[:, None] * ppb + jnp.arange(ppb, dtype=jnp.int32)
    pid = block_table[slot[:, None], jnp.minimum(idx, npages - 1)]
    live = ((pid != 0) & (idx >= first_page[slot][:, None])
            & (idx <= last_page[slot][:, None]))
    # forward fill: the last live page of each operand so far, else page 0
    src = jax.lax.cummax(
        jnp.where(live, jnp.arange(cap, dtype=jnp.int32)[:, None], -1), 0)
    fetch = jnp.where(src >= 0, jnp.take_along_axis(
        pid, jnp.maximum(src, 0), axis=0), 0)
    return (jnp.stack([slot, blk]).astype(jnp.int32),
            fetch.reshape(-1).astype(jnp.int32), total)


def _pipelined_kernel(table_ref, pos_ref, start_ref, ext_ref, steps_ref,
                      fetch_ref, q_ref, *refs, page: int, ppb: int,
                      npages: int, scale: float, has_scale: bool):
    npools = 4 if has_scale else 2
    blocks = [refs[c * ppb:(c + 1) * ppb] for c in range(npools)]
    o_ref, m_ref, l_ref, acc_ref = refs[npools * ppb:]
    i, n = pl.program_id(0), pl.num_programs(0)
    b, blk = steps_ref[0, i], steps_ref[1, i]

    @pl.when((i == 0) | (steps_ref[0, jnp.maximum(i - 1, 0)] != b))
    def _():
        _init_state(m_ref, l_ref, acc_ref)

    def block(c):   # [Hkv, T, D or 1]: the block's pages of pool c
        return jnp.concatenate(
            [r[...].astype(jnp.float32) for r in blocks[c]], axis=1)

    k, v = block(0), block(1)
    if has_scale:   # int8 KV: per-row dequant scales, [Hkv, T, 1]
        k = k * block(2)
        v = v * block(3)
    lives = [live for _, live in _block_pages(
        table_ref, ext_ref, b, blk, ppb=ppb, npages=npages)]
    _attend_block(q_ref[...].astype(jnp.float32), k, v, lives,
                  blk * ppb * page, pos_ref[b], start_ref[b], m_ref, l_ref,
                  acc_ref, page=page, scale=scale)

    @pl.when((i == n - 1) | (steps_ref[0, jnp.minimum(i + 1, n - 1)] != b))
    def _():
        _finish(o_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "pages_per_block", "interpret"),
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
    base: jax.Array | None = None,       # int32 scalar: the layer's first page
    *,
    page_size: int,
    scale: float | None = None,
    pages_per_block: int = 8,
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
    ppb = max(1, int(pages_per_block))
    has_scale = k_scales is not None
    table = block_table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    start = start.astype(jnp.int32)
    ext = live_extent(table, pos, start, page_size=page_size,
                      pages_per_block=ppb)
    base = jnp.reshape(jnp.asarray(0 if base is None else base, jnp.int32),
                       (1,))
    qg = q.reshape(b, hkv, group, d)
    state = [pltpu.VMEM((hkv, group, 1), jnp.float32),
             pltpu.VMEM((hkv, group, 1), jnp.float32),
             pltpu.VMEM((hkv, group, d), jnp.float32)]

    if d % 128 == 0 and not has_scale:
        q_spec = pl.BlockSpec((None, hkv, group, d),
                              lambda bi, *_: (bi, 0, 0, 0))
        hbm = pl.BlockSpec(memory_space=pltpu.HBM)
        tokens = ppb * page_size
        kernel = functools.partial(
            _dma_kernel, page=page_size, ppb=ppb, npages=npages, nslots=b,
            scale=scale)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b,),
            in_specs=[q_spec, hbm, hbm],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, hkv, tokens, d), k_pages.dtype),
                pltpu.VMEM((2, hkv, tokens, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),    # buffer of the next block
            ] + state,
        )
        prefetch = (table, pos, start, ext, base)
        operands = (qg, k_pages, v_pages)
    else:
        steps, fetch, total = _schedule(ext, table, pages_per_block=ppb)
        fetch = fetch + base

        def q_idx(i, table_ref, pos_ref, start_ref, ext_ref, steps_ref,
                  fetch_ref):
            return (steps_ref[0, i], 0, 0, 0)

        def page_idx(j):
            def idx(i, table_ref, pos_ref, start_ref, ext_ref, steps_ref,
                    fetch_ref):
                return (fetch_ref[i * ppb + j], 0, 0, 0)
            return idx

        q_spec = pl.BlockSpec((None, hkv, group, d), q_idx)
        pools = [k_pages, v_pages] + ([k_scales, v_scales] if has_scale
                                      else [])
        in_specs, operands = [q_spec], [qg]
        for pool in pools:   # one operand per page of a block
            in_specs += [pl.BlockSpec((None, hkv, page_size, pool.shape[-1]),
                                      page_idx(j)) for j in range(ppb)]
            operands += [pool] * ppb
        kernel = functools.partial(
            _pipelined_kernel, page=page_size, ppb=ppb, npages=npages,
            scale=scale, has_scale=has_scale)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(total,),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=state,
        )
        prefetch = (table, pos, start, ext, steps, fetch)

    out = pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # sequential: a step hands the next slot's first block on, or
            # a slot's blocks accumulate into one output block
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*prefetch, *operands)
    return out.reshape(b, hq, sq, d)
