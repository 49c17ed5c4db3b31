"""GQA/MHA attention layer with RoPE (or none: ``cfg.rope``), sliding window,
and KV cache decode.

Training/prefill run the flash-attention op (Pallas on TPU, oracle on
CPU).  Decode maintains a KV cache behind the first-class backend API in
:mod:`repro.models.kv_cache`: ``DenseCache`` (contiguous rows),
``RingCache`` (sliding-window ring — O(window) state for the long_500k
cell) or ``PagedCache`` (page pool + block tables for the serving
engine).

Serving paths (``decode_step`` / ``prefill_step``) share one data path:
placement and read-back go through the cache protocol
(``write_token``/``token_view``, ``write_prompt``/``context``) and the
attention itself through ``attn_ops.masked_attention`` — a tiled
online-softmax core (Pallas with scalar-prefetch ``start`` on TPU, a
blocked jnp oracle on CPU).  The layer no longer knows which backend it
is talking to: the ring wrap/validity logic that used to live inline
here is owned by ``RingCache``, and ``PagedCache`` decode reads are IN
PLACE — ``token_view`` returns the page pool + block table (a
``kv_cache.PagedView``) and ``decode_step`` routes it through
``paged_ops.paged_attention``, which streams pages in table order
(= position order, which is what keeps paged decode bit-identical to
dense) instead of materializing the gathered [B, max_len] copy.
``prefill_step`` takes a ``pos0`` chunk offset so prompts longer than
the sliding-window ring are prefilled in chunks that write the cache
through (see ``transformer.Model.prefill``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.paged_attention import ops as paged_ops
from repro.models import kv_cache
from repro.models import layers as L


def init(key, cfg: ModelConfig):
    hd, h, hkv, d = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads, cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], cfg, d, h * hd, bias=cfg.qkv_bias),
        "wk": L.dense_init(ks[1], cfg, d, hkv * hd, bias=cfg.qkv_bias),
        "wv": L.dense_init(ks[2], cfg, d, hkv * hd, bias=cfg.qkv_bias),
        "wo": L.dense_init(ks[3], cfg, h * hd, d, scale=(h * hd) ** -0.5),
    }


def _project(cfg: ModelConfig, p, x, positions):
    b, s, _ = x.shape
    hd = cfg.head_dim
    dt = L.cdtype(cfg)
    q = L.dense_apply(p["wq"], x, dt).reshape(b, s, cfg.num_heads, hd)
    k = L.dense_apply(p["wk"], x, dt).reshape(b, s, cfg.num_kv_heads, hd)
    v = L.dense_apply(p["wv"], x, dt).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.rope:
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    return q, k, v


def _per_shard(fn, dist, b: int, hq: int, hkv: int):
    """Run ``fn(q, k, v)`` shard by shard over ``dist = (mesh, data_axes)``:
    batch over the data axes, heads over ``model`` where both head counts
    divide it (whole GQA groups stay together), else replicated.  A
    Pallas kernel cannot be partitioned automatically, so the sharded
    train step hands each device its own block of the attention."""
    from repro.sharding import _axis_size
    mesh, da = dist
    msize = _axis_size(mesh, "model") if "model" in mesh.axis_names else 1
    heads = "model" if msize > 1 and hq % msize == hkv % msize == 0 else None
    batch = da if b % _axis_size(mesh, da) == 0 else None
    spec = jax.sharding.PartitionSpec(batch, heads, None, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)


def apply(cfg: ModelConfig, p, x, positions=None, dist=None):
    """Full-sequence (train / prefill) forward.  x: [B, S, D].  ``dist``
    (mesh, data_axes) shards the attention core per device."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = _project(cfg, p, x, positions)
    attn = lambda q, k, v: attn_ops.attention(
        q, k, v, causal=True, window=cfg.sliding_window,
        scale=cfg.attn_scale)
    if dist is not None:
        attn = _per_shard(attn, dist, b, cfg.num_heads, cfg.num_kv_heads)
    out = attn(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
               v.transpose(0, 2, 1, 3))
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.num_heads * cfg.head_dim)
    return L.dense_apply(p["wo"], out, L.cdtype(cfg))


# --- KV cache decode ---------------------------------------------------------

def cache_len(cfg: ModelConfig, max_len: int) -> int:
    """Ring-buffer length: the sliding window bounds cache size."""
    return min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len


def resolve_cache_kind(cfg: ModelConfig, kind: str | None) -> str:
    """"auto" (or None) -> ring for sliding-window models, dense else."""
    if kind in (None, "auto"):
        return "ring" if cfg.sliding_window else "dense"
    return kind


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
               quantized: bool = False, kind: str = "auto",
               page_size: int | None = None, pages: int | None = None,
               mapped: bool = True):
    """Build one attention layer's KV cache backend.

    ``kind``: "auto" | "dense" | "ring" | "paged".  int8-KV
    (``quantized``) halves the decode working set — the dominant HBM
    term at long context (§Perf) — and is supported by every backend
    (PagedCache stores the scales per page).  ``page_size``/``pages``/
    ``mapped`` configure the paged pool (see ``kv_cache.paged_init``).
    """
    kind = resolve_cache_kind(cfg, kind)
    if kind == "paged":
        if cfg.sliding_window:
            raise ValueError(
                "PagedCache carries no sliding-window mask; windowed "
                "models serve through the ring backend (kind='ring')")
        return kv_cache.paged_init(
            batch, max_len, cfg.num_kv_heads, cfg.head_dim, dtype,
            quantized=quantized,
            page_size=page_size or kv_cache.DEFAULT_PAGE_SIZE,
            pages=pages, mapped=mapped)
    if kind == "ring" and not cfg.sliding_window:
        raise ValueError("RingCache requires cfg.sliding_window")
    if kind == "dense" and cfg.sliding_window:
        raise ValueError(
            "sliding-window models must use the ring cache: the dense "
            "backend carries no window mask")
    if kind not in ("dense", "ring"):
        raise ValueError(f"unknown cache kind {kind!r}")
    w = cache_len(cfg, max_len)
    shape = (batch, w, cfg.num_kv_heads, cfg.head_dim)
    kw = {}
    if quantized:
        # int8 KV cache with per-(slot, head) scales
        sshape = shape[:-1] + (1,)
        kw = {"k_s": jnp.zeros(sshape, jnp.bfloat16),
              "v_s": jnp.zeros(sshape, jnp.bfloat16)}
        dtype = jnp.int8
    if kind == "ring":
        return kv_cache.RingCache(k=jnp.zeros(shape, dtype),
                                  v=jnp.zeros(shape, dtype),
                                  window=cfg.sliding_window, **kw)
    return kv_cache.DenseCache(k=jnp.zeros(shape, dtype),
                               v=jnp.zeros(shape, dtype), **kw)


def _scale_op(s):
    """[B, S, Hkv, 1] stored scale -> [B, Hkv, S] f32 fold operand."""
    return None if s is None else s[..., 0].transpose(0, 2, 1).astype(jnp.float32)


def _finish(cfg: ModelConfig, p, out):
    """[B, Hq, S, hd] f32 attention -> output projection."""
    b, _, s, _ = out.shape
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.num_heads * cfg.head_dim)
    return L.dense_apply(p["wo"], out.astype(L.cdtype(cfg)), L.cdtype(cfg))


def decode_step(cfg: ModelConfig, p, x, cache, pos, start=None):
    """One-token decode.  x: [B, 1, D]; pos: scalar int32 cache index, or a
    per-sequence [B] vector (continuous batching: each serving slot sits at
    its own depth).  ``cache`` is any :class:`kv_cache.KVCache` backend.

    Returns (y [B, 1, D], updated cache).  Keys are rotated at write
    time; the backend places the row (``write_token``) and hands back its
    read protocol (``token_view``): the row backends return contraction
    operands plus a per-slot validity mask (the ring backend
    reconstructs each slot's absolute position), the paged backend
    returns the page pool + block table for the in-place paged-attention
    kernel.  ``start``
    ([B] int32, optional) is the number of left-pad slots per sequence
    for ragged batches: RoPE positions become ``pos - start`` and slots
    below ``start`` are masked out of the attention forever.  int8-KV
    scales are folded EXACTLY into the attention dots (K: after the q.k
    dot; V: into the probabilities), so int8 KV changes bytes, not math
    beyond round-off.
    """
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_seq = pos.ndim > 0              # [B] positions (serving slots)
    pos_b = jnp.broadcast_to(pos, (b,))
    start_b = (jnp.zeros((b,), jnp.int32) if start is None
               else jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,)))
    positions = (pos_b - start_b)[:, None]
    q, k, v = _project(cfg, p, x, positions)          # q: [B,1,H,hd]

    new = cache.write_token(k, v, pos, per_seq)
    view = new.token_view(pos_b, start_b)

    if isinstance(view, kv_cache.PagedView):
        # in-place paged read: the kernel scalar-prefetches the block
        # table and streams K/V pages (and their per-page int8 scales)
        # straight from the pool — the [B, max_len] gathered view, and
        # the full-view int8->compute cast the row backends pay, are
        # never materialized
        out = paged_ops.paged_attention(
            q.transpose(0, 2, 1, 3), view.k, view.v, view.block_table,
            pos_b, start_b, page_size=view.page_size,
            k_scales=view.k_s, v_scales=view.v_s, scale=cfg.attn_scale,
            layer=view.layer)
        return _finish(cfg, p, out), new

    # attention against the whole cache view through the shared masked
    # core (the mask is position-scattered for rings, so it rides as an
    # explicit ``valid`` [B, 1, W] — decode-sized, never O(S^2)).  The
    # cache stays in its storage dtype — f32 happens only in the
    # contraction accumulator, never as a materialized f32 copy of the
    # multi-GB cache.
    kop, vop, ks, vs, valid = view
    dt = L.cdtype(cfg)
    if kop.dtype == jnp.int8:
        kop, vop = kop.astype(dt), vop.astype(dt)
    out = attn_ops.masked_attention(
        q.transpose(0, 2, 1, 3), kop.transpose(0, 2, 1, 3),
        vop.transpose(0, 2, 1, 3), valid=valid[:, None, :],
        scale=cfg.attn_scale, k_scale=_scale_op(ks), v_scale=_scale_op(vs))
    return _finish(cfg, p, out), new


def verify_step(cfg: ModelConfig, p, x, cache, pos, start=None):
    """Speculative-verify burst: S tokens per sequence at PER-SEQUENCE
    positions — the multi-token twin of ``decode_step`` (where
    ``prefill_step`` is its static-offset batch twin).  x: [B, S, D];
    pos: [B] int32 (each serving slot at its own depth).

    All S rows are rotated and written through ``cache.write_tokens``
    (positions ``pos .. pos+S-1``), then every query attends over the
    SAME full-width storage-order operands decode reads, under a
    per-query mask (``verify_view``) that reproduces, row for row, the
    mask of the S decode ticks it replaces — so on the jnp oracle path
    each query's output is bit-identical to plain decode, which is what
    makes temperature-0 speculative acceptance exact.  ``chunk`` is
    pinned to one kv block for the same reason: the auto-chunked
    online-softmax would reorder the f32 reduction decode performs in
    one block.

    Rollback is the caller's ``pos`` reset (+ block-table restore for
    paged): rejected rows are invisible to every subsequent masked read
    and are rewritten before their position is reached.
    """
    b, s, _ = x.shape
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    start_b = (jnp.zeros((b,), jnp.int32) if start is None
               else jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,)))
    positions = (pos_b - start_b)[:, None] + jnp.arange(s, dtype=jnp.int32)
    q, k, v = _project(cfg, p, x, positions)          # q: [B,S,H,hd]

    new = cache.write_tokens(k, v, pos_b)
    kop, vop, ks, vs, valid = new.verify_view(pos_b, start_b, s)
    dt = L.cdtype(cfg)
    if kop.dtype == jnp.int8:
        kop, vop = kop.astype(dt), vop.astype(dt)
    out = attn_ops.masked_attention(
        q.transpose(0, 2, 1, 3), kop.transpose(0, 2, 1, 3),
        vop.transpose(0, 2, 1, 3), valid=valid, scale=cfg.attn_scale,
        k_scale=_scale_op(ks), v_scale=_scale_op(vs),
        chunk=kop.shape[1])
    return _finish(cfg, p, out), new


def prefill_step(cfg: ModelConfig, p, x, cache, start=None, pos0: int = 0,
                 write: bool = True):
    """Prompt-chunk forward with KV cache write-through: the batched twin
    of ``decode_step``.  x: [B, S, D] -> (y [B, S, D], updated cache).

    ``write=False`` runs the same math read-only: the chunk's K/V are
    rotated/quantized and attended exactly as if they were written, but
    the ORIGINAL cache is returned (XLA dead-code-eliminates the store).
    Serving uses this to recover last-token logits for a fully
    prefix-cached prompt without touching its shared pages.

    All S keys/values are rotated and written to slots ``pos0 .. pos0+S-1``
    (the backend wraps/pages them as its layout demands) in one shot, and
    every query attends through the SAME masked flash core and mask
    semantics as ``decode_step`` — on the shared jnp oracle path (CPU,
    where the parity tests pin it) the result is bit-identical to
    stepping the prompt token by token; on TPU prefill runs the Pallas
    kernel while decode keeps the oracle (the explicit ``valid`` mask),
    so parity there is exact-math at round-off (atol) level.

    ``pos0`` (static int) is the chunk offset for chunked prefill: the
    queries attend over the retained context (``cache.context(pos0)``,
    gathered after the write when the backend's ``context_after_write``
    says the chunk cannot touch it, before the write on the ring — a
    ring chunk may evict exactly the slots the earliest queries still
    attend to) plus the chunk itself.  ``pos0=0`` attends over the fresh
    K/V directly — no cache read-back at all.  Each call requires
    S <= cache width; ``Model.prefill`` chunks longer prompts.
    """
    b, s, _ = x.shape
    pos0 = int(pos0)
    cols = pos0 + jnp.arange(s, dtype=jnp.int32)
    start_b = (jnp.zeros((b,), jnp.int32) if start is None
               else jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,)))
    positions = cols[None, :] - start_b[:, None]      # [B, S] relative
    q, k, v = _project(cfg, p, x, positions)

    new, kf, vf, ksf, vsf = cache.write_prompt(k, v, pos0)
    # Read the retained context AFTER the chunk write wherever the
    # backend guarantees the write cannot touch it (dense rows and paged
    # pages at [0, pos0) are disjoint from the chunk's [pos0, pos0+S)).
    # Gathering the pre-write value would be a second use of the pool
    # the scatter updates, which XLA preserves by copying the WHOLE
    # pool — a pool-sized temp on every chunk.  The ring backend wraps
    # chunk writes onto slots its earliest queries still attend to, so
    # it keeps the pre-write gather (and pays the copy on its small
    # windowed pool).  ``write=False`` also reads pre-write state: the
    # store is dead there and the returned cache stays untouched.
    src = new if (write and cache.context_after_write) else cache
    kc, vc, ksc, vsc, ctx = src.context(pos0)

    def cat(prev, fresh):
        return fresh if prev is None else jnp.concatenate(
            [prev, fresh.astype(prev.dtype)], axis=1)

    kop, vop = cat(kc, kf), cat(vc, vf)
    ks = vs = None
    if new.quantized:
        ks, vs = cat(ksc, ksf), cat(vsc, vsf)
    dt = L.cdtype(cfg)
    if kop.dtype == jnp.int8:
        kop, vop = kop.astype(dt), vop.astype(dt)

    # kv column j holds absolute position pos0 - ctx + j; q row t sits at
    # pos0 + t = local ctx + t.  The left-pad mask converts to local
    # coordinates (clamped: pads older than the retained context are gone
    # from the ring anyway).
    start_local = jnp.clip(start_b - (pos0 - ctx), 0, None)
    out = attn_ops.masked_attention(
        q.transpose(0, 2, 1, 3), kop.transpose(0, 2, 1, 3),
        vop.transpose(0, 2, 1, 3), start=start_local, q_offset=ctx,
        window=new.window, scale=cfg.attn_scale, k_scale=_scale_op(ks),
        v_scale=_scale_op(vs))
    return _finish(cfg, p, out), (new if write else cache)
