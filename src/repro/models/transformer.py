"""Decoder-only LM assembly: scan over layer groups, loss, decode step.

The layer stack is organized as ``num_groups`` repetitions of
``cfg.group`` (a tuple of (mixer, ffn) layer specs).  Parameters for each
group position are stacked with a leading [G] axis and the stack is
traversed with ``jax.lax.scan`` — one compiled group body regardless of
depth, which keeps dry-run HLO size O(1) in num_layers.

Hybrid models (Jamba) simply use a longer group, e.g. 7 SSM + 1 attn
layers with alternating dense/MoE FFNs; pure models use a group of one.

``modality`` "audio"/"vlm" accept precomputed frame/patch embeddings
([B, S, D]) in place of token ids (the stub frontend mandated by the
assignment); labels still index the token vocab.
"""

from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, layers as L, moe, ssm
from repro.models.kv_cache import PagedCache, SSMCache


# --- per-layer init/apply dispatch -------------------------------------------

def _res(cfg: ModelConfig, x, y):
    """Residual add of a layer branch, scaled by the muP
    ``residual_multiplier`` where the model has one."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _layer_init(key, cfg: ModelConfig, spec):
    mixer, ffn = spec
    ks = jax.random.split(key, 4)
    p = {}
    if mixer == "attn":
        p["mixer_norm"] = L.norm_init(cfg, cfg.d_model)
        p["mixer"] = attention.init(ks[0], cfg)
    elif mixer == "ssm":
        p["mixer_norm"] = L.norm_init(cfg, cfg.d_model)
        p["mixer"] = ssm.init(ks[0], cfg)
    if ffn == "dense":
        p["ffn_norm"] = L.norm_init(cfg, cfg.d_model)
        p["ffn"] = L.mlp_init(ks[1], cfg)
    elif ffn == "moe":
        p["ffn_norm"] = L.norm_init(cfg, cfg.d_model)
        p["ffn"] = moe.init(ks[1], cfg)
    return p


def _moe_apply(cfg: ModelConfig, p, x, dist):
    if dist is not None:
        mesh, data_axes = dist
        return moe.apply_sharded(cfg, p, x, mesh, data_axes)
    return moe.apply(cfg, p, x)


def _layer_apply(cfg: ModelConfig, spec, p, x, dist=None):
    """Full-sequence layer forward.  Returns (x, aux_loss)."""
    mixer, ffn = spec
    aux = jnp.zeros((), jnp.float32)
    if mixer == "attn":
        with jax.named_scope("attn"):
            h = L.norm_apply(cfg, p["mixer_norm"], x)
            x = _res(cfg, x, attention.apply(cfg, p["mixer"], h, dist=dist))
    elif mixer == "ssm":
        h = L.norm_apply(cfg, p["mixer_norm"], x)
        x = _res(cfg, x, ssm.apply(cfg, p["mixer"], h))
    if ffn == "dense":
        with jax.named_scope("mlp"):
            h = L.norm_apply(cfg, p["ffn_norm"], x)
            x = _res(cfg, x, L.mlp_apply(cfg, p["ffn"], h))
    elif ffn == "moe":
        h = L.norm_apply(cfg, p["ffn_norm"], x)
        y, aux = _moe_apply(cfg, p["ffn"], h, dist)
        x = _res(cfg, x, y)
    return x, aux


def _layer_decode(cfg: ModelConfig, spec, p, x, cache, pos, group,
                  dist=None, start=None, token_mask=None):
    mixer, ffn = spec
    if mixer == "attn":
        with jax.named_scope("attn"):
            h = L.norm_apply(cfg, p["mixer_norm"], x)
            y, cache = attention.decode_step(cfg, p["mixer"], h, cache, pos,
                                             start=start)
            x = _res(cfg, x, y)
    elif mixer == "ssm":
        h = L.norm_apply(cfg, p["mixer_norm"], x)
        y, cache = ssm.decode_step(cfg, p["mixer"], h, cache, pos, group,
                                   token_mask=token_mask)
        x = _res(cfg, x, y)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            h = L.norm_apply(cfg, p["ffn_norm"], x)
            x = _res(cfg, x, L.mlp_apply(cfg, p["ffn"], h))
    elif ffn == "moe":
        h = L.norm_apply(cfg, p["ffn_norm"], x)
        y, _ = _moe_apply(cfg, p["ffn"], h, dist)
        x = _res(cfg, x, y)
    return x, cache


def _layer_verify(cfg: ModelConfig, spec, p, x, cache, pos, dist=None,
                  start=None):
    """Multi-token verify burst at per-sequence positions.  Returns
    (x, new per-layer cache, SSM per-step states or None)."""
    mixer, ffn = spec
    states = None
    if mixer == "attn":
        with jax.named_scope("attn"):
            h = L.norm_apply(cfg, p["mixer_norm"], x)
            y, cache = attention.verify_step(cfg, p["mixer"], h, cache, pos,
                                             start=start)
            x = _res(cfg, x, y)
    elif mixer == "ssm":
        h = L.norm_apply(cfg, p["mixer_norm"], x)
        y, cache, states = ssm.verify_step(cfg, p["mixer"], h, cache)
        x = _res(cfg, x, y)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            h = L.norm_apply(cfg, p["ffn_norm"], x)
            x = _res(cfg, x, L.mlp_apply(cfg, p["ffn"], h))
    elif ffn == "moe":
        h = L.norm_apply(cfg, p["ffn_norm"], x)
        y, _ = _moe_apply(cfg, p["ffn"], h, dist)
        x = _res(cfg, x, y)
    return x, cache, states


def _layer_prefill(cfg: ModelConfig, spec, p, x, cache, start=None,
                   pad_mask=None, dist=None, pos0: int = 0,
                   write: bool = True):
    """Prompt-chunk layer forward that writes the decode cache through.
    Returns (x [B, S, D], new per-layer cache at pos=pos0+S).
    ``write=False`` returns the cache untouched (attention-only: an SSM
    layer's recurrent state cannot be read around)."""
    mixer, ffn = spec
    if mixer == "attn":
        with jax.named_scope("attn"):
            h = L.norm_apply(cfg, p["mixer_norm"], x)
            y, cache = attention.prefill_step(cfg, p["mixer"], h, cache,
                                              start=start, pos0=pos0,
                                              write=write)
            x = _res(cfg, x, y)
    elif mixer == "ssm":
        if not write:
            raise ValueError(
                "peek prefill is attention-only: an SSM layer must write "
                "its recurrent state through")
        if pos0 == 0:
            # a prompt's first chunk: the recurrence starts from zero,
            # whatever state the slot's view still holds
            cache = jax.tree.map(jnp.zeros_like, cache)
        h = L.norm_apply(cfg, p["mixer_norm"], x)
        y, cache = ssm.prefill_step(cfg, p["mixer"], h, cache, mask=pad_mask)
        x = _res(cfg, x, y)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            h = L.norm_apply(cfg, p["ffn_norm"], x)
            x = _res(cfg, x, L.mlp_apply(cfg, p["ffn"], h))
    elif ffn == "moe":
        h = L.norm_apply(cfg, p["ffn_norm"], x)
        y, _ = _moe_apply(cfg, p["ffn"], h, dist)
        x = _res(cfg, x, y)
    return x, cache


# --- model -------------------------------------------------------------------

class Model:
    """Pure-function model: init / apply (train|prefill) / decode_step.

    ``scan_unroll=True`` inlines the layer scan (used by the dry-run's
    roofline probes, where XLA must see every body to count FLOPs).
    ``act_sharding`` is a PartitionSpec applied to the residual stream
    between groups (Megatron-style sequence parallelism: [B, S/model, D])
    — resolves only under an active mesh context.
    """

    def __init__(self, cfg: ModelConfig, *, scan_unroll: bool = False,
                 act_sharding=None, dist=None, kv_quant: bool = False):
        self.cfg = cfg
        self.scan_unroll = scan_unroll
        self.act_sharding = act_sharding
        self.dist = dist   # (mesh, data_axes) for shard_map layers
        self.kv_quant = kv_quant  # int8 KV cache (decode)

    def _constrain(self, x):
        if self.act_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, self.act_sharding)
        return x

    # .. params ..
    def init(self, key):
        cfg = self.cfg
        kemb, khead, kfinal, klayers = jax.random.split(key, 4)
        params = {"embed": L.embed_init(kemb, cfg),
                  "final_norm": L.norm_init(cfg, cfg.d_model)}
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(
                khead, cfg, cfg.d_model, cfg.padded_vocab)

        def init_group(gkey):
            ks = jax.random.split(gkey, len(cfg.group))
            return tuple(_layer_init(ks[i], cfg, spec)
                         for i, spec in enumerate(cfg.group))

        gkeys = jax.random.split(klayers, cfg.num_groups)
        stacked = jax.vmap(init_group)(gkeys)  # leaves: [G, ...]
        params["groups"] = stacked
        return params

    # .. full-sequence forward (train / prefill) ..
    def apply(self, params, tokens=None, embeds=None, labels=None,
              remat: str = "none", last_only: bool = False,
              fused_loss: bool = False, cache=None, write_cache: bool = False,
              pad_mask=None, pos0: int = 0, start=None,
              need_logits: bool = True, peek: bool = False,
              last_index=None):
        """Full-sequence forward.

        ``write_cache=True`` turns this into the batched serving prefill:
        ``cache`` (from :meth:`init_cache`) is written through — every
        attention layer stores the prompt's rotated K/V, every SSM layer
        its conv window and final SSD state — and the populated cache
        (``pos`` advanced by S) is returned under ``out["cache"]``.  The
        per-layer math mirrors ``decode_step`` exactly, so the logits and
        cache are bit-identical to stepping the prompt token by token.

        ``pos0`` (static int) marks this forward as chunk ``[pos0,
        pos0+S)`` of a longer prompt (chunked prefill — the cache must
        already sit at ``pos == pos0``); :meth:`prefill` drives the chunk
        loop.  ``pad_mask`` ([B, S] bool, True = real token) supports
        ragged batches via LEFT padding: pad columns are masked out of
        attention (and frozen out of SSM state), and RoPE positions count
        from each sequence's first real token.  ``start`` ([B] int32)
        overrides the pad count derived from ``pad_mask`` — required for
        chunks past the first, where the mask slice no longer sees the
        row's left pads.  With ``start`` given, ``pad_mask`` may also
        mark TAIL pads (a serving chunk padded to its window): SSM
        layers freeze on them, and no real query attends them.

        ``peek=True`` (write_cache path, attention-only) runs the chunk
        read-only: logits are exactly what a writing prefill would
        produce, but the cache comes back untouched (``pos``
        unadvanced) — how serving recovers last-token logits for a
        fully prefix-cached prompt without copying its shared pages.
        ``last_index`` (traced int32 scalar) selects which position's
        logits ``last_only`` returns instead of the literal last row —
        tail-padded prompts gather the last REAL token's logits with
        the pad length traced, not baked into the compile key.
        """
        cfg = self.cfg
        if write_cache and cache is None:
            raise ValueError("write_cache=True requires a cache from "
                             "init_cache(batch, max_len)")
        if write_cache and not isinstance(cache["pos"], jax.core.Tracer):
            # prefill writes K/V at slots pos0..pos0+S-1: a cache at any
            # other depth would be silently clobbered.  Best-effort check
            # — a traced pos (cache passed as a jit argument) can't be
            # read.
            import numpy as np
            if np.any(np.asarray(cache["pos"]) != pos0):
                raise ValueError(
                    f"write_cache prefill chunk at pos0={pos0} requires the "
                    f"cache there; got pos={np.asarray(cache['pos'])}")
        if embeds is None:
            x = L.embed_apply(cfg, params["embed"], tokens)
        else:
            x = embeds.astype(L.cdtype(cfg))
        x = self._constrain(x)

        if write_cache:
            s = x.shape[1]
            if start is None and pad_mask is not None and pos0 == 0:
                start = (s - jnp.sum(pad_mask.astype(jnp.int32), axis=1))

            def group_body(carry, scan_in):
                x, full_cache = carry
                gparams, g = scan_in
                gcache = jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(c, g, 0,
                                                           keepdims=False),
                    full_cache)
                new_caches = []
                for i, spec in enumerate(cfg.group):
                    x, c = _layer_prefill(cfg, spec, gparams[i], x, gcache[i],
                                          start, pad_mask, self.dist, pos0,
                                          write=not peek)
                    new_caches.append(c)
                full_cache = jax.tree.map(
                    lambda full, new: jax.lax.dynamic_update_index_in_dim(
                        full, new.astype(full.dtype), g, 0),
                    full_cache, tuple(new_caches))
                return (self._constrain(x), full_cache), None

            (x, new_layers), _ = jax.lax.scan(
                group_body, (x, cache["layers"]),
                (params["groups"], jnp.arange(cfg.num_groups)))
            auxes = jnp.zeros((1,), jnp.float32)
            new_cache = dict(cache)
            new_cache["layers"] = new_layers
            new_cache["pos"] = cache["pos"] + (0 if peek else s)
            if start is not None:
                new_cache["start"] = start.astype(jnp.int32)
        else:
            new_cache = None

            def group_body(x, gparams):
                aux_total = jnp.zeros((), jnp.float32)
                for i, spec in enumerate(cfg.group):
                    x, aux = _layer_apply(cfg, spec, gparams[i], x, self.dist)
                    aux_total += aux
                return self._constrain(x), aux_total

            if remat == "full":
                group_body = jax.checkpoint(group_body)
            elif remat == "dots":
                group_body = jax.checkpoint(
                    group_body,
                    policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
            x, auxes = jax.lax.scan(group_body, x, params["groups"],
                                    unroll=self.cfg.num_groups if self.scan_unroll else 1)
        out = {"aux_loss": jnp.sum(auxes)}
        if new_cache is not None:
            out["cache"] = new_cache
        if not need_logits:   # non-final prefill chunk: cache only, no
            return out        # final norm / vocab projection
        if last_only:   # prefill serving: only the last position's logits
            if last_index is None:
                x = x[:, -1:, :]
            else:       # tail-padded prompt: the last REAL position's
                x = jax.lax.dynamic_slice_in_dim(
                    x, jnp.asarray(last_index, jnp.int32), 1, axis=1)
        x = L.norm_apply(cfg, params["final_norm"], x)
        head = params.get("lm_head")
        if fused_loss:
            # never materializes [B, S, V] logits (chunked + remat)
            assert labels is not None
            row_sharding = None
            if self.act_sharding is not None:
                axes = tuple(a for a in self.act_sharding if a is not None)
                flat = tuple(x for a in axes
                             for x in (a if isinstance(a, tuple) else (a,)))
                row_sharding = type(self.act_sharding)(flat, None)
            ce = L.fused_cross_entropy(cfg, head, params["embed"], x, labels,
                                       row_sharding=row_sharding)
            out["loss"] = ce + out["aux_loss"]
            out["ce_loss"] = ce
            return out
        with jax.named_scope("head"):
            logits = L.lm_head_apply(cfg, head, params["embed"], x)
        if logits.ndim == 3:
            # keep [B@data, S@model, V] sharded through the CE backward
            logits = self._constrain(logits)
        out["logits"] = logits
        if labels is not None:
            ce = L.cross_entropy(logits, labels, cfg.vocab_size)
            out["loss"] = ce + out["aux_loss"]
            out["ce_loss"] = ce
        return out

    # .. decode ..
    def init_cache(self, batch: int, max_len: int, kind: str | None = None,
                   **cache_kw):
        """Decode cache: one backend instance per layer position.

        ``kind`` picks the attention KV backend ("auto" | "dense" |
        "ring" | "paged"; default ``cfg.cache_kind`` — auto resolves to
        ring for sliding-window models, dense otherwise).  ``cache_kw``
        (``page_size``, ``pages``, ``mapped``) configures the paged
        pool; see ``kv_cache.paged_init``.
        """
        cfg = self.cfg
        dtype = L.cdtype(cfg)
        kind = kind if kind is not None else cfg.cache_kind

        def one_group(_):
            caches = []
            for spec in cfg.group:
                mixer, _ = spec
                if mixer == "attn":
                    caches.append(attention.init_cache(
                        cfg, batch, max_len, dtype, quantized=self.kv_quant,
                        kind=kind, **cache_kw))
                elif mixer == "ssm":
                    caches.append(ssm.init_cache(cfg, batch, dtype))
                else:
                    caches.append({})
            return tuple(caches)

        return {
            "layers": jax.vmap(one_group)(jnp.arange(self.cfg.num_groups)),
            "pos": jnp.zeros((), jnp.int32),
        }

    def decode_step(self, params, cache, tokens=None, embeds=None,
                    token_mask=None):
        """One token for the whole batch.  tokens: [B] int32 (or embeds
        [B, 1, D]).  Returns (logits [B, V], new cache).

        ``cache["pos"]`` may be a scalar (the whole batch at one depth)
        or a per-sequence [B] vector (continuous batching: each serving
        slot at its own depth).  An optional ``cache["start"]`` ([B]
        int32, written by the ragged prefill) marks left-pad slots that
        stay masked out of attention; ``token_mask`` ([B] bool) marks the
        CURRENT token as a pad (sequential prefill of ragged batches) so
        SSM layers carry their state through unchanged.
        """
        cfg = self.cfg
        pos = cache["pos"]
        start = cache.get("start")
        if embeds is None:
            x = L.embed_apply(cfg, params["embed"], tokens[:, None])
        else:
            x = embeds.astype(L.cdtype(cfg))

        # The cache rides the scan CARRY — never through xs/ys, which
        # would stage a full copy of the multi-GB cache every token.  The
        # large leaves are not sliced out of the carry at all: a slice
        # and its write-back each cost a pass over the leaf (XLA does
        # not alias them in place, donation or not).  An SSM layer steps
        # its group's SSD state where it lies in the stack, and a paged
        # layer writes and reads its pages where they lie in the stacked
        # pools (``PagedCache.layer``); both kernels alias their stack
        # in place.  The other leaves (conv windows, block tables, the
        # dense and ring backends' rows) keep per-group slices and
        # updates.
        def group_body(carry, scan_in):
            x, full_cache = carry
            gparams, g = scan_in
            index = lambda c: jax.lax.dynamic_index_in_dim(c, g, 0,
                                                           keepdims=False)
            put = lambda full, new: jax.lax.dynamic_update_index_in_dim(
                full, new.astype(full.dtype), g, 0)

            def layer_view(c):
                if isinstance(c, SSMCache):
                    return SSMCache(conv=index(c.conv), ssd=c.ssd)
                if isinstance(c, PagedCache):
                    return replace(c, block_table=index(c.block_table),
                                   layer=g)
                return jax.tree.map(index, c)

            def write_back(full, new):
                if isinstance(full, SSMCache):
                    return SSMCache(conv=put(full.conv, new.conv), ssd=new.ssd)
                if isinstance(full, PagedCache):   # a tick maps no page
                    return replace(full, k=new.k, v=new.v, k_s=new.k_s,
                                   v_s=new.v_s)
                return jax.tree.map(put, full, new)

            gcache = tuple(layer_view(c) for c in full_cache)
            new_caches = []
            for i, spec in enumerate(cfg.group):
                x, c = _layer_decode(cfg, spec, gparams[i], x, gcache[i], pos,
                                     g, self.dist, start, token_mask)
                new_caches.append(c)
            full_cache = tuple(write_back(f, n)
                               for f, n in zip(full_cache, new_caches))
            return (x, full_cache), None

        (x, new_layer_caches), _ = jax.lax.scan(
            group_body, (x, cache["layers"]),
            (params["groups"], jnp.arange(cfg.num_groups)))
        x = L.norm_apply(cfg, params["final_norm"], x)
        with jax.named_scope("head"):
            logits = L.lm_head_apply(cfg, params.get("lm_head"),
                                     params["embed"], x)
        new_cache = dict(cache)
        new_cache["layers"] = new_layer_caches
        new_cache["pos"] = pos + 1
        return logits[:, 0], new_cache

    def verify_step(self, params, cache, tokens):
        """Speculative-verify burst: S tokens for the whole batch at
        per-slot depths in ONE dispatch — the B×S GEMM-shaped twin of
        ``decode_step``'s B×1 tick.  tokens: [B, S] int32 (column 0 is
        the already-sampled next token, columns 1.. the drafts).

        Returns (logits [B, S, V], new cache at pos+S, states): position
        t's logits are bit-identical (oracle path) to what S sequential
        ``decode_step`` calls would produce given the same prefix, which
        is the property speculative acceptance rests on.  ``states``
        mirrors the group structure with each SSM layer's per-step
        post-states ([G, B, S, ...] leaves; None at attention
        positions) — ``select_ssm_states`` rolls the returned cache back
        to any accepted length.  Attention layers roll back by the
        caller's ``pos`` reset (+ paged block-table restore) alone.
        """
        cfg = self.cfg
        pos = cache["pos"]
        start = cache.get("start")
        x = L.embed_apply(cfg, params["embed"], tokens)

        def group_body(carry, scan_in):
            x, full_cache = carry
            gparams, g = scan_in
            gcache = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, g, 0,
                                                       keepdims=False),
                full_cache)
            new_caches, gstates = [], []
            for i, spec in enumerate(cfg.group):
                x, c, st = _layer_verify(cfg, spec, gparams[i], x, gcache[i],
                                         pos, self.dist, start)
                new_caches.append(c)
                gstates.append(st)
            full_cache = jax.tree.map(
                lambda full, new: jax.lax.dynamic_update_index_in_dim(
                    full, new.astype(full.dtype), g, 0),
                full_cache, tuple(new_caches))
            return (x, full_cache), tuple(gstates)

        (x, new_layer_caches), states = jax.lax.scan(
            group_body, (x, cache["layers"]),
            (params["groups"], jnp.arange(cfg.num_groups)))
        x = L.norm_apply(cfg, params["final_norm"], x)
        with jax.named_scope("head"):
            logits = L.lm_head_apply(cfg, params.get("lm_head"),
                                     params["embed"], x)
        new_cache = dict(cache)
        new_cache["layers"] = new_layer_caches
        new_cache["pos"] = pos + tokens.shape[1]
        return logits, new_cache, states

    def select_ssm_states(self, layers, states, sel):
        """Roll every SSM layer cache back to the post-state at step
        ``sel[b]`` (from ``verify_step``'s stacked states); non-SSM
        layer caches pass through untouched."""
        out = []
        for c, st in zip(layers, states):
            if st is None:
                out.append(c)
            else:
                out.append(jax.vmap(ssm.select_state, in_axes=(0, None))(
                    st, sel))
        return tuple(out)

    def _attn_cache_width(self, cache) -> int | None:
        """Logical kv width of the attention cache backend (None:
        attention-free) — the per-chunk prefill bound."""
        for i, (mixer, _) in enumerate(self.cfg.group):
            if mixer == "attn":
                return cache["layers"][i].width
        return None

    def prefill(self, params, cache, tokens=None, embeds=None, pad_mask=None,
                chunk: int | None = None, pos0: int = 0):
        """Batched serving prefill: forward pass(es) that populate the
        decode cache.  Returns (last-token logits [B, V], cache at
        pos=pos0+S0) — exactly what the first decode step needs.

        Prompts longer than the attention cache width (a sliding-window
        ring the prompt would wrap), or any prompt when ``chunk`` /
        ``cfg.prefill_chunk`` is set, are processed in fixed-size chunks
        that write the cache through per chunk — peak activation memory
        is O(chunk * W) instead of O(S0^2), so arbitrarily long prompts
        are servable.  Each chunk runs the same masked-flash layer math,
        so on the oracle path the final logits and cache are
        bit-identical to the one-shot (and to token-by-token) prefill
        until the ring wraps, and exact-math/atol-level after (the ring
        reorders the f32 reduction; same caveat on TPU, where prefill
        runs the Pallas kernel).

        ``pos0 > 0`` RESUMES a prompt: the tokens are positions
        [pos0, pos0+S0) on top of a cache already holding [0, pos0) —
        the prefix-sharing suffix prefill (a prefix-cached admission
        maps the shared pages and prefills only from the first unshared
        position).  The cache must sit at ``pos == pos0``.  Resumed
        prompts are unpadded (``pad_mask`` is rejected: padding offsets
        are a whole-prompt-at-start-0 notion).
        """
        x = tokens if tokens is not None else embeds
        s0 = x.shape[1]
        if pos0 and pad_mask is not None:
            raise ValueError(
                "pos0 > 0 resumes an unpadded prompt at start 0; pad_mask "
                "is unsupported on the resumed-suffix path")
        chunk = chunk if chunk is not None else self.cfg.prefill_chunk
        width = self._attn_cache_width(cache)
        if chunk is None and (width is None or pos0 + s0 <= width):
            out = self.apply(params, tokens=tokens, embeds=embeds, cache=cache,
                             write_cache=True, last_only=True,
                             pad_mask=pad_mask, pos0=pos0)
            return out["logits"][:, 0], out["cache"]

        c = chunk or width          # auto-chunk at the ring width
        if width is not None:
            c = min(c, width)       # prefill_step bound: one chunk per write
        c = max(int(c), 1)
        start = None
        if pad_mask is not None:
            start = (s0 - jnp.sum(pad_mask.astype(jnp.int32), axis=1))
        logits = None
        for lo in range(0, s0, c):
            hi = min(lo + c, s0)
            out = self.apply(
                params,
                tokens=None if tokens is None else tokens[:, lo:hi],
                embeds=None if embeds is None else embeds[:, lo:hi],
                cache=cache, write_cache=True, last_only=True,
                pad_mask=None if pad_mask is None else pad_mask[:, lo:hi],
                pos0=pos0 + lo, start=start, need_logits=(hi == s0))
            cache = out["cache"]
            if hi == s0:
                logits = out["logits"][:, 0]
        return logits, cache


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)


def loss_fn(model: Model, params, batch, remat: str = "none",
            fused_loss: bool = False):
    # fused_loss=True (flattened chunked CE) does not partition under
    # GSPMD (the [B,S]->[T] reshape of a 2D-sharded tensor full-gathers);
    # the sharded 3D CE below is strictly better on the production mesh.
    """Scalar training loss for (tokens|embeds, labels) batches."""
    out = model.apply(params,
                      tokens=batch.get("tokens"),
                      embeds=batch.get("embeds"),
                      labels=batch["labels"],
                      remat=remat,
                      fused_loss=fused_loss)
    return out["loss"], {"ce_loss": out["ce_loss"], "aux_loss": out["aux_loss"]}
