"""Shared model building blocks: norms, rotary embeddings, MLPs, embeddings.

Everything is a pair of pure functions (init(key, cfg) -> params,
apply(params, x) -> y) over plain dict pytrees — no framework.  Logical
sharding axes for every parameter are declared here via
``repro.sharding.logical`` annotations consumed by the partitioner.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# --- Norms -------------------------------------------------------------------

def norm_init(cfg: ModelConfig, dim: int):
    p = {"scale": jnp.ones((dim,), pdtype(cfg))}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((dim,), pdtype(cfg))
    return p


def norm_apply(cfg: ModelConfig, p, x):
    x32 = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(x32), -1, keepdims=True)
        y = x32 * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# --- Rotary embeddings -------------------------------------------------------

def rope(x, positions, theta: float):
    """Apply rotary embedding.  x: [..., S, H, D], positions: [..., S]."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --- Dense projections -------------------------------------------------------

def dense_init(key, cfg: ModelConfig, d_in: int, d_out: int, *, bias=False,
               scale: float | None = None):
    scale = scale if scale is not None else d_in**-0.5
    p = {"kernel": (jax.random.normal(key, (d_in, d_out)) * scale).astype(pdtype(cfg))}
    if bias:
        p["bias"] = jnp.zeros((d_out,), pdtype(cfg))
    return p


# quantized-record markers: packed EN-T planes (serving default), legacy
# 4-plane records, and plane-less plain-int8 records all route to qdense
_QUANT_KEYS = ("planes_packed", "planes", "q")


def dense_apply(p, x, compute_dtype):
    if any(k in p for k in _QUANT_KEYS):
        # EN-T w8a8 record (repro.quant.quantize) — packed records run the
        # fused kernel: in-kernel act quant + 2 plane matmuls + dequant
        from repro.quant.quantize import qdense_apply
        return qdense_apply(p, x, out_dtype=compute_dtype)
    y = x.astype(compute_dtype) @ p["kernel"].astype(compute_dtype)
    if "bias" in p:
        y = y + p["bias"].astype(compute_dtype)
    return y


# --- MLP (swiglu / gelu) -----------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None):
    d_ff = d_ff or cfg.d_ff
    keys = jax.random.split(key, 3)
    if cfg.mlp_type == "swiglu":
        return {
            "wi_gate": dense_init(keys[0], cfg, cfg.d_model, d_ff, bias=cfg.mlp_bias),
            "wi_up": dense_init(keys[1], cfg, cfg.d_model, d_ff, bias=cfg.mlp_bias),
            "wo": dense_init(keys[2], cfg, d_ff, cfg.d_model, bias=cfg.mlp_bias,
                             scale=d_ff**-0.5),
        }
    return {
        "wi": dense_init(keys[0], cfg, cfg.d_model, d_ff, bias=cfg.mlp_bias),
        "wo": dense_init(keys[1], cfg, d_ff, cfg.d_model, bias=cfg.mlp_bias,
                         scale=d_ff**-0.5),
    }


def mlp_apply(cfg: ModelConfig, p, x):
    dt = cdtype(cfg)
    if cfg.mlp_type == "swiglu":
        g = dense_apply(p["wi_gate"], x, dt)
        u = dense_apply(p["wi_up"], x, dt)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    else:
        h = dense_apply(p["wi"], x, dt)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(dt)
    return dense_apply(p["wo"], h, dt)


# --- Embeddings / LM head ----------------------------------------------------

def embed_init(key, cfg: ModelConfig):
    v = cfg.padded_vocab
    p = {"embedding": (jax.random.normal(key, (v, cfg.d_model)) * 0.02).astype(pdtype(cfg))}
    return p


def embed_apply(cfg: ModelConfig, p, tokens):
    x = p["embedding"].astype(cdtype(cfg))[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def lm_head_apply(cfg: ModelConfig, p_head, p_embed, x):
    """Logits in f32 via a bf16 matmul with f32 accumulation — keeps the
    [D, V] kernel (and its FSDP all-gather) in bf16 instead of f32."""
    kernel = (p_embed["embedding"].T if cfg.tie_embeddings
              else p_head["kernel"])
    dt = cdtype(cfg)
    logits = jax.lax.dot_general(
        x.astype(dt), kernel.astype(dt), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def cross_entropy(logits, labels, vocab_size: int):
    """Mean CE over labels >= 0 (negative labels = padding/masked).

    logits: [..., V_padded] f32; labels int32.  Padded vocab entries are
    excluded by masking them to -inf before the softmax.
    """
    v = logits.shape[-1]
    if v > vocab_size:
        pad_mask = jnp.arange(v) >= vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def fused_cross_entropy(cfg: ModelConfig, p_head, p_embed, x, labels,
                        chunk: int = 8192, row_sharding=None):
    """lm_head + CE fused over token chunks — never materializes the full
    [B, S, V] logits (at 32k seq x 152k vocab that is hundreds of GB).

    Each chunk's logits are recomputed in the backward pass
    (jax.checkpoint), so peak memory is one chunk's [chunk, V] f32.

    ``row_sharding``: PartitionSpec for the flattened [T, D] token rows —
    pass P((data..., model), None) so the chunk stacks (and their scan-
    backward cotangents) shard over ALL devices instead of replicating.
    """
    kernel = (p_embed["embedding"].T if cfg.tie_embeddings
              else p_head["kernel"])
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    lt = labels.reshape(t)
    chunk = min(chunk, t)
    if t % chunk:                       # pad to a chunk multiple, mask out
        pad = chunk - t % chunk
        xt = jnp.concatenate([xt, jnp.zeros((pad, d), xt.dtype)])
        lt = jnp.concatenate([lt, jnp.full((pad,), -1, lt.dtype)])
    if row_sharding is not None:
        xt = jax.lax.with_sharding_constraint(xt, row_sharding)
    nc = xt.shape[0] // chunk
    xc = xt.reshape(nc, chunk, d)
    lc = lt.reshape(nc, chunk)
    if row_sharding is not None:
        # keep every chunk's rows spread across all devices
        chunk_spec = type(row_sharding)(None, *row_sharding)
        xc = jax.lax.with_sharding_constraint(xc, chunk_spec)

    @jax.checkpoint
    def chunk_loss(carry, inp):
        xi, li = inp
        logits = xi.astype(jnp.float32) @ kernel.astype(jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        if cfg.logit_softcap:
            c = cfg.logit_softcap
            logits = c * jnp.tanh(logits / c)
        v = logits.shape[-1]
        if v > cfg.vocab_size:
            logits = jnp.where(jnp.arange(v) >= cfg.vocab_size, -1e30, logits)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(li, 0)[:, None], axis=-1)[:, 0]
        mask = (li >= 0).astype(jnp.float32)
        nll_sum, n = carry
        return (nll_sum + jnp.sum((logz - gold) * mask),
                n + jnp.sum(mask)), None

    (nll, n), _ = jax.lax.scan(
        chunk_loss, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, lc))
    return nll / jnp.maximum(n, 1.0)
