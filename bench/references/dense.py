"""Plain reference of a dense decoder-only transformer (Qwen2 / MiniCPM / Llama).

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no cache, no batching tricks.  It imports nothing of the
program under test and takes nothing the program made.  The weights are
drawn here, from the seed, by ``layer_weights`` and ``embedding``; the
harness hands the same draws to the program in the program's own
layout, so both sides serve one model.

Per layer (pre-norm, RMSNorm, rotary embedding on half-split pairs,
grouped-query attention with query head h reading key/value head
``h // (Hq / Hkv)``, SwiGLU)::

    a = x + Wo . attn(rope(Wq . n1(x) + bq), rope(Wk . n1(x) + bk), Wv . n1(x) + bv)
    x' = a + Wd . (silu(Wg . n2(a)) * Wu . n2(a))

and ``logits = n(x) . E^T`` with the embedding tied to the head (or a
head of its own).  Departure for MiniCPM, on both sides alike: its muP
scalars (``scale_emb``, ``scale_depth``, ``dim_model_base``) are not
applied.

``mode`` says in what arithmetic the layers' projections run:

* ``"f32"``: the weights as drawn (bf16 values) in float32;
* ``"w8a8"``: the EN-T semantics: each weight column quantized to int8
  by its absolute maximum, each activation row likewise, integer
  products, scales applied after;
* ``"w4a8"``: as ``w8a8`` with 4-bit weights (the control of an int8
  configuration).

The embedding, the head and the norms are never quantized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
_WEIGHT_BITS = {"w8a8": 8, "w4a8": 4}


def base_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def sizes(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    vocab = cfg["vocab_size"]
    return {"d": cfg["hidden_size"], "hq": h, "hkv": cfg["num_key_value_heads"],
            "hd": hd, "ff": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": vocab,
            "vocab_padded": -(-vocab // 256) * 256,
            "bias": bool(cfg.get("qkv_bias")),
            "tied": bool(cfg.get("tie_word_embeddings")),
            "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"]}


def layer_weights(cfg: dict, key, layer):
    """Layer ``layer``'s weights (bf16), drawn from ``key`` alone, so one
    layer can be drawn without the others.  Projections are ``[in, out]``
    with std ``in ** -0.5``; norm scales ``1 + 0.1 N``; biases ``0.1 N``."""
    s = sizes(cfg)
    d, q, kv, ff = s["d"], s["hq"] * s["hd"], s["hkv"] * s["hd"], s["ff"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1),
                                             layer), 12)
    bf = lambda a: a.astype(jnp.bfloat16)
    nrm = lambda k, shape, std: bf(jax.random.normal(k, shape) * std)
    w = {"attn_norm": bf(1 + 0.1 * jax.random.normal(ks[0], (d,))),
         "wq": nrm(ks[1], (d, q), d ** -0.5),
         "wk": nrm(ks[2], (d, kv), d ** -0.5),
         "wv": nrm(ks[3], (d, kv), d ** -0.5),
         "wo": nrm(ks[4], (q, d), q ** -0.5),
         "mlp_norm": bf(1 + 0.1 * jax.random.normal(ks[5], (d,))),
         "wg": nrm(ks[6], (d, ff), d ** -0.5),
         "wu": nrm(ks[7], (d, ff), d ** -0.5),
         "wd": nrm(ks[8], (ff, d), ff ** -0.5)}
    if s["bias"]:
        w.update(bq=nrm(ks[9], (q,), 0.1), bk=nrm(ks[10], (kv,), 0.1),
                 bv=nrm(ks[11], (kv,), 0.1))
    return w


def embedding(cfg: dict, key):
    """(embedding [Vp, d], final norm scale [d], head [d, Vp] or None);
    the padding rows past the vocabulary are zero."""
    s = sizes(cfg)
    ke, kn, kh = jax.random.split(jax.random.fold_in(key, 2), 3)
    rows = jnp.arange(s["vocab_padded"])[:, None] < s["vocab"]
    emb = jnp.where(rows, jax.random.normal(ke, (s["vocab_padded"], s["d"]))
                    * 0.02, 0.0).astype(jnp.bfloat16)
    norm = (1 + 0.1 * jax.random.normal(kn, (s["d"],))).astype(jnp.bfloat16)
    head = None
    if not s["tied"]:
        head = jnp.where(rows.T, jax.random.normal(
            kh, (s["d"], s["vocab_padded"])) * s["d"] ** -0.5,
            0.0).astype(jnp.bfloat16)
    return emb, norm, head


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x [n, T, H, hd]: rotate (first half, second half) pairs."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = positions[:, :, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _proj(x, w, mode):
    w = w.astype(jnp.float32)
    if mode == "f32":
        return jnp.matmul(x, w, precision=HI)
    qmax = 2.0 ** (_WEIGHT_BITS[mode] - 1) - 1
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / qmax
    wq = jnp.clip(jnp.round(w / sw), -qmax, qmax)
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12) / 127
    xq = jnp.clip(jnp.round(x * (1.0 / sx)), -127, 127)
    return jnp.matmul(xq, wq, precision=HI) * sx * sw


def layer(cfg: dict, mode: str, w, x, pos, seg, block: int = 512):
    """One layer over packed rows x [n, T, d] f32: ``pos`` [n, T] is each
    token's position in its own sequence and ``seg`` [n, T] which
    sequence it belongs to; a token attends the tokens of its sequence up
    to itself.  Queries run in blocks of ``block`` (T is a multiple)."""
    s = sizes(cfg)
    n, t, _ = x.shape
    f32 = lambda a: a.astype(jnp.float32)
    h = _rms(x, f32(w["attn_norm"]), s["eps"])
    q = _proj(h, w["wq"], mode)
    k = _proj(h, w["wk"], mode)
    v = _proj(h, w["wv"], mode)
    if s["bias"]:
        q, k, v = q + f32(w["bq"]), k + f32(w["bk"]), v + f32(w["bv"])
    q = _rope(q.reshape(n, t, s["hq"], s["hd"]), pos, s["theta"])
    k = _rope(k.reshape(n, t, s["hkv"], s["hd"]), pos, s["theta"])
    v = v.reshape(n, t, s["hkv"], s["hd"])
    group = s["hq"] // s["hkv"]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    idx = jnp.arange(t)

    def attend(b):
        lo = b * block
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        sb = jax.lax.dynamic_slice_in_dim(seg, lo, block, axis=1)
        ib = jax.lax.dynamic_slice_in_dim(idx, lo, block)
        sc = jnp.einsum("nqhd,nkhd->nhqk", qb, k,
                        precision=HI) / np.sqrt(s["hd"])
        ok = ((sb[:, :, None] == seg[:, None, :])
              & (ib[:, None] >= idx[None, :])[None])
        sc = jnp.where(ok[:, None], sc, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    o = jax.lax.map(attend, jnp.arange(t // block))      # [t/b, n, b, H, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(n, t, s["hq"] * s["hd"])
    x = x + _proj(o, w["wo"], mode)
    h = _rms(x, f32(w["mlp_norm"]), s["eps"])
    g = _proj(h, w["wg"], mode)
    u = _proj(h, w["wu"], mode)
    return x + _proj(jax.nn.silu(g) * u, w["wd"], mode)


class Reference:
    """The reference forward for one configuration and seed, layer by
    layer so that only one layer's weights live at a time."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.s = cfg, sizes(cfg)
        self.key = base_key(seed)
        self._layer_w = jax.jit(functools.partial(layer_weights, cfg))
        self._layer = jax.jit(functools.partial(layer, cfg),
                              static_argnums=(0,))
        self._embed = jax.jit(functools.partial(embedding, cfg))
        self._final = jax.jit(lambda x, scale: _rms(
            x, scale.astype(jnp.float32), self.s["eps"]))

    def hidden(self, tokens, pos, seg, mode: str):
        """Final-normed hidden states [n, T, d] f32 of packed token rows."""
        emb, norm, _ = self._embed(self.key)
        x = emb.astype(jnp.float32)[jnp.asarray(tokens)]
        del emb
        pos, seg = jnp.asarray(pos), jnp.asarray(seg)
        for i in range(self.s["layers"]):
            x = self._layer(mode, self._layer_w(self.key, i), x, pos, seg)
        return self._final(x, norm)

    def head(self):
        """The output projection [d, vocab] f32 over the real vocabulary."""
        emb, _, head = self._embed(self.key)
        kernel = emb.T if head is None else head
        return kernel[:, : self.s["vocab"]].astype(jnp.float32)
