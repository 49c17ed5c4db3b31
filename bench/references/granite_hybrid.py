"""Plain reference of a Mamba-2 / attention hybrid with muP scalars
(IBM Granite-4.0-H, ``model_type`` ``granitemoehybrid``, without experts).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks; the recurrence runs token by token.  It imports nothing
of the program under test.  The weights are drawn here, from the seed,
by ``layer_weights`` and ``embedding``; the harness hands the same draws
to the program in the program's own layout.  The int8 arithmetic of the
projections (``mode``) and the seed's key are those of ``dense.py``.

Layer ``i`` is a Mamba-2 mixer or a grouped-query attention layer, as
``layer_types[i]`` says, each followed by a SwiGLU MLP; every branch is
scaled by ``residual_multiplier`` before its residual add::

    a  = x + r . mixer(n1(x))
    x' = a + r . Wd . (silu(Wg . n2(a)) * Wu . n2(a))

Mamba-2 mixer (heads H of width P, state N, G groups, conv width W)::

    z | xBC | dt = Win . h
    xBC = silu(conv_W(xBC) + b)          causal depthwise, within a sequence
    x | B | C = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t     S [H, P, N], from 0
    y_t = S_t C_t + D x_t                 head h reads group h // (H / G)
    out = Wout . (gate_norm(y * silu(z)))     RMSNorm over H * P

The state and the conv window start from zero at each sequence's first
token.  Attention has no position embedding (NoPE) and the softmax
scale ``attention_multiplier``.  Embeddings are multiplied by
``embedding_multiplier``; the logits, read through ``head()``, are
divided by ``logits_scaling``; the head is the embedding, tied.

``mode`` says in what arithmetic the projections (in/out of each mixer,
q/k/v/o, the MLP) run: ``"f32"``, ``"w8a8"`` (the EN-T semantics) or
``"w4a8"`` (the control), as in ``dense.py``.  The conv, A, D,
``dt_bias``, the norms, the embedding and the head are never quantized.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

import spec

_dense = spec.reader("references", "dense", os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
base_key = _dense.base_key
_rms, _proj = _dense._rms, _dense._proj

MAMBA, ATTENTION = "mamba", "attention"
# std of the drawn token embedding.  Scaled by ``embedding_multiplier``
# (x12), a larger one outweighs the 80 residual branches at the tied
# head, whose row for the input token then scores |e|^2 against
# ~sqrt(d)|e|^2/45 for the others: greedy decoding echoes one token for
# ever, where every precision agrees (at 0.02, six served runs on a TPU
# v5e read gaps of 0 for the program and for the w4a8 control alike).
EMB_STD = 0.004


def sizes(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    vocab = cfg["vocab_size"]
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {"d": cfg["hidden_size"], "hq": h, "hkv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or cfg["hidden_size"] // h,
            "ff": cfg.get("shared_intermediate_size")
            or cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "types": types,
            "vocab": vocab, "vocab_padded": -(-vocab // 256) * 256,
            "eps": cfg["rms_norm_eps"], "tied": bool(cfg["tie_word_embeddings"]),
            "nope": cfg["position_embedding_type"] == "nope",
            "heads": heads, "p": p, "n": n, "g": g, "w": cfg["mamba_d_conv"],
            "inner": heads * p, "conv_dim": heads * p + 2 * g * n,
            "in_proj": 2 * heads * p + 2 * g * n + heads,
            "conv_bias": bool(cfg["mamba_conv_bias"]),
            "emb_mult": float(cfg["embedding_multiplier"]),
            "res_mult": float(cfg["residual_multiplier"]),
            "attn_scale": float(cfg["attention_multiplier"]),
            "logits_scaling": float(cfg["logits_scaling"])}


def layer_weights(cfg: dict, key, layer, kind: str | None = None):
    """Layer ``layer``'s weights, drawn from ``key`` alone.  ``kind``
    (``"mamba"`` or ``"attention"``) is ``layer_types[layer]`` unless
    given (it must be, where ``layer`` is traced).  Projections are
    ``[in, out]`` bf16 with std ``in ** -0.5``; norm scales, the conv
    bias and D ``1 + 0.1 N`` or ``0.1 N``; A in [1, 16] and dt in
    [1e-3, 0.1], log-uniform, as Mamba-2 initializes them."""
    s = sizes(cfg)
    kind = kind or s["types"][layer]
    d, ff = s["d"], s["ff"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1),
                                             layer), 12)
    bf = lambda a: a.astype(jnp.bfloat16)
    nrm = lambda k, shape, std: bf(jax.random.normal(k, shape) * std)
    one = lambda k, shape: bf(1 + 0.1 * jax.random.normal(k, shape))
    w = {"norm": one(ks[0], (d,)), "mlp_norm": one(ks[1], (d,)),
         "wg": nrm(ks[2], (d, ff), d ** -0.5),
         "wu": nrm(ks[3], (d, ff), d ** -0.5),
         "wd": nrm(ks[4], (ff, d), ff ** -0.5)}
    if kind == ATTENTION:
        q, kv = s["hq"] * s["hd"], s["hkv"] * s["hd"]
        w.update(wq=nrm(ks[5], (d, q), d ** -0.5),
                 wk=nrm(ks[6], (d, kv), d ** -0.5),
                 wv=nrm(ks[7], (d, kv), d ** -0.5),
                 wo=nrm(ks[8], (q, d), q ** -0.5))
        return w
    h, inner, cd = s["heads"], s["inner"], s["conv_dim"]
    u = jax.random.uniform(ks[9], (2, h))
    dt = jnp.exp(np.log(1e-3) + u[1] * (np.log(0.1) - np.log(1e-3)))
    w.update(in_proj=nrm(ks[5], (d, s["in_proj"]), d ** -0.5),
             conv=nrm(ks[6], (s["w"], cd), s["w"] ** -0.5),
             a_log=jnp.log(1 + 15 * u[0]),
             dt_bias=bf(dt + jnp.log(-jnp.expm1(-dt))),
             d_skip=one(ks[10], (h,)),
             gate_norm=one(ks[11], (inner,)),
             out_proj=nrm(ks[8], (inner, d), inner ** -0.5))
    if s["conv_bias"]:
        w["conv_bias"] = nrm(ks[7], (cd,), 0.1)
    return w


def embedding(cfg: dict, key):
    """(embedding [Vp, d] of std ``EMB_STD``, final norm scale [d]); the
    padding rows past the vocabulary are zero."""
    s = sizes(cfg)
    ke, kn = jax.random.split(jax.random.fold_in(key, 2), 2)
    rows = jnp.arange(s["vocab_padded"])[:, None] < s["vocab"]
    emb = jnp.where(rows, jax.random.normal(ke, (s["vocab_padded"], s["d"]))
                    * EMB_STD, 0.0).astype(jnp.bfloat16)
    norm = (1 + 0.1 * jax.random.normal(kn, (s["d"],))).astype(jnp.bfloat16)
    return emb, norm


def _mlp(s, mode, w, x):
    f32 = lambda a: a.astype(jnp.float32)
    h = _rms(x, f32(w["mlp_norm"]), s["eps"])
    g, u = _proj(h, w["wg"], mode), _proj(h, w["wu"], mode)
    return _proj(jax.nn.silu(g) * u, w["wd"], mode)


def _attention(s, mode, w, h, seg, block: int = 512):
    """NoPE grouped-query attention of packed rows h [n, T, d]: a token
    attends the tokens of its own sequence up to itself."""
    n, t, _ = h.shape
    q = _proj(h, w["wq"], mode).reshape(n, t, s["hq"], s["hd"])
    k = _proj(h, w["wk"], mode).reshape(n, t, s["hkv"], s["hd"])
    v = _proj(h, w["wv"], mode).reshape(n, t, s["hkv"], s["hd"])
    group = s["hq"] // s["hkv"]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    idx = jnp.arange(t)

    def attend(b):
        lo = b * block
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        sb = jax.lax.dynamic_slice_in_dim(seg, lo, block, axis=1)
        ib = jax.lax.dynamic_slice_in_dim(idx, lo, block)
        sc = jnp.einsum("nqhd,nkhd->nhqk", qb, k) * s["attn_scale"]
        ok = ((sb[:, :, None] == seg[:, None, :])
              & (ib[:, None] >= idx[None, :])[None])
        sc = jnp.where(ok[:, None], sc, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(attend, jnp.arange(t // block))
    o = jnp.moveaxis(o, 0, 1).reshape(n, t, s["hq"] * s["hd"])
    return _proj(o, w["wo"], mode)


def _mamba(s, mode, w, h, seg):
    """The Mamba-2 mixer over packed rows h [n, T, d], restarted (state
    and conv window zero) at each sequence's first token."""
    f32 = lambda a: a.astype(jnp.float32)
    n, t, _ = h.shape
    heads, p, gn, inner = s["heads"], s["p"], s["g"] * s["n"], s["inner"]
    zxbcdt = _proj(h, w["in_proj"], mode)
    z, xbc, dt = jnp.split(zxbcdt, [inner, inner + s["conv_dim"]], -1)
    # causal depthwise conv: tap k reads the token k back, if it is of
    # the same sequence
    conv = jnp.zeros_like(xbc)
    for k in range(s["w"]):
        back = jnp.pad(xbc, ((0, 0), (k, 0), (0, 0)))[:, :t]
        same = jnp.pad(seg, ((0, 0), (k, 0)), constant_values=-2)[:, :t] == seg
        conv = conv + jnp.where(same[..., None], back, 0.0) * f32(
            w["conv"][s["w"] - 1 - k])
    if "conv_bias" in w:
        conv = conv + f32(w["conv_bias"])
    xbc = jax.nn.silu(conv)
    x, b, c = jnp.split(xbc, [inner, inner + gn], -1)
    x = x.reshape(n, t, heads, p)
    rep = heads // s["g"]
    b = jnp.repeat(b.reshape(n, t, s["g"], s["n"]), rep, axis=2)
    c = jnp.repeat(c.reshape(n, t, s["g"], s["n"]), rep, axis=2)
    dt = jax.nn.softplus(dt + f32(w["dt_bias"]))                 # [n, T, H]
    a = -jnp.exp(f32(w["a_log"]))
    first = jnp.concatenate([jnp.ones((n, 1), bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)

    def step(state, inp):
        x_t, b_t, c_t, dt_t, first_t = inp
        state = jnp.where(first_t[:, None, None, None], 0.0, state)
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("nhpk,nhk->nhp", state, c_t)

    tm = lambda arr: jnp.moveaxis(arr, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((n, heads, p, s["n"])),
                        (tm(x), tm(b), tm(c), tm(dt), tm(first)))
    y = tm(y) + x * f32(w["d_skip"])[:, None]
    y = y.reshape(n, t, inner) * jax.nn.silu(z)
    y = _rms(y, f32(w["gate_norm"]), s["eps"])
    return _proj(y, w["out_proj"], mode)


def layer(cfg: dict, kind: str, mode: str, w, x, pos, seg):
    """One layer over packed rows x [n, T, d] f32; ``seg`` [n, T] says
    which sequence each token belongs to (``pos`` is not read: no
    position embedding, and the recurrence restarts where ``seg``
    changes).  T is a multiple of 512."""
    del pos
    s = sizes(cfg)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm"].astype(jnp.float32), s["eps"])
        y = (_attention(s, mode, w, h, seg) if kind == ATTENTION
             else _mamba(s, mode, w, h, seg))
        x = x + s["res_mult"] * y
        return x + s["res_mult"] * _mlp(s, mode, w, x)


class Reference:
    """The reference forward for one configuration and seed, layer by
    layer so that only one layer's weights live at a time."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.s = cfg, sizes(cfg)
        self.key = base_key(seed)
        # the layer index is traced: one compile serves every layer
        self._layer_w = jax.jit(functools.partial(layer_weights, cfg),
                                static_argnums=(2,))
        self._layer = jax.jit(functools.partial(layer, cfg),
                              static_argnums=(0, 1))
        self._embed = jax.jit(functools.partial(embedding, cfg))
        self._final = jax.jit(lambda x, scale: _rms(
            x, scale.astype(jnp.float32), self.s["eps"]))

    def hidden(self, tokens, pos, seg, mode: str):
        """Final-normed hidden states [n, T, d] f32 of packed token rows."""
        emb, norm = self._embed(self.key)
        x = emb.astype(jnp.float32)[jnp.asarray(tokens)] * self.s["emb_mult"]
        del emb
        pos, seg = jnp.asarray(pos), jnp.asarray(seg)
        for i, kind in enumerate(self.s["types"]):
            x = self._layer(kind, mode, self._layer_w(self.key, i, kind), x,
                            pos, seg)
        return self._final(x, norm)

    def head(self):
        """The output projection [d, vocab] f32 over the real vocabulary,
        divided by ``logits_scaling``: hidden states times it are the
        model's logits."""
        emb, _ = self._embed(self.key)
        return (emb.T[:, : self.s["vocab"]].astype(jnp.float32)
                / self.s["logits_scaling"])
