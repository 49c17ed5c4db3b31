"""Program adapter of a Mamba-2 / attention hybrid with muP scalars
(Granite-4.0-H): ``layer_types`` repeats with a period, each layer a
Mamba-2 mixer or NoPE grouped-query attention, then a SwiGLU MLP.

It pairs with the plain reference of its own name
(``references/<this file's name>.py``): the program is built from the
configuration's published keys as the reference reads them, and handed
the reference's weight draws in the program's own parameter layout (one
layer group per period of ``layer_types``).  ``spec.py`` lists what an
adapter gives.  The engine serves it without a prefix cache: a
recurrent state cannot be resumed from shared pages.

Work is counted as ``work.py`` says.  Projections: ``2 *`` the layers'
matmul parameters a token (each mixer's in and out projections, each
attention layer's q/k/v/o, every MLP).  The rest, in bf16: each
attention layer's ``4 * ctx * Hq * hd``; each mixer's conv
(``2 * W * conv_dim``) and state update (``6 * H * P * N``: decay,
outer product and its add, the C contraction) a token; the tied LM head
where a token is yielded.  ``ssd_decode_work`` counts one call of the
``ssd_decode_step`` kernel for ``ssd_decode_roofline``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

import spec
from repro.configs.base import ModelConfig, SSMConfig

_HERE = os.path.abspath(__file__)
ref = spec.reader("references", os.path.splitext(os.path.basename(_HERE))[0],
                  os.path.dirname(os.path.dirname(os.path.dirname(_HERE))))

# ServeEngine's own choice for a model with SSM layers
PREFIX_CACHE = False


def period(cfg: dict) -> tuple:
    """The shortest repeating unit of ``layer_types``: one layer group."""
    types = ref.sizes(cfg)["types"]
    return next(tuple(types[:n]) for n in range(1, len(types) + 1)
                if types == types[:n] * (len(types) // n))


def model_config(cfg: dict) -> ModelConfig:
    """The program's config from a configuration file's published keys."""
    s = ref.sizes(cfg)
    if s["inner"] % s["d"] or not s["conv_bias"]:
        raise ValueError("the program's Mamba-2 mixer has a conv bias and "
                         "mamba_n_heads * mamba_d_head a multiple of "
                         "hidden_size (its expand factor)")
    group = tuple(("attn" if k == ref.ATTENTION else "ssm", "dense")
                  for k in period(cfg))
    return ModelConfig(
        name=cfg["name"], family="hybrid", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["hq"], num_kv_heads=s["hkv"],
        head_dim=s["hd"], d_ff=s["ff"], vocab_size=s["vocab"],
        norm_eps=s["eps"], rope=not s["nope"], attn_scale=s["attn_scale"],
        embedding_multiplier=s["emb_mult"],
        residual_multiplier=s["res_mult"],
        logits_scaling=s["logits_scaling"], tie_embeddings=s["tied"],
        ssm=SSMConfig(state_dim=s["n"], head_dim=s["p"],
                      expand=s["inner"] // s["d"], ngroups=s["g"],
                      conv_width=s["w"]),
        group=group, param_dtype="bfloat16", compute_dtype="bfloat16")


def make_params(model, cfg: dict, seed: int):
    """The reference's weight draws for ``seed`` in the program's layout,
    made on the device in one jitted call."""
    s = ref.sizes(cfg)
    kinds = period(cfg)
    groups = s["layers"] // len(kinds)

    def layer(w, kind):
        rec = lambda k: {"kernel": w[k]}
        p = {"mixer_norm": {"scale": w["norm"]},
             "ffn_norm": {"scale": w["mlp_norm"]},
             "ffn": {"wi_gate": rec("wg"), "wi_up": rec("wu"),
                     "wo": rec("wd")}}
        if kind == ref.ATTENTION:
            p["mixer"] = {k: rec(k) for k in ("wq", "wk", "wv", "wo")}
        else:
            p["mixer"] = {"in_proj": rec("in_proj"), "conv": w["conv"],
                          "a_log": w["a_log"], "dt_bias": w["dt_bias"],
                          "d_skip": w["d_skip"],
                          "gate_norm": {"scale": w["gate_norm"]},
                          "conv_bias": w["conv_bias"],
                          "out_proj": rec("out_proj")}
        return p

    @jax.jit
    def make(key):
        stack = tuple(
            layer(jax.vmap(lambda g, j=j, k=k: ref.layer_weights(
                cfg, key, g * len(kinds) + j, k))(jnp.arange(groups)), k)
            for j, k in enumerate(kinds))
        emb, norm = ref.embedding(cfg, key)
        return {"embed": {"embedding": emb}, "final_norm": {"scale": norm},
                "groups": stack}

    key = ref.base_key(seed)
    want = jax.eval_shape(model.init, key)
    got = jax.eval_shape(make, key)
    shapes = lambda t: [(a.shape, a.dtype) for a in jax.tree.leaves(t)]
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or shapes(want) != shapes(got)):
        raise ValueError("the reference's weights do not fit the program's "
                         "parameter layout")
    return jax.block_until_ready(make(key))


def query_heads(cfg: dict) -> int:
    """Query heads of each paged attention call."""
    return ref.sizes(cfg)["hq"]


def _layer_counts(cfg: dict) -> tuple[int, int]:
    types = ref.sizes(cfg)["types"]
    n_attn = sum(t == ref.ATTENTION for t in types)
    return n_attn, len(types) - n_attn


def layer_matmul_params(cfg: dict) -> int:
    """Matmul parameters of all the layers together."""
    s = ref.sizes(cfg)
    d = s["d"]
    n_attn, n_mamba = _layer_counts(cfg)
    attn = d * s["hq"] * s["hd"] * 2 + 2 * d * s["hkv"] * s["hd"]
    mamba = d * s["in_proj"] + s["inner"] * d
    return n_attn * attn + n_mamba * mamba + s["layers"] * 3 * d * s["ff"]


def _mixer_ops(cfg: dict) -> float:
    """bf16 ops of one token through every mixer: conv and state update."""
    s = ref.sizes(cfg)
    _, n_mamba = _layer_counts(cfg)
    return n_mamba * (2.0 * s["w"] * s["conv_dim"]
                      + 6.0 * s["heads"] * s["p"] * s["n"])


def token_ops(cfg: dict, ctx: int, head: bool) -> tuple[float, float]:
    """(matmul ops, bf16-only ops) of one token at context ``ctx``."""
    s = ref.sizes(cfg)
    n_attn, _ = _layer_counts(cfg)
    mm = 2.0 * layer_matmul_params(cfg)
    other = 4.0 * ctx * s["hq"] * s["hd"] * n_attn + _mixer_ops(cfg)
    if head:
        other += 2.0 * s["d"] * s["vocab"]
    return mm, other


def prefill_ops(cfg: dict, lo: int, n: int, head: bool) -> tuple[float, float]:
    """(matmul ops, bf16 ops) of prefilling positions [lo, lo + n): each
    attends every position up to itself."""
    s = ref.sizes(cfg)
    n_attn, _ = _layer_counts(cfg)
    mm = 2.0 * layer_matmul_params(cfg) * n
    ctx = n * lo + n * (n + 1) / 2
    other = 4.0 * ctx * s["hq"] * s["hd"] * n_attn + _mixer_ops(cfg) * n
    if head:
        other += 2.0 * s["d"] * s["vocab"]
    return mm, other


def ssd_decode_work(cfg: dict, slots: int) -> tuple[float, float]:
    """(ops, bytes) of one ``ssd_decode_step`` call (one mixer, one tick)
    for ``slots`` decoding slots: each slot's fp32 state read and
    written, its x, B, C, dt read and y written (fp32), and the update's
    ``6 * H * P * N`` operations."""
    s = ref.sizes(cfg)
    h, p, n, g = s["heads"], s["p"], s["n"], s["g"]
    per_slot = 4.0 * (2 * h * p * n + 2 * h * p + 2 * g * n + h)
    return slots * 6.0 * h * p * n, slots * per_slot
