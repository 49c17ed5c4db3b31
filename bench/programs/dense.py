"""Program adapter of a dense decoder-only transformer (Qwen2 / MiniCPM /
Llama): every layer grouped-query attention plus a SwiGLU MLP.

It pairs with the plain reference of its own name
(``references/<this file's name>.py``): the program is built from the
configuration's published keys as the reference reads them, and handed
the reference's weight draws in the program's own parameter layout.
``spec.py`` lists what an adapter gives.

Work is counted as ``work.py`` says: a token's projections (``2 *`` the
layers' matmul parameters), and in bf16 its attention over its context
(``4 * ctx * Hq * hd`` a layer) plus, where it yields a token, the LM
head (the tied head counted once).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

import spec
from repro.configs.base import ModelConfig

_HERE = os.path.abspath(__file__)
ref = spec.reader("references", os.path.splitext(os.path.basename(_HERE))[0],
                  os.path.dirname(os.path.dirname(os.path.dirname(_HERE))))

# ServeEngine's own choice for a paged model without SSM layers
PREFIX_CACHE = True


def model_config(cfg: dict) -> ModelConfig:
    """The program's config from a configuration file's published keys."""
    s = ref.sizes(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["hq"], num_kv_heads=s["hkv"],
        head_dim=s["hd"], d_ff=s["ff"], vocab_size=s["vocab"],
        qkv_bias=s["bias"], norm_eps=s["eps"], rope_theta=s["theta"],
        tie_embeddings=s["tied"], param_dtype="bfloat16",
        compute_dtype="bfloat16")


def make_params(model, cfg: dict, seed: int):
    """The reference's weight draws for ``seed`` in the program's layout,
    made on the device in one jitted call."""
    s = ref.sizes(cfg)

    @jax.jit
    def make(key):
        w = jax.vmap(lambda i: ref.layer_weights(cfg, key, i))(
            jnp.arange(s["layers"]))
        emb, norm, head = ref.embedding(cfg, key)
        rec = lambda k, b=None: ({"kernel": w[k]} if b is None
                                 else {"kernel": w[k], "bias": w[b]})
        bias = s["bias"]
        mixer = {"wq": rec("wq", "bq" if bias else None),
                 "wk": rec("wk", "bk" if bias else None),
                 "wv": rec("wv", "bv" if bias else None),
                 "wo": rec("wo")}
        p = {"embed": {"embedding": emb}, "final_norm": {"scale": norm},
             "groups": ({"mixer_norm": {"scale": w["attn_norm"]},
                         "mixer": mixer,
                         "ffn_norm": {"scale": w["mlp_norm"]},
                         "ffn": {"wi_gate": rec("wg"), "wi_up": rec("wu"),
                                 "wo": rec("wd")}},)}
        if head is not None:
            p["lm_head"] = {"kernel": head}
        return p

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


def layer_matmul_params(cfg: dict) -> int:
    s = ref.sizes(cfg)
    d, q, kv = s["d"], s["hq"] * s["hd"], s["hkv"] * s["hd"]
    return d * q + 2 * d * kv + q * d + 3 * d * s["ff"]


def token_ops(cfg: dict, ctx: int, head: bool) -> tuple[float, float]:
    """(matmul ops, bf16-only ops) of one token at context ``ctx``: the
    layers' projections, and attention plus (when ``head``) the LM head,
    which run in bf16 whatever the weight format."""
    s = ref.sizes(cfg)
    mm = 2.0 * layer_matmul_params(cfg) * s["layers"]
    other = 4.0 * ctx * s["hq"] * s["hd"] * s["layers"]
    if head:
        other += 2.0 * s["d"] * s["vocab"]
    return mm, other


def prefill_ops(cfg: dict, lo: int, n: int, head: bool) -> tuple[float, float]:
    """(matmul ops, bf16 ops) of prefilling positions [lo, lo + n): each
    attends every position up to itself."""
    s = ref.sizes(cfg)
    mm = 2.0 * layer_matmul_params(cfg) * s["layers"] * n
    ctx = n * lo + n * (n + 1) / 2
    other = 4.0 * ctx * s["hq"] * s["hd"] * s["layers"]
    if head:
        other += 2.0 * s["d"] * s["vocab"]
    return mm, other
