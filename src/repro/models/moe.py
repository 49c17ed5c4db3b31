"""Top-k MoE layer with capacity-based dispatch (GShard-style, dropless-ish).

Routing: softmax over top-k router logits (Mixtral convention).  Dispatch
uses position-in-expert computed from a cumulative sum over the token
axis, then scatter/gather into per-expert capacity buffers — this keeps
FLOPs at top_k x capacity_factor x dense-equivalent (no all-experts
densification) and shards cleanly: experts over the EP/model axis when
num_experts divides it, d_ff tensor-parallel otherwise.

Returns the load-balancing auxiliary loss (Switch formulation) alongside
the output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def init(key, cfg: ModelConfig):
    e = cfg.moe.num_experts
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    pd = L.pdtype(cfg)

    def ek(key, din, dout, scale):
        return (jax.random.normal(key, (e, din, dout)) * scale).astype(pd)

    p = {"router": L.dense_init(ks[0], cfg, d, e, scale=d**-0.5)}
    if cfg.mlp_type == "swiglu":
        p["wi_gate"] = ek(ks[1], d, f, d**-0.5)
        p["wi_up"] = ek(ks[2], d, f, d**-0.5)
        p["wo"] = ek(ks[3], f, d, f**-0.5)
    else:
        p["wi"] = ek(ks[1], d, f, d**-0.5)
        p["wo"] = ek(ks[2], f, d, f**-0.5)
    return p


def apply(cfg: ModelConfig, p, x):
    """x: [B, S, D] -> (y, aux_loss)."""
    b, s, d = x.shape
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    t = b * s
    xt = x.reshape(t, d)
    dt = L.cdtype(cfg)

    logits = L.dense_apply(p["router"], xt, jnp.float32)      # [T, E] f32
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)           # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # aux load-balance loss (Switch): E * sum_e f_e * p_e
    assign1h = jax.nn.one_hot(expert_idx[:, 0], e)            # top-1 fraction
    f_e = jnp.mean(assign1h, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e) * cfg.moe.aux_loss_coef

    capacity = int(cfg.moe.capacity_factor * t * k / e + 0.5)
    capacity = max(capacity, 1)

    # position of each (token, slot) within its expert's buffer
    flat_expert = expert_idx.reshape(-1)                      # [T*k]
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)  # [T*k, E]
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot)     # exclusive
    pos = jnp.take_along_axis(pos_in_expert, flat_expert[:, None], 1)[:, 0]
    keep = pos < capacity                                     # dropped beyond capacity

    # dispatch: scatter tokens into [E, C, D]
    tok_idx = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e, capacity, d), dt)
    safe_pos = jnp.where(keep, pos, capacity - 1)
    buf = buf.at[flat_expert, safe_pos].add(
        jnp.where(keep[:, None], xt[tok_idx].astype(dt), 0))

    # expert FFN, batched over E
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"].astype(dt))
        u = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"].astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    else:
        h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(dt))
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(dt)
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt))   # [E, C, D]

    # combine: gather each slot's result, weight by the gate
    gathered = out[flat_expert, safe_pos]                     # [T*k, D]
    gathered = jnp.where(keep[:, None], gathered, 0)
    weights = gate_vals.reshape(-1)[:, None].astype(dt)
    y = jnp.zeros((t, d), dt).at[tok_idx].add(gathered * weights)
    return y.reshape(b, s, d), aux


# --- Distributed MoE: shard_map EP x TP x DP ---------------------------------
#
# The pjit scatter/gather formulation above does not partition under GSPMD
# (data-dependent scatters replicate), so the distributed path expresses
# the parallelism manually:
#
#   * tokens stay sharded over the data axes (each block routes its own
#     T_loc tokens; routing is recomputed identically on every model rank),
#   * experts live on the model axis: rank r serves E_loc = max(E/M, 1)
#     experts; when E < M each expert is split over R = M/E ranks along
#     d_ff (EP x TP unified),
#   * dispatch is a local capacity gather (C = cf * T_loc * k / E slots),
#   * combine is ONE psum over "model": it simultaneously sums the R
#     d_ff-partials and merges different experts' outputs (non-chosen
#     experts contribute zeros).
#
# This keeps FLOPs at top_k x cf x dense-equivalent and bytes at
# O(T_loc x D) per rank — the production EP layout.

def _rank_major(w, m: int):
    """[E, i, o] -> [M, i, o/R] rank-major layout when E < M (R = M/E)."""
    e, din, dout = w.shape
    if e % m == 0:
        return w  # pure EP: block spec slices experts directly
    r = m // e
    assert m % e == 0, (e, m)
    return (w.reshape(e, din, r, dout // r)
            .transpose(0, 2, 1, 3)
            .reshape(m, din, dout // r))


def _rank_major_out(w, m: int):
    """[E, i, o] -> [M, i/R, o] for the row-parallel wo."""
    e, din, dout = w.shape
    if e % m == 0:
        return w
    r = m // e
    return (w.reshape(e, r, din // r, dout)
            .reshape(m, din // r, dout))


def apply_sharded(cfg: ModelConfig, p, x, mesh, data_axes, model_axis="model"):
    """Distributed MoE forward.  x: [B, S, D] (batch over data axes)."""
    b, s, d = x.shape
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    m = mesh.shape[model_axis]
    e_loc = max(e // m, 1)
    r = max(m // e, 1)
    dt = L.cdtype(cfg)
    da = tuple(data_axes) if isinstance(data_axes, (tuple, list)) else (data_axes,)
    d_shards = 1
    for a in da:
        d_shards *= mesh.shape[a]
    if b % d_shards != 0:      # e.g. long_500k batch=1: replicate tokens,
        da = ()                # keep experts sharded on the model axis
        d_shards = 1
    t_loc = b * s // d_shards
    cap = max(int(cfg.moe.capacity_factor * t_loc * k / e + 0.5), 1)

    def _in(w):
        # skip when already pre-laid-out rank-major (serving: done ONCE
        # at load via rank_major_params — the EN-T encode-once pattern
        # applied to layout; per-step relayout reads every expert slab)
        if w.shape[0] == m:
            return w
        return _rank_major(w, m)

    def _out(w):
        if w.shape[0] == m:
            return w
        return _rank_major_out(w, m)

    wig = _in(p["wi_gate"]) if cfg.mlp_type == "swiglu" else None
    wiu = _in(p["wi_up"]) if cfg.mlp_type == "swiglu" else None
    wi = _in(p["wi"]) if cfg.mlp_type != "swiglu" else None
    wo = _out(p["wo"])
    router = p["router"]["kernel"]

    def block(xb, wigb, wiub, wib, wob, wr):
        # xb: [B_loc, S, D]; w*b: [E_loc, d, f_loc]; wr: [D, E]
        bl = xb.shape[0]
        xt = xb.reshape(-1, d)                              # [T_loc, D]
        logits = xt.astype(jnp.float32) @ wr.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)                # [T_loc, k]
        gates = gates / jnp.sum(gates, -1, keepdims=True)

        rank = jax.lax.axis_index(model_axis)
        e0 = (rank // r) * e_loc
        cdt = jnp.dtype(cfg.moe.combine_dtype)
        out = jnp.zeros((t_loc, d), cdt)
        for j in range(e_loc):                              # static, small
            ej = e0 + j
            sel = idx == ej                                 # [T_loc, k]
            gate_e = jnp.sum(jnp.where(sel, gates, 0.0), -1)
            chose = jnp.any(sel, -1)
            pos = jnp.cumsum(chose.astype(jnp.int32)) - 1
            keep = chose & (pos < cap)
            slot = jnp.where(keep, pos, cap)                # cap = spill row
            buf = jnp.zeros((cap + 1, d), dt)
            buf = buf.at[slot].add(jnp.where(keep[:, None], xt.astype(dt), 0))
            h_in = buf[:cap]
            if cfg.mlp_type == "swiglu":
                g = h_in @ wigb[j].astype(dt)
                u = h_in @ wiub[j].astype(dt)
                h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
            else:
                h = h_in @ wib[j].astype(dt)
                h = jax.nn.gelu(h.astype(jnp.float32)).astype(dt)
            o_buf = (h @ wob[j].astype(dt)).astype(cdt)  # [cap, D]
            gathered = o_buf[jnp.minimum(pos, cap - 1)]
            out = out + (jnp.where(keep[:, None], gathered, 0)
                         * gate_e[:, None].astype(cdt))
        out = jax.lax.psum(out, model_axis)                 # merges experts + f-shards

        # Switch aux loss, computed per data shard then averaged — a
        # standard distributed variant (per-shard E[f_e * p_e] differs
        # from the global product by O(1/T_loc) shard-correlation terms;
        # both push toward balance)
        assign1h = jax.nn.one_hot(idx[:, 0], e)
        f_e = jnp.mean(assign1h, axis=0)
        p_e = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(f_e * p_e) * cfg.moe.aux_loss_coef
        if da:
            aux = jax.lax.pmean(aux, da)
        return out.reshape(bl, s, d).astype(dt), aux

    P = jax.sharding.PartitionSpec
    w_spec = P(model_axis, None, None)
    x_spec = P(da, None, None) if da else P(None, None, None)
    y, aux = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(x_spec, w_spec, w_spec, w_spec, w_spec, P(None, None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x,
      wig if wig is not None else jnp.zeros((m, 1, 1), dt),
      wiu if wiu is not None else jnp.zeros((m, 1, 1), dt),
      wi if wi is not None else jnp.zeros((m, 1, 1), dt),
      wo, router)
    return y, aux


def rank_major_params(params, m: int):
    """Pre-transform every MoE expert stack to rank-major [M, i, o/R]
    layout (serving load-time; amortized over all steps).  Walks the
    grouped params tree; leaves non-MoE nodes untouched."""
    def walk(node, under_ffn=False):
        if isinstance(node, dict):
            return {k: walk(v, under_ffn or k == "ffn") for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, under_ffn) for v in node)
        return node

    import jax

    def fix(path, leaf):
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        if "ffn" in keys and leaf.ndim == 4:      # [G, E, i, o]
            name = keys[-1]
            if name in ("wi", "wi_gate", "wi_up"):
                return jax.vmap(lambda w: _rank_major(w, m))(leaf)
            if name == "wo":
                return jax.vmap(lambda w: _rank_major_out(w, m))(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)
