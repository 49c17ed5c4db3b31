"""Mamba-2 (SSD) mixer layer: in_proj -> causal conv -> SSD scan -> gated out.

Follows the Mamba-2 block: a single input projection produces
[z | x | B | C | dt]; x/B/C pass through a depthwise causal conv; the SSD
scan (Pallas kernel on TPU, recurrence oracle on CPU) evolves the [P, N]
state per head; output is RMS-norm-gated by z and projected back.

Decode carries (conv_state [B, W-1, conv_dim], ssd_state [B, H, P, N]) —
O(1) in sequence length, which is what makes the long_500k cell feasible.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan.ref import ssd_decode_step_ref
from repro.models import layers as L
from repro.models.kv_cache import SSMCache


def dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.ngroups * s.state_dim
    return d_inner, nheads, conv_dim


def init(key, cfg: ModelConfig):
    s = cfg.ssm
    d_inner, nheads, conv_dim = dims(cfg)
    ks = jax.random.split(key, 5)
    pd = L.pdtype(cfg)
    d_in_proj = 2 * d_inner + 2 * s.ngroups * s.state_dim + nheads
    import numpy as np
    dt = jnp.exp(jax.random.uniform(
        ks[2], (nheads,), minval=float(np.log(s.dt_min)),
        maxval=float(np.log(s.dt_max))))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    return {
        "in_proj": L.dense_init(ks[0], cfg, cfg.d_model, d_in_proj),
        "conv": (jax.random.normal(ks[1], (s.conv_width, conv_dim)) *
                 (s.conv_width**-0.5)).astype(pd),
        "conv_bias": jnp.zeros((conv_dim,), pd),
        "a_log": jnp.log(jnp.arange(1, nheads + 1, dtype=jnp.float32)),
        "dt_bias": jnp.asarray(dt_bias, pd),
        "d_skip": jnp.ones((nheads,), pd),
        "gate_norm": {"scale": jnp.ones((d_inner,), pd)},
        "out_proj": L.dense_init(ks[4], cfg, d_inner, cfg.d_model,
                                 scale=d_inner**-0.5),
    }


def _split(cfg: ModelConfig, zxbcdt):
    s = cfg.ssm
    d_inner, nheads, _ = dims(cfg)
    gn = s.ngroups * s.state_dim
    z, xin, bmat, cmat, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + gn, 2 * d_inner + 2 * gn], -1)
    return z, xin, bmat, cmat, dt


def _gated_out(cfg, p, y_flat, z):
    # RMSNorm(y * silu(z)) gating, Mamba-2 convention
    g = y_flat * jax.nn.silu(z.astype(jnp.float32)).astype(y_flat.dtype)
    g32 = g.astype(jnp.float32)
    ms = jnp.mean(jnp.square(g32), -1, keepdims=True)
    g = (g32 * jax.lax.rsqrt(ms + cfg.norm_eps)
         * p["gate_norm"]["scale"].astype(jnp.float32)).astype(L.cdtype(cfg))
    return L.dense_apply(p["out_proj"], g, L.cdtype(cfg))


def apply(cfg: ModelConfig, p, x):
    """Full-sequence forward.  x: [B, S, D]."""
    s = cfg.ssm
    b, slen, _ = x.shape
    d_inner, nheads, conv_dim = dims(cfg)
    dtype = L.cdtype(cfg)

    zxbcdt = L.dense_apply(p["in_proj"], x, dtype)
    z, xin, bmat, cmat, dtt = _split(cfg, zxbcdt)

    # depthwise causal conv over [x | B | C]
    xbc = jnp.concatenate([xin, bmat, cmat], -1)             # [B, S, conv_dim]
    pad = jnp.zeros((b, s.conv_width - 1, conv_dim), xbc.dtype)
    xbc_pad = jnp.concatenate([pad, xbc], 1)
    windows = jnp.stack(
        [xbc_pad[:, i:i + slen] for i in range(s.conv_width)], axis=-1)
    xbc = jnp.einsum("bsdw,wd->bsd", windows.astype(jnp.float32),
                     p["conv"].astype(jnp.float32))
    xbc = jax.nn.silu(xbc + p["conv_bias"].astype(jnp.float32)).astype(dtype)
    xin, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + s.ngroups * s.state_dim], -1)

    xh = xin.reshape(b, slen, nheads, s.head_dim)
    bm = bmat.reshape(b, slen, s.ngroups, s.state_dim)
    cm = cmat.reshape(b, slen, s.ngroups, s.state_dim)
    dt_soft = jax.nn.softplus(dtt.astype(jnp.float32)
                              + p["dt_bias"].astype(jnp.float32))  # [B,S,H]
    a = -jnp.exp(p["a_log"].astype(jnp.float32))                   # [H] < 0

    y = ssd_ops.ssd(xh.astype(jnp.float32), dt_soft, a,
                    bm.astype(jnp.float32), cm.astype(jnp.float32))
    y = y + xh.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y_flat = y.reshape(b, slen, d_inner).astype(dtype)
    return _gated_out(cfg, p, y_flat, z)


# --- Decode ------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, dtype) -> SSMCache:
    s = cfg.ssm
    d_inner, nheads, conv_dim = dims(cfg)
    return SSMCache(
        conv=jnp.zeros((batch, s.conv_width - 1, conv_dim), dtype),
        ssd=jnp.zeros((batch, nheads, s.head_dim, s.state_dim), jnp.float32))


def decode_step(cfg: ModelConfig, p, x, cache, pos, group, token_mask=None):
    """One-token decode.  x: [B, 1, D] -> (y [B, 1, D], new cache).

    ``cache.ssd`` is the model's whole stack of layer groups
    ``[L, B, H, P, N]``, of which group ``group`` (int32 scalar) steps,
    and comes back whole: the state is updated where it lies, never
    sliced out and written back.  ``cache.conv`` is this layer's own.

    ``token_mask`` ([B] bool, optional): False entries are not decoded
    — their conv window and SSD state are left untouched.  Left pads
    of a ragged prompt take this path (they precede every real token,
    so the window they leave is the zero window a padding-free sequence
    starts from), as do the serving slots a decode tick does not
    advance: one parked mid-admission, whose next prefill chunk resumes
    its state, and idle slots.
    """
    del pos  # SSM state is position-free
    s = cfg.ssm
    b = x.shape[0]
    d_inner, nheads, conv_dim = dims(cfg)
    dtype = L.cdtype(cfg)

    zxbcdt = L.dense_apply(p["in_proj"], x[:, 0], dtype)     # [B, d_in_proj]
    z, xin, bmat, cmat, dtt = _split(cfg, zxbcdt)

    xbc = jnp.concatenate([xin, bmat, cmat], -1)             # [B, conv_dim]
    if token_mask is not None:
        xbc = xbc * token_mask[:, None].astype(xbc.dtype)
    hist = jnp.concatenate([cache.conv, xbc[:, None]], 1)  # [B, W, conv_dim]
    conv_out = jnp.einsum("bwd,wd->bd", hist.astype(jnp.float32),
                          p["conv"].astype(jnp.float32))
    conv_out = jax.nn.silu(conv_out + p["conv_bias"].astype(jnp.float32)).astype(dtype)
    new_conv = hist[:, 1:]
    if token_mask is not None:  # pad or frozen slot: the window stays
        new_conv = jnp.where(token_mask[:, None, None], new_conv, cache.conv)
    xin, bmat, cmat = jnp.split(conv_out, [d_inner, d_inner + s.ngroups * s.state_dim], -1)

    xh = xin.reshape(b, nheads, s.head_dim).astype(jnp.float32)
    bm = bmat.reshape(b, s.ngroups, s.state_dim).astype(jnp.float32)
    cm = cmat.reshape(b, s.ngroups, s.state_dim).astype(jnp.float32)
    dt_soft = jax.nn.softplus(dtt.astype(jnp.float32)
                              + p["dt_bias"].astype(jnp.float32))  # [B,H]
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    # the state update: the Pallas kernel on TPU (in place; slots that
    # ``token_mask`` leaves out keep their state), the oracle elsewhere
    new_ssd, y = ssd_ops.ssd_decode_step(cache.ssd, xh, dt_soft, a, bm, cm,
                                         group, mask=token_mask)
    y = y + xh * p["d_skip"].astype(jnp.float32)[None, :, None]
    y_flat = y.reshape(b, 1, d_inner).astype(dtype)
    out = _gated_out(cfg, p, y_flat, z[:, None])
    return out, SSMCache(conv=new_conv, ssd=new_ssd)


def prefill_step(cfg: ModelConfig, p, x, cache, mask=None):
    """Whole-prompt forward with cache write-through: the batched twin of
    ``decode_step``.  x: [B, S, D] -> (y [B, S, D], new cache).

    The projections and the depthwise conv are computed for all S
    positions at once; only the [B, H, P, N] state recurrence runs as a
    ``lax.scan`` over time, using the SAME per-step update as decode —
    which makes the final (conv, ssd) cache bit-identical to stepping the
    prompt token by token.  ``mask`` ([B, S] bool, True = real token)
    handles left-padded ragged batches exactly like ``token_mask`` in
    decode, and tail pads (a serving chunk padded to its window): pad
    columns contribute zeros to the conv window and leave the SSD state
    untouched, and the conv window returned ends at the last real token.
    """
    out, new, _ = _scan_core(cfg, p, x, cache, mask, collect=False)
    return out, new


def verify_step(cfg: ModelConfig, p, x, cache):
    """Speculative-verify burst: the same write-through scan as
    ``prefill_step``, additionally emitting EVERY per-step post-state —
    an SSM has no position vector to roll back, so accept/rollback must
    SELECT the state after the last accepted token.  x: [B, S, D] ->
    (y [B, S, D], cache after all S steps, states :class:`SSMCache` with
    leaves [B, S, ...]: ``states[b, t]`` is the (conv, ssd) state after
    feeding token t — ``select_state(states, n_acc)`` restores it)."""
    return _scan_core(cfg, p, x, cache, None, collect=True)


def select_state(states: SSMCache, sel) -> SSMCache:
    """Pick each sequence's post-state at step ``sel[b]`` from verify's
    stacked states ([B, S, ...] leaves) — the SSM rollback primitive."""
    def take(st):
        idx = sel.reshape((-1,) + (1,) * (st.ndim - 1))
        return jnp.take_along_axis(st, idx, axis=1)[:, 0]
    return SSMCache(conv=take(states.conv), ssd=take(states.ssd))


def _scan_core(cfg: ModelConfig, p, x, cache, mask, collect: bool):
    s = cfg.ssm
    b, slen, _ = x.shape
    d_inner, nheads, conv_dim = dims(cfg)
    dtype = L.cdtype(cfg)

    zxbcdt = L.dense_apply(p["in_proj"], x, dtype)           # [B, S, d_in_proj]
    z, xin, bmat, cmat, dtt = _split(cfg, zxbcdt)

    xbc = jnp.concatenate([xin, bmat, cmat], -1)             # [B, S, conv_dim]
    if mask is not None:
        xbc = xbc * mask[..., None].astype(xbc.dtype)
    hist = jnp.concatenate([cache.conv.astype(xbc.dtype), xbc], 1)
    if mask is None:
        new_conv = hist[:, slen:]                            # last W-1 columns
    else:
        # the W-1 columns ending at each row's last real token: left
        # pads come before it, tail pads (a chunk padded to its window)
        # after it, and neither may enter the window
        end = slen - jnp.argmax(mask[:, ::-1], axis=1)
        new_conv = jax.vmap(lambda h, e: jax.lax.dynamic_slice_in_dim(
            h, e, s.conv_width - 1, 0))(hist, end)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    # The conv contraction, split and SSD update run per timestep inside
    # the scan with the SAME operand shapes as decode_step — any batched
    # reformulation (e.g. one [B, S] conv einsum) changes XLA's reduction
    # fusion and breaks bit-identity with the sequential path.  ``hist``
    # is closed over and sliced per step: O(1) extra memory, no [S, B, W]
    # window stack.
    def step(h, inp):
        t, dtt_t, mt = inp    # scalar step index, [B, nheads], [B]
        w_t = jax.lax.dynamic_slice_in_dim(hist, t, s.conv_width, axis=1)
        conv_out = jnp.einsum("bwd,wd->bd", w_t.astype(jnp.float32),
                              p["conv"].astype(jnp.float32))
        conv_out = jax.nn.silu(
            conv_out + p["conv_bias"].astype(jnp.float32)).astype(dtype)
        xin_t, bm_t, cm_t = jnp.split(
            conv_out, [d_inner, d_inner + s.ngroups * s.state_dim], -1)
        xh_t = xin_t.reshape(b, nheads, s.head_dim).astype(jnp.float32)
        bm_t = bm_t.reshape(b, s.ngroups, s.state_dim).astype(jnp.float32)
        cm_t = cm_t.reshape(b, s.ngroups, s.state_dim).astype(jnp.float32)
        dt_soft = jax.nn.softplus(dtt_t.astype(jnp.float32)
                                  + p["dt_bias"].astype(jnp.float32))
        h2, yt = ssd_decode_step_ref(h, xh_t, dt_soft, a, bm_t, cm_t)
        if mask is not None:
            h2 = jnp.where(mt[:, None, None, None], h2, h)
        yt = yt + xh_t * p["d_skip"].astype(jnp.float32)[None, :, None]
        return h2, (yt, h2) if collect else yt

    tmask = (jnp.ones((b, slen), bool) if mask is None else mask)
    new_ssd, ys = jax.lax.scan(
        step, cache.ssd,
        (jnp.arange(slen), jnp.moveaxis(dtt, 1, 0), jnp.moveaxis(tmask, 1, 0)))
    states = None
    if collect:
        ys, hs = ys
        # conv state after step t = the window ending at hist column
        # t+W-1 — slices of the already-materialized hist, not a scan y
        conv_states = jnp.stack(
            [hist[:, t + 1:t + s.conv_width] for t in range(slen)],
            axis=1).astype(cache.conv.dtype)              # [B, S, W-1, C]
        states = SSMCache(conv=conv_states,
                          ssd=jnp.moveaxis(hs, 0, 1))     # [B, S, H, P, N]
    y_flat = jnp.moveaxis(ys, 0, 1).reshape(b, slen, d_inner).astype(dtype)
    out = _gated_out(cfg, p, y_flat, z)
    new = SSMCache(conv=new_conv.astype(cache.conv.dtype), ssd=new_ssd)
    return out, new, states
