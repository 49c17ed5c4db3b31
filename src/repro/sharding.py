"""Divisibility-aware logical->physical sharding rules.

Every parameter/cache/batch array gets a PartitionSpec from path-based
rules (Megatron-style TP on the ``model`` axis, DP on ``data`` — and
``("pod","data")`` when the multi-pod mesh is active).  A central
divisibility guard drops any proposed mapping whose dimension does not
divide the mesh axis, falling back to replication — this is what lets
every (arch x mesh) cell compile without per-arch hand-tuning (e.g.
MiniCPM's 36 heads don't divide model=16, but its flattened q dim
36*64=2304 does; Qwen2.5's kv=2 heads fall back to replication).

Conventions (2D: TP on "model" + FSDP/ZeRO-3 on the data axes — both
dims of every big matrix are sharded, so params + AdamW moments scale
1/num_devices; XLA inserts the FSDP all-gathers per scan step):
  column-parallel:  wq wk wv wi wi_gate wi_up in_proj  -> (data, model)
  row-parallel:     wo out_proj                        -> (model, data)
  experts [E,i,o]: EP on "model" when E | axis, FSDP on i -> (model, data, None)
                   else expert-internal TP              -> (None, data, model)
  embedding [V,D] -> (model, data);  lm_head [D,V] -> (data, model)
  norms, router, scalar vectors: replicated
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([_axis_size(mesh, a) for a in axis]))
    return mesh.shape[axis]


def _guard(spec_dims, shape, mesh: Mesh):
    """Drop mappings whose dim doesn't divide the axis size."""
    out = []
    for dim, axis in zip(shape, spec_dims):
        if axis is not None and dim % _axis_size(mesh, axis) == 0 and dim > 0:
            out.append(axis)
        else:
            out.append(None)
    return P(*out)


def data_axes(mesh: Mesh):
    """The (possibly compound) data-parallel axis spec."""
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):   # GetAttrKey: cache-backend dataclass fields
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


_COL = ("wq", "wk", "wv", "wi", "wi_gate", "wi_up", "in_proj")
_ROW = ("wo", "out_proj")


def param_pspec(path: str, shape, mesh: Mesh, profile: str = "2d") -> P:
    """PartitionSpec for one parameter given its tree path.

    Profiles (the §Perf sharding search space):
      "2d"       TP(model) x FSDP(data) — the training default.
      "fsdp"     pure data parallel over ALL axes: kernels row-sharded
                 over (data+model), no TP.  Wins when the model is small
                 relative to the mesh (TP collectives >> compute).
      "serve_tp" TP(model) only, replicated over data: weights stay
                 STATIONARY per chip — no per-step FSDP gathers, the
                 right layout for decode serving.
    """
    parts = path.split("/")
    grouped = parts and parts[0] == "groups"   # leading [G] scan axis
    nlead = 1 if grouped else 0
    name = parts[-1]
    parent = parts[-2] if len(parts) >= 2 else ""

    core = shape[nlead:]
    # quantized records (repro.quant): q/planes/scale live under the
    # projection name; q shards like the kernel, planes add a lead [4]
    # (or [2] packed) axis, scales follow the out-channel
    if name in ("q", "planes", "planes_packed") and parent in _COL + _ROW + ("lm_head",):
        extra = 0 if name == "q" else 1
        sub = param_pspec("/".join(parts[:-1]) + "/kernel",
                          shape[:nlead] + core[extra:], mesh, profile)
        return _guard((None,) * nlead + (None,) * extra + tuple(sub)[nlead:],
                      shape, mesh)
    if name == "scale" and parent in _COL + _ROW + ("lm_head",):
        ker = param_pspec("/".join(parts[:-1]) + "/kernel",
                          shape[:nlead] + (1,) + core[-1:], mesh, profile)
        return _guard((None,) * nlead + (None, tuple(ker)[-1]), shape, mesh)

    spec: tuple = (None,) * len(core)
    da = data_axes(mesh)
    if profile == "fsdp":
        all_axes = (da + ("model",)) if isinstance(da, tuple) else (da, "model")
        if name == "embedding" or (name == "kernel" and len(core) >= 2):
            spec = (all_axes,) + (None,) * (len(core) - 1)
        elif parent == "ffn" and name in _COL + _ROW and len(core) == 3:
            spec = (None, all_axes, None)
        full = (None,) * nlead + spec
        return _guard(full, shape, mesh)

    fs = None if profile == "serve_tp" else da   # FSDP axis (or stationary)

    if name == "embedding":                       # [V, D]
        spec = ("model", fs)
    elif parent == "lm_head" and name == "kernel":
        spec = (fs, "model")
    elif name == "kernel" and parent in _COL:
        spec = (fs, "model")
    elif name == "kernel" and parent in _ROW:
        spec = ("model", fs)
    elif name == "bias" and parent in _COL:
        spec = ("model",)
    elif parent == "ffn" and name in _COL and len(core) == 3:
        # MoE experts [E, din, dout]: EP when E divides, else internal TP
        e = core[0]
        if e % _axis_size(mesh, "model") == 0:
            spec = ("model", fs, None)
        else:
            spec = (None, fs, "model")
    elif parent == "ffn" and name in _ROW and len(core) == 3:
        e = core[0]
        if e % _axis_size(mesh, "model") == 0:
            spec = ("model", fs, None)
        else:
            spec = (None, "model", fs)
    elif name in ("conv", "conv_bias", "a_log", "dt_bias", "d_skip",
                  "scale", "router"):
        spec = (None,) * len(core)
    # everything else (norm scales, biases of row-parallel, ...) replicates

    full = (None,) * nlead + spec
    return _guard(full, shape, mesh)


def params_shardings(params_shapes, mesh: Mesh, profile: str = "2d"):
    """Tree of NamedShardings for a params (shape) tree."""
    def one(path, leaf):
        return NamedSharding(
            mesh, param_pspec(_path_str(path), leaf.shape, mesh, profile))
    return jax.tree_util.tree_map_with_path(one, params_shapes)


def batch_pspec(shape, mesh: Mesh) -> P:
    """Token/label/embeds batches: batch dim over (pod,)data if divisible."""
    da = data_axes(mesh)
    spec = (da,) + (None,) * (len(shape) - 1)
    return _guard(spec, shape, mesh)


def cache_pspec(path: str, shape, mesh: Mesh) -> P:
    """Decode caches.  Layout (after the [G] scan axis):

    attn k/v   [G, B, W, Hkv, hd]  -> batch on data; ring/seq on model
                                      (flash-decoding style partial softmax)
    ssm  ssd   [G, B, H, P, N]     -> batch on data; heads on model
    ssm  conv  [G, B, W-1, C]      -> batch on data
    """
    parts = path.split("/")
    name = parts[-1]
    da = data_axes(mesh)
    if name in ("k", "v", "k_s", "v_s") and len(shape) == 5:
        spec = (None, da, "model", None, None)
    elif name == "ssd" and len(shape) == 5:
        spec = (None, da, "model", None, None)
    elif name == "conv" and len(shape) == 4:
        spec = (None, da, None, None)
    elif name == "pos":
        spec = ()
    else:
        spec = (None,) * len(shape)
    return _guard(spec, shape, mesh)


def cache_shardings(cache_shapes, mesh: Mesh):
    def one(path, leaf):
        return NamedSharding(mesh, cache_pspec(_path_str(path), leaf.shape, mesh))
    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def batch_shardings(batch_shapes, mesh: Mesh):
    def one(path, leaf):
        return NamedSharding(mesh, batch_pspec(leaf.shape, mesh))
    return jax.tree_util.tree_map_with_path(one, batch_shapes)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
