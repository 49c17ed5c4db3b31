"""Public ops: SSD scan and decode step with backend dispatch."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan import ssd_decode
from repro.kernels.ssd_scan.ssd_scan import ssd_scan
from repro.kernels.ssd_scan.ref import (  # noqa: F401
    ssd_decode_step_ref, ssd_scan_chunked, ssd_scan_ref)


def ssd(x, dt, a, b, c, *, chunk: int = 128, use_kernel: str = "auto"):
    if use_kernel == "auto":
        if jax.default_backend() == "tpu":
            use_kernel = "pallas"
        else:  # chunked jnp: same algorithm, per-chunk (not per-step) state
            use_kernel = "chunked" if x.shape[1] > chunk else "ref"
    if use_kernel == "ref":
        return ssd_scan_ref(x, dt, a, b, c)
    if use_kernel == "chunked":
        return ssd_scan_chunked(x, dt, a, b, c, chunk=chunk)
    return ssd_scan(x, dt, a, b, c, chunk=chunk,
                    interpret=(use_kernel == "interpret"))


def ssd_decode_step(state, x, dt, a, b, c, group, *, mask=None,
                    use_kernel: str = "auto"):
    """One decode step of layer group ``group`` (int32 scalar) of the
    model's stacked SSD state ``state`` [L, B, H, P, N]: (the whole new
    stack, y [B, H, P]).  Other shapes as ``ssd_decode_step_ref``;
    ``mask`` ([B] bool, optional) keeps the state of the slots it leaves
    out.  ``auto`` runs the Pallas kernel on TPU (the group's rows
    updated in place) and the oracle elsewhere."""
    if use_kernel == "auto":
        use_kernel = "pallas" if jax.default_backend() == "tpu" else "ref"
    if use_kernel == "ref":
        st = jax.lax.dynamic_index_in_dim(state, group, 0, False)
        new, y = ssd_decode_step_ref(st, x, dt, a, b, c)
        if mask is not None:
            new = jnp.where(mask[:, None, None, None], new, st)
        return jax.lax.dynamic_update_index_in_dim(state, new, group, 0), y
    if mask is None:
        mask = jnp.ones(x.shape[:1], jnp.int32)
    return ssd_decode.ssd_decode_step(state, x, dt, a, b, c, mask, group,
                                      interpret=(use_kernel == "interpret"))
