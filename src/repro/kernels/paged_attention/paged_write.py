"""Pallas TPU kernel: a decode tick's KV row write, in place.

Each slot writes one new row (its token's K, V and, for int8 KV, their
per-row scales) into row ``off[b]`` of page ``page[b]`` of every pool it
is given.  The pools arrive flat, ``[G*P, H, page, X]``: the model's
stack of layer groups ``[G, P, H, page, X]`` with the group axis folded
into the page axis (a free reshape in the default layout), so a page id
here already holds the layer's offset ``g * P``.  Each output pool is
aliased to its input (``input_output_aliases``): the rows land where the
pool lies, and the compiled decode tick holds no copy, slice or
write-back of a pool (``tests/test_tpu_compile.py`` checks it).

The unit of work is one whole page per slot and pool: the grid's
pipeline brings the page in, the kernel selects the new row into it and
the page goes back.  A page is ``[H, page, X]`` with X the full minor
dim, so one block shape serves head dims of 128 and 64 and the
``[.., 1]`` scale pools alike (Mosaic cannot DMA-slice a minor dim under
128 lanes).  Slots run in order; two slots that name the same page in a
row see the first's write lost, which only the null page (page 0, never
read unmasked) can see: every other page written at decode belongs to
one slot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(page_ref, off_ref, *refs):
    del page_ref                    # read by the index maps
    n = len(refs) // 3
    rows, pages, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
    off = off_ref[pl.program_id(0)]
    for row_ref, in_ref, out_ref in zip(rows, pages, outs):
        page = in_ref[...].astype(jnp.float32)                # [H, page, X]
        at = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1) == off
        out_ref[...] = jnp.where(at, row_ref[...].astype(jnp.float32),
                                 page).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_write_kernel(pools, rows, page, off, *, interpret: bool = False):
    """pools: tuple of flat ``[G*P, H, page, X]`` pools; rows: tuple of
    ``[B, H, X]`` rows in each pool's dtype; page: [B] int32 flat page
    ids (layer offset included); off: [B] int32 row in the page.
    Returns the new pools, each in its input's buffer."""
    pools, rows = tuple(pools), tuple(rows)
    b = page.shape[0]
    n = len(pools)
    row_specs = [pl.BlockSpec((None, r.shape[1], 1, r.shape[2]),
                              lambda i, p, o: (i, 0, 0, 0)) for r in rows]
    page_specs = [pl.BlockSpec((None,) + c.shape[1:],
                               lambda i, p, o: (p[i], 0, 0, 0))
                  for c in pools]
    return tuple(pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=row_specs + page_specs,
            out_specs=page_specs),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in pools],
        input_output_aliases={2 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            # slots in order (see the module docstring on shared pages)
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_write",
    )(page.astype(jnp.int32), off.astype(jnp.int32),
      *[r.astype(c.dtype)[:, :, None, :] for r, c in zip(rows, pools)],
      *pools))
