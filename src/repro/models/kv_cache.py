"""First-class KV-cache API: dense / ring / paged backends, one protocol.

Before this module the serving stack passed KV state around as untyped
``{k, v[, k_s, v_s]}`` dicts with per-path placement closures: the ring
(sliding-window) wrap logic was smeared across ``attention.decode_step``,
``attention.prefill_step`` and ``Model.prefill``, and the ServeEngine
could only admit a request by splicing whole contiguous cache rows.  The
cache layer is now a small protocol implemented by registered-pytree
dataclasses:

``CacheSlots``
    The slot-management half, shared by every per-layer cache (including
    the SSM conv/SSD state): ``prefill_view`` / ``admit`` / ``free_slot``
    — what the continuous-batching engine needs to move one slot's state
    in and out of the batch without knowing the layout.

``KVCache`` (DenseCache | RingCache | PagedCache)
    The attention half: where rows land (``write_token`` /
    ``write_prompt``), how they read back as contraction operands
    (``token_view`` / ``context``).  All *math* (RoPE, masked flash
    attention, scale folding) stays in ``models/attention.py``; the
    backend only answers layout questions.

Backends:

* :class:`DenseCache` — contiguous ``[B, W, H, hd]`` rows, slot = pos.
* :class:`RingCache` — sliding-window ring of ``window`` slots
  (slot = pos % W), absorbing the wrap placement and the scattered-slot
  validity mask that used to live in ``attention.py``.
* :class:`PagedCache` — fixed-size pages in a shared pool plus per-slot
  int32 block tables (vLLM-style).  The DECODE read is in place:
  ``token_view`` returns a :class:`PagedView` (pool + table + per-page
  scales) that ``attention.decode_step`` hands to the paged-attention
  kernel (``repro.kernels.paged_attention``), which streams pages in
  table order — the gathered [B, max_len] KV copy that used to be
  materialized per decode step is gone, and decode stays bit-identical
  to :class:`DenseCache` (page 0 is a reserved null page; unallocated
  table entries point at it and are masked out).  Chunked prefill keeps
  the pages-covering-prefix gather (``context``), whose cost is
  O(prompt), not O(pool).  int8-KV scales are stored per page alongside
  the values.  Admission allocates pages instead of copying rows, and a
  freed slot returns its pages to the pool — the data-reuse-through-
  indirection move EN-T makes at the MAC level, applied to cache slots.

Every class is a frozen dataclass registered with
``jax.tree_util.register_dataclass``: instances flow through ``jit`` /
``scan`` / ``vmap`` like the dicts they replace, with layout constants
(page size, window) riding as static metadata.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import ops as paged_ops

DEFAULT_PAGE_SIZE = 16


class PagedView(NamedTuple):
    """In-place decode read: the page pools AS STORED plus the block
    table — what the paged-attention kernel consumes.  Returned by
    ``PagedCache.token_view`` in place of the row backends' gathered
    ``(k, v, k_s, v_s, valid)`` operands; masking (pos / start / null
    page) happens inside the kernel from the same [B] vectors."""

    k: jax.Array                  # [P, H, page, hd] pool
    v: jax.Array
    k_s: jax.Array | None         # [P, H, page, 1] per-row scales
    v_s: jax.Array | None
    block_table: jax.Array        # [B, pages_per_slot] int32
    page_size: int
    layer: jax.Array | None = None   # pools stacked [G, ..]: the layer read


def _register(meta=()):
    """Class decorator: register a cache dataclass as a jax pytree with
    ``meta`` as static fields and everything else as data leaves."""
    def reg(cls):
        fields = [f.name for f in dataclasses.fields(cls)]
        jax.tree_util.register_dataclass(
            cls, data_fields=[f for f in fields if f not in meta],
            meta_fields=list(meta))
        return cls
    return reg


# --- placement / quantization primitives -------------------------------------

def quantize_kv(t):
    """[B, S, H, hd] -> (int8 values, bf16 per-(slot, head) scale)."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def token_update(c, new, slot, per_seq: bool):
    """Write [B, S, ...] rows at ``slot .. slot+S-1`` (decode / verify
    burst; S=1 is the plain decode tick)."""
    new = new.astype(c.dtype)
    if per_seq:  # one write index per sequence (serving slots)
        return jax.vmap(
            lambda cb, nb, sb: jax.lax.dynamic_update_slice_in_dim(
                cb, nb, sb, 0))(c, new, slot)
    return jax.lax.dynamic_update_slice_in_dim(c, new, slot, 1)


def burst_valid(pos_b, start_b, s: int, w: int):
    """[B, S, W] causal validity for a multi-token verify burst over a
    position-ordered width-``w`` view: query t (absolute position
    ``pos_b + t``) sees columns ``start_b .. pos_b + t`` — exactly the
    mask ``s`` successive decode ticks would apply, stacked."""
    qpos = pos_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [B, S]
    col = jnp.arange(w, dtype=jnp.int32)[None, None, :]
    return ((col <= qpos[:, :, None])
            & (col >= start_b[:, None, None]))


def prompt_update(c, new, pos0: int, ring: bool):
    """Write [B, S, ...] rows at slots ``pos0 .. pos0+S-1`` (prefill).

    ``pos0`` is a static chunk offset; with ``ring`` the slots wrap
    modulo the cache width (sliding-window chunked prefill).  A chunk
    longer than the ring (S > W) laps itself: only the last W rows are
    still visible, so the overwritten prefix is dropped up front — the
    old scatter-with-duplicate-indices write had unspecified order and
    could keep a stale lap's rows.
    """
    s, w = new.shape[1], c.shape[1]
    new = new.astype(c.dtype)
    if ring and s > w:                  # multi-wrap: keep the last W rows
        new = new[:, s - w:]
        pos0, s = pos0 + (s - w), w
    if not ring or pos0 + s <= w:       # contiguous, no wrap
        return jax.lax.dynamic_update_slice_in_dim(c, new, pos0, 1)
    idx = (pos0 + np.arange(s)) % w     # static wrapped slot indices
    return c.at[:, idx].set(new)


# --- protocol ----------------------------------------------------------------

class CacheSlots:
    """Slot-management protocol: how the serving engine moves ONE slot's
    state in and out of a batched cache.

    These methods run on the ENGINE's view of the cache, where every
    array leaf carries the model's ``[G]`` layer-group axis in front
    (``[G, B, ...]``): the batch axis of a stacked leaf is axis 1.
    """

    def prefill_view(self, slot):
        """A single-slot cache for admission prefill.  Row-based
        backends return zeroed state; PagedCache returns a live view of
        the shared pool restricted to ``slot``'s block-table row, so the
        admission prefill writes pages through with no copy at all;
        SSMCache returns the slot's own recurrent state."""
        del slot
        return jax.tree.map(
            lambda c: jnp.zeros(c.shape[:1] + (1,) + c.shape[2:], c.dtype),
            self)

    def admit(self, one, slot):
        """Merge a prefilled single-slot cache back at ``slot``."""
        return jax.tree.map(
            lambda f, n: jax.lax.dynamic_update_slice_in_dim(
                f, n.astype(f.dtype), slot, 1), self, one)

    def free_slot(self, slot):
        """Drop ``slot``'s state (no-op for row backends: stale rows are
        masked by pos/start; PagedCache unmaps the block-table row)."""
        del slot
        return self

    def clone(self):
        """Deep device copy of every array leaf — the SNAPSHOT view.

        The serving jits donate their cache arguments (in-place pool
        updates), which invalidates the donated buffers: a snapshot that
        merely aliased the live leaves would die with the first
        post-snapshot tick.  ``clone`` materializes fresh buffers, so
        ``ServeEngine.snapshot()/restore()`` can roll a failed tick back
        to the last consistent boundary any number of times."""
        return jax.tree.map(jnp.copy, self)


class KVCache(CacheSlots):
    """Attention-cache protocol on top of :class:`CacheSlots`.

    Layout contract (unstacked, as seen inside one layer's serving
    step): the *logical* kv view is ``width`` rows per sequence in
    position-or-slot order.  ``token_view`` returns the decode read in
    one of two protocols — row backends (dense/ring) hand back
    ``(k, v, k_s, v_s, valid)`` operands in storage layout
    ``[B, W, H, *]`` plus a ``[B, W]`` validity mask; the paged backend
    hands back a :class:`PagedView` (pool + block table, consumed
    in place by the paged-attention kernel).  ``context`` returns
    ``[B, ctx, H, *]`` operands for chunked prefill on every backend.
    ``window`` is the attention sliding window the backend implies
    (ring only) — dense/paged carry no window mask.
    """

    window: int | None = None

    #: ``context(pos0)`` may be satisfied from the POST-write cache: a
    #: prompt chunk writes positions ``[pos0, pos0+S)``, disjoint from
    #: the retained context ``[0, pos0)`` (dense rows / paged pages), so
    #: the read-back is bit-identical and the pre-write pool keeps a
    #: single use — the in-place chunk write needs no pool-sized copy.
    #: The ring backend wraps chunk writes onto the very slots its
    #: earliest queries still attend to and must gather BEFORE writing.
    context_after_write = True

    @property
    def quantized(self) -> bool:
        return self.k_s is not None

    @property
    def width(self) -> int:
        """Logical kv view length (slots per sequence)."""
        return self.k.shape[-3]

    def _stored(self, k, v):
        """k/v rows in storage form: (k, v, k_s, v_s), quantized when
        the cache is int8 (scales None otherwise)."""
        if self.k_s is not None:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            return kq, vq, ks, vs
        return k.astype(self.k.dtype), v.astype(self.v.dtype), None, None

    def _write(self, upd, k, v):
        """Apply placement ``upd(leaf, new) -> leaf`` to every leaf,
        quantizing k/v en route when the cache is int8.  Returns the new
        cache plus the freshly written values in storage form (the
        operand views prefill contracts against, bit-identical to
        reading the written slots back without the round-trip)."""
        kc, vc, ks, vs = self._stored(k, v)
        new = replace(self, k=upd(self.k, kc), v=upd(self.v, vc))
        if ks is not None:
            new = replace(new, k_s=upd(self.k_s, ks), v_s=upd(self.v_s, vs))
        return new, kc, vc, ks, vs

    # .. speculative decoding ..
    #
    # A verify burst writes K+1 rows at per-slot positions
    # (``write_tokens``) and reads them back through a position-ordered
    # full-width view with a per-query mask (``verify_view``) — the
    # multi-token twin of ``write_token``/``token_view``.  Rolling a
    # rejected draft back is a LAYOUT operation, not a data one: rows
    # past the accepted position are invisible to every masked read and
    # are rewritten before the position is reached, so row backends roll
    # back by resetting the engine's ``pos`` vector alone; the paged
    # backend additionally snapshots its block table (``fork``) so page
    # mappings can be restored (``rollback``).

    def write_tokens(self, k, v, pos):
        """Write ``S`` rows per sequence at positions ``pos .. pos+S-1``
        (``pos``: [B] int32; k/v: [B, S, H, hd]).  Parity contract:
        bit-identical to S sequential ``write_token`` calls."""
        raise NotImplementedError

    def verify_view(self, pos_b, start_b, s: int):
        """Multi-query read for a verify burst: position-ordered
        ``(k, v, k_s, v_s, valid)`` operands with ``valid`` [B, S, W] —
        query t masked exactly like the decode tick at ``pos_b + t``."""
        raise NotImplementedError

    def fork(self):
        """Speculative checkpoint taken BEFORE a verify burst writes;
        returns the snapshot ``rollback`` restores (None for row
        backends — see the protocol note above)."""
        return None

    def rollback(self, snap):
        """Restore a ``fork`` snapshot after rejected drafts.  Row
        backends: no-op (the engine's ``pos`` reset is the rollback)."""
        del snap
        return self

    # subclasses: write_token / token_view / write_prompt / context


@_register()
@dataclass(frozen=True)
class DenseCache(KVCache):
    """Contiguous [B, W, H, hd] rows; slot = absolute position."""

    k: jax.Array
    v: jax.Array
    k_s: jax.Array | None = None   # [B, W, H, 1] bf16 scales (int8 KV)
    v_s: jax.Array | None = None

    def write_token(self, k, v, pos, per_seq: bool):
        new, *_ = self._write(
            lambda c, n: token_update(c, n, pos, per_seq), k, v)
        return new

    def write_tokens(self, k, v, pos):
        # slot = position: the decode row write already takes [B, S, ...]
        new, *_ = self._write(
            lambda c, n: token_update(c, n, pos, per_seq=True), k, v)
        return new

    def token_view(self, pos_b, start_b):
        b, w = pos_b.shape[0], self.width
        idx = jnp.arange(w)[None, :]
        slot_pos = jnp.broadcast_to(idx, (b, w))
        valid = ((slot_pos >= 0) & (slot_pos <= pos_b[:, None])
                 & (slot_pos >= start_b[:, None]))
        return self.k, self.v, self.k_s, self.v_s, valid

    def verify_view(self, pos_b, start_b, s: int):
        valid = burst_valid(pos_b, start_b, s, self.width)
        return self.k, self.v, self.k_s, self.v_s, valid

    def write_prompt(self, k, v, pos0: int):
        s, w = k.shape[1], self.width
        if pos0 + s > w:
            raise ValueError(
                f"prefill chunk [{pos0}, {pos0 + s}) exceeds cache width {w}")
        return self._write(
            lambda c, n: prompt_update(c, n, pos0, ring=False), k, v)

    def context(self, pos0: int):
        """Rows [pos0-ctx, pos0) in position order, gathered BEFORE the
        chunk write (ring chunk writes may evict exactly the slots the
        earliest queries still attend to).  Returns
        (k, v, k_s, v_s, ctx_len); Nones at pos0 == 0."""
        if pos0 == 0:
            return None, None, None, None, 0
        sl = lambda c: None if c is None else c[:, :pos0]
        return sl(self.k), sl(self.v), sl(self.k_s), sl(self.v_s), pos0


@_register(meta=("window",))
@dataclass(frozen=True)
class RingCache(KVCache):
    """Sliding-window ring of W = min(max_len, window) slots; slot =
    pos % W.  Owns the wrap placement and the scattered-slot validity
    mask that previously lived inline in ``attention.py``."""

    k: jax.Array
    v: jax.Array
    k_s: jax.Array | None = None
    v_s: jax.Array | None = None
    window: int = 0                # attention window (static metadata)
    context_after_write = False    # wrap writes can evict context slots

    def write_token(self, k, v, pos, per_seq: bool):
        slot = pos % self.width
        new, *_ = self._write(
            lambda c, n: token_update(c, n, slot, per_seq), k, v)
        return new

    def write_tokens(self, k, v, pos):
        b, s = k.shape[:2]
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        slots = (pos_b[:, None]
                 + jnp.arange(s, dtype=jnp.int32)[None, :]) % self.width
        new, *_ = self._write(
            lambda c, n: jax.vmap(lambda cb, nb, ib: cb.at[ib].set(nb))(
                c, n, slots), k, v)
        return new

    def verify_view(self, pos_b, start_b, s: int):
        # A burst write EVICTS ring rows that the burst's earlier queries
        # still attend to (slot = pos % W aliases past and future), and a
        # rolled-back ``pos`` would re-interpret surviving future rows as
        # stale past positions — there is no mask that makes a
        # multi-token verify over the ring match sequential decode.
        # Sliding-window models serve speculation-free.
        raise ValueError(
            "RingCache does not support speculative verify bursts: a "
            "K-token write evicts window rows earlier burst queries "
            "need, and rollback cannot restore them (slot = pos % W). "
            "Serve sliding-window models with plain decode.")

    def token_view(self, pos_b, start_b):
        b, w = pos_b.shape[0], self.width
        idx = jnp.arange(w)[None, :]
        # absolute position held by each ring slot
        slot_pos = pos_b[:, None] - ((pos_b[:, None] - idx) % w)
        valid = ((slot_pos >= 0) & (slot_pos <= pos_b[:, None])
                 & (slot_pos >= start_b[:, None])
                 & (slot_pos > pos_b[:, None] - self.window))
        return self.k, self.v, self.k_s, self.v_s, valid

    def write_prompt(self, k, v, pos0: int):
        s, w = k.shape[1], self.width
        if s > w:
            raise ValueError(
                f"prefill chunk length {s} exceeds ring width {w}; use "
                "chunked prefill (Model.prefill splits prompts beyond the "
                "ring width)")
        return self._write(
            lambda c, n: prompt_update(c, n, pos0, ring=True), k, v)

    def context(self, pos0: int):
        ctx = min(pos0, self.width)
        if ctx == 0:
            return None, None, None, None, 0
        idx = (np.arange(pos0 - ctx, pos0)) % self.width
        sl = lambda c: None if c is None else c[:, idx]
        return sl(self.k), sl(self.v), sl(self.k_s), sl(self.v_s), ctx


@_register(meta=("page_size",))
@dataclass(frozen=True)
class PagedCache(KVCache):
    """Fixed-size pages + per-slot block tables over a shared pool.

    ``k``/``v``: ``[P, H, page, hd]`` page pools (page 0 reserved as the
    null page) — head-major, so one (page, head) tile is a contiguous
    ``[page, hd]`` slab, the block shape the TPU kernel streams;
    ``k_s``/``v_s``: ``[P, H, page, 1]`` scale pools for int8 KV;
    ``block_table``: ``[B, pages_per_slot]`` int32 page ids (0 =
    unmapped).  Decode reads are IN PLACE: ``token_view`` hands the pool
    + table to the paged-attention kernel as a :class:`PagedView`
    (table order is position order, so the logical view — and the
    serving math — stays bit-identical to :class:`DenseCache` without
    ever materializing it); chunked-prefill ``context`` still gathers
    the pages covering the prefix.  Writes scatter into the owning
    page.  Slot admission and release move page *indices*, never rows.

    ``layer`` set (an int32 scalar) marks a decode tick's view of one
    layer of the model's stack: the pools stay the whole stack
    ``[G, P, H, page, *]`` and only the block table is the layer's.
    ``write_token`` then writes the layer's rows where they lie
    (``paged_ops.paged_write``, in place on TPU) and ``token_view``
    hands the stack and the layer to the kernel, so the tick never
    slices a pool out of the stack or writes one back
    (``Model.decode_step``).  Only those two are defined on such a view.
    """

    k: jax.Array
    v: jax.Array
    block_table: jax.Array
    k_s: jax.Array | None = None
    v_s: jax.Array | None = None
    page_size: int = DEFAULT_PAGE_SIZE
    layer: jax.Array | None = None

    @property
    def width(self) -> int:
        return self.block_table.shape[-1] * self.page_size

    def _gather(self, c):
        """[P, H, page, *] pool -> [B, W, H, *] position-ordered view."""
        return _rows(c, self.block_table)

    def write_token(self, k, v, pos, per_seq: bool):
        del per_seq  # the page scatter is per-sequence by construction
        b = k.shape[0]
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        pp, off = pos_b // self.page_size, pos_b % self.page_size
        pid = jnp.take_along_axis(self.block_table, pp[:, None], axis=1)[:, 0]
        if self.layer is None:
            new, *_ = self._write(
                lambda c, n: c.at[pid, :, off].set(n[:, 0]), k, v)
            return new
        names = ("k", "v", "k_s", "v_s")
        rows = [r for r in self._stored(k, v) if r is not None]
        pools = paged_ops.paged_write(
            [getattr(self, n) for n in names[:len(rows)]],
            [r[:, 0] for r in rows], pid, off, self.layer)
        return replace(self, **dict(zip(names, pools)))

    def write_tokens(self, k, v, pos):
        b, s = k.shape[:2]
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        cols = pos_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        off = cols % self.page_size                           # [B, S]
        pid = jnp.take_along_axis(self.block_table,
                                  cols // self.page_size, axis=1)
        new, *_ = self._write(lambda c, n: c.at[pid, :, off].set(n), k, v)
        return new

    def verify_view(self, pos_b, start_b, s: int):
        # burst read = the gathered position-ordered view (the verify
        # dispatch is matmul-shaped; the in-place kernel stays the
        # single-query decode path)
        valid = burst_valid(pos_b, start_b, s, self.width)
        sl = lambda c: None if c is None else self._gather(c)
        return sl(self.k), sl(self.v), sl(self.k_s), sl(self.v_s), valid

    def fork(self):
        """Block-table snapshot: rollback must restore page MAPPINGS
        (a verify burst may have crossed into pages the accepted prefix
        never reached), not page contents — rejected rows are masked and
        rewritten exactly like a dense cache's."""
        return self.block_table

    def rollback(self, snap):
        return replace(self, block_table=snap)

    def token_view(self, pos_b, start_b):
        """In-place decode read: pool + table, NO gathered copy.  The
        kernel masks unmapped (null-page) columns and positions outside
        [start, pos] from the same vectors the row backends bake into
        ``valid``."""
        del pos_b, start_b   # masked in-kernel
        return PagedView(self.k, self.v, self.k_s, self.v_s,
                         self.block_table, self.page_size, self.layer)

    def gather_view(self, pos_b, start_b):
        """The pre-kernel read: pages gathered into a position-ordered
        [B, W] copy + explicit validity mask (the row backends' operand
        contract).  Kept as the parity/benchmark baseline the in-place
        kernel is measured against."""
        b, w = pos_b.shape[0], self.width
        idx = jnp.arange(w)[None, :]
        slot_pos = jnp.broadcast_to(idx, (b, w))
        # unmapped tail pages hold positions > pos: masked by causality,
        # exactly like a dense cache's unwritten rows
        valid = ((slot_pos >= 0) & (slot_pos <= pos_b[:, None])
                 & (slot_pos >= start_b[:, None]))
        sl = lambda c: None if c is None else self._gather(c)
        return sl(self.k), sl(self.v), sl(self.k_s), sl(self.v_s), valid

    def write_prompt(self, k, v, pos0: int):
        s, w = k.shape[1], self.width
        if pos0 + s > w:
            raise ValueError(
                f"prefill chunk [{pos0}, {pos0 + s}) exceeds paged cache "
                f"width {w}")
        cols = pos0 + np.arange(s)
        off = jnp.asarray(cols % self.page_size, jnp.int32)
        pid = self.block_table[:, cols // self.page_size]     # [B, S]
        off_b = jnp.broadcast_to(off[None, :], pid.shape)
        return self._write(lambda c, n: c.at[pid, :, off_b].set(n), k, v)

    def context(self, pos0: int):
        if pos0 == 0:
            return None, None, None, None, 0
        # gather only the pages covering [0, pos0): chunked prefill cost
        # stays O(pos0), not O(pool width)
        bt = self.block_table[:, :-(-pos0 // self.page_size)]
        sl = lambda c: None if c is None else _rows(c, bt)[:, :pos0]
        return sl(self.k), sl(self.v), sl(self.k_s), sl(self.v_s), pos0

    # .. engine slot management: indices move, rows don't ..
    def prefill_view(self, slot):
        return replace(self, block_table=jax.lax.dynamic_slice_in_dim(
            self.block_table, slot, 1, axis=-2))

    def admit(self, one, slot):
        # the view wrote straight through the shared pool; adopting its
        # pools IS the admission — only indices ever moved
        del slot
        return replace(self, k=one.k, v=one.v, k_s=one.k_s, v_s=one.v_s)

    def free_slot(self, slot):
        return replace(self, block_table=jnp.asarray(
            self.block_table).at[..., slot, :].set(0))

    def with_table(self, table):
        """Adopt the engine allocator's host-side block-table mirror
        ([B, pages_per_slot] int32 page ids) wholesale — one dispatch
        regardless of how many slots changed."""
        bt = self.block_table
        return replace(self, block_table=jnp.broadcast_to(
            table.astype(bt.dtype), bt.shape))

    def copy_pages(self, src, dst):
        """Copy whole pages ``src[i] -> dst[i]`` in every pool (K, V and
        the int8 scale pools) — the device half of copy-on-write: the
        engine copies a still-shared page to a fresh one and remaps the
        writing slot's table BEFORE the write lands, so the other
        holders' bytes never change.  ``src``/``dst``: [n] int32 page
        ids.  Works on both per-layer pools ([P, H, page, hd]) and the
        engine's group-stacked leaves ([G, P, H, page, hd]) — the page
        axis is indexed from the right."""
        cp = lambda c: None if c is None else (
            c.at[..., dst, :, :, :].set(c[..., src, :, :, :]))
        return replace(self, k=cp(self.k), v=cp(self.v),
                       k_s=cp(self.k_s), v_s=cp(self.v_s))


@_register()
@dataclass(frozen=True)
class SSMCache(CacheSlots):
    """Mamba-2 per-slot state (conv window [B, W-1, C] + SSD state
    [B, H, P, N]).  Joins the slot protocol so the engine moves SSM and
    attention state through one code path — no layer-type special cases."""

    conv: jax.Array
    ssd: jax.Array

    def prefill_view(self, slot):
        """The slot's live state: a prefill chunk that resumes a prompt
        carries it on.  (A prompt's first chunk starts from zero:
        ``transformer._layer_prefill`` resets it at position 0.)"""
        return jax.tree.map(
            lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1), self)


def _rows(pool, table):
    """[P, H, page, *] pool + [B, n] page ids -> [B, n*page, H, *] rows in
    table (= position) order — the row backends' operand layout."""
    g = pool[table].swapaxes(2, 3)              # [B, n, page, H, *]
    return g.reshape((g.shape[0], -1) + g.shape[3:])


def paged_init(batch: int, max_len: int, kv_heads: int, head_dim: int,
               dtype, *, quantized: bool = False,
               page_size: int = DEFAULT_PAGE_SIZE, pages: int | None = None,
               mapped: bool = True) -> PagedCache:
    """Build a PagedCache.  ``pages`` sizes the pool (default: full
    provisioning, batch * pages_per_slot); ``mapped=False`` starts every
    block table unmapped (engine-managed allocation), else slot ``b``
    owns pages ``1 + b*pps .. 1 + (b+1)*pps - 1`` (identity mapping — a
    drop-in DenseCache replacement for model-level use)."""
    pps = max(1, math.ceil(max_len / page_size))
    npages = batch * pps if pages is None else pages
    if mapped and npages < batch * pps:
        raise ValueError(f"identity mapping needs {batch * pps} pages, "
                         f"pool has {npages}")
    shape = (npages + 1, kv_heads, page_size, head_dim)  # +1: null page 0
    if mapped:
        table = 1 + np.arange(batch * pps, dtype=np.int32).reshape(batch, pps)
    else:
        table = np.zeros((batch, pps), np.int32)
    kw = {}
    if quantized:
        kw = {"k_s": jnp.zeros(shape[:-1] + (1,), jnp.bfloat16),
              "v_s": jnp.zeros(shape[:-1] + (1,), jnp.bfloat16)}
        dtype = jnp.int8
    return PagedCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                      block_table=jnp.asarray(table), page_size=page_size,
                      **kw)
