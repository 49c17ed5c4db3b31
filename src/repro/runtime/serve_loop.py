"""Serving engine: flash prefill, chunked admission, continuous batching.

Three layers, bottom-up:

- ``make_prefill_step`` / ``make_serve_step``: the single jitted
  functions the decode_* and long_* dry-run cells lower;
  ``make_serve_programs``: the engine's masked decode ticks and
  admission chunk programs, which ``ServeEngine`` and
  ``repro.runtime.scheduler.PipelinedScheduler`` both dispatch;
- ``generate``: the end-to-end loop used by examples and tests.  The
  prompt is prefilled through the masked flash-attention cache
  write-through path (one ``model.prefill`` call; prompts longer than
  the sliding-window ring — or a ``prefill_chunk`` knob — are processed
  in fixed-size chunks), with left-padding + attention masking for
  ragged prompt batches and per-sequence EOS early-stop;
- ``ServeEngine``: a fixed-slot continuous-batching engine over the
  first-class KV-cache backends (``repro.models.kv_cache``).  Requests
  are admitted into free batch slots by prefilling the newcomer while
  the other slots keep decoding; finished slots are refilled from the
  queue.  With the default PAGED backend, admission allocates
  fixed-size pages from a shared pool and prefills straight through a
  block-table view, in windows on a fixed grid — page indices move,
  cache rows never do — and a finished request's pages return to the
  pool; the decode tick then reads those pages IN PLACE:
  ``decode_step`` hands the pool + block
  table to the paged-attention kernel (``repro.kernels.paged_attention``
  — scalar-prefetched table, per-page int8 scales dequantized
  in-kernel, null pages compute-skipped), so a tick never materializes
  the gathered [slots, max_len] KV view (the admission prefill's
  pages-covering-prefix gather only runs for chunked prompts).
  Page LIFETIME lives in ``repro.runtime.page_allocator.PageAllocator``
  (per-page refcounts, double-free/leak detection) and prompt KV is
  SHARED across requests through ``repro.runtime.prefix_cache``: a
  radix trie over page-sized token blocks maps a newcomer's longest
  cached prompt prefix straight into its block table, so admission
  prefills only the unshared suffix — O(new tokens), not O(prompt) —
  with copy-on-write protecting shared pages from divergent writes.
  Sliding-window models serve through the RING backend (absolute
  per-slot positions over a window-sized ring, prompts longer than the
  window included).
  Sampling runs ON DEVICE (``repro.runtime.sampling``): each decode
  tick is one dispatch that decodes and samples every slot, and only
  [B] int32 tokens cross back to the host — never the [B, V] logits.

With EN-T quantized params every projection in every one of these paths
runs the FUSED packed-plane matmul (repro.quant.qdense_apply): per-row
activation quant happens inside the kernel against the [2, K, N] packed
planes, so batched decode never materializes int8 activations in HBM and
issues 2 plane matmuls per layer instead of 4.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import kv_cache
from repro.models.transformer import Model
from repro.runtime import sampling
from repro.runtime.faults import InjectedFault, fault_point
from repro.runtime.metrics import SPANS
from repro.runtime.page_allocator import PageAllocator
from repro.runtime.prefix_cache import PrefixCache


def make_prefill_step(model: Model):
    """Returns last-token logits only — full [B, S, V] logits at 32k x 152k
    vocab would be hundreds of GB; serving only needs the next-token head."""
    def prefill(params, batch):
        out = model.apply(params, tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"), last_only=True)
        return out["logits"][:, 0]
    return prefill


def make_serve_step(model: Model, *, donate_cache: bool | None = None):
    """(params, cache, tokens[B]) -> (logits [B, V], cache) — one token.

    ``donate_cache`` donates the KV cache buffers to the jitted step so
    decode updates happen in place (defaults to on for TPU, where buffer
    donation is supported; harmless elsewhere but noisy).
    """
    if donate_cache is None:
        donate_cache = jax.default_backend() == "tpu"

    def serve_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens=tokens)

    return jax.jit(serve_step, donate_argnums=(1,) if donate_cache else ())


def make_serve_programs(model: Model, sampler):
    """The engine's device programs ``(greedy_tick, sampled_tick,
    chunk_mid, chunk_last)``: a decode tick over every slot (argmax, or
    ``sampler``), and one window of one slot's prompt prefilled at the
    static absolute position ``pos0`` (the final window also returns the
    last real token's logits).  ``pmask`` marks the slots a tick does not
    decode (idle, or parked mid-admission): their position is pinned to
    ``ppos`` and their recurrent state, if any, left as it is.

    Every program DONATES the cache it threads: a serving loop's lineage
    is strictly linear, so XLA updates the pool in place instead of
    copying tens of MB on every dispatch.
    """
    ssm = bool(model.cfg.ssm_positions)

    def _greedy_tick(params, cache, toks, pmask, ppos):
        cache = dict(cache)
        cache["pos"] = jnp.where(pmask, ppos, cache["pos"])
        logits, cache = model.decode_step(params, cache, tokens=toks,
                                          token_mask=~pmask)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    def _sampled_tick(params, cache, toks, pmask, ppos, temps, keys):
        cache = dict(cache)
        cache["pos"] = jnp.where(pmask, ppos, cache["pos"])
        logits, cache = model.decode_step(params, cache, tokens=toks,
                                          token_mask=~pmask)
        tok, keys = sampler(logits, keys, temps)
        return tok, keys, cache

    # The chunk programs fuse view-gather -> apply -> pool merge into one
    # dispatch over the FULL layer tuple (slot traced, pos0 static):
    # splitting them would force the donated pool out through three jit
    # boundaries and re-copy it at each one.

    def _chunk_mid(params, toks, layers, slot, pos0):
        # interior prefill chunk: cache write-through only — no
        # final norm / vocab projection for chunks that don't
        # contain the last real token
        view = tuple(c.prefill_view(slot) if hasattr(c, "prefill_view")
                     else c for c in layers)
        c = {"layers": view, "pos": jnp.full((), pos0, jnp.int32)}
        out = model.apply(params, tokens=toks, cache=c,
                          write_cache=True, need_logits=False,
                          pos0=pos0)
        return tuple(f.admit(o, slot) if hasattr(f, "admit") else f
                     for f, o in zip(layers, out["cache"]["layers"]))

    def _chunk_last(params, toks, layers, slot, pos0, last_index):
        # final chunk: tail-padded to the grid window, the last REAL
        # token's logits gathered at a traced index so the compile
        # key is (window shape, pos0) — not the raw prompt length
        view = tuple(c.prefill_view(slot) if hasattr(c, "prefill_view")
                     else c for c in layers)
        c = {"layers": view, "pos": jnp.full((), pos0, jnp.int32)}
        tail = {}
        if ssm:   # the tail pad must not advance the recurrence
            tail = {"pad_mask": (jnp.arange(toks.shape[1])[None, :]
                                 <= last_index),
                    "start": jnp.zeros((1,), jnp.int32)}
        out = model.apply(params, tokens=toks, cache=c,
                          write_cache=True, last_only=True, pos0=pos0,
                          last_index=last_index, **tail)
        merged = tuple(f.admit(o, slot) if hasattr(f, "admit") else f
                       for f, o in zip(layers, out["cache"]["layers"]))
        return out["logits"][:, 0], merged

    return (jax.jit(_greedy_tick, donate_argnums=(1,)),
            jax.jit(_sampled_tick, donate_argnums=(1,)),
            jax.jit(_chunk_mid, static_argnums=(4,), donate_argnums=(2,)),
            jax.jit(_chunk_last, static_argnums=(4,), donate_argnums=(2,)))


def _pad_mask_from_lens(prompt_lens, b: int, s0: int):
    """[B] real-token counts -> (left-pad mask [B, S0], start [B])."""
    lens = jnp.asarray(prompt_lens, jnp.int32)
    if lens.shape != (b,):
        raise ValueError(f"prompt_lens must have shape ({b},), got {lens.shape}")
    lens_np = np.asarray(lens)
    if (lens_np < 1).any() or (lens_np > s0).any():
        raise ValueError(f"prompt_lens must be in [1, {s0}], got {lens_np}")
    mask = jnp.arange(s0)[None, :] >= (s0 - lens[:, None])
    return mask, (s0 - lens).astype(jnp.int32)


def generate(model: Model, params, prompt_tokens, steps: int, *,
             temperature: float = 0.0, key=None, max_len: int | None = None,
             eos_id: int | None = None, pad_id: int = 0, prompt_lens=None,
             prefill: str = "batched", prefill_chunk: int | None = None,
             top_k: int | None = None, top_p: float | None = None,
             cache_kind: str | None = None):
    """Greedy/temperature generation on top of the batched prefill.

    prompt_tokens: [B, S0] int32, LEFT-padded when ragged (``prompt_lens``
    [B] gives each row's real-token count; real tokens occupy the last
    ``prompt_lens[b]`` columns).  Returns [B, steps] int32; rows that hit
    ``eos_id`` emit it and then ``pad_id`` for the remaining columns, and
    the loop stops early once every row is done.

    ``prefill`` selects "batched" (model.prefill cache write-through —
    the fast path; prompts longer than the sliding-window ring, or than
    ``prefill_chunk`` when set, are processed in cache-write-through
    chunks) or "sequential" (token-by-token decode steps; the reference
    path the equivalence tests compare against).

    Sampling is the on-device batched sampler (``repro.runtime.sampling``)
    with one PRNG key per row: ``temperature``/``top_k``/``top_p`` apply
    to every row, and a whole decode step is two device dispatches.

    ``cache_kind`` selects the KV backend ("auto" | "dense" | "ring" |
    "paged"; default = the model config's ``cache_kind``) — every
    backend decodes bit-identically on the oracle path.
    """
    prompt_tokens = jnp.asarray(prompt_tokens, jnp.int32)
    if prompt_tokens.ndim != 2 or 0 in prompt_tokens.shape:
        raise ValueError(
            "prompt_tokens must be [B, S0] with B >= 1 and S0 >= 1 (empty "
            f"prompts cannot be prefilled); got shape {prompt_tokens.shape}")
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if prefill not in ("batched", "sequential"):
        raise ValueError(f"unknown prefill mode {prefill!r}")
    b, s0 = prompt_tokens.shape
    if key is None:
        key = jax.random.PRNGKey(0)
    max_len = max_len or (s0 + steps)

    mask = start = None
    if prompt_lens is not None:
        mask, start = _pad_mask_from_lens(prompt_lens, b, s0)

    cache = model.init_cache(b, max_len, kind=cache_kind)
    if start is not None:
        cache["start"] = start
    step = make_serve_step(model)

    if prefill == "batched":
        logits, cache = model.prefill(params, cache, tokens=prompt_tokens,
                                      pad_mask=mask, chunk=prefill_chunk)
    else:
        logits = None
        if mask is None:
            for t in range(s0):
                logits, cache = step(params, cache, prompt_tokens[:, t])
        else:
            def masked_step(p, c, t, m):
                return model.decode_step(p, c, tokens=t, token_mask=m)

            sstep = jax.jit(masked_step)
            for t in range(s0):
                logits, cache = sstep(params, cache, prompt_tokens[:, t],
                                      mask[:, t])

    greedy = temperature <= 0 and top_k is None and top_p is None
    if not greedy:
        sampler = sampling.make_sampler(top_k, top_p, pad_id)
        keys = sampling.init_keys(key, b)
        temp = jnp.full((b,), temperature, jnp.float32)
    outs = []
    done = jnp.zeros((b,), bool)
    tok = None
    for _ in range(steps):
        if tok is not None:
            logits, cache = step(params, cache, tok)
        if greedy:   # no [B, V] Gumbel draw on the pure-argmax path
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            tok, keys = sampler(logits, keys, temp)
        if eos_id is not None:
            tok = jnp.where(done, pad_id, tok)
            done = done | (tok == eos_id)
        outs.append(tok)
        if eos_id is not None and bool(done.all()):
            break
    out = jnp.stack(outs, axis=1)
    if out.shape[1] < steps:   # early EOS stop: keep the [B, steps] contract
        out = jnp.pad(out, ((0, 0), (0, steps - out.shape[1])),
                      constant_values=pad_id)
    return out


# --- continuous-batching engine ----------------------------------------------

# Backend-dispatched slot management over the per-layer caches
# (kv_cache.CacheSlots): the SAME jitted helpers drive dense row splices,
# ring splices and paged zero-copy pool adoption.  Named functions, so
# each is its own module (``jit_kv_set_tables``, ...) in a device trace.

def kv_view(layers, slot):
    return tuple(c.prefill_view(slot) if hasattr(c, "prefill_view") else c
                 for c in layers)


def kv_admit_slot(full, one, slot):
    return tuple(f.admit(o, slot) if hasattr(f, "admit") else f
                 for f, o in zip(full, one))


def kv_release_slot(layers, slot):
    return tuple(c.free_slot(slot) if hasattr(c, "free_slot") else c
                 for c in layers)


def kv_set_tables(layers, table):
    return tuple(c.with_table(table) if isinstance(c, kv_cache.PagedCache)
                 else c for c in layers)


def kv_copy_pages(layers, src, dst):
    """Device half of copy-on-write: duplicate whole pages src -> dst
    across every paged layer pool in one dispatch."""
    return tuple(c.copy_pages(src, dst) if isinstance(c, kv_cache.PagedCache)
                 else c for c in layers)


def host_copy(mirror: np.ndarray) -> jax.Array:
    """A device array of a host mirror that the engine mutates in place.
    On the CPU backend ``jnp.asarray`` (and ``jnp.array``, whose copy is
    dispatched) may read the numpy buffer only once the device queue
    reaches it, after later host writes: copy on the host first."""
    return jnp.asarray(mirror.copy())


def _bucket(n: int, lo: int) -> int:
    """Round a prompt length up to a power of two (>= lo) so prefill jits
    once per bucket instead of once per length."""
    b = max(lo, 1)
    while b < n:
        b *= 2
    return b


@dataclass
class Request:
    """One serving request; ``tokens`` is the raw (unpadded) prompt."""
    uid: int
    tokens: list[int]
    max_new_tokens: int = 32
    temperature: float = 0.0


@dataclass
class _SlotState:
    req: Request
    emitted: list[int] = field(default_factory=list)


@dataclass
class _Parked:
    """A stream suspended mid-generation: everything needed to resume it
    bit-identically in any free slot later.  The page references move
    from the slot to this record — the pool keeps the KV resident, no
    other slot can allocate those pages, and ``check_leaks`` counts the
    record as a holder."""

    req: Request
    emitted: list[int]
    pos: int
    start: int
    temp: float
    key: object                  # [2] uint32 PRNG row (device, immutable)
    next_tok: int
    table_row: np.ndarray
    pages: list[int]
    shared: list[int]
    reserved: int


@dataclass
class EngineSnapshot:
    """One consistent tick boundary of a :class:`ServeEngine`.

    Device pools are CLONED (``kv_cache.CacheSlots.clone``) because the
    serving jits donate the live cache — an aliasing snapshot would be
    invalidated by the first post-snapshot dispatch.  Host mirrors,
    allocator refcounts and prefix-cache pins are copied so a rollback
    unwinds partial tick mutations exactly.  ``restore`` re-copies, so
    one snapshot restores any number of times.
    """

    cache: dict
    dcache: dict | None
    keys: object
    pos: np.ndarray
    start: np.ndarray
    temp: np.ndarray
    next_tok: np.ndarray
    free: list
    queue: list
    active: dict                 # slot -> (req, emitted copy)
    results: dict
    parked: dict                 # uid -> _Parked (emitted copied)
    next_uid: int
    spec_stats: dict
    cow_copies: int
    table: np.ndarray | None = None
    slot_pages: dict | None = None
    slot_shared: dict | None = None
    slot_reserved: dict | None = None
    alloc: tuple | None = None
    prefix: tuple | None = None


class ServeEngine:
    """Fixed-slot continuous-batching serving engine.

    The engine keeps one [slots, max_len] decode cache with PER-SLOT
    positions and pad offsets (``cache["pos"]``/``cache["start"]`` are [B]
    vectors).  Each ``step()`` tick first admits queued requests into free
    slots (``admit``), then runs ONE masked decode tick that decodes and
    samples every active slot on device (``make_serve_programs``; idle
    slots are masked out): per-slot temperatures ride in a [slots]
    vector, each slot draws from its own PRNG key (folded from the
    engine seed and the request uid, so replays are slot-placement
    independent), and only the [slots] sampled tokens are transferred
    back.  A slot is freed on EOS or ``max_new_tokens`` and immediately
    becomes refillable, so long and short requests share the batch
    without barriers (continuous batching).

    ADMISSION.  A paged engine admits a prompt at its exact length and
    absolute positions: ``begin_admission`` maps its pages,
    ``admit_chunk`` prefills one window of the ``chunk`` grid, and
    ``complete_admission`` activates the slot and samples the first
    token on device.  ``admit`` runs them back to back; a
    ``PipelinedScheduler`` runs one window per tick.  Dense and ring
    backends admit in one dispatch (``_admit_one``).

    ``cache_kind`` picks the KV backend; the default is PAGED for
    full-attention models and RING for sliding-window models:

    * "paged" — fixed-size pages + per-slot block tables over a shared
      pool.  Admission reserves the request's worst case
      (ceil((prompt + max_new_tokens) / page) pages), maps the prompt's
      pages from the refcounted host allocator
      (``repro.runtime.page_allocator.PageAllocator`` — ALL page
      lifetime flows through it), and prefills straight through the
      pool, so admitting a request moves page INDICES, never [max_len]
      cache rows; the decode tick reads the pages in place through the
      paged-attention kernel (no gathered KV view — a freshly admitted
      slot's unmapped tail and a freed slot's all-null table row are
      masked/compute-skipped in-kernel), maps one reserved page at a
      time as a slot crosses a page boundary, and EOS releases the
      slot's page references (exclusive pages return to the pool).
      ``pages`` caps the pool (default: full provisioning, slots *
      ceil(max_len / page_size)) — an undersized pool admission-stalls
      instead of failing, and in-flight requests can never run out of
      pages.

    PREFIX SHARING (``prefix_cache``, default "auto" = on for paged
    attention-only models): full-page prompt prefixes are cached in a
    radix trie (``repro.runtime.prefix_cache.PrefixCache``) keyed on
    page-sized token blocks and pinned with allocator refcounts.  A
    newcomer whose prompt starts with a cached prefix maps the SHARED
    pages into its block table (refcount + 1 each, zero KV compute,
    zero new pages) and prefills only the unshared suffix, resuming the
    chunked prefill at the first unshared position — admission cost is
    O(new tokens), and N requests sharing a system prompt hold ONE copy
    of its KV.  A FULLY cached prompt admits with a read-only peek of
    its last token's logits (``Model.apply(peek=True)``): zero fresh
    pages, zero copies — the thundering-herd case costs one forward of
    one token.  Paged admission keeps absolute positions, so RoPE
    rotations line up for every request and shared pages stay
    byte-identical across holders; match depths are page-quantized, so
    a suffix window starts on one of a handful of grid points and
    shares their compiles.  Admission counts pages it is about to
    pin OUT of the availability check (a matched page the cache alone
    holds stops being evictable the moment it is shared), so pool
    pressure stalls admission instead of breaking a live reservation.
    Every write is additionally gated by COPY-ON-WRITE: before a
    prefill/decode/verify write lands in a page some other holder still
    references, the engine copies the page to a fresh one
    (``PagedCache.copy_pages``, one device dispatch) and remaps this
    slot's table — other holders' bytes never change.
    Cached pages idle at refcount 1 and are LRU-evicted only under pool
    pressure, so a warm cache never steals capacity from admission.
    On drain, ``run()`` asserts the allocator leak check: refcounts ==
    block-table occupancy + cache pins, and free + resident pages tile
    the pool exactly.  SSM/hybrid models can't share (the post-prefix
    recurrent state isn't paged): "auto" resolves to off and an
    explicit ``prefix_cache=True`` raises.
    * "ring" — sliding-window decode: slots still track ABSOLUTE
      positions while rows live in a ``window``-slot ring, so prompts
      longer than the window are servable end to end (admission chunks
      at the ring width).
    * "dense" — the contiguous row-splice backend (the pre-paged
      behavior).

    ``on_token(uid, token, done)`` streams tokens as they are sampled.

    SPECULATIVE DECODING (``draft_model``/``draft_params``): a small
    drafter proposes ``spec_k`` greedy tokens per slot per tick (ONE
    jitted scan dispatch over K+1 drafter decode steps, the extra step
    pre-writing the last draft's row so a clean sweep needs no
    catch-up), the target scores all K+1 positions in ONE batched
    ``verify_step`` dispatch (B×K+1 GEMM-shaped — the matmul shape the
    EN-T engines are built for, vs. decode's B×1 GEMV), and each slot
    commits its longest accepted prefix plus the free token the
    target's own distribution supplies at the first mismatch.  Rollback
    is O(1) layout work, not data work: the per-slot ``pos`` vector
    resets to the accepted depth (rejected rows are invisible to every
    masked read and rewritten in place), SSM layers select the
    after-accepted-token state from the verify scan's stacked per-step
    states, and the paged allocator's mapped-ahead pages stay within
    the slot's reservation (``_pages_needed`` reserves ``spec_k`` extra
    pages so a verify burst can never exhaust the pool mid-tick).  At
    temperature 0 the emitted streams are bit-identical to plain
    decode; ``spec_mode="match"`` (default) keeps that guarantee at
    every temperature/top-k/top-p by Gumbel-coupling acceptance to the
    plain sampler's key chain, and keys advance once per EMITTED token,
    so replay is unaffected by rejected drafts.  ``spec_mode=
    "rejection"`` trades replay-identity for classic rejection-sampling
    acceptance.  Sliding-window (ring) targets and drafters are
    rejected: a burst write evicts window rows rollback cannot restore.
    The drafter must share the target's tokenizer (vocab); its KV runs
    a dense cache prefilled alongside the target's at admission (the
    whole prompt in one dispatch, after a paged target's chunks).
    """

    def __init__(self, model: Model, params, *, slots: int = 4,
                 max_len: int = 128, eos_id: int | None = None,
                 pad_id: int = 0, prefill_bucket: int = 8, seed: int = 0,
                 prefill_chunk: int | None = None, top_k: int | None = None,
                 top_p: float | None = None, on_token=None,
                 cache_kind: str | None = None, page_size: int | None = None,
                 pages: int | None = None, draft_model: Model | None = None,
                 draft_params=None, spec_k: int = 4,
                 spec_mode: str = "match",
                 prefix_cache: bool | str = "auto"):
        if slots < 1:
            raise ValueError(f"ServeEngine needs at least one slot, got {slots}")
        if cache_kind in (None, "auto"):
            cache_kind = "ring" if model.cfg.sliding_window else "paged"
        self.cache_kind = cache_kind
        self.model, self.params = model, params
        self.slots, self.max_len = slots, max_len
        self.eos_id, self.pad_id = eos_id, pad_id
        self.prefill_bucket = prefill_bucket
        self.prefill_chunk = prefill_chunk
        # the paged admission grid: windows end at multiples of it (a
        # PipelinedScheduler takes it as its default)
        self.chunk = max(1, prefill_chunk or 32)
        self.on_token = on_token
        if cache_kind == "paged":
            self.page_size = page_size or kv_cache.DEFAULT_PAGE_SIZE
            self._pps = -(-max_len // self.page_size)   # pages per slot
            self._npages = self._pps * slots if pages is None else pages
            cache = model.init_cache(slots, max_len, kind="paged",
                                     page_size=self.page_size,
                                     pages=self._npages, mapped=False)
            # host-side page accounting: the refcounted allocator + a
            # block-table mirror + per-slot page-reference sets, so
            # ticks never sync on the device.  Admission RESERVES each
            # request's worst case (prompt + max_new_tokens) but maps
            # pages lazily at page boundaries: mid-decode grabs always
            # draw from the slot's own reservation, so an undersized
            # pool can only ever stall admission — never fail a request
            # in flight.
            self._alloc = PageAllocator(self._npages)
            self._slot_pages: dict[int, list[int]] = {}    # exclusive refs
            self._slot_shared: dict[int, list[int]] = {}   # prefix-shared refs
            self._slot_reserved: dict[int, int] = {}
            self._table = np.zeros((slots, self._pps), np.int32)
            has_ssm = any(m == "ssm" for m, _ in model.cfg.group)
            if prefix_cache == "auto":
                prefix_cache = not has_ssm
            if prefix_cache and has_ssm:
                raise ValueError(
                    "prefix caching shares attention KV pages only; SSM "
                    "layers carry recurrent state the cache cannot resume "
                    "from — serve hybrid/SSM models with "
                    "prefix_cache=False")
            self._prefix = (PrefixCache(self.page_size, self._alloc)
                            if prefix_cache else None)
        else:
            if prefix_cache is True:
                raise ValueError(
                    f"prefix caching requires the paged backend, not "
                    f"{cache_kind!r}: only block tables can map one page "
                    "into many slots")
            self._prefix = None
            cache = model.init_cache(slots, max_len, kind=cache_kind)
        self._cow_copies = 0
        cache["pos"] = jnp.zeros((slots,), jnp.int32)
        cache["start"] = jnp.zeros((slots,), jnp.int32)
        self.cache = cache

        # where the engine's spans go (a scheduler hands it its own log)
        self.spans = SPANS
        # no layer-type or backend special cases in the tick loop
        self._view = jax.jit(kv_view)
        self._admit_slot = jax.jit(kv_admit_slot)
        self._release = jax.jit(kv_release_slot)
        self._set_tables = jax.jit(kv_set_tables)

        def _prefill_into(params, toks, mask, layers):
            c = {"layers": layers, "pos": jnp.zeros((), jnp.int32)}
            return model.prefill(params, c, tokens=toks, pad_mask=mask,
                                 chunk=prefill_chunk)

        # row-backend admission: jit's own shape-keyed cache compiles
        # once per length bucket
        self._prefill = jax.jit(_prefill_into)

        def _peek_last(params, toks, layers, pos0):
            # fully prefix-cached prompt: every KV row already lives in
            # shared pages, so admission only needs the LAST token's
            # logits — a read-only forward (no cache write, hence no
            # fresh page and no copy-on-write)
            c = {"layers": layers, "pos": jnp.full((), pos0, jnp.int32)}
            out = model.apply(params, tokens=toks, cache=c,
                              write_cache=True, peek=True, last_only=True,
                              pos0=pos0)
            return out["logits"][:, 0]

        self._peek = jax.jit(_peek_last, static_argnums=(3,))
        self._copy_pages = jax.jit(kv_copy_pages)
        self._sampler = sampling.make_sampler(top_k, top_p, pad_id)
        self._truncates = top_k is not None or top_p is not None
        (self._greedy_tick, self._sampled_tick, self._chunk_mid,
         self._chunk_last) = make_serve_programs(model, self._sampler)
        self._seed_key = jax.random.PRNGKey(seed)
        self._keys = sampling.init_keys(self._seed_key, slots)
        self._temp = np.zeros((slots,), np.float32)
        # host mirrors of cache["pos"]/cache["start"] so per-slot
        # bookkeeping never syncs on the device cache mid-tick
        self._pos = np.zeros((slots,), np.int64)
        self._start = np.zeros((slots,), np.int64)
        self._parked: dict[int, _Parked] = {}
        self._queue: deque[Request] = deque()
        self._free = list(range(slots))
        self._active: dict[int, _SlotState] = {}
        self._next_tok = np.full((slots,), pad_id, np.int32)
        self._results: dict[int, list[int]] = {}
        self._next_uid = 0
        # extra per-request page reservation (``reserve_ahead``)
        self._reserve_slack = 0

        # .. speculative decoding ..
        self._spec = draft_model is not None
        # graceful-degradation knob: a scheduler's DegradePolicy can flip
        # this off to fall back to plain decode ticks.  spec_mode="match"
        # couples acceptance to the plain sampler's key chain, so the
        # emitted streams are bit-identical either way — disabling is a
        # pure perf change.  (Re-enabling leaves the drafter's KV holes
        # for the plainly-decoded stretch: acceptance dips, output
        # doesn't.)
        self.spec_enabled = True
        self.spec_stats = {"ticks": 0, "drafted": 0, "accepted": 0,
                           "emitted": 0}
        if not self._spec:
            return
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if self.cache_kind == "ring":
            raise ValueError(
                "speculative decoding is unsupported on the ring backend: "
                "a K-token verify burst evicts sliding-window rows that "
                "rollback cannot restore (see kv_cache.RingCache."
                "verify_view)")
        if draft_model.cfg.sliding_window:
            raise ValueError(
                "sliding-window drafters are unsupported: rolling the "
                "drafter's ring back past an eviction would resurface "
                "overwritten rows as stale history")
        if draft_model.cfg.vocab_size != model.cfg.vocab_size:
            raise ValueError(
                f"drafter vocab ({draft_model.cfg.vocab_size}) != target "
                f"vocab ({model.cfg.vocab_size}): speculative pairs must "
                "share a tokenizer")
        self.spec_k, self.spec_mode = spec_k, spec_mode
        self.draft_model, self.draft_params = draft_model, draft_params
        dcache = draft_model.init_cache(slots, max_len, kind="dense")
        dcache["pos"] = jnp.zeros((slots,), jnp.int32)
        dcache["start"] = jnp.zeros((slots,), jnp.int32)
        self._dcache = dcache

        def _dprefill_into(dp, toks, mask, layers):
            c = {"layers": layers, "pos": jnp.zeros((), jnp.int32)}
            return draft_model.prefill(dp, c, tokens=toks, pad_mask=mask,
                                       chunk=prefill_chunk)

        self._dprefill = jax.jit(_dprefill_into)

        def _draft_fn(dp, dcache, tok0, k):
            # K+1 drafter decode steps as ONE scan dispatch: iteration i
            # consumes the token at position pos+i and proposes the
            # next; the (K+1)-th writes the last draft's KV row so a
            # clean sweep leaves the drafter fully caught up.  SSM layer
            # states are snapshotted per step ([K+1, G, B, ...] ys) for
            # the post-acceptance rollback select.
            def body(carry, _):
                tok, c = carry
                logits, c = draft_model.decode_step(dp, c, tokens=tok)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                snap = tuple(
                    lc if isinstance(lc, kv_cache.SSMCache) else None
                    for lc in c["layers"])
                return (nxt, c), (nxt, snap)
            (_, dc), (toks, snaps) = jax.lax.scan(
                body, (tok0, dcache), None, length=k + 1)
            drafts = jnp.moveaxis(toks, 0, 1)[:, :k]        # [B, K]
            burst = jnp.concatenate([tok0[:, None], drafts], axis=1)
            return drafts, burst, dc, snaps

        self._draft = jax.jit(_draft_fn, static_argnums=(3,))

        def verify_burst(p, c, t):
            return model.verify_step(p, c, tokens=t)

        def draft_select(layers, snaps, sel):
            return draft_model.select_ssm_states(
                layers, jax.tree.map(lambda x: jnp.moveaxis(x, 0, 2), snaps),
                sel)

        self._verify = jax.jit(verify_burst)
        self._verifier = sampling.make_spec_verifier(top_k, top_p, spec_mode)
        self._gverify = jax.jit(sampling.greedy_verify)
        self._t_has_ssm = any(m == "ssm" for m, _ in model.cfg.group)
        self._d_has_ssm = any(m == "ssm" for m, _ in draft_model.cfg.group)
        self._t_select = jax.jit(model.select_ssm_states)
        self._d_select = jax.jit(draft_select)

    # .. request intake ..
    def submit(self, tokens, *, max_new_tokens: int = 32,
               temperature: float = 0.0) -> int:
        req = self.new_request(tokens, max_new_tokens=max_new_tokens,
                               temperature=temperature)
        self._queue.append(req)
        return req.uid

    def new_request(self, tokens, *, max_new_tokens: int = 32,
                    temperature: float = 0.0) -> Request:
        """Check a prompt against the engine's capacity and mint its uid:
        the :class:`Request`, for ``submit`` (or a scheduler) to queue."""
        tokens = [int(t) for t in np.asarray(tokens).reshape(-1)]
        if not tokens:
            raise ValueError("cannot serve an empty prompt")
        # paged admission keeps absolute positions (tail pads never
        # occupy one), so the exact length is the capacity bound
        exact = self.cache_kind == "paged"
        sp = len(tokens) if exact else _bucket(len(tokens),
                                               self.prefill_bucket)
        if sp + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(tokens)} tokens"
                f"{'' if exact else ', bucketed'}) + "
                f"max_new_tokens ({max_new_tokens}) exceeds engine max_len "
                f"{self.max_len}")
        if self.cache_kind == "paged":
            need = self._pages_needed(sp, max_new_tokens)
            if need > self._npages:
                raise ValueError(
                    f"request needs {need} pages worst-case but the pool "
                    f"only has {self._npages}; raise pages= or page_size=")
        uid = self._next_uid
        self._next_uid += 1
        return Request(uid, tokens, max_new_tokens, temperature)

    def reserve_ahead(self, positions: int) -> None:
        """Widen every later page reservation by ``positions``: each tick
        a driver dispatches ahead of the host may write one past it."""
        self._reserve_slack = positions

    # .. internals ..
    def _release_slot(self, slot: int) -> None:
        """Tear ``slot`` down to refillable: return it to the free list,
        zero its pos/start/temp mirrors, and drop every page reference
        it holds (exclusive pages free immediately; prefix-shared pages
        just lose one holder — the cache's pin keeps them resident).
        Records NOTHING: ``emit`` stores the result first on normal
        completion, while ``cancel`` calls this directly so an aborted
        request leaves no trace but its freed capacity.  Tolerates a
        slot that is mid-admission (reserved pages but no ``_active``
        entry yet — the async scheduler cancels mid-prefill)."""
        st = self._active.pop(slot, None)
        if slot not in self._free:
            self._free.append(slot)
        self.cache["pos"] = self.cache["pos"].at[slot].set(0)
        self.cache["start"] = self.cache["start"].at[slot].set(0)
        self._pos[slot] = 0
        self._start[slot] = 0
        self._temp[slot] = 0.0
        self._next_tok[slot] = self.pad_id
        if self._spec:
            self._dcache["pos"] = self._dcache["pos"].at[slot].set(0)
            self._dcache["start"] = (
                self._dcache["start"].at[slot].set(0))
        if self.cache_kind == "paged":
            for pid in self._slot_pages.pop(slot, ()):
                self._alloc.release(pid)
            for pid in self._slot_shared.pop(slot, ()):
                self._alloc.release(pid)
            self._slot_reserved.pop(slot, None)
            self._table[slot] = 0
            with self.spans.span("kv.release",
                                 uid=-1 if st is None else st.req.uid):
                self._on_pools(self._release, slot)

    def cancel(self, uid: int) -> bool:
        """Abort ``uid`` wherever it is — queued (dropped) or active
        (slot, pages, and prefix-cache pins released via
        ``_release_slot``); no result is recorded either way.  Valid at
        any tick boundary: the next decode tick simply sees one more
        free slot (an in-flight write for the old occupant lands before
        the release's zeroing in device-dispatch order, so it can never
        outlive the teardown).  Returns False for unknown or
        already-finished uids."""
        for i, req in enumerate(self._queue):
            if req.uid == uid:
                del self._queue[i]
                return True
        for slot, st in list(self._active.items()):
            if st.req.uid == uid:
                self._release_slot(slot)
                return True
        return False

    def emit(self, slot: int, tok: int) -> bool:
        """Record one sampled token; returns True if the request finished."""
        st = self._active[slot]
        st.emitted.append(tok)
        done = (tok == self.eos_id if self.eos_id is not None else False)
        done = done or len(st.emitted) >= st.req.max_new_tokens
        done = done or int(self._pos[slot]) >= self.max_len - 1
        if self.on_token is not None:
            self.on_token(st.req.uid, tok, done)
        if done:
            self._results[st.req.uid] = st.emitted
            self._release_slot(slot)
        else:
            self._next_tok[slot] = tok
        return done

    def emit_decoded(self, slot: int, tok: int) -> bool:
        """``emit`` a decode tick's token, past the position the tick
        wrote; returns True if the request finished."""
        self._pos[slot] += 1
        return self.emit(slot, tok)

    def idle_mask(self) -> np.ndarray:
        """[slots] bool: the slots no request is decoding in."""
        idle = np.ones((self.slots,), bool)
        idle[list(self._active)] = False
        return idle

    def _pages_needed(self, prompt_len: int, max_new: int) -> int:
        """Worst-case pages one request can touch: positions
        [0, prompt + max_new), plus ``spec_k`` speculative positions
        (a verify burst writes up to ``spec_k`` rows past the last
        committed token, and rollback keeps them mapped), plus any
        pipeline ``_reserve_slack`` (dispatch-ahead ticks overshoot the
        same way), capped at the per-slot table length."""
        extra = (self.spec_k if self._spec else 0) + self._reserve_slack
        return min(-(-(prompt_len + max_new + extra) // self.page_size),
                   self._pps)

    @property
    def page_stats(self) -> dict | None:
        """Pool accounting for the paged backend (None otherwise):
        {total, free (unmapped), shared (refcount > 1), resident
        (refcount >= 1), reserved (worst-case holds), cached
        (prefix-cache pins, when enabled)}."""
        if self.cache_kind != "paged":
            return None
        stats = self._alloc.stats()
        stats["reserved"] = sum(self._slot_reserved.values())
        if self._prefix is not None:
            stats["cached"] = self._prefix.resident
        return stats

    @property
    def ssm_state_bytes(self) -> int:
        """Bytes of recurrent (SSM) state the engine holds on the device:
        every slot's conv window and SSD state, whether in use or not."""
        return sum(a.nbytes for c in self.cache["layers"]
                   if isinstance(c, kv_cache.SSMCache)
                   for a in jax.tree.leaves(c))

    @property
    def prefix_stats(self) -> dict | None:
        """Prefix-cache counters + the engine's CoW copy count (None
        when prefix caching is off)."""
        if self._prefix is None:
            return None
        stats = self._prefix.stats()
        stats["cow_copies"] = self._cow_copies
        return stats

    def _pages_available(self) -> int:
        """Pages a NEW reservation may count on: free pages, plus
        cached pages nobody maps (evictable under pressure), minus the
        lazily-mapped remainder of every live reservation."""
        evictable = self._prefix.evictable if self._prefix is not None else 0
        outstanding = sum(
            reserved - len(self._slot_pages.get(slot, ()))
            for slot, reserved in self._slot_reserved.items())
        return self._alloc.free + evictable - outstanding

    def _take_pages(self, n: int) -> list[int]:
        """Allocate ``n`` exclusive pages, evicting idle prefix-cache
        entries to cover a shortfall.  Exhaustion here means the
        reservation accounting is broken — admission guarantees every
        live request's worst case."""
        if n <= 0:
            return []
        short = n - self._alloc.free
        if short > 0 and self._prefix is not None:
            self._prefix.evict(short)
        try:
            return self._alloc.alloc(n)
        except InjectedFault:
            raise                 # keep site/uid attribution for recovery
        except RuntimeError as e:
            raise RuntimeError(
                "page reservation accounting is broken: pool exhausted "
                "under a live reservation") from e

    def _cow(self, slot: int, lo: int, hi: int) -> bool:
        """Copy-on-write gate for ``slot`` writing positions [lo, hi]:
        any mapped page in that range still shared with another holder
        (refcount > 1) is copied to a fresh page and the slot's table
        remapped BEFORE the write, so the other holders' bytes never
        change.  Returns True when the table mirror changed (caller
        pushes it with the rest of the tick's table updates)."""
        src, dst = [], []
        for pp in range(lo // self.page_size, hi // self.page_size + 1):
            pid = int(self._table[slot, pp])
            if pid == 0 or self._alloc.refcount(pid) <= 1:
                continue
            new = self._take_pages(1)[0]
            src.append(pid)
            dst.append(new)
            self._table[slot, pp] = new
            self._slot_pages[slot].append(new)
            # drop this slot's hold on the shared original (the other
            # holders — cache pin, sibling slots — keep it alive)
            if pid in self._slot_shared.get(slot, ()):
                self._slot_shared[slot].remove(pid)
            else:
                self._slot_pages[slot].remove(pid)
            self._alloc.release(pid)
        if src:
            self._cow_copies += len(src)
            self._on_pools(self._copy_pages, jnp.asarray(src, jnp.int32),
                           jnp.asarray(dst, jnp.int32))
        return bool(src)

    def _push_tables(self) -> None:
        """Hand the host block table to every paged layer: one dispatch
        over the whole pool (span ``kv.set_tables``)."""
        with self.spans.span("kv.set_tables"):
            self._on_pools(self._set_tables, host_copy(self._table))

    def _on_pools(self, step, *args) -> None:
        """Run a jitted page-table step (``_set_tables``, ``_release``,
        ``_copy_pages``) over the paged layers alone.  These jits do not
        donate, so a layer they passed through unchanged, such as an SSM
        layer's recurrent state, would come back as a copy of itself."""
        layers = self.cache["layers"]
        new = step(tuple(c if isinstance(c, kv_cache.PagedCache) else None
                         for c in layers), *args)
        self.cache["layers"] = tuple(c if n is None else n
                                     for c, n in zip(layers, new))

    def _map_pages(self, slot: int, req: Request) -> int | None:
        """Reserve ``req``'s worst case and map its prompt onto ``slot``'s
        block table: with a prefix cache, the longest cached prefix's
        shared pages (refcount + 1 each), then fresh pages for the rest
        of the prompt.  Returns the resume position ``pos0`` (the first
        position the prefill must compute; ``len(req.tokens)`` when the
        whole prompt is cached), or None when the pool can't cover the
        reservation (admission stalls)."""
        n, ps = len(req.tokens), self.page_size
        matched, spids = (self._prefix.match(req.tokens)
                          if self._prefix is not None else (0, []))
        reserve = (self._pages_needed(n, req.max_new_tokens)
                   - len(spids))
        # the matched pages are about to be pinned for the slot's
        # lifetime, but _pages_available still counts any of them the
        # cache alone holds (refcount 1) as evictable — admitting
        # against that double count would let a later _take_pages under
        # a live reservation find the pool empty with nothing evictable
        # (a crash, not a stall).  Exclude them before the check.
        locked = sum(1 for pid in spids if self._alloc.refcount(pid) == 1)
        if self._pages_available() - locked < reserve:
            return None
        for pid in spids:
            self._alloc.share(pid)
        self._slot_shared[slot] = list(spids)
        self._slot_pages[slot] = []
        self._slot_reserved[slot] = reserve
        self._table[slot] = 0
        self._table[slot, :len(spids)] = spids
        prompt_pages = -(-n // ps)
        fresh = self._take_pages(prompt_pages - len(spids))
        self._slot_pages[slot].extend(fresh)
        self._table[slot, len(spids):prompt_pages] = fresh
        self._push_tables()
        return matched

    # .. admission ..
    def begin_admission(self, slot: int, req: Request) -> int | None:
        """Map ``req``'s prompt pages onto free ``slot`` and claim it.
        Returns the position the prefill resumes at (``len(req.tokens)``:
        all cached, see ``peek_admission``), or None when the pool can't
        cover the request's worst case yet (admission waits for an EOS)."""
        fault_point("prefill.dispatch", uid=req.uid)
        pos0 = self._map_pages(slot, req)
        if pos0 is not None:
            self._free.remove(slot)
        return pos0

    def peek_admission(self, slot: int, req: Request):
        """A fully cached prompt's last-token logits: a read-only forward,
        so admission takes zero fresh pages and copies nothing."""
        view = self._view(self.cache["layers"], slot)
        toks = jnp.asarray([[req.tokens[-1]]], jnp.int32)
        return self._peek(self.params, toks, view, len(req.tokens) - 1)

    def chunk_end(self, lo: int, chunk: int) -> int:
        """End of the window from ``lo`` on the ``chunk`` grid (capped at
        the block table's reach): compiles are keyed on window starts."""
        return min((lo // chunk + 1) * chunk, self._pps * self.page_size)

    def admit_chunk(self, slot: int, req: Request, lo: int, hi: int):
        """Prefill positions [lo, hi) of ``req``'s prompt into ``slot``,
        tail-padded past its end (rows decode overwrites before any read).
        Returns the last token's logits [1, V] if the window holds it."""
        fault_point("prefill.dispatch", uid=req.uid)
        n = len(req.tokens)
        real_hi = min(hi, n)
        toks = jnp.asarray(
            [req.tokens[lo:real_hi] + [self.pad_id] * (hi - real_hi)],
            jnp.int32)
        slot_t = jnp.int32(slot)
        if real_hi < n:
            self.cache["layers"] = self._chunk_mid(
                self.params, toks, self.cache["layers"], slot_t, lo)
            return None
        logits, self.cache["layers"] = self._chunk_last(
            self.params, toks, self.cache["layers"], slot_t, lo,
            jnp.asarray(n - 1 - lo, jnp.int32))
        return logits

    def complete_admission(self, slot: int, req: Request, logits,
                           pos: int | None = None, start: int = 0):
        """Activate ``slot`` once ``req``'s prompt is prefilled: cache its
        full pages for sharing, set the position (default: the prompt
        length) and pad offset, fold the request's key and sample from
        ``logits``.  Returns the first token [1] on the device."""
        if pos is None:
            pos = len(req.tokens)
        if self._prefix is not None:
            # register the full-page prompt blocks for future sharing
            # (already-cached blocks keep their canonical pages)
            self._prefix.insert(
                req.tokens, [int(p) for p in self._table[
                    slot, :len(req.tokens) // self.page_size]])
        self.cache["pos"] = self.cache["pos"].at[slot].set(pos)
        self.cache["start"] = self.cache["start"].at[slot].set(start)
        self._pos[slot] = pos
        self._start[slot] = start
        self._active[slot] = _SlotState(req)
        self._temp[slot] = req.temperature
        # per-request key: replaying a request samples the same stream
        # regardless of which slot (or neighbours) it lands with
        self._keys = self._keys.at[slot].set(
            jax.random.fold_in(self._seed_key, req.uid))
        fault_point("sampler", uid=req.uid)
        tok, krow = self._sampler(
            logits, self._keys[slot:slot + 1],
            jnp.full((1,), req.temperature, jnp.float32))
        self._keys = self._keys.at[slot].set(krow[0])
        return tok

    def admit(self, slot: int, req: Request) -> bool:
        """Admit ``req`` into free ``slot`` and emit its first token;
        False when the pool can't cover its worst case yet."""
        n = len(req.tokens)
        if self.cache_kind != "paged":
            logits, pos, start = self._admit_one(slot, req)
        else:
            lo = self.begin_admission(slot, req)
            if lo is None:
                return False
            if lo >= n:
                logits = self.peek_admission(slot, req)
            else:
                logits = None
                while logits is None:
                    hi = self.chunk_end(lo, self.chunk)
                    logits = self.admit_chunk(slot, req, lo, hi)
                    lo = hi
            if self._spec:
                # the drafter prefills the WHOLE prompt at start 0,
                # tail-padded to a bucket (compiles per bucket, not per
                # length); an SSM drafter's conv window/SSD state must
                # end on the real last token, so it stays unpadded
                spd = (n if self._d_has_ssm else
                       min(_bucket(n, self.prefill_bucket), self.max_len))
                self._prefill_drafter(
                    slot, jnp.asarray(
                        [req.tokens + [self.pad_id] * (spd - n)],
                        jnp.int32), None, n, 0)
            pos, start = n, 0
        tok = self.complete_admission(slot, req, logits, pos, start)
        self.emit(slot, int(tok[0]))
        return True

    def _admit_one(self, slot: int, req: Request):
        """Dense/ring admission prefill: ``req``'s prompt, left-padded to
        a power-of-two bucket, into a zeroed single-slot view of free
        ``slot`` (claimed), merged back through the backend's ``admit``.
        Returns (logits, position, pad offset)."""
        fault_point("prefill.dispatch", uid=req.uid)
        n = len(req.tokens)
        sp = _bucket(n, self.prefill_bucket)
        toks = jnp.asarray([[self.pad_id] * (sp - n) + req.tokens],
                           jnp.int32)
        mask, _ = _pad_mask_from_lens([n], 1, sp)
        view = self._view(self.cache["layers"], slot)
        logits, c1 = self._prefill(self.params, toks, mask, view)
        self.cache["layers"] = self._admit_slot(
            self.cache["layers"], c1["layers"], slot)
        self._free.remove(slot)
        if self._spec:
            self._prefill_drafter(slot, toks, mask, sp, sp - n)
        return logits, sp, sp - n

    def _prefill_drafter(self, slot, toks, mask, pos, start) -> None:
        """The drafter shadows the target's admission in one dispatch."""
        dview = self._view(self._dcache["layers"], slot)
        _, d1 = self._dprefill(self.draft_params, toks, mask, dview)
        self._dcache["layers"] = self._admit_slot(
            self._dcache["layers"], d1["layers"], slot)
        self._dcache["pos"] = self._dcache["pos"].at[slot].set(pos)
        self._dcache["start"] = self._dcache["start"].at[slot].set(start)

    def _admit(self):
        while self._queue and self._free:
            if not self.admit(self._free[-1], self._queue[0]):
                break          # pool dry: requests wait for a slot's EOS
            self._queue.popleft()

    def _map_tick_pages(self, span: int = 0) -> None:
        """Make positions ``[pos, pos+span]`` write-safe for every active
        slot before a decode-family dispatch: map each still-null page in
        that range (one grab at a time from the slot's own reservation —
        positions are host-mirrored, so this never syncs on the device)
        and run the copy-on-write gate over it, so no write can land on
        an unmapped page or on a page another holder still references.
        All of a tick's table changes push as ONE table dispatch.

        ``span=0`` is the plain decode tick (the next token's position);
        a speculative tick passes ``tick_k`` (the verify burst writes
        that far ahead); the pipelined scheduler passes its dispatch
        depth, because a tick dispatched before the previous one is
        processed writes one position past the host mirror."""
        if self.cache_kind != "paged":
            return
        dirty = False
        for slot in self._active:
            p = int(self._pos[slot])
            hi = min(p + span, self.max_len - 1)
            for pp in range(p // self.page_size,
                            min(hi // self.page_size, self._pps - 1) + 1):
                if self._table[slot, pp] == 0:
                    pid = self._take_pages(1)[0]
                    self._slot_pages[slot].append(pid)
                    self._table[slot, pp] = pid
                    dirty = True
            if self._prefix is not None:
                dirty |= self._cow(slot, p, hi)
        if dirty:
            self._push_tables()

    # .. driving ..
    def decode_tick(self, greedy_tick, sampled_tick, toks, pmask, ppos):
        """Dispatch one decode tick through ``greedy_tick`` (no slot
        samples) or ``sampled_tick`` — the engine's programs or a driver's
        wrapping — and keep the cache and keys it returns.  Returns the
        [slots] tokens on the device."""
        fault_point("sampler")
        if self._temp.any() or self._truncates:
            tok, self._keys, self.cache = sampled_tick(
                self.params, self.cache, toks, pmask, ppos,
                host_copy(self._temp), self._keys)
        else:
            tok, self.cache = greedy_tick(self.params, self.cache, toks,
                                          pmask, ppos)
        return tok

    def step(self) -> bool:
        """Admit newcomers, then one decode tick that decodes and samples
        every active slot on device (only the [slots] sampled tokens
        come back to the host).  With a drafter the tick is
        draft-K -> verify-1-dispatch -> accept/rollback instead (see the
        class docstring).  Returns True while there is (or will be) work
        left."""
        self._admit()
        if not self._active:
            return bool(self._queue)
        if self._spec and self.spec_enabled:
            return self._spec_tick()
        fault_point("decode.dispatch")
        self._map_tick_pages()
        # idle slots stay pinned at position 0, where _release_slot
        # left them
        toks = self.decode_tick(
            self._greedy_tick, self._sampled_tick, host_copy(self._next_tok),
            host_copy(self.idle_mask()), jnp.zeros((self.slots,), jnp.int32))
        toks = np.asarray(toks)          # the ONE device->host transfer
        for slot in list(self._active):
            self.emit_decoded(slot, int(toks[slot]))
        if self._spec:
            # spec temporarily degraded to plain decode: keep the
            # drafter's pos in step so re-enabling resumes cleanly (its
            # missing KV rows only cost acceptance, never correctness)
            self._dcache["pos"] = jnp.asarray(self._pos.astype(np.int32))
        return bool(self._active or self._queue)

    def _spec_tick(self) -> bool:
        """One speculative tick: draft K greedy tokens per slot (one
        scan dispatch), verify all K+1 positions through the target
        (one burst dispatch), then commit each slot's accepted prefix
        and roll the rest back — pos-vector reset for attention rows,
        per-step state select for SSM layers."""
        fault_point("spec.verify")
        active = list(self._active)
        # headroom cap: the burst writes rows pos .. pos+tick_k, which
        # must stay inside max_len for every slot (slots free at
        # max_len-1, so tick_k >= 1 always)
        max_pos = max(int(self._pos[s]) for s in active)
        tick_k = min(self.spec_k, self.max_len - 1 - max_pos)
        # map every page the burst can touch up front (from each slot's
        # reservation) and CoW-clear the whole burst range: the verify
        # write must never land on an unmapped (null) page, nor — with
        # prefix sharing — on a page another holder still references (a
        # rolled-back burst would scribble on the shared prompt)
        self._map_tick_pages(tick_k)

        drafts, burst, dc, snaps = self._draft(
            self.draft_params, self._dcache, host_copy(self._next_tok),
            tick_k)
        vlogits, vcache, states = self._verify(self.params, self.cache,
                                               burst)
        if self._temp.any() or self._truncates:
            toks, n_acc, self._keys = self._verifier(
                vlogits, drafts, self._keys, host_copy(self._temp))
        else:              # all-greedy tick: argmax matching, keys idle
            toks, n_acc = self._gverify(vlogits, drafts)

        layers = vcache["layers"]
        if self._t_has_ssm:    # SSM rollback: select the accepted state
            layers = self._t_select(layers, states, n_acc)
        self.cache["layers"] = layers
        dlayers = dc["layers"]
        if self._d_has_ssm:
            dlayers = self._d_select(dlayers, snaps, n_acc)
        self._dcache["layers"] = dlayers

        toks_h = np.asarray(toks)        # [B, K+1] + [B]: the only pulls
        acc_h = np.asarray(n_acc)
        self.spec_stats["ticks"] += 1
        for slot in active:
            a = int(acc_h[slot])
            self.spec_stats["drafted"] += tick_k
            self.spec_stats["accepted"] += a
            # emit the accepted prefix + the free mismatch/bonus token,
            # advancing the pos mirror per token so EOS / max_new /
            # max_len stop exactly where plain decode would
            for j in range(a + 1):
                self.spec_stats["emitted"] += 1
                if self.emit_decoded(slot, int(toks_h[slot, j])):
                    break
        # attention rollback IS this pos push: rejected rows sit beyond
        # every slot's committed depth, masked until overwritten (freed
        # slots were zeroed by emit and the mirror agrees)
        posv = jnp.asarray(self._pos.astype(np.int32))
        self.cache["pos"] = posv
        self._dcache["pos"] = posv
        return bool(self._active or self._queue)

    @property
    def acceptance_rate(self) -> float | None:
        """Fraction of drafted tokens accepted (None before any spec
        tick)."""
        d = self.spec_stats["drafted"]
        return None if d == 0 else self.spec_stats["accepted"] / d

    # .. snapshot / restore (the fault-tolerance rollback boundary) ..
    def _clone_cache(self, cache: dict) -> dict:
        """Deep device copy of a cache dict — safe against the decode
        jit's buffer donation invalidating the live arrays later."""
        out = dict(cache)
        out["layers"] = tuple(
            c.clone() if hasattr(c, "clone") else jax.tree.map(jnp.copy, c)
            for c in cache["layers"])
        out["pos"] = jnp.copy(cache["pos"])
        out["start"] = jnp.copy(cache["start"])
        return out

    @staticmethod
    def _copy_parked(parked: dict) -> dict:
        return {u: replace(rec, emitted=list(rec.emitted),
                           pages=list(rec.pages), shared=list(rec.shared),
                           table_row=rec.table_row.copy())
                for u, rec in parked.items()}

    def snapshot(self) -> EngineSnapshot:
        """Capture one consistent tick boundary (see
        :class:`EngineSnapshot`).  Call only BETWEEN ticks — a snapshot
        taken mid-dispatch would mix pre- and post-tick state."""
        snap = EngineSnapshot(
            cache=self._clone_cache(self.cache),
            dcache=self._clone_cache(self._dcache) if self._spec else None,
            keys=jnp.copy(self._keys),
            pos=self._pos.copy(), start=self._start.copy(),
            temp=self._temp.copy(), next_tok=self._next_tok.copy(),
            free=list(self._free), queue=list(self._queue),
            active={s: (st.req, list(st.emitted))
                    for s, st in self._active.items()},
            results={u: list(v) for u, v in self._results.items()},
            parked=self._copy_parked(self._parked),
            next_uid=self._next_uid,
            spec_stats=dict(self.spec_stats),
            cow_copies=self._cow_copies)
        if self.cache_kind == "paged":
            snap.table = self._table.copy()
            snap.slot_pages = {s: list(v)
                               for s, v in self._slot_pages.items()}
            snap.slot_shared = {s: list(v)
                                for s, v in self._slot_shared.items()}
            snap.slot_reserved = dict(self._slot_reserved)
            snap.alloc = self._alloc.snapshot()
            if self._prefix is not None:
                snap.prefix = self._prefix.snapshot()
        return snap

    def restore(self, snap: EngineSnapshot) -> None:
        """Roll the engine back to ``snap``.  Everything is re-copied on
        the way in, so the same snapshot restores any number of times
        (retry loops restore once per attempt).  ``check_leaks`` must
        pass immediately after — restore unwinds partial allocations,
        pins and table updates a failed tick left behind."""
        self.cache = self._clone_cache(snap.cache)
        if self._spec:
            self._dcache = self._clone_cache(snap.dcache)
        self._keys = jnp.copy(snap.keys)
        self._pos = snap.pos.copy()
        self._start = snap.start.copy()
        self._temp = snap.temp.copy()
        self._next_tok = snap.next_tok.copy()
        self._free = list(snap.free)
        self._queue = deque(snap.queue)
        self._active = {s: _SlotState(req, list(em))
                        for s, (req, em) in snap.active.items()}
        self._results = {u: list(v) for u, v in snap.results.items()}
        self._parked = self._copy_parked(snap.parked)
        self._next_uid = snap.next_uid
        self.spec_stats = dict(snap.spec_stats)
        self._cow_copies = snap.cow_copies
        if self.cache_kind == "paged":
            self._table = snap.table.copy()
            self._slot_pages = {s: list(v)
                                for s, v in snap.slot_pages.items()}
            self._slot_shared = {s: list(v)
                                 for s, v in snap.slot_shared.items()}
            self._slot_reserved = dict(snap.slot_reserved)
            self._alloc.restore(snap.alloc)
            if self._prefix is not None:
                self._prefix.restore(snap.prefix)

    # .. park / resume (the elastic-capacity boundary) ..
    def park_slot(self, slot: int) -> int:
        """Suspend the stream in ``slot`` mid-generation: its pages (and
        their KV bytes) stay resident under a :class:`_Parked` record
        while the SLOT frees for other work.  ``resume_parked`` later
        continues the stream bit-identically.  Paged backend only — row
        backends physically reuse the slot's KV rows for the next
        occupant.  Returns the parked request's uid."""
        if self.cache_kind != "paged":
            raise ValueError(
                "parking requires the paged backend: dense/ring slots "
                "reuse the parked stream's KV rows for the next occupant")
        if self.model.cfg.ssm_positions:
            raise ValueError(
                "parking models with SSM layers is unsupported: their "
                "recurrent state lives in the slot, which the next "
                "occupant reuses")
        if self._spec:
            raise ValueError(
                "parking speculative engines is unsupported: the "
                "drafter's dense cache rows cannot survive slot reuse")
        st = self._active.pop(slot)
        uid = st.req.uid
        self._parked[uid] = _Parked(
            req=st.req, emitted=st.emitted, pos=int(self._pos[slot]),
            start=int(self._start[slot]), temp=float(self._temp[slot]),
            key=self._keys[slot], next_tok=int(self._next_tok[slot]),
            table_row=self._table[slot].copy(),
            pages=self._slot_pages.pop(slot, []),
            shared=self._slot_shared.pop(slot, []),
            reserved=self._slot_reserved.pop(slot, 0))
        self._free.append(slot)
        self._pos[slot] = 0
        self._start[slot] = 0
        self._temp[slot] = 0.0
        self._next_tok[slot] = self.pad_id
        self.cache["pos"] = self.cache["pos"].at[slot].set(0)
        self.cache["start"] = self.cache["start"].at[slot].set(0)
        self._table[slot] = 0
        self._push_tables()
        return uid

    def resume_parked(self, uid: int) -> int:
        """Resume a parked stream into a free slot.  The block table,
        PRNG key row, positions and pending token are restored exactly,
        so the continued stream is bit-identical to one that was never
        parked.  Returns the slot; raises when no slot is free."""
        rec = self._parked[uid]
        if not self._free:
            raise RuntimeError(
                f"cannot resume parked request {uid}: no free slot")
        slot = self._free[-1]
        self._free.remove(slot)
        del self._parked[uid]
        self._slot_pages[slot] = rec.pages
        self._slot_shared[slot] = rec.shared
        self._slot_reserved[slot] = rec.reserved
        self._table[slot] = rec.table_row
        self._push_tables()
        self.cache["pos"] = self.cache["pos"].at[slot].set(rec.pos)
        self.cache["start"] = self.cache["start"].at[slot].set(rec.start)
        self._pos[slot] = rec.pos
        self._start[slot] = rec.start
        self._temp[slot] = rec.temp
        self._next_tok[slot] = rec.next_tok
        self._keys = self._keys.at[slot].set(rec.key)
        self._active[slot] = _SlotState(rec.req, rec.emitted)
        return slot

    def drop_parked(self, uid: int) -> None:
        """Abandon a parked stream (quarantine/cancel while parked):
        release every page reference its record holds."""
        rec = self._parked.pop(uid)
        for pid in rec.pages:
            self._alloc.release(pid)
        for pid in rec.shared:
            self._alloc.release(pid)

    @property
    def parked_uids(self) -> list[int]:
        return list(self._parked)

    def check_leaks(self) -> None:
        """Allocator leak check (no-op for row backends): every page's
        refcount must equal its observable holder count — block-table
        occurrences across slots plus the prefix cache's pins — and the
        free list + resident pages must tile the pool exactly.  Raises
        ``AssertionError`` on drift.  Valid at any tick boundary;
        ``run()`` asserts it after every drain."""
        if self.cache_kind != "paged":
            return
        occupancy: dict[int, int] = {}
        for pid in self._table.reshape(-1).tolist():
            if pid:
                occupancy[pid] = occupancy.get(pid, 0) + 1
        if self._prefix is not None:
            for pid in self._prefix.pages():
                occupancy[pid] = occupancy.get(pid, 0) + 1
        # parked streams hold their pages outside any block table
        for rec in self._parked.values():
            for pid in rec.pages:
                occupancy[pid] = occupancy.get(pid, 0) + 1
            for pid in rec.shared:
                occupancy[pid] = occupancy.get(pid, 0) + 1
        self._alloc.check(occupancy)

    def run(self) -> dict[int, list[int]]:
        """Drive until queue and slots drain; returns {uid: emitted tokens}."""
        while self.step():
            pass
        self.check_leaks()
        return dict(self._results)
