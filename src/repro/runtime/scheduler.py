"""Pipelined prefill/decode scheduler: the async half of the serving stack.

``ServeEngine.run`` is a synchronous admit -> dispatch -> block loop:
every tick the host syncs the sampled tokens (``np.asarray``) before it
may dispatch the next, so the device idles during host bookkeeping, and
a long prompt's admission prefill stalls every in-flight decoder for
its full duration.  :class:`PipelinedScheduler` drives the SAME engine
— same jitted steps, same page allocator, same sampler keys — with two
structural changes, and emits bit-identical streams while doing it:

1. **Pipelined decode.**  Tick N+1 is dispatched before tick N's
   sampled tokens are synced: the sampler's [slots] device array feeds
   straight back in as the next tick's input (no host round-trip), and
   the host processes tick N's tokens — EOS checks, emission, slot
   frees — while tick N+1 computes.  ``np.asarray`` (the one blocking
   sync) happens only when an entry leaves the pipeline;
   ``jax.block_until_ready`` only at the stream boundary (``flush``).
   Because the engine can't know a slot finished until its token is
   processed, a dispatch-ahead tick may write one position past the
   host mirror — admission therefore reserves ``pipeline_depth`` extra
   positions per request (``ServeEngine._reserve_slack``), mirroring
   how speculative ticks reserve ``spec_k``, and every dispatch runs
   ``_map_tick_pages(in_flight)`` so the write can only land in a page
   this slot holds exclusively (or the compute-skipped null page) —
   never in prefix-shared bytes.  A processed entry whose slot was
   freed or re-admitted in the meantime is discarded by uid guard.

2. **Split prefill/decode streams.**  On a paged engine (with a prefix
   cache or without), admission no longer runs as one fused
   dispatch + host sync.  The prompt's unshared suffix prefills in
   grid-aligned chunks (windows of ``prefill_chunk`` tokens at
   absolute multiples of it, so jit compile keys stay bounded:
   ``pos0`` is static in ``Model.apply``), ONE chunk dispatched per
   tick between decode dispatches — a 10k-token prompt admits as many
   small dispatches interleaved with everyone else's decode ticks
   instead of one monolithic stall.  While a slot is mid-prefill it is
   parked: the decode tick's pos-override pins its device position to
   the chunk frontier, whose write lands in the slot's own
   exclusively-held (or unmapped -> null) page and is overwritten by
   the next chunk before any pos-bounded read can see it, and the tick
   leaves its recurrent state (SSM layers) untouched, as it does every
   idle slot's: the next chunk resumes that state.  The first
   sampled token stays ON DEVICE (fed to the next tick via the token
   feed) and its host emission is deferred to entry processing, so
   admission never syncs.  Chunk boundaries don't change prefill
   numerics (each K/V row depends on token + absolute position only;
   the oracle softmax sees the same columns), so streams match the
   synchronous engine bit for bit — asserted in tests.

   Dense/ring backends and speculative engines admit atomically
   through ``_admit_one``
   (speculative engines tick through ``engine.step()`` — the verify
   burst IS the decode stream); they still get admission control and
   metrics, and non-spec engines still get pipelined decode.

On top sit the serving policies the synchronous loop never had:
**admission control** (``max_queue`` bound — submit past it is shed
with a 429-style ``None``, never a stall), **priorities** (lower value
admits first; FIFO within a priority), and **per-request deadlines**
(a request still queued past its deadline is shed, not started).  All
request lifecycle events feed a :class:`~repro.runtime.metrics.
ServingMetrics` (TTFT, inter-token latency, queue depth, shed counts),
and every tick records spans into that metrics' span log (the engine's
too): ``sched.tick`` around ``sched.decode``, ``sched.chunk``,
``sched.admit``, ``sched.sync`` (the blocking device->host copies
alone) and ``sched.emit``.

Thread safety: one re-entrant lock guards every public method, so an
HTTP thread may ``submit``/``cancel`` while the engine thread runs
``tick`` — cancellation mid-prefill or mid-flight releases the slot's
pages and prefix-cache pins through ``ServeEngine._release_slot`` and
the allocator leak check stays clean (asserted in tests, cancelling at
every tick).

FAULT TOLERANCE (``max_retries`` / ``watchdog_timeout`` / ``degrade``
— any of them turns it on): every tick then runs inside a
snapshot/rollback envelope.  The scheduler captures the engine (device
pools cloned — the decode jits donate their cache, so an aliasing
snapshot would die with the next dispatch) plus its own queue/status
state at the tick boundary, runs the tick, and on ANY raised fault —
injected (``repro.runtime.faults``), organic, or a watchdog-detected
stall — restores both and retries.  Fault-tolerant mode forces
``pipeline_depth = 0``: a snapshot with dispatched-but-unprocessed
ticks in flight would capture device positions ahead of the host
mirror, so boundaries must be fully processed.  Retries REPLAY
deterministically — per-request sampler keys fold from ``(seed, uid)``,
so the retried stream is bit-identical to a never-failed run — and
tokens the client already saw before the rollback are suppressed by a
forwarded-count guard (``_fwd`` never rolls back; ``_progress`` does),
so streams observe each token exactly once.  A request that keeps
failing past ``max_retries`` is QUARANTINED: removed wherever it
lives, reported through ``errors[uid]`` as a structured record, and
its stream closed with a ``(None, True)`` failure sentinel.  Faults
that carry no uid (a poisoned batched decode) blame the oldest active
request once the anonymous failure streak passes the same budget.
A :class:`DegradePolicy` adds graceful degradation on top: each
recovered fault escalates one level (1: disable speculative bursts —
``spec_mode="match"`` makes that bit-identical; 2: halve the prefill
chunk window; 3: shed the lowest-priority queued request), and clean
ticks walk the level back down.

ELASTIC CAPACITY (``set_capacity`` / ``drain``): a health event can
shrink the scheduler to ``n`` concurrent slots — excess streams PARK
mid-generation (``ServeEngine.park_slot``: pages stay resident, the
slot frees) and resume bit-identically when capacity returns, oldest
first, ahead of fresh admissions.  ``drain`` stops admission entirely
(new submits shed with reason "draining") while in-flight streams
finish; ``undrain`` reopens.  ``runtime.elastic.ElasticSupervisor``
drives both from heartbeat state.
"""

from __future__ import annotations

import heapq
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.faults import fault_point
from repro.runtime.metrics import ServingMetrics
from repro.runtime.serve_loop import (Request, ServeEngine, _SlotState,
                                      host_copy)

QUEUED, PREFILL, ACTIVE = "queued", "prefill", "active"
DONE, SHED, CANCELLED = "done", "shed", "cancelled"
PARKED, FAILED = "parked", "failed"


class WatchdogTimeout(RuntimeError):
    """A tick exceeded the watchdog budget (stuck/poisoned dispatch)."""


@dataclass
class DegradePolicy:
    """Graceful-degradation ladder for the fault-tolerant scheduler.

    Each recovered fault escalates one level; ``recover_after``
    consecutive clean ticks walk one level back down:

    * level 1 — disable speculative bursts (``engine.spec_enabled``):
      with ``spec_mode="match"`` the emitted streams are bit-identical
      either way, so this is a pure blast-radius reduction;
    * level 2 — halve the prefill chunk window (floored at
      ``min_chunk``): smaller dispatches, smaller rollbacks;
    * level 3 — shed the lowest-priority queued request on each further
      escalation (load drops before latency does).
    """

    min_chunk: int = 8
    recover_after: int = 16

    def __post_init__(self):
        if self.min_chunk < 1:
            raise ValueError(f"min_chunk must be >= 1, got {self.min_chunk}")
        if self.recover_after < 1:
            raise ValueError(
                f"recover_after must be >= 1, got {self.recover_after}")


@dataclass(order=True)
class _QEntry:
    priority: int
    seq: int
    req: Request = field(compare=False)
    deadline: float | None = field(compare=False, default=None)


@dataclass
class _Entry:
    """One dispatched-but-unprocessed decode tick."""
    tok_dev: object                  # [slots] int32 on device (or None)
    active: list                     # [(slot, uid)] snapshot at dispatch
    admits: list = field(default_factory=list)   # [(slot, uid, tok[1] dev)]
    span: int = -1                   # id of the sched.decode span


@dataclass
class _Prefill:
    """A chunked admission in flight: positions [lo, n) still to write."""
    slot: int
    req: Request
    lo: int                          # frontier: next position to prefill
    n: int                           # prompt length


class PipelinedScheduler:
    """Asynchronous front-end scheduler over a :class:`ServeEngine`.

    Parameters
    ----------
    engine: the (idle) engine to drive.  The scheduler owns admission —
        don't mix with ``engine.submit``/``engine.run``.
    pipeline_depth: decode ticks allowed in flight past the host (0 =
        synchronous processing; 1 = classic host/device overlap).
    max_queue: queued-request bound; ``submit`` past it returns None
        (shed) instead of queueing — overload sheds, it never stalls.
    prefill_chunk: chunk-grid width for split-stream admission
        (default: the engine's ``prefill_chunk``, else 32).
    metrics: a ``ServingMetrics`` to record into (default: fresh one).
    max_retries: per-request retry budget after a recovered fault; past
        it the request is quarantined (status FAILED, ``errors[uid]``).
    watchdog_timeout: seconds one tick may take before it is treated as
        stuck — rolled back and retried like any other fault.
    degrade: a :class:`DegradePolicy` for graceful degradation.
    Setting any of the three enables fault-tolerant ticking (snapshot/
    rollback envelope; forces ``pipeline_depth = 0`` — every tick then
    pays one engine snapshot, the price of an exact rollback boundary).
    """

    def __init__(self, engine: ServeEngine, *, pipeline_depth: int = 1,
                 max_queue: int = 256, prefill_chunk: int | None = None,
                 metrics: ServingMetrics | None = None,
                 clock=time.monotonic, max_retries: int = 0,
                 watchdog_timeout: float | None = None,
                 degrade: DegradePolicy | None = None):
        if pipeline_depth < 0:
            raise ValueError(f"pipeline_depth must be >= 0, got "
                             f"{pipeline_depth}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        if watchdog_timeout is not None and watchdog_timeout <= 0:
            raise ValueError(
                f"watchdog_timeout must be > 0, got {watchdog_timeout}")
        if engine._active or engine._queue:
            raise ValueError("scheduler must take over an idle engine")
        self.engine = engine
        self.max_retries = max_retries
        self.watchdog_timeout = watchdog_timeout
        self.degrade = degrade
        self._ft = (max_retries > 0 or watchdog_timeout is not None
                    or degrade is not None)
        self.depth = (0 if engine._spec or self._ft else pipeline_depth)
        self.max_queue = max_queue
        self.chunk = max(1, prefill_chunk or engine.prefill_chunk or 32)
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.spans = engine.spans = self.metrics.spans
        self._clock = clock
        # dispatch-ahead ticks write past the host pos mirror: widen
        # every reservation by the pipeline depth (the speculative
        # engine's spec_k slack, generalized) BEFORE any admission
        engine._reserve_slack = self.depth
        self._chunked = engine.cache_kind == "paged" and not engine._spec
        engine._exact_prompts = self._chunked
        # recurrent (SSM) layers: a chunk after a prompt's first resumes
        # the slot's state, and the final chunk's tail pad must not
        # advance it
        self._ssm = bool(engine.model.cfg.ssm_positions)
        self._lock = threading.RLock()
        self._heap: list[_QEntry] = []
        self._seq = 0
        self._queued = 0                 # live (non-cancelled) heap entries
        self._status: dict[int, str] = {}
        self._streams: dict[int, object] = {}   # uid -> cb(tok, done)
        self._pipeline: deque[_Entry] = deque()
        self._prefill: _Prefill | None = None
        self._tok_dev = None             # [slots] device token feed
        self._park_mask = np.zeros((engine.slots,), bool)
        self._park_pos = np.zeros((engine.slots,), np.int32)
        self._chain_on_token = engine.on_token
        engine.on_token = self._on_token

        # .. fault-tolerance / elastic state ..
        self.errors: dict[int, dict] = {}      # uid -> structured failure
        self._retry_counts: dict[int, int] = {}
        self._fail_streak = 0                  # consecutive anonymous faults
        self._clean_ticks = 0
        self._degrade_level = 0
        self._base_chunk = self.chunk
        # emission dedup across rollbacks: ``_progress`` counts tokens
        # the ENGINE has emitted per uid (rolls back with the snapshot);
        # ``_fwd`` counts tokens the CLIENT has seen (never rolls back).
        # A retried tick re-emits history deterministically; _on_token
        # forwards a token only when progress passes the forwarded mark.
        self._progress: dict[int, int] = {}
        self._fwd: dict[int, int] = {}
        self._capacity = engine.slots
        self._draining = False

        model, sampler = engine.model, engine._sampler

        # Every jit below DONATES the cache it threads: the KV pool is
        # tens of MB, and a functional scatter without donation copies
        # all of it on every dispatch.  The scheduler's lineage is
        # strictly linear (each tick's cache feeds exactly the next
        # dispatch, nothing on the host retains the old buffers), so
        # XLA updates the pool in place and a decode tick or prefill
        # chunk costs only its compute.

        # ``pmask`` marks the slots the tick does not decode (parked
        # mid-admission, or idle): their position is pinned to ``ppos``
        # and their recurrent state, if any, left as it is

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

        self._greedy_tick = jax.jit(_greedy_tick, donate_argnums=(1,))
        self._sampled_tick = jax.jit(_sampled_tick, donate_argnums=(1,))

        # The chunk jits fuse view-gather -> apply -> pool merge into one
        # dispatch over the FULL layer tuple (slot traced, pos0 static):
        # splitting them would force the donated pool out through three
        # jit boundaries and re-copy it at each one.

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
            if self._ssm:   # the tail pad must not advance the recurrence
                tail = {"pad_mask": (jnp.arange(toks.shape[1])[None, :]
                                     <= last_index),
                        "start": jnp.zeros((1,), jnp.int32)}
            out = model.apply(params, tokens=toks, cache=c,
                              write_cache=True, last_only=True, pos0=pos0,
                              last_index=last_index, **tail)
            merged = tuple(f.admit(o, slot) if hasattr(f, "admit") else f
                           for f, o in zip(layers, out["cache"]["layers"]))
            return out["logits"][:, 0], merged

        self._chunk_mid = jax.jit(_chunk_mid, static_argnums=(4,),
                                  donate_argnums=(2,))
        self._chunk_last = jax.jit(_chunk_last, static_argnums=(4,),
                                   donate_argnums=(2,))

    # .. intake ..
    def submit(self, tokens, *, max_new_tokens: int = 32,
               temperature: float = 0.0, priority: int = 0,
               deadline: float | None = None, on_token=None) -> int | None:
        """Queue a request; returns its uid, or None when the queue is
        full (shed — the caller answers 429).  ``priority``: lower
        admits first (FIFO within a level).  ``deadline``: seconds from
        now; still queued past it, the request is shed instead of
        started.  ``on_token(tok, done)`` streams tokens as they are
        emitted (called under the scheduler lock — keep it quick)."""
        with self._lock:
            if self._draining:
                self.metrics.shed("draining")
                return None
            if self._queued >= self.max_queue:
                self.metrics.shed("queue_full")
                return None
            # engine.submit runs the capacity validation (prompt length
            # vs max_len, worst-case pages vs pool) and mints the uid;
            # the request then moves to the scheduler's own queue
            uid = self.engine.submit(tokens, max_new_tokens=max_new_tokens,
                                     temperature=temperature)
            req = self.engine._queue.pop()
            self._seq += 1
            heapq.heappush(self._heap, _QEntry(
                priority, self._seq, req,
                None if deadline is None else self._clock() + deadline))
            self._queued += 1
            self._status[uid] = QUEUED
            if on_token is not None:
                self._streams[uid] = on_token
            self.metrics.submitted(uid)
            return uid

    def cancel(self, uid: int) -> bool:
        """Abort ``uid`` wherever it is — queued, mid-prefill, or
        decoding.  Slot, pages, and prefix-cache pins are released
        (allocator leak check stays clean); no result is recorded.
        Returns False for unknown or already-terminal uids."""
        with self._lock:
            st = self._status.get(uid)
            if st not in (QUEUED, PREFILL, ACTIVE, PARKED):
                return False
            if st == QUEUED:
                self._queued -= 1        # heap entry dies lazily at pop
            elif st == PREFILL:
                pf = self._prefill
                assert pf is not None and pf.req.uid == uid
                self._prefill = None
                self._park_mask[pf.slot] = False
                self.engine._release_slot(pf.slot)
            elif st == PARKED:
                self.engine.drop_parked(uid)
            else:
                self.engine.cancel(uid)
            self._status[uid] = CANCELLED
            self._streams.pop(uid, None)
            self.metrics.cancelled(uid)
            return True

    def status(self, uid: int) -> str | None:
        with self._lock:
            return self._status.get(uid)

    @property
    def results(self) -> dict[int, list[int]]:
        with self._lock:
            return dict(self.engine._results)

    @property
    def busy(self) -> bool:
        with self._lock:
            return bool(self._queued or self.engine._active
                        or self._prefill or self._pipeline
                        or self.engine._parked)

    @property
    def state(self) -> str:
        """Serving state for readiness probes: "draining" | "degraded" |
        "ready" (the server layers "starting" on top before its loop
        spins up)."""
        with self._lock:
            if self._draining:
                return "draining"
            if self._degrade_level > 0:
                return "degraded"
            return "ready"

    # .. emission ..
    def _on_token(self, uid: int, tok: int, done: bool) -> None:
        cur = self._progress.get(uid, 0) + 1
        self._progress[uid] = cur
        if cur <= self._fwd.get(uid, 0):
            # deterministic replay of an already-delivered token (a
            # retried tick re-emitting history): the client saw this
            # exact token — record engine progress, forward nothing
            if done:
                self._status[uid] = DONE
            return
        self._fwd[uid] = cur
        self.metrics.token(uid)
        if done:
            self.metrics.finished(uid)
            self._status[uid] = DONE
        cb = self._streams.get(uid)
        if cb is not None:
            cb(tok, done)
            if done:
                del self._streams[uid]
        if self._chain_on_token is not None:
            self._chain_on_token(uid, tok, done)

    # .. decode stream ..
    def _feed(self):
        if self._tok_dev is None:
            self._tok_dev = host_copy(self.engine._next_tok)
        return self._tok_dev

    def _dispatch_decode(self) -> _Entry:
        eng = self.engine
        with self.spans.span("sched.decode", n=len(eng._active)) as sp:
            fault_point("decode.dispatch")
            # a dispatch-ahead tick writes up to len(pipeline) positions
            # past the host mirror: make that whole span write-safe first
            eng._map_tick_pages(len(self._pipeline))
            toks = self._feed()
            idle = np.ones((eng.slots,), bool)
            idle[list(eng._active)] = False
            pmask = host_copy(self._park_mask | idle)
            ppos = host_copy(self._park_pos)
            fault_point("sampler")
            if eng._temp.any() or eng._truncates:
                tok, eng._keys, eng.cache = self._sampled_tick(
                    eng.params, eng.cache, toks, pmask, ppos,
                    host_copy(eng._temp), eng._keys)
            else:
                tok, eng.cache = self._greedy_tick(
                    eng.params, eng.cache, toks, pmask, ppos)
            self._tok_dev = tok
            return _Entry(tok, [(s, st.req.uid) for s, st in
                                eng._active.items()], span=sp.id)

    def _process_entry(self, entry: _Entry) -> None:
        eng = self.engine
        # sched.sync holds only the blocking device->host copies (its
        # payload: the id of the sched.decode span whose tokens it waits
        # for, -1 for an admission's first token); emission, and the
        # releases it triggers, is sched.emit.  The decode tokens are
        # emitted before an admission's first token is synced: that one
        # waits for the admission's final chunk, dispatched after them.
        with self.spans.span("sched.sync", n=entry.span):
            toks = (None if entry.tok_dev is None
                    else np.asarray(entry.tok_dev))
        with self.spans.span("sched.emit") as sp:
            for slot, uid in entry.active:
                st = eng._active.get(slot)
                if st is None or st.req.uid != uid:
                    continue    # finished/cancelled after this dispatch:
                                # the in-flight token is discarded
                eng._pos[slot] += 1
                eng._emit(slot, int(toks[slot]))
                sp.n += 1
        for slot, uid, tdev in entry.admits:
            st = eng._active.get(slot)
            if st is None or st.req.uid != uid:
                continue        # cancelled between admission and here
            with self.spans.span("sched.sync", n=-1):
                tok = int(np.asarray(tdev)[0])
            with self.spans.span("sched.emit", n=1):
                eng._emit(slot, tok)

    # .. prefill stream ..
    def _pop_ready(self, now: float) -> Request | None:
        """Next admissible request: drops cancelled heap entries and
        sheds queued requests whose deadline already passed."""
        while self._heap:
            qe = heapq.heappop(self._heap)
            uid = qe.req.uid
            if self._status.get(uid) != QUEUED:
                continue                       # cancelled: lazy delete
            if qe.deadline is not None and now > qe.deadline:
                self._queued -= 1
                self._status[uid] = SHED
                self._streams.pop(uid, None)
                self.metrics.shed("deadline")
                continue
            self._qe_backout = qe              # for pool-dry re-push
            self._queued -= 1
            return qe.req
        return None

    def _push_back(self) -> None:
        heapq.heappush(self._heap, self._qe_backout)
        self._queued += 1
        self._status[self._qe_backout.req.uid] = QUEUED

    def _occupied(self) -> int:
        return len(self.engine._active) + (1 if self._prefill else 0)

    def _admit_loop(self, now: float) -> None:
        eng = self.engine
        # parked streams resume FIRST, oldest first — they were already
        # admitted once and their pages are still resident, so resuming
        # costs zero prefill and frees held capacity soonest
        while (eng._parked and eng._free
               and self._occupied() < self._capacity):
            uid = min(eng._parked)
            slot = eng.resume_parked(uid)
            self._status[uid] = ACTIVE
            self.metrics.resumed(uid)
            self._tok_dev = self._feed().at[slot].set(
                jnp.int32(int(eng._next_tok[slot])))
        if self._draining:
            return
        while (eng._free and self._prefill is None
               and self._occupied() < self._capacity):
            req = self._pop_ready(now)
            if req is None:
                return
            slot = eng._free[-1]
            if self._chunked:
                ok = self._start_admission(slot, req)
            else:
                t_admit = self.metrics.clock()
                with self.spans.span("sched.admit", uid=req.uid):
                    ok = eng._admit_one(slot, req)
                if ok:
                    eng._free.remove(slot)
                    self._status[req.uid] = ACTIVE
                    self.metrics.admitted(req.uid, now=t_admit)
                    if slot in eng._active:   # not done at token one
                        self._tok_dev = self._feed().at[slot].set(
                            jnp.int32(int(eng._next_tok[slot])))
            if not ok:
                self._push_back()             # pool dry: wait for an EOS
                return
            if self._prefill is not None:
                # a multi-chunk admission paces itself one chunk per
                # tick from here on; don't start another behind it
                self._advance_chunk()
                return

    def _start_admission(self, slot: int, req: Request) -> bool:
        """Begin split-stream admission: map the prompt's pages (shared
        prefix + fresh suffix, or without a prefix cache the whole
        prompt's) and either finish immediately (fully cached prompt —
        one peek dispatch) or park the slot and hand the rest to the
        chunk stream."""
        eng = self.engine
        n = len(req.tokens)
        with self.spans.span("sched.admit", uid=req.uid):
            fault_point("prefill.dispatch", uid=req.uid)
            if eng._prefix is not None:
                pos0 = eng._map_prefix(slot, req)
            else:
                pos0 = 0 if eng._alloc_pages(
                    slot, -(-n // eng.page_size),
                    eng._pages_needed(n, req.max_new_tokens)) else None
            if pos0 is None:
                return False
            eng._free.remove(slot)
            self.metrics.admitted(req.uid)
            if pos0 >= n:
                # fully cached: a read-only peek of the last token's logits
                view = eng._view(eng.cache["layers"], slot)
                toks = jnp.asarray([[req.tokens[-1]]], jnp.int32)
                logits = eng._peek(eng.params, toks, view, n - 1)
                self._complete_admission(slot, req, logits)
            else:
                self._prefill = _Prefill(slot, req, pos0, n)
                self._status[req.uid] = PREFILL
                self._park_mask[slot] = True
                self._park_pos[slot] = pos0
            return True

    def _advance_chunk(self) -> None:
        """Dispatch ONE grid-aligned prefill chunk for the admission in
        flight; the final chunk completes it.  Windows end at absolute
        multiples of ``self.chunk`` (capped at the block table's reach),
        so jit compiles once per (window shape, window start) — shared
        across prompts and match depths on the grid."""
        pf = self._prefill
        assert pf is not None
        eng, req, slot, lo = self.engine, pf.req, pf.slot, pf.lo
        cap = eng._pps * eng.page_size
        hi = min((lo // self.chunk + 1) * self.chunk, cap)
        real_hi = min(hi, pf.n)
        with self.spans.span("sched.chunk", uid=req.uid, n=real_hi - lo,
                             carry=int(self._ssm and lo > 0)):
            fault_point("prefill.dispatch", uid=req.uid)
            toks = jnp.asarray(
                [req.tokens[lo:real_hi]
                 + [eng.pad_id] * (hi - real_hi)], jnp.int32)
            slot_t = jnp.int32(slot)
            if real_hi < pf.n:
                eng.cache["layers"] = self._chunk_mid(
                    eng.params, toks, eng.cache["layers"], slot_t, lo)
                pf.lo = hi
                self._park_pos[slot] = hi     # frontier moved
            else:
                logits, eng.cache["layers"] = self._chunk_last(
                    eng.params, toks, eng.cache["layers"], slot_t, lo,
                    jnp.asarray(pf.n - 1 - lo, jnp.int32))
                self._prefill = None
                self._park_mask[slot] = False
                self._complete_admission(slot, req, logits)

    def _complete_admission(self, slot: int, req: Request, logits) -> None:
        """Activate the slot and sample the first token WITHOUT a host
        sync: the token stays on device (fed to the next decode tick),
        and its emission rides the pipeline as an admit record."""
        eng, n = self.engine, len(req.tokens)
        if eng._prefix is not None:
            eng._prefix.insert(
                req.tokens,
                [int(p) for p in eng._table[slot, :n // eng.page_size]])
        eng.cache["pos"] = eng.cache["pos"].at[slot].set(n)
        eng.cache["start"] = eng.cache["start"].at[slot].set(0)
        eng._pos[slot] = n
        eng._active[slot] = _SlotState(req)
        eng._temp[slot] = req.temperature
        eng._keys = eng._keys.at[slot].set(
            jax.random.fold_in(eng._seed_key, req.uid))
        fault_point("sampler", uid=req.uid)
        tok, krow = eng._sampler(
            logits, eng._keys[slot:slot + 1],
            jnp.full((1,), req.temperature, jnp.float32))
        eng._keys = eng._keys.at[slot].set(krow[0])
        self._tok_dev = self._feed().at[slot].set(tok[0])
        self._status[req.uid] = ACTIVE
        record = (slot, req.uid, tok)
        if self._pipeline:
            self._pipeline[-1].admits.append(record)
        else:
            self._pipeline.append(_Entry(None, [], [record]))

    # .. driving ..
    def tick(self) -> bool:
        """One scheduler tick: dispatch the next decode tick (if any
        slot is decoding), advance the prefill stream by one chunk /
        admission, then process pipeline entries beyond the allowed
        in-flight depth.  In fault-tolerant mode the whole tick runs
        inside a snapshot/rollback envelope (see the class docstring).
        Returns True while there is (or will be) work."""
        with self._lock, self.spans.span("sched.tick",
                                         n=len(self.engine._active)):
            if not self._ft:
                return self._tick_inner()
            return self._tick_ft()

    def _tick_inner(self) -> bool:
        now = self._clock()
        eng = self.engine
        if eng._spec:
            # speculative fallback: the draft/verify burst is its
            # own host-synced stream — admission control + metrics
            # apply, pipelining doesn't.  (A degraded spec engine —
            # spec_enabled off — still ticks here: engine.step()
            # falls back to plain decode internally.)
            self._admit_loop(now)
            if eng._active:
                eng.step()
            self._gauges()
            return self.busy
        dispatched = False
        if eng._active:
            self._pipeline.append(self._dispatch_decode())
            dispatched = True
        if self._prefill is not None:
            self._advance_chunk()
        self._admit_loop(now)
        limit = self.depth if dispatched else 0
        while len(self._pipeline) > limit:
            self._process_entry(self._pipeline.popleft())
        self._gauges()
        return self.busy

    # .. fault-tolerant envelope ..
    def _snap_all(self) -> tuple:
        pf = self._prefill
        return (self.engine.snapshot(), list(self._heap), self._queued,
                self._seq, dict(self._status), dict(self._progress),
                None if pf is None else _Prefill(pf.slot, pf.req, pf.lo,
                                                 pf.n),
                self._park_mask.copy(), self._park_pos.copy())

    def _restore_all(self, snap: tuple) -> None:
        (esnap, heap, queued, seq, status, progress, pf,
         park_mask, park_pos) = snap
        self.engine.restore(esnap)
        self._heap = list(heap)       # entries are never mutated in place
        self._queued = queued
        self._seq = seq
        self._status = dict(status)
        self._progress = dict(progress)
        self._prefill = (None if pf is None
                         else _Prefill(pf.slot, pf.req, pf.lo, pf.n))
        self._park_mask = park_mask.copy()
        self._park_pos = park_pos.copy()
        self._pipeline.clear()        # depth 0: nothing in flight anyway
        self._tok_dev = None          # feed rebuilds from the host mirror

    def _tick_ft(self) -> bool:
        snap = self._snap_all()
        t0 = self._clock()
        try:
            out = self._tick_inner()
            if (self.watchdog_timeout is not None
                    and self._clock() - t0 > self.watchdog_timeout):
                self.metrics.watchdog_trip()
                raise WatchdogTimeout(
                    f"tick exceeded the {self.watchdog_timeout}s watchdog "
                    "budget: treating the dispatch as stuck")
        except Exception as exc:                    # noqa: BLE001
            self._recover(snap, exc)
            return self.busy
        self._fail_streak = 0
        if self.degrade is not None and self._degrade_level:
            self._clean_ticks += 1
            if self._clean_ticks >= self.degrade.recover_after:
                self._degrade_level -= 1
                self._clean_ticks = 0
                self._apply_degrade()
        return out

    def _recover(self, snap: tuple, exc: Exception) -> None:
        """Roll back to the tick-boundary snapshot, attribute blame, and
        either retry (deterministic replay next tick) or quarantine."""
        site = getattr(exc, "site", None) or (
            "watchdog" if isinstance(exc, WatchdogTimeout) else "internal")
        self.metrics.fault(site)
        self._restore_all(snap)
        self.engine.check_leaks()     # rollback must leave zero drift
        uid = getattr(exc, "uid", None)
        if uid is not None:
            self._retry_counts[uid] = self._retry_counts.get(uid, 0) + 1
            if self._retry_counts[uid] > self.max_retries:
                self._quarantine(uid, exc, site)
            else:
                self.metrics.retried(uid)
        else:
            self._fail_streak += 1
            if self._fail_streak > self.max_retries:
                # an anonymous fault that keeps recurring: quarantine
                # the oldest in-flight request as the deterministic
                # scapegoat (poisoned batches are usually led by their
                # longest-lived member)
                victim = self._blame_victim()
                if victim is not None:
                    self._quarantine(victim, exc, site)
                self._fail_streak = 0
            else:
                self.metrics.retried()
        if self.degrade is not None:
            self._degrade_level = min(3, self._degrade_level + 1)
            self._clean_ticks = 0
            self._apply_degrade()
            if self._degrade_level >= 3:
                self._shed_worst()

    def _blame_victim(self) -> int | None:
        eng = self.engine
        if eng._active:
            return min(st.req.uid for st in eng._active.values())
        if self._prefill is not None:
            return self._prefill.req.uid
        live = [qe for qe in self._heap
                if self._status.get(qe.req.uid) == QUEUED]
        if live:
            return min(live).req.uid
        return None

    def _quarantine(self, uid: int, exc: Exception, site: str) -> None:
        """Fail ``uid`` permanently: release whatever it holds, record a
        structured error, and close its stream with a (None, True)
        failure sentinel so clients distinguish 'failed' from 'done'."""
        eng = self.engine
        st = self._status.get(uid)
        if st == QUEUED:
            self._queued -= 1              # heap entry dies lazily at pop
        elif st == PREFILL and self._prefill is not None \
                and self._prefill.req.uid == uid:
            slot = self._prefill.slot
            self._prefill = None
            self._park_mask[slot] = False
            eng._release_slot(slot)
        elif st == PARKED:
            eng.drop_parked(uid)
        else:
            eng.cancel(uid)
        self._status[uid] = FAILED
        self.errors[uid] = {
            "uid": uid,
            "site": site,
            "error": type(exc).__name__,
            "message": str(exc),
            "retries": self._retry_counts.get(uid, 0),
        }
        self.metrics.quarantined(uid)
        cb = self._streams.pop(uid, None)
        if cb is not None:
            cb(None, True)

    def _apply_degrade(self) -> None:
        lvl = self._degrade_level
        eng = self.engine
        if eng._spec:
            eng.spec_enabled = lvl < 1
        self.chunk = (self._base_chunk if lvl < 2 else
                      max(self.degrade.min_chunk, self._base_chunk // 2))
        self.metrics.set_degrade_level(lvl)

    def _shed_worst(self) -> None:
        live = [qe for qe in self._heap
                if self._status.get(qe.req.uid) == QUEUED]
        if not live:
            return
        victim = max(live, key=lambda qe: (qe.priority, qe.seq))
        self._queued -= 1                  # heap entry dies lazily at pop
        self._status[victim.req.uid] = SHED
        self._streams.pop(victim.req.uid, None)
        self.metrics.shed("degraded")

    # .. elastic capacity ..
    def set_capacity(self, n: int) -> None:
        """Shrink/grow to at most ``n`` concurrently-served slots.
        Shrinking below current occupancy PARKS the youngest active
        streams (pages stay resident; ``resume_parked`` continues them
        bit-identically when capacity returns).  Engines that cannot
        park (row backends, speculative, recurrent layers: the state
        stays in the slot) shrink by attrition: no new admissions until
        occupancy fits."""
        with self._lock:
            self._capacity = max(0, min(n, self.engine.slots))
            self._enforce_capacity()

    @property
    def capacity(self) -> int:
        with self._lock:
            return self._capacity

    def _enforce_capacity(self) -> None:
        eng = self.engine
        if eng.cache_kind != "paged" or eng._spec or self._ssm:
            return                         # attrition-only shrink
        # process in-flight ticks first so parking sees settled state
        while self._pipeline:
            self._process_entry(self._pipeline.popleft())
        self._tok_dev = None
        while self._occupied() > self._capacity and eng._active:
            slot = max(eng._active, key=lambda s: eng._active[s].req.uid)
            uid = eng.park_slot(slot)
            self._status[uid] = PARKED
            self.metrics.parked(uid)

    def drain(self) -> None:
        """Stop admitting: queued requests wait, new submits shed with
        reason "draining" (the HTTP layer answers 429), in-flight
        streams run to completion.  ``undrain`` reopens admission."""
        with self._lock:
            self._draining = True

    def undrain(self) -> None:
        with self._lock:
            self._draining = False

    def _gauges(self) -> None:
        self.metrics.set_queue_depth(self._queued,
                                     len(self.engine._active)
                                     + (1 if self._prefill else 0))

    def flush(self) -> None:
        """Drain the pipeline (host-sync every in-flight tick) and block
        until the device stream is quiet — THE stream-boundary barrier."""
        with self._lock:
            while self._pipeline:
                self._process_entry(self._pipeline.popleft())
            jax.block_until_ready(self.engine.cache["layers"])

    def run(self) -> dict[int, list[int]]:
        """Drive until every queued/active request drains, then flush,
        leak-check, and return ``{uid: emitted tokens}`` (shed and
        cancelled uids are absent — check ``status``)."""
        while self.tick():
            pass
        self.flush()
        self.engine.check_leaks()
        return self.results

    def stats(self) -> dict:
        """JSON-ready metrics document (see ``ServingMetrics.snapshot``),
        plus engine page/prefix-cache/spec counters when present."""
        with self._lock:
            eng = self.engine
            extra = {"state": self.state,
                     "capacity": self._capacity,
                     "parked": len(eng._parked),
                     "failed": len(self.errors)}
            if eng.page_stats is not None:
                extra["pages"] = eng.page_stats
            if self._ssm:
                extra["ssm_state_bytes"] = eng.ssm_state_bytes
            if eng.prefix_stats is not None:
                extra["prefix_cache"] = eng.prefix_stats
            return self.metrics.snapshot(
                spec_stats=dict(eng.spec_stats) if eng._spec else None,
                extra=extra)
