"""Pipelined prefill/decode scheduler: the async half of the serving stack.

``ServeEngine.run`` is a synchronous admit -> dispatch -> block loop:
every tick the host syncs the sampled tokens (``np.asarray``) before it
may dispatch the next, so the device idles during host bookkeeping, and
a long prompt's admission prefill stalls every in-flight decoder for
its full duration.  :class:`PipelinedScheduler` drives the SAME engine
— the engine's own admission steps and device programs, page allocator
and sampler keys; the scheduler decides only WHEN each runs — with two
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
   positions per request (``ServeEngine.reserve_ahead``), mirroring
   how speculative ticks reserve ``spec_k``, and every dispatch runs
   ``_map_tick_pages(in_flight)`` so the write can only land in a page
   this slot holds exclusively (or the compute-skipped null page) —
   never in prefix-shared bytes.  A processed entry whose slot was
   freed or re-admitted in the meantime is discarded by uid guard.

2. **Split prefill/decode streams.**  On a paged engine (with a prefix
   cache or without), the engine's admission steps run one per tick,
   not back to back as in ``ServeEngine.step``.  The prompt's unshared
   suffix prefills in grid-aligned chunks (windows of ``prefill_chunk``
   tokens at absolute multiples of it, so jit compile keys stay
   bounded: ``pos0`` is static in ``Model.apply``), ONE chunk dispatched per
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

   Dense/ring backends and speculative engines admit in one call
   (``ServeEngine.admit``), and speculative engines tick through
   ``engine.step()`` — the verify burst IS the decode stream; they
   still get admission control and metrics, and non-spec engines
   still get pipelined decode.

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
from repro.runtime.serve_loop import Request, ServeEngine, host_copy

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
        (default: the engine's ``chunk``).
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
        self.chunk = max(1, prefill_chunk or engine.chunk)
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.spans = engine.spans = self.metrics.spans
        self._clock = clock
        # dispatch-ahead ticks write past the host pos mirror: widen
        # every reservation by the pipeline depth BEFORE any admission
        engine.reserve_ahead(self.depth)
        self._chunked = engine.cache_kind == "paged" and not engine._spec
        # recurrent (SSM) layers: a chunk after a prompt's first resumes
        # the slot's state
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

        # the engine's programs; every decode tick dispatches through
        # these two names (a wrapper may swap them on the instance)
        self._greedy_tick = engine._greedy_tick
        self._sampled_tick = engine._sampled_tick
        self._chunk_mid = engine._chunk_mid
        self._chunk_last = engine._chunk_last

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
            # checked against the engine's capacity, queued here
            req = self.engine.new_request(
                tokens, max_new_tokens=max_new_tokens,
                temperature=temperature)
            uid = req.uid
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
            pmask = host_copy(self._park_mask | eng.idle_mask())
            ppos = host_copy(self._park_pos)
            tok = eng.decode_tick(self._greedy_tick, self._sampled_tick,
                                  toks, pmask, ppos)
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
                eng.emit_decoded(slot, int(toks[slot]))
                sp.n += 1
        for slot, uid, tdev in entry.admits:
            st = eng._active.get(slot)
            if st is None or st.req.uid != uid:
                continue        # cancelled between admission and here
            with self.spans.span("sched.sync", n=-1):
                tok = int(np.asarray(tdev)[0])
            with self.spans.span("sched.emit", n=1):
                eng.emit(slot, tok)

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
                    ok = eng.admit(slot, req)
                if ok:
                    self.metrics.admitted(req.uid, now=t_admit)
                    if slot in eng._active:   # not done at token one
                        self._status[req.uid] = ACTIVE
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
        """Begin split-stream admission and either finish immediately
        (fully cached prompt — one peek dispatch) or park the slot and
        hand the rest to the chunk stream."""
        eng, n = self.engine, len(req.tokens)
        with self.spans.span("sched.admit", uid=req.uid):
            pos0 = eng.begin_admission(slot, req)
            if pos0 is None:
                return False
            self.metrics.admitted(req.uid)
            if pos0 >= n:
                self._complete_admission(slot, req,
                                         eng.peek_admission(slot, req))
            else:
                self._prefill = _Prefill(slot, req, pos0, n)
                self._status[req.uid] = PREFILL
                self._park_mask[slot] = True
                self._park_pos[slot] = pos0
            return True

    def _advance_chunk(self) -> None:
        """Dispatch ONE grid-aligned prefill chunk for the admission in
        flight; the final chunk completes it.  Windows end at absolute
        multiples of ``self.chunk`` (``ServeEngine.chunk_end``)."""
        pf = self._prefill
        assert pf is not None
        eng, req, slot, lo = self.engine, pf.req, pf.slot, pf.lo
        hi = eng.chunk_end(lo, self.chunk)
        real_hi = min(hi, pf.n)
        with self.spans.span("sched.chunk", uid=req.uid, n=real_hi - lo,
                             carry=int(self._ssm and lo > 0)):
            logits = eng.admit_chunk(slot, req, lo, hi)
            if logits is None:
                pf.lo = hi
                self._park_pos[slot] = hi     # frontier moved
            else:
                self._prefill = None
                self._park_mask[slot] = False
                self._complete_admission(slot, req, logits)

    def _complete_admission(self, slot: int, req: Request, logits) -> None:
        """Activate the slot and sample the first token WITHOUT a host
        sync: the token stays on device (fed to the next decode tick),
        and its emission rides the pipeline as an admit record."""
        tok = self.engine.complete_admission(slot, req, logits)
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
