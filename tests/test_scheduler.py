"""PipelinedScheduler: bit-identity with the synchronous engine across
backends (paged+prefix chunked admission, dense atomic admission,
speculative fallback), admission-control policies (shed/priority/
deadline), cancellation at every pipeline stage with a clean
allocator leak check, and the spans of the serving path (one per tick,
sync, chunk, table update and request; on the profiler's host plane;
the hooks the benchmark's recording wrapper overrides)."""

import ast
import glob
import inspect
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models.transformer import build_model
from repro.runtime.metrics import ServingMetrics, SpanLog
from repro.runtime.scheduler import (ACTIVE, CANCELLED, DONE, PREFILL,
                                     QUEUED, SHED, PipelinedScheduler)
from repro.runtime.serve_loop import ServeEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced_config(get_config("qwen2.5-3b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n, *, prefix_len=6, seed=11, temps=(0.0, 0.9)):
    """Deterministic ragged request set sharing a common prefix."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, cfg.vocab_size, prefix_len).tolist()
    out = []
    for i in range(n):
        tail = rng.integers(1, cfg.vocab_size, int(rng.integers(2, 8)))
        out.append((prefix + tail.tolist(), 6, temps[i % len(temps)]))
    return out


def _sync_reference(model, params, reqs, **engine_kw):
    eng = ServeEngine(model, params, **engine_kw)
    for toks, mx, temp in reqs:
        eng.submit(toks, max_new_tokens=mx, temperature=temp)
    return eng.run()


class TestBitIdentity:
    """The pipelined scheduler must emit the exact token streams of the
    synchronous ``ServeEngine.run`` — same jits, same sampler keys."""

    def test_paged_prefix_chunked_matches_sync(self, tiny):
        cfg, model, params = tiny
        kw = dict(slots=2, max_len=64, seed=5, top_k=8)
        reqs = _requests(cfg, 6)
        ref = _sync_reference(model, params, reqs, **kw)

        eng = ServeEngine(model, params, **kw)
        sched = PipelinedScheduler(eng, pipeline_depth=2, prefill_chunk=4)
        for toks, mx, temp in reqs:
            assert sched.submit(toks, max_new_tokens=mx,
                                temperature=temp) is not None
        got = sched.run()
        assert got == ref
        assert all(sched.status(u) == DONE for u in got)

    def test_dense_backend_matches_sync(self, tiny):
        cfg, model, params = tiny
        kw = dict(slots=2, max_len=48, seed=5, cache_kind="dense")
        reqs = _requests(cfg, 4, temps=(0.0,))
        ref = _sync_reference(model, params, reqs, **kw)

        eng = ServeEngine(model, params, **kw)
        sched = PipelinedScheduler(eng, pipeline_depth=1)
        for toks, mx, temp in reqs:
            sched.submit(toks, max_new_tokens=mx, temperature=temp)
        assert sched.run() == ref

    def test_depth_zero_is_synchronous_processing(self, tiny):
        cfg, model, params = tiny
        kw = dict(slots=2, max_len=64, seed=5)
        reqs = _requests(cfg, 3)
        ref = _sync_reference(model, params, reqs, **kw)

        eng = ServeEngine(model, params, **kw)
        sched = PipelinedScheduler(eng, pipeline_depth=0, prefill_chunk=4)
        for toks, mx, temp in reqs:
            sched.submit(toks, max_new_tokens=mx, temperature=temp)
        assert sched.run() == ref

    def test_spec_fallback_matches_sync(self, tiny):
        """Speculative engines tick through engine.step() (the verify
        burst is the decode stream) — depth is forced to 0 and streams
        still match the sync spec engine bit for bit."""
        cfg, model, params = tiny
        kw = dict(slots=2, max_len=64, seed=5, draft_model=model,
                  draft_params=params, spec_k=2)
        reqs = _requests(cfg, 4, temps=(0.0,))
        ref = _sync_reference(model, params, reqs, **kw)

        eng = ServeEngine(model, params, **kw)
        sched = PipelinedScheduler(eng, pipeline_depth=3)
        assert sched.depth == 0
        for toks, mx, temp in reqs:
            sched.submit(toks, max_new_tokens=mx, temperature=temp)
        assert sched.run() == ref
        assert eng.spec_stats["accepted"] > 0

    def test_atomic_admission_done_at_first_token(self, tiny):
        """A request an atomic admission finishes at its first token is
        DONE and hands its slot to the next one."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=32, seed=5,
                          cache_kind="dense")
        sched = PipelinedScheduler(eng)
        uids = [sched.submit(p, max_new_tokens=1) for p in ([1, 2, 3], [4])]
        for _ in range(8):                        # bounded: no spin
            if not sched.tick():
                break
        assert [sched.status(u) for u in uids] == [DONE, DONE]
        assert sorted(sched.results) == uids

    def test_tight_pool_reserve_slack(self, tiny):
        """Dispatch-ahead ticks overshoot reservations by pipeline_depth
        positions: on a page pool too small to hold every request at
        once, admission must back off (never corrupt a neighbour's
        page) and streams stay bit-identical."""
        cfg, model, params = tiny
        kw = dict(slots=2, max_len=48, seed=5, page_size=4, pages=9)
        reqs = _requests(cfg, 5, prefix_len=4)
        ref = _sync_reference(model, params, reqs, **kw)

        eng = ServeEngine(model, params, **kw)
        sched = PipelinedScheduler(eng, pipeline_depth=2, prefill_chunk=4)
        assert eng._reserve_slack == 2
        for toks, mx, temp in reqs:
            assert sched.submit(toks, max_new_tokens=mx,
                                temperature=temp) is not None
        assert sched.run() == ref


class TestCancellation:
    def test_cancel_at_every_tick_leaks_clean(self, tiny):
        """Cancel one request at every pipeline stage (queued, parked
        mid-prefill, decoding, with in-flight dispatched ticks) while
        the rest keep serving; the allocator leak check must stay clean
        and survivors' streams must match the sync engine."""
        cfg, model, params = tiny
        kw = dict(slots=2, max_len=64, seed=5)
        reqs = _requests(cfg, 6)
        ref = _sync_reference(model, params, reqs, **kw)

        eng = ServeEngine(model, params, **kw)
        sched = PipelinedScheduler(eng, pipeline_depth=2, prefill_chunk=4)
        uids = [sched.submit(t, max_new_tokens=m, temperature=tp)
                for t, m, tp in reqs]
        cancel_at = {uids[1]: 0, uids[3]: 2, uids[4]: 5}
        tick = 0
        while sched.tick():
            for uid, at in cancel_at.items():
                if at == tick:
                    sched.cancel(uid)
                    eng.check_leaks()        # frees landed immediately
            tick += 1
        sched.flush()
        eng.check_leaks()
        got = sched.results
        for uid in uids:
            if sched.status(uid) == DONE:
                assert got[uid] == ref[uid]
            else:
                assert sched.status(uid) == CANCELLED
                assert uid not in got
        assert any(sched.status(u) == DONE for u in uids)
        assert sched.metrics.cancelled_total == sum(
            sched.status(u) == CANCELLED for u in uids)

    def test_cancel_mid_prefill_releases_slot(self, tiny):
        cfg, model, params = tiny
        rng = np.random.default_rng(3)
        eng = ServeEngine(model, params, slots=1, max_len=64, seed=5)
        sched = PipelinedScheduler(eng, prefill_chunk=4)
        long_prompt = rng.integers(1, cfg.vocab_size, 20).tolist()
        uid = sched.submit(long_prompt, max_new_tokens=4)
        sched.tick()                         # admission starts, slot parks
        assert sched.status(uid) == PREFILL
        assert sched.cancel(uid)
        assert not sched.cancel(uid)         # already terminal
        sched.flush()
        eng.check_leaks()
        assert len(eng._free) == 1           # slot returned to the pool
        # the freed slot serves a new request end to end
        uid2 = sched.submit(long_prompt[:6], max_new_tokens=3)
        res = sched.run()
        assert len(res[uid2]) == 3

    def test_cancel_queued_and_unknown(self, tiny):
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=32, seed=5)
        sched = PipelinedScheduler(eng, prefill_chunk=4)
        toks = [1, 2, 3]
        a = sched.submit(toks, max_new_tokens=2)
        b = sched.submit(toks, max_new_tokens=2)
        assert sched.cancel(b)               # still queued
        assert not sched.cancel(9999)        # unknown uid
        res = sched.run()
        assert a in res and b not in res
        assert sched.status(b) == CANCELLED


class TestAdmissionControl:
    def test_queue_full_sheds_with_none(self, tiny):
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=32, seed=5)
        sched = PipelinedScheduler(eng, max_queue=1, prefill_chunk=4)
        assert sched.submit([1, 2], max_new_tokens=2) is not None
        assert sched.submit([3, 4], max_new_tokens=2) is None
        assert sched.metrics.shed_counts == {"queue_full": 1}
        sched.run()

    def test_priority_orders_admission(self, tiny):
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=48, seed=5)
        sched = PipelinedScheduler(eng, prefill_chunk=4)
        first_token_order = []
        toks = [5, 6, 7, 8]

        def watcher(uid):
            def cb(tok, done):
                if uid not in first_token_order:
                    first_token_order.append(uid)
            return cb

        a = sched.submit(toks, max_new_tokens=3, priority=1,
                         on_token=watcher("a"))
        b = sched.submit(toks, max_new_tokens=3, priority=5,
                         on_token=watcher("b"))
        c = sched.submit(toks, max_new_tokens=3, priority=0,
                         on_token=watcher("c"))
        sched.run()
        assert first_token_order == ["c", "a", "b"]
        assert {sched.status(u) for u in (a, b, c)} == {DONE}

    def test_deadline_sheds_stale_queued_request(self, tiny):
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=48, seed=5)
        sched = PipelinedScheduler(eng, prefill_chunk=4)
        a = sched.submit([1, 2, 3, 4], max_new_tokens=8)
        b = sched.submit([5, 6, 7, 8], max_new_tokens=2, deadline=0.0)
        res = sched.run()
        assert sched.status(a) == DONE and len(res[a]) == 8
        assert sched.status(b) == SHED and b not in res
        assert sched.metrics.shed_counts.get("deadline") == 1

    def test_constructor_validation(self, tiny):
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=32, seed=5)
        with pytest.raises(ValueError, match="pipeline_depth"):
            PipelinedScheduler(eng, pipeline_depth=-1)
        with pytest.raises(ValueError, match="max_queue"):
            PipelinedScheduler(eng, max_queue=0)
        eng.submit([1, 2], max_new_tokens=1)
        with pytest.raises(ValueError, match="idle"):
            PipelinedScheduler(eng)


class TestMetricsWiring:
    def test_lifecycle_counts_and_latency_sections(self, tiny):
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=2, max_len=64, seed=5)
        metrics = ServingMetrics()
        sched = PipelinedScheduler(eng, pipeline_depth=1, prefill_chunk=4,
                                   metrics=metrics)
        reqs = _requests(cfg, 4, temps=(0.0,))
        for toks, mx, temp in reqs:
            sched.submit(toks, max_new_tokens=mx, temperature=temp)
        res = sched.run()
        total = sum(len(v) for v in res.values())
        snap = sched.stats()
        assert snap["requests"]["submitted"] == 4
        assert snap["requests"]["finished"] == 4
        assert snap["requests"]["in_flight"] == 0
        assert snap["tokens"]["emitted"] == total
        assert snap["ttft"]["count"] == 4
        assert snap["inter_token"]["count"] == total - 4
        assert snap["ttft"]["p99_us"] >= snap["ttft"]["p50_us"]
        assert "pages" in snap and "prefix_cache" in snap

    def test_status_transitions(self, tiny):
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=48, seed=5)
        sched = PipelinedScheduler(eng, prefill_chunk=4)
        uid = sched.submit(list(range(1, 11)), max_new_tokens=2)
        assert sched.status(uid) == QUEUED
        sched.tick()
        assert sched.status(uid) in (PREFILL, ACTIVE)
        sched.run()
        assert sched.status(uid) == DONE


class _CountingScheduler(PipelinedScheduler):
    """Records the uid of every prefill chunk it dispatches."""

    def __init__(self, engine, **kw):
        super().__init__(engine, **kw)
        self.chunk_uids = []

    def _advance_chunk(self):
        self.chunk_uids.append(self._prefill.req.uid)
        return super()._advance_chunk()


class TestSpans:
    def test_spans_of_a_served_batch(self, tiny):
        cfg, model, params = tiny
        kw = dict(slots=2, max_len=64, seed=5, top_k=8)
        reqs = _requests(cfg, 6)
        ref = _sync_reference(model, params, reqs, **kw)

        log = SpanLog()
        eng = ServeEngine(model, params, **kw)
        sched = _CountingScheduler(eng, pipeline_depth=1, prefill_chunk=4,
                                   metrics=ServingMetrics(spans=log))
        assert eng.spans is log
        set_tables, table_pushes = eng._set_tables, []

        def counting_set_tables(*a):
            table_pushes.append(1)
            return set_tables(*a)

        eng._set_tables = counting_set_tables
        uids = [sched.submit(t, max_new_tokens=m, temperature=tp)
                for t, m, tp in reqs]
        ticks = 1
        while sched.tick():
            ticks += 1
        sched.flush()
        eng.check_leaks()
        got = sched.results
        assert got == ref                    # spans change no stream

        ticks_ = log.select("sched.tick")
        assert len(ticks_) == ticks
        tick_ids = {t.id for t in ticks_}
        decodes = log.select("sched.decode")
        assert decodes and all(d.parent in tick_ids for d in decodes)
        assert all(0 < d.n <= 2 for d in decodes)
        syncs = log.select("sched.sync")
        # every decode tick is synced once, inside a tick or at the flush
        decode_syncs = [s for s in syncs if s.n >= 0]
        assert sorted(s.n for s in decode_syncs) == sorted(
            d.id for d in decodes)
        assert all(s.parent in tick_ids or s.parent == -1 for s in syncs)
        in_tick = [s for s in decode_syncs if s.parent in tick_ids]
        assert len(in_tick) >= len(decodes) - sched.depth
        for tick in ticks_:                  # a sync nests in its tick
            for s in (s for s in syncs if s.parent == tick.id):
                assert tick.t0 <= s.t0 <= s.t1 <= tick.t1
        emits = log.select("sched.emit")
        assert sum(e.n for e in emits) == sum(len(v) for v in got.values())

        chunks = log.select("sched.chunk")
        assert [c.uid for c in chunks] == sched.chunk_uids
        assert all(c.n > 0 for c in chunks) and set(sched.chunk_uids)
        admits = log.select("sched.admit")
        assert sorted(a.uid for a in admits) == sorted(uids)
        assert len(log.select("kv.set_tables")) == len(table_pushes) > 0
        releases = log.select("kv.release")
        assert sorted(r.uid for r in releases) == sorted(uids)
        prefills = log.select("req.prefill")
        assert sorted(r.uid for r in prefills) == sorted(uids)
        assert all(r.t0 <= r.t1 for r in prefills)
        # the queue wait is the histogram's alone
        assert sched.metrics.queue_wait.count == len(uids)
        assert not log.select("req.queued")

    def test_atomic_admission_spans(self, tiny):
        cfg, model, params = tiny
        log = SpanLog()
        eng = ServeEngine(model, params, slots=2, max_len=48, seed=5,
                          cache_kind="dense")
        sched = PipelinedScheduler(eng, metrics=ServingMetrics(spans=log))
        uids = [sched.submit(t, max_new_tokens=m, temperature=tp)
                for t, m, tp in _requests(cfg, 3, temps=(0.0,))]
        sched.run()
        for name in ("sched.admit", "req.prefill"):
            assert sorted(s.uid for s in log.select(name)) == uids
        assert not log.select("sched.chunk")

    def test_span_log_off_serves_the_same_streams(self, tiny):
        cfg, model, params = tiny
        kw = dict(slots=2, max_len=64, seed=5)
        reqs = _requests(cfg, 3)
        ref = _sync_reference(model, params, reqs, **kw)
        eng = ServeEngine(model, params, **kw)
        off = SpanLog(capacity=0)
        sched = PipelinedScheduler(eng, prefill_chunk=4,
                                   metrics=ServingMetrics(spans=off))
        for toks, mx, temp in reqs:
            sched.submit(toks, max_new_tokens=mx, temperature=temp)
        assert sched.run() == ref
        assert off.select() == [] and "spans" in sched.stats()

    def test_spans_on_the_profiler_host_plane(self, tiny, tmp_path):
        """A profiled run shows the spans as host events (the clock of
        the device ops) and each KV-table step under its own name."""
        from jax.profiler import ProfileData
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=32, seed=5)
        sched = PipelinedScheduler(eng, prefill_chunk=4,
                                   metrics=ServingMetrics(spans=SpanLog()))
        sched.submit([1, 2, 3, 4, 5], max_new_tokens=4)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            sched.run()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
        names = {e.name for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events}
        assert {"sched.tick", "sched.sync", "sched.decode", "sched.chunk",
                "sched.emit", "kv.set_tables", "kv.release"} <= names
        assert "PjitFunction(kv_set_tables)" in names
        assert not any("lambda" in n for n in names if "Pjit" in n)


class _SyncProbe:
    """An admission's first token on device; notes, when the host syncs
    it, how many of the entry's live decode streams had been emitted."""

    def __init__(self, tdev, sched, base, live, seen):
        self.tdev, self.sched, self.base = tdev, sched, base
        self.live, self.seen = live, seen

    def __array__(self, dtype=None, copy=None):
        emitted = self.sched.metrics.tokens_total - self.base
        self.seen.append((emitted, self.live))
        return np.asarray(self.tdev)


class _OrderScheduler(PipelinedScheduler):
    def __init__(self, engine, **kw):
        super().__init__(engine, **kw)
        self.seen = []

    def _process_entry(self, entry):
        eng = self.engine
        live = sum(1 for slot, uid in entry.active
                   if slot in eng._active and eng._active[slot].req.uid == uid)
        base = self.metrics.tokens_total
        entry.admits = [(s, u, _SyncProbe(t, self, base, live, self.seen))
                        for s, u, t in entry.admits]
        super()._process_entry(entry)


def test_decode_tokens_emitted_before_admission_sync(tiny):
    """An admission's first token rides the decode entry dispatched just
    before its final chunk; syncing it waits for that chunk.  The
    entry's decode tokens go out to their streams first, so an
    admission never delays the other streams' tokens of that tick."""
    cfg, model, params = tiny
    kw = dict(slots=2, max_len=64, seed=5, top_k=8)
    reqs = _requests(cfg, 6)
    ref = _sync_reference(model, params, reqs, **kw)
    eng = ServeEngine(model, params, **kw)
    sched = _OrderScheduler(eng, pipeline_depth=1, prefill_chunk=4)
    for toks, mx, temp in reqs:
        sched.submit(toks, max_new_tokens=mx, temperature=temp)
    assert sched.run() == ref
    assert any(live for _, live in sched.seen)   # admitted beside decodes
    assert all(emitted >= live for emitted, live in sched.seen)


def _recording_hooks() -> dict:
    """{method: parameter names} of ``RecordingScheduler`` in the
    benchmark's ``bench/system.py``, read from its source."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "system.py")
    tree = ast.parse(open(path).read())
    (cls,) = [n for n in tree.body if isinstance(n, ast.ClassDef)
              and n.name == "RecordingScheduler"]
    return {f.name: [a.arg for a in f.args.args]
            for f in cls.body if isinstance(f, ast.FunctionDef)}


def test_benchmark_recording_hooks_still_bind(tiny):
    """Every scheduler method the benchmark's recording wrapper
    overrides still exists with the same parameters, and the state it
    reads is still there: a renamed hook would silently stop it."""
    hooks = _recording_hooks()
    assert {"tick", "_dispatch_decode", "_advance_chunk",
            "_start_admission", "_process_entry"} <= set(hooks)
    for name, params in hooks.items():
        want = list(inspect.signature(
            getattr(PipelinedScheduler, name)).parameters)
        if name == "__init__":
            assert params[:2] == want[:2]
        else:
            assert params == want, name
    cfg, model, params = tiny
    eng = ServeEngine(model, params, slots=1, max_len=32, seed=5)
    sched = PipelinedScheduler(eng, prefill_chunk=4)
    for attr in ("_lock", "_status", "_queued", "_prefill", "chunk",
                 "_greedy_tick", "_chunk_last"):
        assert hasattr(sched, attr), attr
    for attr in ("_pos", "_active", "_pps", "page_size", "params",
                 "cache", "slots"):
        assert hasattr(eng, attr), attr
    sched.submit(list(range(1, 11)), max_new_tokens=2)
    sched.tick()
    assert {"lo", "n"} <= set(vars(sched._prefill))
    sched.run()


def test_engine_and_scheduler_dispatch_the_same_programs(tiny):
    """A paged engine has one set of device programs: the scheduler
    dispatches the engine's own tick and chunk programs, and the
    synchronous ``ServeEngine.run`` admits through the same chunk
    programs, window by window on the grid, and decodes through the
    same masked tick."""
    cfg, model, params = tiny
    eng = ServeEngine(model, params, slots=2, max_len=32, seed=5)
    sched = PipelinedScheduler(eng, prefill_chunk=4)
    for name in ("_greedy_tick", "_sampled_tick", "_chunk_mid",
                 "_chunk_last"):
        assert getattr(sched, name) is getattr(eng, name), name

    eng = ServeEngine(model, params, slots=2, max_len=32, seed=5,
                      prefill_chunk=4)
    eng.submit(list(range(1, 11)), max_new_tokens=3)
    assert len(eng.run()[0]) == 3
    # windows [0, 4) and [4, 8) write through, [8, 12) ends the prompt
    assert eng._chunk_mid._cache_size() == 2
    assert eng._chunk_last._cache_size() == 1
    assert eng._greedy_tick._cache_size() == 1
    assert eng._sampled_tick._cache_size() == 0
