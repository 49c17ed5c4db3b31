"""Serving metrics layer: histogram percentiles over a sliding window,
request-lifecycle accounting (TTFT / inter-token gaps), shed counters,
snapshot shape, and the span log (ring, nesting, windows) — all with a
pinned fake clock or fixed span times, no device work."""

import threading
import time

import numpy as np
import pytest

from repro.runtime.metrics import (SPANS, LatencyHistogram, ServingMetrics,
                                   SpanLog)


class TestLatencyHistogram:
    def test_empty(self):
        h = LatencyHistogram()
        assert h.percentile(50) is None
        assert h.mean_us is None
        snap = h.snapshot()
        assert snap == {"count": 0, "mean_us": None,
                        "p50_us": None, "p99_us": None}

    def test_exact_percentiles(self):
        h = LatencyHistogram()
        for ms in range(1, 101):            # 1..100 ms
            h.record(ms / 1e3)
        assert h.percentile(50) == pytest.approx(
            float(np.percentile(np.arange(1, 101), 50)) * 1e3)
        assert h.percentile(99) == pytest.approx(
            float(np.percentile(np.arange(1, 101), 99)) * 1e3)
        assert h.mean_us == pytest.approx(50.5e3)

    def test_window_slides_but_lifetime_counts_dont(self):
        h = LatencyHistogram(window=4)
        for s in [1.0, 1.0, 1.0, 1.0, 9.0, 9.0, 9.0, 9.0]:
            h.record(s)
        # window holds only the four 9s; count/mean stay lifetime
        assert h.percentile(50) == pytest.approx(9e6)
        assert h.count == 8
        assert h.mean_us == pytest.approx(5e6)

    def test_bad_window_raises(self):
        with pytest.raises(ValueError, match="window"):
            LatencyHistogram(window=0)

    def test_thread_safe_record(self):
        h = LatencyHistogram(window=64)

        def pound():
            for _ in range(500):
                h.record(0.001)

        ts = [threading.Thread(target=pound) for _ in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert h.count == 2000


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class TestServingMetrics:
    def test_ttft_and_itl_split(self):
        clk = FakeClock()
        m = ServingMetrics(clock=clk)
        m.submitted(1)
        clk.t += 0.5                        # 500ms to first token
        m.token(1)
        clk.t += 0.01
        m.token(1)
        clk.t += 0.03
        m.token(1)
        m.finished(1)
        assert m.ttft.count == 1
        assert m.ttft.percentile(50) == pytest.approx(0.5e6)
        assert m.itl.count == 2
        assert m.itl.percentile(50) == pytest.approx(0.02e6)

    def test_queue_wait_recorded_at_admission(self):
        clk = FakeClock()
        m = ServingMetrics(clock=clk)
        m.submitted(7)
        clk.t += 2.0
        m.admitted(7)
        assert m.queue_wait.percentile(50) == pytest.approx(2e6)

    def test_shed_by_reason_and_totals(self):
        m = ServingMetrics()
        m.shed("queue_full")
        m.shed("queue_full")
        m.shed("deadline")
        assert m.shed_total == 3
        snap = m.snapshot()
        assert snap["requests"]["shed"] == 3
        assert snap["requests"]["shed_by_reason"] == {
            "queue_full": 2, "deadline": 1}

    def test_cancelled_removes_live_request(self):
        clk = FakeClock()
        m = ServingMetrics(clock=clk)
        m.submitted(3)
        m.token(3)
        m.cancelled(3)
        m.token(3)                          # raced-out token: ignored
        snap = m.snapshot()
        assert snap["requests"]["cancelled"] == 1
        assert snap["requests"]["in_flight"] == 0
        assert snap["tokens"]["emitted"] == 1

    def test_tokens_per_s_and_queue_gauges(self):
        clk = FakeClock()
        m = ServingMetrics(clock=clk)
        m.submitted(1)
        for _ in range(10):
            clk.t += 0.1
            m.token(1)
        m.finished(1)
        assert m.tokens_per_s() == pytest.approx(10.0)
        m.set_queue_depth(5, active=2)
        m.set_queue_depth(1, active=1)
        snap = m.snapshot()
        assert snap["queue"] == {"depth": 1, "depth_peak": 5,
                                 "active_slots": 1}

    def test_spec_stats_acceptance_weighting(self):
        m = ServingMetrics()
        snap = m.snapshot(spec_stats={"ticks": 4, "drafted": 8,
                                      "accepted": 6, "emitted": 10})
        assert snap["spec_decode"]["acceptance"] == pytest.approx(0.75)
        snap = m.snapshot(spec_stats={"ticks": 0, "drafted": 0,
                                      "accepted": 0, "emitted": 0})
        assert snap["spec_decode"]["acceptance"] is None


class TestSpanLog:
    def test_ring_keeps_the_newest_oldest_first(self):
        log = SpanLog(capacity=4)
        for i in range(6):
            log.record("s", float(i), float(i) + 0.5, uid=i, n=10 * i)
        got = log.select("s")
        assert [s.uid for s in got] == [2, 3, 4, 5]
        assert [s.n for s in got] == [20, 30, 40, 50]
        assert [s.t1 for s in got] == [2.5, 3.5, 4.5, 5.5]
        assert len({s.id for s in got}) == 4

    def test_ring_reports_what_it_overwrote(self):
        log = SpanLog(capacity=3)
        log.record("s", 0.0, 4.0)
        log.record("s", 1.0, 2.0)
        log.record("s", 2.0, 3.0)
        assert log.lost_t1 == -np.inf          # full, nothing lost yet
        log.record("s", 3.0, 5.0)
        assert log.lost_t1 == 4.0
        log.record("s", 4.0, 6.0)              # the span ending at 2.0
        assert log.lost_t1 == 4.0              # the latest end is kept
        log.record("s", 5.0, 7.0)
        log.record("s", 6.0, 8.0)
        assert log.lost_t1 == 5.0
        assert [s.t1 for s in log.select()] == [6.0, 7.0, 8.0]

    def test_parent_linkage_across_nesting(self):
        log = SpanLog()
        with log.span("outer", uid=3) as outer:
            with log.span("mid") as mid:
                with log.span("inner", n=7):
                    pass
                log.record("rec", 0.0, 1.0)
            with log.span("sibling"):
                pass
        with log.span("after"):
            pass
        by = {s.name: s for s in log.select()}
        assert by["outer"].parent == -1 and by["outer"].uid == 3
        assert by["mid"].parent == outer.id == by["outer"].id
        assert by["inner"].parent == mid.id and by["inner"].n == 7
        assert by["rec"].parent == mid.id
        assert by["sibling"].parent == outer.id
        assert by["after"].parent == -1
        # children end first: the ring holds finished spans in end order
        assert [s.name for s in log.select()] == [
            "inner", "rec", "mid", "sibling", "outer", "after"]
        assert by["outer"].t0 <= by["mid"].t0 <= by["inner"].t0
        assert by["inner"].t1 <= by["mid"].t1 <= by["outer"].t1

    def test_payload_set_inside_the_block_and_kept_on_error(self):
        log = SpanLog()
        with pytest.raises(RuntimeError):
            with log.span("work") as sp:
                sp.uid, sp.n = 9, 4
                raise RuntimeError("boom")
        (s,) = log.select("work")
        assert (s.uid, s.n) == (9, 4)

    def test_thread_local_stacks(self):
        log = SpanLog()
        barrier = threading.Barrier(4)

        def worker(k):
            with log.span(f"root{k}"):
                barrier.wait(timeout=30)   # every root open at once
                with log.span(f"child{k}"):
                    barrier.wait(timeout=30)

        ts = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
        [t.start() for t in ts]
        [t.join(timeout=60) for t in ts]
        assert not any(t.is_alive() for t in ts)
        by = {s.name: s for s in log.select()}
        for k in range(4):
            assert by[f"child{k}"].parent == by[f"root{k}"].id
            assert by[f"root{k}"].parent == -1

    def test_select_window_on_end_time(self):
        log = SpanLog()
        for t0, t1 in [(0.0, 1.0), (0.5, 2.0), (2.5, 3.0), (1.0, 4.0)]:
            log.record("a", t0, t1)
        log.record("b", 1.5, 2.5)
        assert [s.t1 for s in log.select("a", 1.0, 3.0)] == [1.0, 2.0]
        assert [s.name for s in log.select(None, 2.0, 2.6)] == ["a", "b"]
        assert log.select("missing") == []

    def test_capacity_zero_records_nothing(self):
        log = SpanLog(capacity=0)
        with log.span("x", uid=1) as sp:
            sp.n += 2
        log.record("y", 0.0, 1.0)
        assert log.select() == [] and log.summary() == {}
        with pytest.raises(ValueError, match="capacity"):
            SpanLog(capacity=-1)

    def test_metrics_snapshot_has_span_summary(self):
        log = SpanLog()
        now = time.monotonic()
        for ms in range(1, 101):
            log.record("sched.tick", now - 1.0, now - 1.0 + ms / 1e3)
        log.record("old", now - 120.0, now - 119.0)   # outside the 60 s
        snap = ServingMetrics(spans=log).snapshot()
        assert set(snap["spans"]) == {"sched.tick"}
        tick = snap["spans"]["sched.tick"]
        assert tick["count"] == 100
        assert tick["p50_ms"] == pytest.approx(50.5, abs=1e-3)
        assert tick["p99_ms"] == pytest.approx(99.01, abs=1e-3)
        assert ServingMetrics().spans is SPANS

    def test_request_spans_split_ttft(self):
        clk, log = FakeClock(), SpanLog()
        m = ServingMetrics(clock=clk, spans=log)
        m.submitted(1)
        clk.t += 2.0
        m.admitted(1)
        clk.t += 0.5
        m.token(1)
        clk.t += 0.1
        m.token(1)
        (p,) = log.select("req.prefill")
        assert (p.t0, p.t1, p.uid) == (102.0, 102.5, 1)
        # the queue wait has one source, the histogram, and no span
        assert m.queue_wait.snapshot()["p50_us"] == pytest.approx(2e6)
        assert [s.name for s in log.select()] == ["req.prefill"]

    def test_request_spans_when_first_token_precedes_admitted(self):
        """Atomic admission emits the first token inside the admission;
        the scheduler reports ``admitted`` after, with its start time."""
        clk, log = FakeClock(), SpanLog()
        m = ServingMetrics(clock=clk, spans=log)
        m.submitted(4)
        clk.t += 0.5
        m.token(4)
        m.admitted(4, now=100.25)
        (p,) = log.select("req.prefill")
        assert (p.t0, p.t1, p.uid) == (100.25, 100.5, 4)
        assert m.queue_wait.snapshot()["p50_us"] == pytest.approx(2.5e5)
