"""The readers of the program's span log, on a synthetic log: a decode
loop one tick deep whose device takes 200 ms a tick and 100 ms a prefill
chunk, with two admissions, page-table pushes, releases and SSE writes.
Every reader gives None where the program has no span log."""

import sys
import types

import pytest

import spec
from repro.runtime.metrics import Span

TICK, CHUNK = 0.2, 0.1
NAMES = ("tick_ms", "admit_stall_ms", "host_tick_p95_ms",
         "table_updates_per_tick", "emit_lag_p95_ms")


class FakeLog:
    """What the readers use of ``SpanLog``: ``select(name, lo, hi)`` and
    ``lost_t1``."""

    def __init__(self):
        self.spans, self.next_id = [], 0
        self.lost_t1 = float("-inf")

    def add(self, name, t0, t1, parent=-1, uid=-1, n=0):
        self.spans.append(Span(name, t0, t1, self.next_id, parent, uid, n))
        self.next_id += 1
        return self.next_id - 1

    def select(self, name=None, lo=float("-inf"), hi=float("inf")):
        return sorted((s for s in self.spans if (name is None or s.name == name)
                       and lo <= s.t1 < hi), key=lambda s: s.t1)


def simulate(ticks=40, admit_at=(10, 25), chunks=(1, 2), blocking=False):
    """The host dispatches decode tick k, then any admission, then waits
    for tick k-1; the device runs what it is handed in order.  With
    ``blocking`` a chunk's dispatch returns only once the device has run
    it, as on the chip."""
    log, free, pending, h = FakeLog(), 0.0, None, 10.0
    for k in range(ticks):
        tick = log.add("sched.tick", h, 0.0)          # end set below
        log.add("kv.set_tables", h, h + 0.0005, parent=tick)
        dec = log.add("sched.decode", h, h + 0.001, parent=tick, n=64)
        free = max(h + 0.001, free) + TICK
        done = free
        t = h + 0.001
        if k in admit_at:
            uid = 100 + k
            log.add("sched.admit", t, t + 0.001, parent=tick, uid=uid)
            t += 0.001
            for _ in range(chunks[admit_at.index(k)]):
                log.add("sched.chunk", t, t + 0.001, parent=tick, uid=uid,
                        n=256)
                t += 0.001
                free = max(t, free) + CHUNK
                if blocking:
                    log.spans[-1] = log.spans[-1]._replace(t1=free)
                    t = free
            log.add("req.prefill", h, free + 0.5, uid=uid)
        if pending is not None:
            p_dec, p_done, p_first = pending
            end = max(t + 0.001, p_done)
            log.add("sched.sync", t + 0.001, end, parent=tick, n=p_dec)
            log.add("sched.emit", end, end + 0.002, parent=tick, n=64)
            if k == 30:
                log.add("kv.release", end, end + 0.001, parent=tick, uid=1)
            for i in range(64):
                log.add("http.write", end, end + (i + 1) * 1e-4, uid=i)
            t = end + 0.003
            if p_first is not None:     # an admission's token: its chunk
                ready = max(t, p_first)
                log.add("sched.sync", t, ready, parent=tick, n=-1)
                log.add("sched.emit", ready, ready + 1e-4, parent=tick, n=1)
                t = ready + 1e-4
        log.spans[tick] = log.spans[tick]._replace(t1=t)
        pending = (dec, done, free if k in admit_at else None)
        h = t + 0.0005
    return log


@pytest.fixture
def program(monkeypatch):
    def use(log):
        mod = types.SimpleNamespace() if log is None else \
            types.SimpleNamespace(SPANS=log)
        monkeypatch.setitem(sys.modules, "repro.runtime.metrics", mod)
    return use


def read(name, window, trace_span=None):
    ctx = types.SimpleNamespace(window=window, trace_span=trace_span)
    return spec.reader("layer_metrics", name).read(ctx)


def test_readers_on_a_synthetic_log(program):
    log = simulate()
    program(log)
    window = (10.0, 10.0 + 40 * TICK)
    assert read("tick_ms", window) == pytest.approx(1e3 * TICK, abs=1e-6)
    # one admission of one chunk, one of two: 100 ms a chunk in each
    assert read("admit_stall_ms", window) == pytest.approx(100.0, abs=1e-6)
    # the host's own time: 4.5 ms a plain tick, 5.5 one that admits
    assert 4.5 <= read("host_tick_p95_ms", window) <= 5.5
    decodes = sum(window[0] <= s.t0 and s.t1 < window[1]
                  for s in log.select("sched.decode"))
    assert read("table_updates_per_tick", window) == pytest.approx(
        (decodes + 1) / decodes)
    # lags 0.1-6.4 ms, 64 to a tick
    assert read("emit_lag_p95_ms", window) == pytest.approx(6.1, abs=0.02)


def test_stall_in_a_blocking_dispatch_is_counted(program):
    program(simulate(blocking=True))
    window = (10.0, 10.0 + 40 * TICK)
    assert read("tick_ms", window) == pytest.approx(1e3 * TICK, abs=0.1)
    # as before, plus the device's idle while the host dispatches again
    assert 100.0 < read("admit_stall_ms", window) < 110.0
    # the engine thread waits in the chunk's dispatch; that wait is not
    # its own time, even with an admission every fourth tick
    program(simulate(admit_at=tuple(range(2, 40, 4)), chunks=(1,) * 10,
                     blocking=True))
    assert 4.5 <= read("host_tick_p95_ms", window) <= 5.5


def test_window_and_traced_span_leave_spans_out(program):
    program(simulate())
    # the window holds only the first admission: its stall alone
    assert read("admit_stall_ms", (10.0, 13.5)) == pytest.approx(100.0)
    # the two-chunk admission alone: 200 ms over its two chunks
    assert read("admit_stall_ms", (14.0, 30.0)) == pytest.approx(100.0)
    # a traced span over the second admission hides it the same way
    assert read("admit_stall_ms", (10.0, 30.0),
                trace_span=(14.0, 16.0)) == pytest.approx(100.0)
    # no release outside the traced span: one table push per decode tick
    assert read("table_updates_per_tick", (10.0, 30.0),
                trace_span=(15.0, 17.0)) == pytest.approx(1.0)
    # a window before any sync: nothing to read
    for name in NAMES:
        assert read(name, (0.0, 1.0)) is None


@pytest.mark.parametrize("log", ["no module", "no SpanLog"])
@pytest.mark.parametrize("name", NAMES)
def test_none_without_a_span_log(program, monkeypatch, log, name):
    if log == "no module":
        monkeypatch.delitem(sys.modules, "repro.runtime.metrics",
                            raising=False)
    else:
        program(None)
    assert read(name, (0.0, 100.0)) is None


@pytest.mark.parametrize("name", NAMES)
def test_none_once_the_ring_lost_spans_of_the_window(program, name):
    log = simulate()
    window = (10.0, 10.0 + 40 * TICK)
    log.lost_t1 = 9.0                    # lost only spans before the window
    program(log)
    assert read(name, window) is not None
    log.lost_t1 = 10.5
    assert read(name, window) is None


def test_a_wrapped_ring_refuses_the_windows_it_lost(program):
    from repro.runtime.metrics import SpanLog
    log = SpanLog(capacity=8)
    for i in range(20):
        log.record("sched.tick", float(i), float(i) + 0.5)
    program(log)
    base = spec.reader("layer_metrics", "tick_ms")
    ctx = types.SimpleNamespace(window=(11.0, 30.0), trace_span=None)
    assert base.span_log(ctx) is None    # ended at 11.5, overwritten
    ctx.window = (12.0, 30.0)
    assert base.span_log(ctx) is log
