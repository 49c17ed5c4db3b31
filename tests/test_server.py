"""HTTP/SSE front end: streaming completions, /metrics, error paths,
mid-stream client disconnect -> scheduler cancellation with a clean
allocator leak check, readiness states, injected socket-write faults,
failed-request reporting, slow-client backpressure, and clean
shutdown."""

import http.client
import json
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models.transformer import build_model
from repro.runtime.faults import Fault, FaultPlan
from repro.runtime.scheduler import PipelinedScheduler
from repro.runtime.serve_loop import ServeEngine
from repro.runtime.server import ServingServer


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced_config(get_config("qwen2.5-3b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def served(tiny_model):
    cfg, model, params = tiny_model
    eng = ServeEngine(model, params, slots=2, max_len=512, seed=7)
    sched = PipelinedScheduler(eng, pipeline_depth=1, prefill_chunk=8)
    srv = ServingServer(sched)
    host, port = srv.start()
    yield cfg, eng, sched, srv, host, port
    srv.stop()
    eng.check_leaks()


def _conn(served, timeout=600):
    _, _, _, _, host, port = served
    return http.client.HTTPConnection(host, port, timeout=timeout)


def _post(served, doc, timeout=600):
    c = _conn(served, timeout)
    c.request("POST", "/v1/completions", json.dumps(doc),
              {"Content-Type": "application/json"})
    return c, c.getresponse()


def _get_json(served, path):
    c = _conn(served, 60)
    c.request("GET", path)
    r = c.getresponse()
    body = json.loads(r.read())
    c.close()
    return r.status, body


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).tolist()


# The tiny model decodes ~1000 tokens/s on a fast host, so a 480-token
# stream can finish before the server sees the client go away, and the
# cancellation these tests assert never happens.  Each decode dispatch
# sleeps 10 ms under this fault: a stream then lasts seconds on any host.
PACE_DECODE = Fault("decode.dispatch", times=1 << 30, kind="hang",
                    seconds=0.01)


def test_healthz(served):
    status, body = _get_json(served, "/healthz")
    assert (status, body) == (200, {"ok": True, "state": "ready"})


def test_unknown_route_404(served):
    status, body = _get_json(served, "/nope")
    assert status == 404


def test_bad_body_400(served):
    for doc in ({}, {"tokens": []}, {"tokens": "abc"}, {"tokens": [1.5]}):
        c, r = _post(served, doc)
        assert r.status == 400, doc
        r.read()
        c.close()


def test_sse_stream_matches_final_event(served):
    cfg, eng, sched, *_ = served
    c, r = _post(served, {"tokens": _prompt(cfg, 12),
                          "max_new_tokens": 6})
    assert r.status == 200
    assert r.getheader("Content-Type") == "text/event-stream"
    events = [json.loads(ln[6:]) for ln in r.read().decode().splitlines()
              if ln.startswith("data: ")]
    c.close()
    assert events[-1]["done"] is True
    streamed = [e["token"] for e in events[:-1]]
    assert [e["index"] for e in events[:-1]] == list(range(len(streamed)))
    assert streamed == events[-1]["tokens"]
    assert len(streamed) == 6
    uid = events[-1]["uid"]
    assert sched.results[uid] == streamed


def test_non_streaming_collect(served):
    cfg, *_ = served
    c, r = _post(served, {"tokens": _prompt(cfg, 10, seed=1),
                          "max_new_tokens": 4, "stream": False,
                          "temperature": 0.8})
    assert r.status == 200
    body = json.loads(r.read())
    c.close()
    assert len(body["tokens"]) == 4
    assert all(isinstance(t, int) for t in body["tokens"])


def test_metrics_endpoint_shape_and_leak_probe(served):
    status, m = _get_json(served, "/metrics")
    assert status == 200
    assert m["leaks_clean"] is True
    assert m["requests"]["finished"] >= 2
    assert m["ttft"]["count"] >= 2
    assert m["inter_token"]["p99_us"] >= m["inter_token"]["p50_us"]
    assert "pages" in m and "prefix_cache" in m


def test_http_spans_per_streamed_token(served):
    """One ``http.write`` per streamed token (engine emission to its SSE
    event written) and one ``http.submit``, each with the request's uid;
    ``/metrics`` summarizes them with the rest of the serving path."""
    cfg, eng, sched, *_ = served
    spans = sched.spans
    t_start = time.monotonic()
    c, r = _post(served, {"tokens": _prompt(cfg, 9, seed=3),
                          "max_new_tokens": 5})
    events = [json.loads(ln[6:]) for ln in r.read().decode().splitlines()
              if ln.startswith("data: ")]
    c.close()
    uid = events[-1]["uid"]
    t_end = time.monotonic()
    writes = [s for s in spans.select("http.write", t_start, t_end)
              if s.uid == uid]
    assert len(writes) == len(events) - 1 == 5
    assert all(t_start <= s.t0 <= s.t1 <= t_end for s in writes)
    submits = [s for s in spans.select("http.submit", t_start, t_end)
               if s.uid == uid]
    assert len(submits) == 1 and submits[0].t1 <= writes[0].t0
    status, m = _get_json(served, "/metrics")
    assert status == 200
    for name in ("http.write", "http.submit", "sched.tick", "sched.sync",
                 "req.prefill"):
        assert m["spans"][name]["count"] >= 1, name
        assert m["spans"][name]["p99_ms"] >= m["spans"][name]["p50_ms"]


def test_disconnect_cancels_and_frees(served):
    """Close the client socket mid-stream on a long completion: the EOF
    watcher must cancel the request through the scheduler — slot and
    pages return to the pool and the leak probe stays clean."""
    cfg, eng, sched, *_ = served
    before = sched.metrics.cancelled_total
    with FaultPlan([PACE_DECODE]):
        c, r = _post(served, {"tokens": _prompt(cfg, 8, seed=2),
                              "max_new_tokens": 480})
        assert r.status == 200
        r.read(40)               # a couple of events, then walk away
        r.close()                # closes the socket fd (FIN/RST)
        c.close()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            _, m = _get_json(served, "/metrics")
            if (m["requests"]["cancelled"] > before
                    and m["queue"]["active_slots"] == 0):
                break
            time.sleep(0.2)
    assert m["requests"]["cancelled"] == before + 1
    assert m["leaks_clean"] is True
    assert m["queue"]["active_slots"] == 0


def test_oversized_body_413(served):
    # declare the oversized body without sending it: the server must
    # refuse on the header alone, before reading a single body byte
    c = _conn(served)
    c.putrequest("POST", "/v1/completions")
    c.putheader("Content-Type", "application/json")
    c.putheader("Content-Length", str((8 << 20) + 1))
    c.endheaders()
    r = c.getresponse()
    assert r.status == 413
    r.read()
    c.close()


def test_serving_continues_after_errors(served):
    """The server survives every error path above and still completes
    fresh requests (regression guard for handler-task leaks)."""
    cfg, *_ = served
    c, r = _post(served, {"tokens": _prompt(cfg, 6, seed=3),
                          "max_new_tokens": 3, "stream": False})
    assert r.status == 200
    assert len(json.loads(r.read())["tokens"]) == 3
    c.close()


def test_state_starting_until_started(tiny_model):
    cfg, model, params = tiny_model
    eng = ServeEngine(model, params, slots=2, max_len=64, seed=7)
    sched = PipelinedScheduler(eng)
    srv = ServingServer(sched)
    assert srv.state == "starting"       # constructed but not serving
    host, port = srv.start()
    try:
        c = http.client.HTTPConnection(host, port, timeout=60)
        c.request("GET", "/healthz")
        r = c.getresponse()
        assert r.status == 200 and json.loads(r.read())["state"] == "ready"
        c.close()
    finally:
        srv.stop()
    eng.check_leaks()


def test_healthz_draining_503_and_429(served):
    """A draining server flips readiness (load balancers stop routing)
    and answers new submissions 429 until undrained."""
    cfg, eng, sched, *_ = served
    sched.drain()
    try:
        status, body = _get_json(served, "/healthz")
        assert (status, body) == (503, {"ok": False, "state": "draining"})
        c, r = _post(served, {"tokens": _prompt(cfg, 6, seed=4),
                              "max_new_tokens": 2})
        assert r.status == 429
        assert json.loads(r.read())["error"] == "draining"
        c.close()
    finally:
        sched.undrain()
    status, body = _get_json(served, "/healthz")
    assert (status, body) == (200, {"ok": True, "state": "ready"})


def test_injected_write_fault_cancels_stream(served):
    """An injected socket-write fault mid-SSE behaves exactly like a
    vanished client: the request is cancelled through the scheduler and
    the leak probe stays clean."""
    cfg, eng, sched, *_ = served
    before = sched.metrics.cancelled_total
    with FaultPlan([Fault("server.write", at=3), PACE_DECODE]):
        c, r = _post(served, {"tokens": _prompt(cfg, 8, seed=5),
                              "max_new_tokens": 480})
        assert r.status == 200
        try:
            r.read()                     # server kills the stream mid-way
        except (http.client.HTTPException, ConnectionError, OSError):
            pass
        c.close()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            _, m = _get_json(served, "/metrics")
            if (m["requests"]["cancelled"] > before
                    and m["queue"]["active_slots"] == 0):
                break
            time.sleep(0.2)
    assert m["requests"]["cancelled"] == before + 1
    assert m["leaks_clean"] is True


def test_quarantined_request_reports_structured_error(tiny_model):
    """A request that exhausts its retry budget answers 500 (non-stream)
    with the scheduler's structured error attached, and the server keeps
    serving fresh requests afterwards."""
    cfg, model, params = tiny_model
    eng = ServeEngine(model, params, slots=2, max_len=64, seed=7)
    sched = PipelinedScheduler(eng, prefill_chunk=8, max_retries=1)
    srv = ServingServer(sched)
    host, port = srv.start()
    try:
        # the first request on a fresh engine is uid 0: pin the fault
        with FaultPlan([Fault("prefill.dispatch", uid=0, times=99)]):
            c = http.client.HTTPConnection(host, port, timeout=600)
            c.request("POST", "/v1/completions",
                      json.dumps({"tokens": _prompt(cfg, 8, seed=6),
                                  "max_new_tokens": 4, "stream": False}),
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            assert r.status == 500
            body = json.loads(r.read())
            c.close()
            assert body["error"] == "request failed"
            assert body["detail"]["site"] == "prefill.dispatch"
            assert body["detail"]["error"] == "InjectedFault"
            # uid 0 is quarantined; the next stream is untouched
            c = http.client.HTTPConnection(host, port, timeout=600)
            c.request("POST", "/v1/completions",
                      json.dumps({"tokens": _prompt(cfg, 6, seed=7),
                                  "max_new_tokens": 3, "stream": False}),
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            assert r.status == 200
            assert len(json.loads(r.read())["tokens"]) == 3
            c.close()
    finally:
        srv.stop()
    eng.check_leaks()
    assert sched.errors[0]["uid"] == 0


def test_slow_client_bounded_queue_disconnects(tiny_model):
    """A client that stops draining its stream: a hung socket write
    backs tokens up into the bounded per-stream queue; overflow is
    treated as a dead client — cancel + abort, no unbounded buffering,
    no leak."""
    cfg, model, params = tiny_model
    eng = ServeEngine(model, params, slots=2, max_len=512, seed=7)
    sched = PipelinedScheduler(eng, prefill_chunk=8)
    srv = ServingServer(sched, max_stream_queue=1)
    host, port = srv.start()
    # EVERY write hangs: the writer drains ~5 events/s while the paced
    # engine produces up to 100 — the bounded queue must overflow long
    # before the 400-token request finishes
    plan = FaultPlan([Fault("server.write", times=9999, kind="hang",
                            seconds=0.2), PACE_DECODE])
    try:
        with plan:
            c = http.client.HTTPConnection(host, port, timeout=600)
            c.request("POST", "/v1/completions",
                      json.dumps({"tokens": _prompt(cfg, 8, seed=8),
                                  "max_new_tokens": 400}),
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            assert r.status == 200
            deadline = time.monotonic() + 60
            while (sched.metrics.cancelled_total < 1
                   and time.monotonic() < deadline):
                time.sleep(0.2)
            try:
                r.read()
            except (http.client.HTTPException, ConnectionError, OSError):
                pass
            c.close()
    finally:
        srv.stop()
    assert sched.metrics.cancelled_total == 1
    assert any(f.site == "server.write" and f.kind == "hang"
               for f in plan.fired)
    eng.check_leaks()
