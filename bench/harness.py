"""One run of one cell: set up, drive the window, read the metrics, check.

``run_cell`` is the whole run minus the command line and the chip check
(``run.py`` adds those).  In order:

1. set-up: the program's persistent compile cache, every program kept;
   through the program adapter of the configuration's architecture
   (``programs/<r>.py``), the model and its weights drawn from ``--seed``
   on the device; the weights encoded as the configuration states;
   engine -> scheduler -> HTTP server on threads of this process, checked
   against the adapter (``system.serve``); the compiled decode tick and
   prefill chunk checked for the configuration's Pallas kernels; a
   warm-up burst over HTTP that compiles every chunk window the cell's
   prompts reach; then the ramp: the cell's own traffic until every
   client's request is decoding (each slot it can fill is active, none
   queued or in prefill), and at least ``RAMP_MIN_S`` seconds, so the
   window opens on the steady batch;
2. the window: ``--seconds`` of traffic from the JAX-free load generator
   (``loadgen.py``) in a child process, the device's bytes in use
   sampled all through it; with ``trace``, the profiler records
   ``TRACE_S`` seconds in its middle;
3. readings: the compiles inside the window, the end-to-end metrics (or,
   traced, the per-layer ones) through their readers in ``end_to_end/``
   and ``layer_metrics/``;
4. the check, once the program's state is freed: a sample of the
   requests the window served, drawn from the seed with the longest
   among them, is run through the configuration's plain reference; the
   widest gap by which a served token's logit lies below the reference's
   best must stay within the cell's limit (``correct.py``).
"""

from __future__ import annotations

import gc
import http.client
import json
import os
import subprocess
import sys
import threading
import time
from collections import Counter
from dataclasses import dataclass, field

import numpy as np

import correct
import spec
from traffic import Traffic

RAMP_MIN_S = 8.0       # the cell's own traffic before the window, at least
RAMP_MAX_S = 150.0     # the window opens then, batch filled or not
GRACE_S = 60.0         # how long the client waits past the window's end
TRACE_S = 3.0          # traced part of a --trace 1 window
MEMORY_EVERY_S = 0.1   # bytes in use are sampled this often in the window
WARM_TIMEOUT_S = 600   # a warm-up request compiles its chunk windows
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts programs compiled or loaded from the cache (one backend
    compile event each) while ``on``."""

    def __init__(self):
        import jax
        self.count, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name == COMPILE_EVENT:
            self.count += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._event)


class MemorySampler(threading.Thread):
    """The most bytes in use on the device, sampled until ``stop()``: what
    serving holds, not the process's high-water mark, which set-up (the
    EN-T encode holds bf16 weights and planes together) may have set."""

    def __init__(self, device):
        super().__init__(daemon=True)
        self.device, self.peak = device, 0
        self._done = threading.Event()

    def _sample(self):
        stats = self.device.memory_stats() or {}
        self.peak = max(self.peak, int(stats.get("bytes_in_use", 0)))

    def run(self):
        while not self._done.is_set():
            self._sample()
            self._done.wait(MEMORY_EVERY_S)

    def stop(self) -> int:
        self._done.set()
        self.join()
        self._sample()
        return self.peak


def _post(host: str, port: int, tokens: list, max_new: int) -> list:
    conn = http.client.HTTPConnection(host, port, timeout=WARM_TIMEOUT_S)
    try:
        conn.request("POST", "/v1/completions", json.dumps(
            {"tokens": tokens, "max_new_tokens": max_new,
             "temperature": 0.0, "stream": False}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        raise RuntimeError(f"warm-up request answered {resp.status}: "
                           f"{body[:200]!r}")
    return json.loads(body)["tokens"]


def warm_up(host: str, port: int, traffic: Traffic, chunk: int,
            seed: int) -> int:
    """Compile every prefill window the cell's prompts reach (a prompt of
    (w + 1) chunks ends in window w) and the decode tick.  Returns the
    number of warm-up requests."""
    rng = np.random.default_rng([seed % (1 << 64), 99])
    longest = traffic.longest_prompt()
    prompts = [rng.integers(0, traffic.vocab, min((w + 1) * chunk,
                                                  longest)).tolist()
               for w in range(-(-longest // chunk))]
    errors = []

    def one(p):
        try:
            _post(host, port, p, 4)
        except Exception as e:          # reported below, after the join
            errors.append(e)

    threads = [threading.Thread(target=one, args=(p,)) for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return len(prompts)


def batch_full(sched, clients: int) -> bool:
    """Every client's request decoding: as many slots active as the
    clients can fill, nothing queued, no prefill under way."""
    with sched._lock:
        return (len(sched.engine._active) >= min(clients, sched.engine.slots)
                and not sched._queued and sched._prefill is None)


def ramp(sched, clients: int, t_go: float) -> None:
    """Wait for the batch to fill (see the module's doc)."""
    while True:
        now = time.monotonic()
        if now - t_go >= RAMP_MAX_S or (now - t_go >= RAMP_MIN_S
                                        and batch_full(sched, clients)):
            return
        time.sleep(0.02)


@dataclass
class Context:
    """What the readers in ``end_to_end/`` and ``layer_metrics/`` read."""
    cell: spec.Cell
    program: object                # the cell's adapter, programs/<r>.py
    window: tuple                  # (start, end), time.monotonic()
    records: list                  # the client's, times absolute
    setup_s: float
    device_kind: str
    trace: object = None           # trace_reduce.Reduction
    trace_span: tuple | None = None
    decodes: list = field(default_factory=list)
    chunks: list = field(default_factory=list)

    @property
    def cfg(self) -> dict:
        return self.cell.config

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def peaks(self) -> dict:
        from peaks import peaks
        return peaks(self.device_kind)

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    def live(self, rec: dict) -> bool:
        """The request was in flight at some time in the window."""
        return (rec["sent"] is not None and rec["sent"] < self.window[1]
                and (not rec["times"] or rec["times"][-1] >= self.window[0]))


def _child(spec_doc: dict) -> subprocess.Popen:
    proc = subprocess.Popen(
        [sys.executable, os.path.join(spec.BENCH, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    proc.stdin.write(json.dumps(spec_doc) + "\n")
    proc.stdin.flush()
    if proc.stdout.readline().strip() != "ready":
        proc.kill()
        proc.wait()
        raise RuntimeError("the load generator did not start")
    return proc


def _say(proc: subprocess.Popen, word: str) -> None:
    proc.stdin.write(word + "\n")
    proc.stdin.flush()


def _trace_window(seconds: float, tmp: str) -> tuple:
    """Profile ``TRACE_S`` seconds (or a third of a shorter window) and
    return (host span, path of the .xplane.pb)."""
    import glob

    import jax
    span = min(TRACE_S, seconds / 3)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        a = time.monotonic()
        time.sleep(span)
        b = time.monotonic()
    jax.profiler.stop_trace()
    return (a, b), sorted(glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb"))[-1]


def _verdict(checks: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, control: bool = False) -> dict:
    """One run of ``cell``; returns the result document (see ``run.py``).
    ``control`` also puts the configuration's control in the program's
    place on the same sample and gives its verdict at the cell's limit
    (``control_correct``; the calibration's upper reading)."""
    import shutil
    import tempfile

    import jax

    import system

    system.enable_compile_cache()
    compiles = CompileCounter()
    cfg, wl = cell.config, cell.workload
    bench_cfg = cfg["bench"]
    prog = system.program(cell)
    model = system.build_model(prog.model_config(cfg))
    params = system.encode(prog.make_params(model, cfg, seed),
                           bench_cfg["weights"])
    eng, sched, srv = system.serve(model, params, wl["engine"], record=trace,
                                   prefix_cache=prog.PREFIX_CACHE)
    del params
    missing = system.missing_kernels(system.compiled_kernels(eng, sched),
                                     bench_cfg["kernels"])
    host, port = srv.start()
    dev = jax.devices()[0]
    child = None
    try:
        traffic = Traffic(cell.traffic, seed, cfg["vocab_size"])
        warm_up(host, port, traffic, sched.chunk, seed)
        child = _child({"host": host, "port": port, "mix": cell.traffic,
                        "seed": seed, "vocab": cfg["vocab_size"],
                        "clients": wl["clients"], "grace": GRACE_S})
        _say(child, "go")
        ramp(sched, wl["clients"], time.monotonic())
        compiles.on = True
        memory = MemorySampler(dev)
        memory.start()
        start = time.monotonic()
        window = (start, start + seconds)
        setup_s = start - t_start
        trace_span = trace_path = None
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        if trace:
            time.sleep(max(0.0, start + 0.4 * seconds - time.monotonic()))
            trace_span, trace_path = _trace_window(seconds, tmp)
        time.sleep(max(0.0, window[1] - time.monotonic()))
        _say(child, "stop")
        bytes_in_use = memory.stop()
        out = child.stdout.readline()
        child.wait(timeout=60)
        compiles.on = False
        client = json.loads(out)
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        system.cancel_all(sched)
        srv.stop()
    compiles.close()

    t0 = client["t0"]
    records = []
    for r in client["records"]:
        r = dict(r, times=[t0 + x for x in r["times"]])
        if r["sent"] is not None:
            r["sent"] += t0
        records.append(r)
    ctx = Context(cell=cell, program=prog, window=window, records=records,
                  setup_s=setup_s, device_kind=dev.device_kind)
    if trace:
        import trace_reduce
        ctx.trace = trace_reduce.reduce_trace(trace_path)
        ctx.trace_span = trace_span
        ctx.decodes, ctx.chunks = sched.decodes, sched.chunks
    shutil.rmtree(tmp, ignore_errors=True)
    leaks_clean = True
    try:
        eng.check_leaks()
    except AssertionError:
        leaks_clean = False
    del eng, sched, srv, model
    gc.collect()

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader("layer_metrics" if trace else "end_to_end",
                            m["name"], cell.root).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    live = [r for r in records if ctx.live(r)]
    bad = [r for r in live if r["status"] not in ("ok", "cut")]
    check = correct.check(cell, seed, records, window, control=control)
    limit = wl["correct"]["logit_gap"]
    checks = {"logit_gap": {"value": check["gap"], "limit": limit},
              "wrong_length": {"value": check["wrong_length"], "limit": 0},
              "failed_requests": {"value": len(bad), "limit": 0},
              "kernels_missing": {"value": len(missing), "limit": 0},
              "leaks": {"value": 0 if leaks_clean else 1, "limit": 0}}
    result = {
        "correct": _verdict(checks),
        "attempted": len(live), "failed": len(bad), "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": bytes_in_use},
        "compiles_in_window": compiles.count,
        "sample": {k: check[k] for k in ("requests", "served_tokens",
                                         "reference_s")},
        "missing_kernels": missing,
        "statuses": dict(Counter(r["status"] for r in records)),
        "dispatches": {
            "decode": sum(ctx.in_window(t) for t, _ in ctx.decodes),
            "chunk": sum(ctx.in_window(t) for t, *_ in ctx.chunks),
            "tokens_in_window": sum(ctx.in_window(t) for r in records
                                    for t in r["times"])},
    }
    if control:
        result["control_gap"] = check["control_gap"]
        result["control_correct"] = _verdict(dict(checks, logit_gap={
            "value": check["control_gap"], "limit": limit}))
    if trace:
        result["device"]["busy_s"] = ctx.trace.busy_s
        result["device"]["window_s"] = ctx.trace.window_s
        result["breakdown"] = {"device_ops": ctx.trace.device_ops(10),
                               "idle_gaps": [list(g) for g in
                                             ctx.trace.idle_gaps[:10]]}
    result["checks"] = checks
    return result
