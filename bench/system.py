"""The system under test, built from a configuration file, and the spans
and counters the benchmark takes from it.

This module and the program adapters (``programs/<r>.py``, found by
``program``) are the benchmark's only code that imports the program
(``repro``); what is particular to an architecture lives in its
adapter.  Here the weights the adapter made are encoded to EN-T packed
planes where the configuration says so, and ``ServeEngine`` (paged
cache, with a prefix cache where the adapter says so) ->
``PipelinedScheduler`` (chunked admission) -> ``ServingServer`` is stood
up, the path a deployment serves through.

``RecordingScheduler`` is the benchmark's wrapper for a traced run: host
spans (``bench.tick``, ``bench.decode``, ``bench.chunk``, ``bench.sync``,
``bench.admit``) around the scheduler's steps, and a record of each
decode dispatch (the context of every active slot, from the engine's
host mirror of positions) and each prefill chunk.
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp

import spec

sys.path.insert(0, os.path.join(spec.ROOT, "src"))

from repro.configs.base import QuantConfig  # noqa: E402
from repro.launch.env import enable_compile_cache as _program_cache  # noqa: E402
from repro.models.transformer import build_model  # noqa: E402
from repro.quant.quantize import quantize_params  # noqa: E402
from repro.runtime.scheduler import PipelinedScheduler  # noqa: E402
from repro.runtime.serve_loop import ServeEngine  # noqa: E402
from repro.runtime.server import ServingServer  # noqa: E402


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache``), keeping every program however fast it
    compiled: a warm compile of these programs takes about a second, under
    JAX's default threshold for caching."""
    path = _program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def program(cell: spec.Cell):
    """The program adapter of the cell's architecture:
    ``programs/<bench.reference>.py`` of the cell's checkout."""
    return spec.reader("programs", cell.config["bench"]["reference"],
                       cell.root)


def encode(params, weights: str):
    """Serve the weights as the configuration states: ``bf16`` as drawn,
    ``ent`` as EN-T packed planes encoded on the device."""
    if weights == "bf16":
        return params
    if weights == "ent":
        return jax.block_until_ready(
            quantize_params(params, QuantConfig(enabled=True)))
    raise ValueError(f"unknown weight format {weights!r}")


class RecordingScheduler(PipelinedScheduler):
    """``PipelinedScheduler`` with host spans and dispatch records."""

    def __init__(self, engine, **kw):
        super().__init__(engine, **kw)
        self.decodes: list = []     # (t, [context of each active slot])
        self.chunks: list = []      # (t, lo, real tokens, final chunk)

    def tick(self):
        with jax.profiler.TraceAnnotation("bench.tick"):
            return super().tick()

    def _dispatch_decode(self):
        eng = self.engine
        self.decodes.append((time.monotonic(),
                             [int(eng._pos[s]) + 1 for s in eng._active]))
        with jax.profiler.TraceAnnotation("bench.decode"):
            return super()._dispatch_decode()

    def _advance_chunk(self):
        pf, eng = self._prefill, self.engine
        hi = min((pf.lo // self.chunk + 1) * self.chunk,
                 eng._pps * eng.page_size)
        self.chunks.append((time.monotonic(), pf.lo, min(hi, pf.n) - pf.lo,
                            hi >= pf.n))
        with jax.profiler.TraceAnnotation("bench.chunk"):
            return super()._advance_chunk()

    def _start_admission(self, slot, req):
        with jax.profiler.TraceAnnotation("bench.admit"):
            return super()._start_admission(slot, req)

    def _process_entry(self, entry):
        with jax.profiler.TraceAnnotation("bench.sync"):
            return super()._process_entry(entry)


def serve(model, params, engine_kw: dict, record: bool, prefix_cache: bool):
    """Engine -> scheduler -> HTTP server (not yet started): the path a
    deployment serves through.  Returns (engine, scheduler, server).

    The engine picks its cache for the model; it must come up paged, with
    a prefix cache exactly when the adapter says so (``prefix_cache``),
    and the scheduler must split admissions into chunks: the kernel
    check, the warm-up and the chunk records know no other prefill
    program, so an atomic admission would be measured on the wrong one."""
    eng = ServeEngine(model, params, prefix_cache="auto", seed=0, **engine_kw)
    if eng.cache_kind != "paged":
        raise RuntimeError(f"the engine came up with a {eng.cache_kind!r} "
                           "cache, not a paged one")
    has = eng._prefix is not None
    if has != prefix_cache:
        raise RuntimeError(
            f"the engine came up {'with' if has else 'without'} a prefix "
            f"cache, where the program adapter says it must "
            f"{'' if prefix_cache else 'not '}have one")
    sched = RecordingScheduler(eng) if record else PipelinedScheduler(eng)
    if not sched._chunked:
        raise RuntimeError(
            "the scheduler admits each request atomically, not in prefill "
            "chunks: the harness checks, warms up and records only the "
            "chunked prefill program")
    srv = ServingServer(sched)
    return eng, sched, srv


def cancel_all(sched) -> int:
    """Cancel every request still queued or in flight (the client has
    gone), so the server stops without decoding them to their end."""
    with sched._lock:
        live = [u for u, st in sched._status.items()
                if st in ("queued", "prefill", "active", "parked")]
        return sum(sched.cancel(u) for u in live)


def compiled_kernels(eng, sched) -> dict:
    """Compile the decode tick and a prefill chunk as the scheduler serves
    them and return their program text (``{"decode": ..., "chunk": ...}``),
    to show which kernels they hold."""
    slots = eng.slots
    decode = sched._greedy_tick.lower(
        eng.params, eng.cache, jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), bool), jnp.zeros((slots,), jnp.int32)).compile()
    chunk = sched._chunk_last.lower(
        eng.params, jnp.zeros((1, sched.chunk), jnp.int32),
        eng.cache["layers"], jnp.int32(0), 0,
        jnp.int32(sched.chunk - 1)).compile()
    return {"decode": decode.as_text(), "chunk": chunk.as_text()}


def missing_kernels(texts: dict, want: dict) -> list[str]:
    """Kernels the configuration names that a program does not hold (a
    Pallas kernel is a ``tpu_custom_call`` named after it)."""
    out = []
    for prog, names in want.items():
        text = texts[prog]
        if names and "tpu_custom_call" not in text:
            out.append(f"{prog}:tpu_custom_call")
        out += [f"{prog}:{n}" for n in names if n not in text]
    return out
