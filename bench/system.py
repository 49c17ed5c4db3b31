"""The system under test, built from a configuration file, and the spans
and counters the benchmark takes from it.

This is the one module of the benchmark that imports the program
(``repro``).  It builds the model from the configuration's published
numbers, hands it the reference's weight draws in the program's own
parameter layout (one jitted call on the device), encodes them to EN-T
packed planes where the configuration says so, and stands up
``ServeEngine`` (paged cache, prefix cache) -> ``PipelinedScheduler`` ->
``ServingServer``, the path a deployment serves through.

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

from spec import ROOT

sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs.base import ModelConfig, QuantConfig  # noqa: E402
from repro.launch.env import enable_compile_cache as _program_cache  # noqa: E402
from repro.models.transformer import build_model  # noqa: E402
from repro.quant.quantize import quantize_params  # noqa: E402
from repro.runtime.scheduler import PipelinedScheduler  # noqa: E402
from repro.runtime.serve_loop import ServeEngine  # noqa: E402
from repro.runtime.server import ServingServer  # noqa: E402

from references import dense  # noqa: E402


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache``), keeping every program however fast it
    compiled: a warm compile of these programs takes about a second, under
    JAX's default threshold for caching."""
    path = _program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def model_config(cfg: dict) -> ModelConfig:
    """The program's config from a configuration file's published keys."""
    s = dense.sizes(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["hq"], num_kv_heads=s["hkv"],
        head_dim=s["hd"], d_ff=s["ff"], vocab_size=s["vocab"],
        qkv_bias=s["bias"], norm_eps=s["eps"], rope_theta=s["theta"],
        tie_embeddings=s["tied"], param_dtype="bfloat16",
        compute_dtype="bfloat16")


def make_params(model, cfg: dict, seed: int):
    """The reference's weight draws for ``seed`` in the program's layout,
    made on the device in one jitted call."""
    s = dense.sizes(cfg)

    @jax.jit
    def make(key):
        w = jax.vmap(lambda i: dense.layer_weights(cfg, key, i))(
            jnp.arange(s["layers"]))
        emb, norm, head = dense.embedding(cfg, key)
        rec = lambda k, b=None: ({"kernel": w[k]} if b is None
                                 else {"kernel": w[k], "bias": w[b]})
        bias = s["bias"]
        mixer = {"wq": rec("wq", "bq" if bias else None),
                 "wk": rec("wk", "bk" if bias else None),
                 "wv": rec("wv", "bv" if bias else None),
                 "wo": rec("wo")}
        p = {"embed": {"embedding": emb}, "final_norm": {"scale": norm},
             "groups": ({"mixer_norm": {"scale": w["attn_norm"]},
                         "mixer": mixer,
                         "ffn_norm": {"scale": w["mlp_norm"]},
                         "ffn": {"wi_gate": rec("wg"), "wi_up": rec("wu"),
                                 "wo": rec("wd")}},)}
        if head is not None:
            p["lm_head"] = {"kernel": head}
        return p

    key = dense.base_key(seed)
    want = jax.eval_shape(model.init, key)
    got = jax.eval_shape(make, key)
    shapes = lambda t: [(a.shape, a.dtype) for a in jax.tree.leaves(t)]
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or shapes(want) != shapes(got)):
        raise ValueError("the reference's weights do not fit the program's "
                         "parameter layout")
    return jax.block_until_ready(make(key))


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


def serve(model, params, engine_kw: dict, record: bool):
    """Engine -> scheduler -> HTTP server (not yet started): the path a
    deployment serves through.  Returns (engine, scheduler, server)."""
    eng = ServeEngine(model, params, prefix_cache="auto", seed=0, **engine_kw)
    if eng.cache_kind != "paged" or eng._prefix is None:
        raise RuntimeError("the engine did not come up paged with a prefix "
                           "cache")
    sched = RecordingScheduler(eng) if record else PipelinedScheduler(eng)
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
