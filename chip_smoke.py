"""Chip smoke: the serving main path at Qwen2.5-3B's full width on one TPU.

    python chip_smoke.py                # one chip: kernels, serve-bf16, serve-ent
    python chip_smoke.py --four-chips   # 2x2 data x model training step only

Phases (each prints its own lines; any failure exits non-zero):

* device — JAX must see a TPU; otherwise exit 1 without a result.
* kernels — every main-path Pallas kernel at the model's shapes against
  its jnp oracle (the ops' ``use_kernel="ref"``), on the chip: the fused
  packed-plane EN-T matmul (decode M = 8, prefill M = 512, all four
  projection shapes), paged decode attention (bf16 and int8 KV) and the
  masked flash prefill (S = 512, ragged start).
* serve-bf16 / serve-ent — the full-width model (36 layers, random
  weights from ``--seed``) behind ``ServeEngine`` (paged, prefix cache)
  -> ``PipelinedScheduler`` -> ``ServingServer`` on a thread of this
  process; four greedy HTTP requests (two share a 256-token prefix, one
  streams over SSE) must answer 200, streams must end with ``done``,
  ``/metrics`` must report ``leaks_clean``, and every token stream must
  equal a synchronous ``ServeEngine.run`` of the same requests.  The
  compiled decode and prefill programs must hold the paged, flash (and,
  for EN-T, the fused packed) kernels — not their oracles.  serve-ent
  first encodes the bf16 weights into packed planes on the chip.
* --four-chips — only the sharded training step of ``launch/train.py``
  on a 2x2 data x model mesh against the same step on one device, at
  Qwen2.5-3B widths cut to 4 layers (the one-device side then needs
  ~10.5 GiB of 16 GB; 36 layers would need ~32 GiB).

The last line of stdout is ``{"ok": true, "device": {...}}``.  Timings
printed here are smoke figures, not benchmark results.
"""

from __future__ import annotations

import argparse
import gc
import http.client
import json
import os
import sys
import threading
import time
from dataclasses import replace

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen2.5-3b"
SLOTS, PAGE, MAX_LEN, CHUNK = 8, 16, 1024, 256
NEW_TOKENS, PREFIX = 32, 256
PROJ = [(2048, 2048), (2048, 256), (2048, 11008), (11008, 2048)]
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 8, 256, 4


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check_device(want: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devs[0].platform!r})", file=sys.stderr)
        sys.exit(1)
    if len(devs) < want:
        print(f"chip_smoke: needs {want} TPU chips, found {len(devs)}",
              file=sys.stderr)
        sys.exit(1)
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log("device", f"platform={dev['platform']} kind={dev['kind']} "
                  f"count={dev['count']}")
    return dev


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats().get("peak_bytes_in_use", 0))


def _compare(phase, name, got, want, bound, relative):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite kernel output"
    err = float(np.max(np.abs(got - want)))
    if relative:   # Frobenius error relative to the oracle's norm
        measure = float(np.linalg.norm(got - want)
                        / max(np.linalg.norm(want), 1e-30))
    else:          # max error relative to the oracle's largest entry
        measure = err / max(float(np.max(np.abs(want))), 1e-30)
    log(phase, f"{name}: max_abs_err={err:.3e} "
               f"{'rel_norm_err' if relative else 'rel_max_err'}="
               f"{measure:.3e} (bound {bound:g})")
    assert measure <= bound, f"{name}: kernel vs oracle {measure} > {bound}"


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.multiplier import ent_packed_planes
    from repro.kernels.ent_matmul import ops as ent_ops
    from repro.kernels.flash_attention import ops as attn_ops
    from repro.kernels.paged_attention import ops as paged_ops

    t0 = time.perf_counter()
    key = jax.random.PRNGKey(seed)
    fused = jax.jit(ent_ops.ent_quantized_matmul_fused,
                    static_argnames=("use_kernel",))
    for k, n in PROJ:
        key, kw, ks = jax.random.split(key, 3)
        w = jax.random.randint(kw, (k, n), -127, 128, jnp.int32)
        packed = jax.jit(ent_packed_planes)(w.astype(jnp.int8))
        sw = jax.random.uniform(ks, (1, n), jnp.float32, 1e-3, 2e-2)
        for m in (8, 512):
            key, kx = jax.random.split(key)
            x = jax.random.normal(kx, (m, k), jnp.bfloat16)
            got = fused(x, packed, sw, use_kernel="pallas")
            want = fused(x, packed, sw, use_kernel="ref")
            # int32 accumulation is exact; only the f32 epilogue rounds
            _compare("kernels", f"ent_fused M={m} K={k} N={n}", got, want,
                     1e-6, relative=False)

    hq, hkv, hd, pps = 16, 2, 128, MAX_LEN // PAGE
    npool = SLOTS * pps + 1
    rng = np.random.default_rng(seed)
    pos = rng.integers(64, MAX_LEN - 1, SLOTS).astype(np.int32)
    start = np.zeros(SLOTS, np.int32)
    start[-1] = 5                                   # one left-padded slot
    table = np.zeros((SLOTS, pps), np.int32)        # unmapped tail = null
    perm = rng.permutation(np.arange(1, npool)).reshape(SLOTS, pps)
    for b in range(SLOTS):
        used = int(pos[b]) // PAGE + 1
        table[b, :used] = perm[b, :used]
    paged = jax.jit(paged_ops.paged_attention,
                    static_argnames=("page_size", "use_kernel"))
    for kv in ("bf16", "int8"):
        key, kq, kk, kv_, ksk, ksv = jax.random.split(key, 6)
        q = jax.random.normal(kq, (SLOTS, hq, 1, hd), jnp.bfloat16)
        shape = (npool, hkv, PAGE, hd)
        scales = {}
        if kv == "int8":
            kp = jax.random.randint(kk, shape, -127, 128).astype(jnp.int8)
            vp = jax.random.randint(kv_, shape, -127, 128).astype(jnp.int8)
            sshape = shape[:-1] + (1,)
            scales = {"k_scales": jax.random.uniform(
                          ksk, sshape, jnp.float32, 5e-3, 2e-2
                      ).astype(jnp.bfloat16),
                      "v_scales": jax.random.uniform(
                          ksv, sshape, jnp.float32, 5e-3, 2e-2
                      ).astype(jnp.bfloat16)}
        else:
            kp = jax.random.normal(kk, shape, jnp.bfloat16)
            vp = jax.random.normal(kv_, shape, jnp.bfloat16)
        args = (q, kp, vp, jnp.asarray(table), jnp.asarray(pos),
                jnp.asarray(start))
        got = paged(*args, page_size=PAGE, use_kernel="pallas", **scales)
        want = paged(*args, page_size=PAGE, use_kernel="ref", **scales)
        _compare("kernels", f"paged_attention kv={kv} B={SLOTS} "
                            f"pool={npool}x{hkv}x{PAGE}x{hd}", got, want,
                 2e-2, relative=True)

    s = 512
    key, kq, kk, kv_ = jax.random.split(key, 4)
    q = jax.random.normal(kq, (2, hq, s, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (2, hkv, s, hd), jnp.bfloat16)
    v = jax.random.normal(kv_, (2, hkv, s, hd), jnp.bfloat16)
    st = jnp.asarray([0, 77], jnp.int32)           # ragged left padding
    flash = jax.jit(attn_ops.masked_attention,
                    static_argnames=("use_kernel",))
    got = flash(q, k, v, start=st, use_kernel="pallas")
    want = flash(q, k, v, start=st, use_kernel="ref")
    _compare("kernels", f"flash_attention_masked S={s} start=[0,77]",
             got, want, 2e-2, relative=True)
    log("kernels", f"ok in {time.perf_counter() - t0:.1f}s")


def make_requests(vocab: int, seed: int):
    """Four greedy requests, prompts 200..456 tokens: A and B share a
    256-token prefix, D streams over SSE."""
    import numpy as np
    rng = np.random.default_rng(seed)
    tok = lambda n: rng.integers(0, vocab, n).tolist()
    prefix = tok(PREFIX)
    return [
        {"tokens": prefix + tok(128), "stream": False},     # A: 384
        {"tokens": prefix + tok(200), "stream": False},     # B: 456
        {"tokens": tok(320), "stream": False},              # C: 320
        {"tokens": tok(200), "stream": True},               # D: 200, SSE
    ]


def _post(host, port, req):
    body = dict(req, max_new_tokens=NEW_TOKENS, temperature=0.0)
    conn = http.client.HTTPConnection(host, port, timeout=900)
    conn.request("POST", "/v1/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    raw = resp.read().decode()
    conn.close()
    if resp.status != 200:
        return resp.status, None, raw[:300]
    if not req["stream"]:
        return 200, json.loads(raw)["tokens"], None
    events = [json.loads(line[6:]) for line in raw.splitlines()
              if line.startswith("data: ")]
    if not events or not events[-1].get("done"):
        return 200, None, "SSE stream did not end with done"
    streamed = [e["token"] for e in events[:-1]]
    if streamed != events[-1]["tokens"]:
        return 200, None, "SSE tokens differ from the done record"
    return 200, streamed, None


def _kernels_in(program_text: str, want) -> None:
    assert "tpu_custom_call" in program_text, "no Pallas kernel compiled"
    for name in want:
        assert name in program_text, f"kernel {name!r} not in the program"


def phase_serve(phase: str, model, params, requests, kernels) -> None:
    import jax.numpy as jnp
    from repro.runtime.scheduler import PipelinedScheduler
    from repro.runtime.serve_loop import ServeEngine
    from repro.runtime.server import ServingServer

    engine_kw = dict(slots=SLOTS, max_len=MAX_LEN, page_size=PAGE,
                     prefill_chunk=CHUNK, prefix_cache="auto", seed=0)
    t0 = time.perf_counter()
    eng = ServeEngine(model, params, **engine_kw)
    sched = PipelinedScheduler(eng)
    assert eng.cache_kind == "paged" and eng._prefix is not None

    # the programs the scheduler serves with: compile them up front and
    # read which kernels they hold (the persistent cache then serves the
    # scheduler's own first calls)
    tc = time.perf_counter()
    slot_mask = jnp.zeros((SLOTS,), bool)
    decode = sched._greedy_tick.lower(
        eng.params, eng.cache, jnp.zeros((SLOTS,), jnp.int32), slot_mask,
        jnp.zeros((SLOTS,), jnp.int32)).compile()
    t_dec = time.perf_counter() - tc
    _kernels_in(decode.as_text(), kernels["decode"])
    tc = time.perf_counter()
    prefill = sched._chunk_last.lower(
        eng.params, jnp.zeros((1, CHUNK), jnp.int32), eng.cache["layers"],
        jnp.int32(0), 0, jnp.int32(CHUNK - 1)).compile()
    t_pre = time.perf_counter() - tc
    _kernels_in(prefill.as_text(), kernels["prefill"])
    del decode, prefill
    log(phase, f"compile: decode {t_dec:.1f}s, prefill chunk {t_pre:.1f}s; "
               f"decode holds {kernels['decode']}, prefill holds "
               f"{kernels['prefill']}")

    srv = ServingServer(sched)
    host, port = srv.start()
    try:
        out = [None] * len(requests)

        def client(i):
            out[i] = _post(host, port, requests[i])

        th = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(requests))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_http = time.perf_counter() - th
        for i, (status, toks, err) in enumerate(out):
            assert status == 200, f"request {i}: HTTP {status} {err}"
            assert err is None and toks is not None, f"request {i}: {err}"
            assert len(toks) == NEW_TOKENS, (i, len(toks))
        conn = http.client.HTTPConnection(host, port, timeout=120)
        conn.request("GET", "/metrics")
        metrics = json.loads(conn.getresponse().read())
        conn.close()
        assert metrics["leaks_clean"], "page allocator leak after serving"
    finally:
        srv.stop()
    prefix = metrics.get("prefix_cache", {})
    log(phase, f"http: {len(requests)} requests 200, SSE done, leaks_clean; "
               f"prefix-cache hits {prefix.get('hits')}/"
               f"{prefix.get('lookups')}")
    served = sum(len(o[1]) for o in out)
    log(phase, f"smoke figure (not a benchmark): {served} tokens in "
               f"{t_http:.2f}s over HTTP = {served / t_http:.1f} tok/s")
    del sched, srv, eng
    gc.collect()

    ref = ServeEngine(model, params, **engine_kw)
    uids = [ref.submit(r["tokens"], max_new_tokens=NEW_TOKENS)
            for r in requests]
    results = ref.run()
    for i, uid in enumerate(uids):
        assert out[i][1] == [int(t) for t in results[uid]], (
            f"request {i}: HTTP stream != ServeEngine.run")
    del ref
    gc.collect()
    log(phase, f"HTTP streams == ServeEngine.run for all {len(requests)} "
               f"requests; phase {time.perf_counter() - t0:.1f}s; "
               f"peak_bytes_in_use {peak_bytes()}")


def phase_train_mesh(seed: int) -> None:
    import jax
    import numpy as np
    from repro.configs import get_config, get_optim
    from repro.configs.base import TrainConfig
    from repro.data.pipeline import SyntheticSource, TokenStream
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_train

    cfg = replace(get_config(ARCH), num_layers=TRAIN_LAYERS)
    ocfg = get_optim(ARCH)
    tcfg = TrainConfig(seq_len=TRAIN_SEQ, global_batch=TRAIN_BATCH,
                       remat="full")
    stream = TokenStream(SyntheticSource(cfg.vocab_size, seed=1234),
                         global_batch=TRAIN_BATCH, seq_len=TRAIN_SEQ)
    batches = [stream.next() for _ in range(TRAIN_STEPS)]
    log("train-mesh", f"{ARCH} widths cut to {TRAIN_LAYERS} layers "
                      f"({cfg.param_count() / 1e9:.2f}B params), batch "
                      f"{TRAIN_BATCH}x{TRAIN_SEQ}, remat full")

    def run(shape, devices):
        t0 = time.perf_counter()
        mesh = make_mesh(shape, ("data", "model"), devices=devices)
        params, opt, step, bsh = build_train(cfg, mesh, ocfg, tcfg,
                                             seed=seed)
        hist = []
        with mesh:
            for b in batches:
                batch = {k: jax.device_put(v, bsh) for k, v in b.items()}
                params, opt, m = step(params, opt, batch)
                hist.append((float(m["loss"]), float(m["grad_norm"])))
        del params, opt
        gc.collect()
        log("train-mesh", f"mesh {shape[0]}x{shape[1]}: losses "
                          f"{[round(h[0], 5) for h in hist]}, step-0 "
                          f"grad_norm {hist[0][1]:.5f} "
                          f"({time.perf_counter() - t0:.1f}s)")
        return hist

    one = run((1, 1), jax.devices()[:1])
    four = run((2, 2), jax.devices()[:4])
    checks = [("step-0 loss", one[0][0], four[0][0], 1e-3),
              ("step-0 grad_norm", one[0][1], four[0][1], 1e-2),
              (f"loss after {TRAIN_STEPS - 1} steps", one[-1][0],
               four[-1][0], 2e-3)]
    for name, a, b, rtol in checks:
        rel = abs(a - b) / max(abs(a), 1e-30)
        log("train-mesh", f"{name}: 1 chip {a:.6f} vs 2x2 {b:.6f} "
                          f"(rel diff {rel:.2e}, bound {rtol:g})")
        assert np.isfinite(b) and rel <= rtol, f"{name} differs: {rel}"
    log("train-mesh", f"ok; peak_bytes_in_use(dev0) {peak_bytes()}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 sharded training-step parity "
                         "phase (needs four chips)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    args = ap.parse_args()

    dev = check_device(4 if args.four_chips else 1)
    from repro.launch.env import enable_compile_cache
    log("device", f"compile cache: {enable_compile_cache()}")

    if args.four_chips:
        phase_train_mesh(args.seed)
    else:
        import jax
        from repro.configs import get_config
        from repro.configs.base import QuantConfig
        from repro.models.transformer import build_model
        from repro.quant.quantize import quantize_params

        phase_kernels(args.seed)
        cfg = get_config(ARCH)
        assert cfg.param_dtype == "bfloat16"
        model = build_model(cfg)
        t0 = time.perf_counter()
        params = jax.block_until_ready(
            jax.jit(model.init)(jax.random.PRNGKey(args.seed)))
        log("serve-bf16", f"{ARCH}: {cfg.num_layers} layers, d_model "
                          f"{cfg.d_model}, vocab {cfg.vocab_size}, "
                          f"{cfg.param_count() / 1e9:.2f}B bf16 params "
                          f"initialised in {time.perf_counter() - t0:.1f}s")
        requests = make_requests(cfg.vocab_size, args.seed)
        phase_serve("serve-bf16", model, params, requests,
                    {"decode": ["paged_attention"],
                     "prefill": ["flash_attention_masked"]})

        t0 = time.perf_counter()
        qparams = jax.block_until_ready(
            quantize_params(params, QuantConfig(enabled=True)))
        del params
        gc.collect()
        log("serve-ent", f"EN-T packed-plane encode on chip "
                         f"{time.perf_counter() - t0:.1f}s; "
                         f"peak_bytes_in_use {peak_bytes()}")
        phase_serve("serve-ent", model, qparams, requests,
                    {"decode": ["paged_attention", "ent_matmul_packed_fused"],
                     "prefill": ["flash_attention_masked",
                                 "ent_matmul_packed_fused"]})
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
