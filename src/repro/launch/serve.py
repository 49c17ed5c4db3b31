"""Serving entry point: batched prefill + decode, optional EN-T w8a8.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
        [--quantize] [--steps 32] [--batch 4]

``--engine`` serves a ragged request stream through the continuous-
batching ``ServeEngine`` (fixed slots, batched prefill on admission,
per-slot EOS/max-token stop) instead of one fixed-shape ``generate``.

``--http`` puts the async front end on top: a ``PipelinedScheduler``
driving the engine plus the stdlib HTTP/SSE server from
``runtime.server`` (``POST /v1/completions`` streams tokens,
``GET /metrics`` reports TTFT/ITL percentiles).  ``--http-smoke`` runs
a scripted client against the live server instead of blocking — one
streamed completion, a ``/metrics`` probe, a clean-shutdown leak check
— which is what the CI smoke step invokes.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.configs.base import QuantConfig
from repro.launch import env as envmod
from repro.models.transformer import build_model
from repro.quant.quantize import quantize_params
from repro.runtime.metrics import SPANS
from repro.runtime.serve_loop import ServeEngine, generate


def _resolve_plan(spec: str):
    """--fault-plan value -> FaultPlan: a registered name ("ci-chaos")
    or "seeded:<n>" for a deterministic randomized plan."""
    from repro.runtime.faults import FaultPlan

    if spec.startswith("seeded:"):
        return FaultPlan.seeded(int(spec.split(":", 1)[1]))
    return FaultPlan.named(spec)


def _serve_http(args, cfg, engine) -> None:
    """--http: scheduler + SSE server; --http-smoke runs the scripted
    client (one streamed completion, /metrics, clean shutdown) —
    under the --fault-plan chaos plan when one is given."""
    from repro.runtime.scheduler import PipelinedScheduler
    from repro.runtime.server import ServingServer

    plan = _resolve_plan(args.fault_plan) if args.fault_plan else None
    retries = args.max_retries
    if plan is not None and retries == 0:
        retries = 3     # a chaos plan without a retry budget just dies
        print(f"fault plan {plan.name or '<seeded>'} active: "
              f"defaulting --max-retries to {retries}")
    sched = PipelinedScheduler(engine, pipeline_depth=args.pipeline_depth,
                               max_queue=args.max_queue,
                               prefill_chunk=args.prefill_chunk or None,
                               max_retries=retries,
                               watchdog_timeout=args.watchdog or None)
    srv = ServingServer(sched, host=args.host, port=args.port)
    if plan is not None:
        plan.activate()
    host, port = srv.start()
    print(f"serving http://{host}:{port} "
          f"(backend={engine.cache_kind}, slots={engine.slots}, "
          f"depth={sched.depth})")
    if not args.http_smoke:
        try:
            srv.serve_forever()
        except KeyboardInterrupt:
            srv.stop()
        finally:
            if plan is not None:
                plan.deactivate()
        return

    import http.client

    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, args.prompt_len).tolist()
    conn = http.client.HTTPConnection(host, port, timeout=600)
    conn.request(
        "POST", "/v1/completions",
        json.dumps({"tokens": prompt, "max_new_tokens": args.steps}),
        {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200, f"completions: HTTP {resp.status}"
    events = [json.loads(line[6:])
              for line in resp.read().decode().splitlines()
              if line.startswith("data: ")]
    conn.close()
    assert events and events[-1].get("done"), "SSE stream did not finish"
    streamed = [e["token"] for e in events[:-1]]
    assert streamed == events[-1]["tokens"], "stream/final token mismatch"

    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/healthz")
    hz = conn.getresponse()
    assert hz.status == 200, f"healthz: HTTP {hz.status}"
    hz.read()
    conn.request("GET", "/metrics")
    m = json.loads(conn.getresponse().read())
    conn.close()
    assert m["leaks_clean"], "allocator leak after completion"
    assert m["requests"]["finished"] == 1
    if plan is not None:
        fired = plan.fired
        assert fired, "fault plan active but no fault fired"
        assert m["faults"]["quarantined"] == 0, \
            f"chaos smoke quarantined a request: {sched.errors}"
        assert m["faults"]["total"] == len(fired)

    srv.stop()
    if plan is not None:
        plan.deactivate()
    engine.check_leaks()
    ttft, itl = m["ttft"], m["inter_token"]
    chaos = ""
    if plan is not None:
        chaos = (f", {len(fired)} faults injected "
                 f"({m['faults']['retries']} retries, all recovered)")
    print(f"http smoke: {len(streamed)} tokens streamed, "
          f"ttft p50 {ttft['p50_us']}us, itl p50 {itl['p50_us']}us / "
          f"p99 {itl['p99_us']}us, 0 leaks{chaos}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--quantize", action="store_true",
                    help="EN-T w8a8: encode weights once, serve int8")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching ServeEngine over a ragged "
                         "request stream (requests = 2x --batch)")
    ap.add_argument("--slots", type=int, default=0,
                    help="engine batch slots (default: --batch)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: process prompts in chunks of "
                         "this many tokens (0 = one shot / ring-width auto)")
    ap.add_argument("--cache-kind", default="auto",
                    choices=["auto", "dense", "ring", "paged"],
                    help="KV-cache backend (auto: engine picks paged, or "
                         "ring for sliding-window archs)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged backend: tokens per page (0 = default)")
    ap.add_argument("--pages", type=int, default=0,
                    help="paged backend: pool size in pages (0 = full "
                         "provisioning, slots * pages-per-slot)")
    ap.add_argument("--draft-arch", default="", choices=[""] + list(ARCH_IDS),
                    help="engine speculative decoding: drafter arch (same "
                         "arch = weight-shared drafter, 100%% acceptance "
                         "smoke; needs --engine)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per slot per tick")
    ap.add_argument("--spec-mode", default="match",
                    choices=["match", "rejection"],
                    help="verify sampler: 'match' replays the plain "
                         "engine's stream bit-for-bit; 'rejection' is "
                         "classic rejection sampling")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="radix prefix cache + copy-on-write page sharing "
                         "on the paged engine (default: auto — on for "
                         "paged attention-only models)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="engine: prepend a common N-token system prompt "
                         "to every request (exercises prefix sharing)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="on-device sampler top-k truncation (0 = off)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="on-device sampler nucleus truncation (0 = off)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP/SSE: PipelinedScheduler + "
                         "stdlib asyncio server (implies --engine)")
    ap.add_argument("--port", type=int, default=0,
                    help="HTTP port (0 = ephemeral, printed on bind)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="decode ticks dispatched ahead of host sync")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="admission control: shed (429) past this depth")
    ap.add_argument("--http-smoke", action="store_true",
                    help="scripted client against the live server, then "
                         "clean shutdown + leak check (CI smoke)")
    ap.add_argument("--fault-plan", default="",
                    help="chaos testing: activate a deterministic fault "
                         "plan — a registered name (e.g. 'ci-chaos') or "
                         "'seeded:<n>' (needs --http)")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="fault tolerance: per-request retry budget; any "
                         "nonzero value turns on snapshot/rollback ticks "
                         "(defaults to 3 when --fault-plan is set)")
    ap.add_argument("--watchdog", type=float, default=0.0,
                    help="fault tolerance: per-tick watchdog timeout in "
                         "seconds (0 = off)")
    envmod.add_env_args(ap)
    args = ap.parse_args()
    envmod.apply_env_args(args)
    envmod.enable_compile_cache()
    if args.fault_plan and not args.http:
        ap.error("--fault-plan needs --http: only the fault-tolerant "
                 "scheduler can recover from injected faults")
    chunk = args.prefill_chunk or None
    top_k = args.top_k or None
    top_p = args.top_p or None

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced_config(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.quantize:
        t0 = time.perf_counter()
        with SPANS.span("quant.encode", n=cfg.num_layers):
            params = jax.block_until_ready(
                quantize_params(params, QuantConfig(enabled=True)))
        print(f"EN-T encode (once): {time.perf_counter()-t0:.2f}s")

    rng = np.random.default_rng(0)

    if args.engine or args.http:
        slots = args.slots or args.batch
        n_req = 2 * args.batch
        max_len = 2 * args.prompt_len + args.steps + 8
        spec_kw = {}
        if args.draft_arch:
            if args.draft_arch == args.arch:
                # weight-shared drafter: agreement (and acceptance) by
                # construction — the spec-path smoke configuration
                dmodel, dparams = model, params
            else:
                dcfg = get_config(args.draft_arch)
                if args.smoke:
                    dcfg = reduced_config(dcfg)
                dmodel = build_model(dcfg)
                dparams = dmodel.init(jax.random.PRNGKey(1))
            spec_kw = {"draft_model": dmodel, "draft_params": dparams,
                       "spec_k": args.spec_k, "spec_mode": args.spec_mode}
        max_len += args.shared_prefix
        engine = ServeEngine(model, params, slots=slots, max_len=max_len,
                             prefill_chunk=chunk, top_k=top_k, top_p=top_p,
                             cache_kind=args.cache_kind,
                             page_size=args.page_size or None,
                             pages=args.pages or None,
                             prefix_cache=("auto" if args.prefix_cache is None
                                           else args.prefix_cache), **spec_kw)
        if args.http:
            _serve_http(args, cfg, engine)
            return
        sys_prompt = rng.integers(0, cfg.vocab_size,
                                  args.shared_prefix).tolist()
        lens = rng.integers(max(1, args.prompt_len // 2),
                            args.prompt_len + 1, n_req)
        t0 = time.perf_counter()
        for n in lens:
            engine.submit(
                sys_prompt + rng.integers(0, cfg.vocab_size, int(n)).tolist(),
                max_new_tokens=args.steps,
                temperature=args.temperature)
        results = engine.run()
        dt = time.perf_counter() - t0
        total = sum(len(v) for v in results.values())
        print(f"engine[{engine.cache_kind}]: served {n_req} ragged requests "
              f"(prompt lens {lens.min()}..{lens.max()}) on {slots} slots: "
              f"{total} tokens in {dt:.2f}s ({total/dt:.1f} tok/s)")
        if args.draft_arch:
            st = engine.spec_stats
            rate = engine.acceptance_rate
            print(f"spec[k={args.spec_k}, {args.spec_mode}]: "
                  f"{st['ticks']} ticks, {st['drafted']} drafted, "
                  f"{st['accepted']} accepted "
                  f"({0.0 if rate is None else rate:.2%}), "
                  f"{st['emitted']} emitted "
                  f"({st['emitted'] / max(st['ticks'], 1):.2f} tok/tick)")
        if engine.page_stats is not None:
            ps = engine.page_stats
            print(f"pages: {ps['total']} total, {ps['free']} free, "
                  f"{ps['resident']} resident, {ps['shared']} shared, "
                  f"{ps.get('cached', 0)} cached")
        if engine.prefix_stats is not None:
            fs = engine.prefix_stats
            saved = fs["hit_tokens"] - fs["cow_copies"] * engine.page_size
            print(f"prefix cache: {fs['hits']}/{fs['lookups']} hits "
                  f"({fs['hit_rate']:.0%}), {fs['hit_tokens']} prompt "
                  f"tokens reused (~{max(saved, 0)} net of CoW), "
                  f"{fs['resident']} pages cached, {fs['evicted']} "
                  f"evicted, {fs['cow_copies']} CoW copies")
        uid0 = min(results)
        print("sample:", results[uid0][:16])
        return

    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    t0 = time.perf_counter()
    out = generate(model, params, prompts, steps=args.steps,
                   temperature=args.temperature, prefill_chunk=chunk,
                   top_k=top_k, top_p=top_p,
                   cache_kind=None if args.cache_kind == "auto"
                   else args.cache_kind)
    dt = time.perf_counter() - t0
    print(f"generated {args.batch}x{args.steps} tokens in {dt:.2f}s "
          f"({args.batch*args.steps/dt:.1f} tok/s)")
    print("sample:", np.asarray(out)[0, :16].tolist())


if __name__ == "__main__":
    main()
