"""§Perf hillclimbing driver: hypothesis -> change -> measure -> record.

Runs named experiment variants on the three selected cells and writes
experiments/perf/<cell>__<variant>.json.  Each variant is a config-level
change (sharding profile, microbatch count, collective dtype, remat,
quantization) applied to the same lower+compile+roofline pipeline as the
baseline, so before/after numbers are directly comparable.

    PYTHONPATH=src python -m benchmarks.hillclimb --cell mamba2 [--variant X]

``--tune-kernels`` instead hill-climbs the Pallas kernel block tables:
every (family, serving shape) pair sweeps its divisibility-filtered
candidate grid through ``tuning.autotune``, and the winners persist in
the shared shape-keyed JSON cache (``~/.cache/repro/tuning.json``,
override with ``REPRO_TUNING_CACHE``) that ``int8_matmul`` / ``ent_*`` /
``flash_attention`` launches consult — so one sweep per machine warms
every later serving process.

    PYTHONPATH=src python -m benchmarks.hillclimb --tune-kernels [--quick]
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
from dataclasses import replace

from repro.configs.base import TrainConfig

# (cell key) -> arch, shape, {variant: run_cell kwargs}
def _T(**kw):
    kw.setdefault("remat", "full")
    return TrainConfig(seq_len=4096, global_batch=256, **kw)

EXPERIMENTS = {
    "mamba2": ("mamba2-370m", "train_4k", {
        # H1: 370M params over 256 chips: TP(16) moves more activation
        # bytes than it saves compute -> pure FSDP (batch over all 256)
        "baseline": {},
        "fsdp": {"profile": "fsdp"},
        # H2: at batch 1/device the activations are tiny; remat only adds
        # recompute HBM traffic -> turn it off
        "fsdp_noremat": {"profile": "fsdp",
                         "tcfg": _T(microbatch=64, remat="none")},
        # H3: with no remat the model fits without microbatching either
        "fsdp_noremat_nomicro": {"profile": "fsdp",
                                 "tcfg": _T(microbatch=0, remat="none")},
        # H4: no-remat blows HBM (54 GB); remat + single batch keeps the
        # 4x memory win while fitting
        "fsdp_nomicro": {"profile": "fsdp", "tcfg": _T(microbatch=0)},
    }),
    "minicpm": ("minicpm-2b", "decode_32k", {
        # H1: decode re-gathers FSDP-sharded weights EVERY token; serving
        # weights should be stationary (TP-only, replicated over data)
        "baseline": {},
        "serve_tp": {"profile": "serve_tp"},
        # H2: w8a8 int8 weights halve the weight-read bytes (and are what
        # the EN-T TCU actually consumes)
        "serve_tp_w8a8": {"profile": "serve_tp", "quantized": True},
        # H3 (code change, models/transformer.py): cache rides the scan
        # carry with slice updates instead of xs/ys staging -> the 2x
        # full-cache copy per token disappears
        "serve_tp_carrycache": {"profile": "serve_tp"},
        "serve_tp_carrycache_w8a8": {"profile": "serve_tp", "quantized": True},
        # H4: int8 KV cache with per-(slot,head) scales folded exactly
        # into the attention dots -> the dominant decode HBM term halves
        "serve_tp_kv8": {"profile": "serve_tp", "quantized": True,
                         "kv_quant": True},
    }),
    "jamba": ("jamba-1.5-large", "train_4k", {
        # 398B hybrid MoE: collective-dominated
        "baseline": {},
        # H1: the MoE combine psum moves f32; bf16 halves it
        "bf16_combine": {"cfg_transform": "bf16_combine"},
        # H2: wgrads leave the backward replicated (all-reduce) before the
        # accumulator pin; pinning each microbatch grad turns them into
        # reduce-scatters into the FSDP shard
        "grad_prepin": {"tcfg": _T(microbatch=32, grad_prepin=True)},
        # H3: FSDP weight gathers scale with microbatch count; 8 -> 4
        # halves them (memory allows after H1)
        "micro4": {"tcfg": _T(microbatch=64)},
        # H4: grads reduced in bf16 (AR bytes halve); f32 master weights
        "bf16_grads": {"tcfg": _T(microbatch=32, grad_dtype="bfloat16")},
        # combined best (prepin refuted -> dropped)
        "combined": {"cfg_transform": "bf16_combine",
                     "tcfg": _T(microbatch=64, grad_dtype="bfloat16")},
    }),
}


def _transform(name):
    if name is None:
        return None
    if name == "bf16_combine":
        def f(cfg):
            return replace(cfg, moe=replace(cfg.moe, combine_dtype="bfloat16"))
        return f
    raise KeyError(name)


# --- kernel block-table autotuning -------------------------------------------

# the serving shapes that matter: decode (skinny M / Sq=1 suffix), the
# canonical M=256 engine matmul, and a 1k prefill tile
TUNE_MATMUL_SHAPES = [(8, 1024, 1024), (256, 1024, 1024), (1024, 4096, 1024)]
TUNE_ATTENTION_SHAPES = [(256, 256, 64), (1024, 1024, 64)]
# paged decode kernel: (page_size, head_dim) shape keys; the bench runs
# at the serving pool scale (max_len below)
TUNE_PAGED_SHAPES = [(16, 64), (32, 64)]
TUNE_PAGED_MAX_LEN = 1024


def tune_kernels(quick: bool = False) -> dict:
    """Sweep the shared block tables via ``tuning.autotune`` and persist.

    On TPU the real Pallas kernels are measured; elsewhere they run in
    interpret mode (slow but faithful tiling), so ``--quick`` trims the
    candidate grids and shapes for smoke coverage.
    """
    import jax

    from repro.kernels import tuning
    from repro.kernels.flash_attention.flash_attention import flash_attention
    from repro.kernels.int8_matmul.int8_matmul import int8_matmul

    import numpy as np
    import jax.numpy as jnp

    interpret = jax.default_backend() != "tpu"
    iters, warmup = (1, 1) if interpret else (5, 2)
    rng = np.random.default_rng(0)
    results = {}

    mm_shapes = [(64, 256, 256)] if quick else TUNE_MATMUL_SHAPES
    at_shapes = [(128, 128, 64)] if quick else TUNE_ATTENTION_SHAPES

    for m, k, n in mm_shapes:
        x = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
        w = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int8))
        sx = jnp.ones((m, 1), jnp.float32)
        sw = jnp.ones((1, n), jnp.float32)
        cands = tuning.matmul_candidates(m, k, n)
        if quick:
            cands = cands[:4]

        def bench_int8(cfg):
            jax.block_until_ready(int8_matmul(
                x, w, sx, sw, out_dtype=jnp.float32, interpret=interpret,
                **cfg))

        best = tuning.autotune("int8_matmul", (m, k, n), bench_int8, cands,
                               iters=iters, warmup=warmup)
        results[f"int8_matmul:{m}x{k}x{n}"] = best

        from repro.core.multiplier import ent_packed_planes
        from repro.kernels.ent_matmul.ent_matmul import ent_matmul_packed
        packed = ent_packed_planes(w)

        def bench_ent(cfg):
            jax.block_until_ready(ent_matmul_packed(
                x, packed, sx, sw, out_dtype=jnp.float32,
                interpret=interpret, **cfg))

        best = tuning.autotune("ent_matmul", (m, k, n), bench_ent, cands,
                               iters=iters, warmup=warmup)
        results[f"ent_matmul:{m}x{k}x{n}"] = best

    for sq, skv, d in at_shapes:
        q = jnp.asarray(rng.normal(size=(1, 8, sq, d)).astype(np.float32))
        kv = jnp.asarray(rng.normal(size=(1, 2, skv, d)).astype(np.float32))
        cands = tuning.attention_candidates(sq, skv)
        if quick:
            cands = cands[:4]

        def bench_flash(cfg):
            jax.block_until_ready(flash_attention(
                q, kv, kv, causal=True, interpret=interpret, **cfg))

        best = tuning.autotune("flash_attention", (sq, skv, d), bench_flash,
                               cands, iters=iters, warmup=warmup)
        results[f"flash_attention:{sq}x{skv}x{d}"] = best

    # paged decode attention: on TPU the kernel's whole-page kv block
    # (pages_per_block) is what matters; on CPU the serving path is the jnp
    # oracle, so the sweep measures its pages-per-block streaming
    # granularity (block_pages) instead — both knobs live in the one
    # "paged_attention" table entry
    import functools

    from repro.kernels.paged_attention.paged_attention import (
        paged_attention_kernel)
    from repro.kernels.paged_attention.ref import paged_attention_ref

    pg_shapes = [(16, 64)] if quick else TUNE_PAGED_SHAPES
    max_len = 128 if quick else TUNE_PAGED_MAX_LEN
    for page, d in pg_shapes:
        b, hkv, hq = 4, 2, 8
        pps = -(-max_len // page)
        pool_shape = (b * pps + 1, hkv, page, d)
        kp = jnp.asarray(rng.normal(size=pool_shape).astype(np.float32))
        vp = jnp.asarray(rng.normal(size=pool_shape).astype(np.float32))
        table = jnp.asarray(
            1 + np.arange(b * pps, dtype=np.int32).reshape(b, pps))
        q = jnp.asarray(rng.normal(size=(b, hq, 1, d)).astype(np.float32))
        pos = jnp.full((b,), max_len - 1, jnp.int32)
        start = jnp.zeros((b,), jnp.int32)
        # sweep only the knob this host's backend responds to — the
        # kernel ignores block_pages and the oracle pages_per_block
        cands = tuning.paged_attention_candidates(
            page, knob="oracle" if interpret else "kernel")
        if quick:
            cands = cands[:4]

        @functools.lru_cache(maxsize=None)
        def jitted_ref(block_pages):
            # force the blocks path: block_pages is ITS knob (the auto
            # dispatch may pick pool-wide scores, which ignore it)
            return jax.jit(functools.partial(
                paged_attention_ref, page_size=page,
                block_pages=block_pages, score_mode="blocks"))

        def bench_paged(cfg):
            if interpret:
                out = jitted_ref(int(cfg["block_pages"]))(
                    q, kp, vp, table, pos, start)
            else:
                out = paged_attention_kernel(
                    q, kp, vp, table, pos, start, page_size=page,
                    pages_per_block=cfg["pages_per_block"])
            jax.block_until_ready(out)

        best = tuning.autotune("paged_attention", (page, d), bench_paged,
                               cands, iters=iters, warmup=warmup)
        results[f"paged_attention:{page}x{d}"] = best

    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=tuple(EXPERIMENTS))
    ap.add_argument("--variant", default=None)
    ap.add_argument("--out", default="experiments/perf")
    ap.add_argument("--tune-kernels", action="store_true",
                    help="autotune the shared Pallas block tables instead "
                         "of running a cell experiment")
    ap.add_argument("--quick", action="store_true",
                    help="trimmed candidate grids (CI smoke)")
    args = ap.parse_args()

    if args.tune_kernels:
        from repro.kernels import tuning
        results = tune_kernels(quick=args.quick)
        for key, cfg in sorted(results.items()):
            print(f"{key}: {cfg}")
        print(f"persisted to {tuning.cache_path()}")
        return
    if args.cell is None:
        ap.error("--cell is required unless --tune-kernels is given")

    from repro.launch.dryrun import run_cell
    arch, shape, variants = EXPERIMENTS[args.cell]
    os.makedirs(args.out, exist_ok=True)
    todo = [args.variant] if args.variant else list(variants)
    for name in todo:
        kw = dict(variants[name])
        if "cfg_transform" in kw:
            kw["cfg_transform"] = _transform(kw["cfg_transform"])
        print(f"=== {args.cell} :: {name}")
        rec = run_cell(arch, shape, **kw)
        rec["variant"] = name
        with open(os.path.join(args.out, f"{args.cell}__{name}.json"), "w") as f:
            json.dump(rec, f, indent=2)


if __name__ == "__main__":
    main()
