"""Shape-keyed block-size selection shared by every Pallas kernel family.

One table serves the kernel families (``int8_matmul``, ``ent_matmul`` —
4-plane, packed and fused variants — ``flash_attention`` and
``paged_attention``): callers that don't pass explicit block sizes get
them from here instead of from per-call-site constants.

Resolution order for a (family, shape) query:

1. the in-memory table (autotuned this process, or loaded from the
   JSON cache file at import of the first query);
2. the persistent JSON cache (``REPRO_TUNING_CACHE`` env var, default
   ``~/.cache/repro/tuning.json``) written by :func:`autotune`;
3. divisibility-aware heuristic defaults (largest power-of-two block
   that divides the dim, capped at the MXU-friendly sizes the seed
   kernels shipped with).

Entries are keyed by the ``device_kind`` of the first JAX device, so a
sweep on the CPU or in interpret mode never supplies a chip's block
sizes.  A cached entry is used only if its blocks divide the launch's
dims and obey the TPU block rule (see :func:`_valid`).

``autotune`` measures a candidate sweep with a caller-provided bench
closure and persists the winner, so expensive searches run once per
machine per shape bucket and every later process starts warm.  Shapes
are bucketed to powers of two: one tuned entry covers the whole bucket.
"""

from __future__ import annotations

import json
import os
import time

__all__ = [
    "get_block_config",
    "autotune",
    "matmul_candidates",
    "attention_candidates",
    "paged_attention_candidates",
    "record",
    "clear",
    "cache_path",
    "pad_rows",
    "padded_matmul",
]

MATMUL_FAMILIES = ("int8_matmul", "ent_matmul")
ATTENTION_FAMILIES = ("flash_attention",)
PAGED_FAMILIES = ("paged_attention",)

# (family, key) -> config dict.  Populated by autotune()/record() and by
# the JSON cache; consulted before the heuristics.
_TABLE: dict = {}
_LOADED = False


def cache_path() -> str:
    return os.environ.get(
        "REPRO_TUNING_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro", "tuning.json"),
    )


def _bucket(dim: int) -> int:
    """Round up to a power of two — one table entry per bucket."""
    b = 1
    while b < dim:
        b *= 2
    return b


def _device_kind() -> str:
    import jax   # deferred: importing this module must not touch devices

    return jax.devices()[0].device_kind


def _key(family: str, shape) -> str:
    return (f"{_device_kind()}/{family}:"
            + "x".join(str(_bucket(int(d))) for d in shape))


def _load() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    try:
        with open(cache_path()) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return  # missing, truncated or corrupt cache: start from heuristics
    if isinstance(data, dict):  # tolerate a clobbered non-dict payload too
        _TABLE.update({k: v for k, v in data.items() if isinstance(v, dict)})


def _save() -> None:
    path = cache_path()
    # write-to-temp + atomic rename: concurrent pytest/benchmark processes
    # each land a complete file instead of interleaving into corrupt JSON
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(_TABLE, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        # read-only FS: in-memory table still serves this process
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _fit(dim: int, cap: int) -> int:
    """Largest power-of-two block <= cap that divides dim (>=1)."""
    b = 1
    while b < cap:
        b *= 2
    while b > 1 and (b > cap or dim % b != 0):
        b //= 2
    return b


def _heuristic(family: str, shape) -> dict:
    if family in MATMUL_FAMILIES:
        m, k, n = (int(d) for d in shape)
        # decode-like skinny M keeps the full row; big M tiles at 128
        return {
            "block_m": _fit(m, 128),
            "block_n": _fit(n, 128),
            "block_k": _fit(k, 512),
        }
    if family in ATTENTION_FAMILIES:
        sq, skv, d = (int(x) for x in shape)
        return {"block_q": _fit(sq, 128), "block_kv": _fit(skv, 128)}
    if family in PAGED_FAMILIES:
        # (page_size, head_dim): pages_per_block is the kernel's kv block
        # in whole pages (one async copy per page, every kv head at
        # once) — enough pages for >= 128 tokens a block, 8 at page 16;
        # block_pages is the jnp oracle's K-streaming granularity — 64
        # pages per block, i.e. one assembled read at the 1024-token
        # serving width (on CPU an indexed page read cannot fuse into the
        # GEMM, so coarse blocks win; the sweep refines per host)
        page, d = (int(x) for x in shape)
        return {"pages_per_block": -(-128 // page), "block_pages": 64}
    raise KeyError(f"unknown kernel family: {family}")


# TPU block rule: a block's last two dims must be multiples of the
# (sublane, lane) tile — 8 and 128 — or equal the array's dims
_ALIGN = {"block_m": 8, "block_n": 128, "block_k": 128,
          "block_q": 8, "block_kv": 8}


def _valid(family: str, shape, cfg: dict) -> bool:
    """Does the config divide the ACTUAL dims after the kernels' min-clamp,
    and is each clamped block aligned to its tile or the full dim?
    (Shapes are bucketed in the table, so a tuned entry from elsewhere in
    the bucket may not divide this launch's dims.)"""
    if family in MATMUL_FAMILIES:
        dims = {"block_m": shape[0], "block_k": shape[1], "block_n": shape[2]}
    elif family in PAGED_FAMILIES:
        # blocks of whole pages: any positive count fits; a within-page
        # block_kv (left in a cache by the per-page kernel) means nothing
        ppb = cfg.get("pages_per_block")
        return ("block_kv" not in cfg and isinstance(ppb, int)
                and not isinstance(ppb, bool) and ppb > 0)
    else:
        dims = {"block_q": shape[0], "block_kv": shape[1]}
    for k, dim in dims.items():
        if k not in cfg:
            continue
        dim = int(dim)
        blk = min(int(cfg[k]), dim)
        if dim % blk or (blk != dim and blk % _ALIGN[k]):
            return False
    return True


def pad_rows(m: int) -> int:
    """Rows a matmul kernel launches with: ``m`` rounded up to the 8-row
    sublane tile, so every ``block_m`` the table picks is aligned."""
    return -(-int(m) // 8) * 8


def padded_matmul(kernel, family: str, x, w, scale_x, scale_w,
                  block_kw: dict, **kw):
    """Launch ``kernel(x, w, scale_x, scale_w, **blocks, **kw)`` with the
    rows of ``x``/``scale_x`` zero-padded to :func:`pad_rows` (scale rows
    padded with 1, so a fused in-kernel quant never divides by zero) and
    the result sliced back to ``m`` rows.  Decode (M = slots) and
    speculative-verify (M = slots * (K+1)) shapes need no alignment of
    their own."""
    import jax.numpy as jnp

    m = x.shape[0]
    mp = pad_rows(m)
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
        scale_x = jnp.pad(scale_x, ((0, mp - m), (0, 0)), constant_values=1)
    bk = get_block_config(family, (mp, x.shape[1], w.shape[-1]), block_kw)
    return kernel(x, w, scale_x, scale_w, **bk, **kw)[:m]


def get_block_config(family: str, shape, overrides: dict | None = None) -> dict:
    """Block sizes for one kernel launch; explicit overrides always win."""
    _load()
    cached = _TABLE.get(_key(family, shape))
    if cached is not None and not _valid(family, shape, cached):
        cached = None
    cfg = dict(cached or _heuristic(family, shape))
    if overrides:
        cfg.update({k: v for k, v in overrides.items() if v is not None})
    return cfg


def record(family: str, shape, config: dict, persist: bool = True) -> None:
    """Pin ``config`` for the shape bucket (and persist it)."""
    _load()
    _TABLE[_key(family, shape)] = dict(config)
    if persist:
        _save()


def clear() -> None:
    """Drop the in-memory table (tests)."""
    global _LOADED
    _TABLE.clear()
    _LOADED = True  # don't reload the file over a deliberate clear


def matmul_candidates(m: int, k: int, n: int) -> list[dict]:
    """Divisibility-filtered candidate sweep for the matmul families."""
    out = []
    for bm in (64, 128, 256):
        for bn in (64, 128, 256):
            for bk in (128, 256, 512, 1024):
                if m % min(bm, m) or n % min(bn, n) or k % min(bk, k):
                    continue
                out.append({"block_m": min(bm, m), "block_n": min(bn, n),
                            "block_k": min(bk, k)})
    # dedupe after the min() clamps
    uniq = {tuple(sorted(c.items())): c for c in out}
    return list(uniq.values())


def attention_candidates(sq: int, skv: int) -> list[dict]:
    out = []
    for bq in (64, 128, 256):
        for bkv in (64, 128, 256, 512):
            if sq % min(bq, sq) or skv % min(bkv, skv):
                continue
            out.append({"block_q": min(bq, sq), "block_kv": min(bkv, skv)})
    uniq = {tuple(sorted(c.items())): c for c in out}
    return list(uniq.values())


def paged_attention_candidates(page_size: int,
                               knob: str = "both") -> list[dict]:
    """Sweep for the paged decode attention family.

    The two knobs belong to different backends — ``pages_per_block``
    (whole pages per kv block) to the Pallas kernel, ``block_pages``
    (pages per score block) to the jnp oracle — so a bench that
    exercises one backend should sweep only its own knob
    (``knob="kernel"`` / ``"oracle"``): the cross product would time
    duplicates and persist the other knob from noise.  The un-swept
    knob rides along at its heuristic default.
    """
    base = _heuristic("paged_attention", (page_size, 0))
    # kernel blocks of 1 page up to 512 tokens, the heuristic's included
    ppbs = sorted({p for p in (1, 2, 4, 8, 16, 32) if p * page_size <= 512}
                  | {base["pages_per_block"]})
    bps = (8, 16, 32, 64, 128)
    if knob == "kernel":
        out = [{"pages_per_block": p, "block_pages": base["block_pages"]}
               for p in ppbs]
    elif knob == "oracle":
        out = [{"pages_per_block": base["pages_per_block"], "block_pages": p}
               for p in bps]
    else:
        out = [{"pages_per_block": k, "block_pages": p}
               for k in ppbs for p in bps]
    uniq = {tuple(sorted(c.items())): c for c in out}
    return list(uniq.values())


def autotune(family: str, shape, bench, candidates: list[dict],
             *, iters: int = 5, warmup: int = 2, persist: bool = True) -> dict:
    """Measure ``bench(config) -> None`` over candidates, cache the winner.

    ``bench`` must run the kernel to completion (block_until_ready) for
    one call with the given block config; failures (e.g. VMEM overflow
    for an oversized block) just disqualify that candidate.
    """
    _load()
    best, best_t = None, float("inf")
    for cfg in candidates:
        try:
            for _ in range(warmup):
                bench(cfg)
            t0 = time.perf_counter()
            for _ in range(iters):
                bench(cfg)
            dt = (time.perf_counter() - t0) / iters
        except Exception:
            continue
        if dt < best_t:
            best, best_t = cfg, dt
    if best is None:
        best = _heuristic(family, shape)
    record(family, shape, best, persist=persist)
    return best
