"""Process-environment helpers for the launch entry points.

JAX reads most of its tuning knobs from environment variables exactly
once — ``XLA_FLAGS`` at backend initialisation, ``LD_PRELOAD`` at
process start — so every launcher wants the same dance: compose the
right flag set *before* touching a device, respect anything the user
already exported, and never crash when an optional library (tcmalloc)
is missing from the image.  This module centralises that dance:

* :func:`find_tcmalloc` / :func:`tcmalloc_env` — locate
  ``libtcmalloc`` in the usual distro paths and build the
  ``LD_PRELOAD`` + allocation-report-threshold pair.  ``LD_PRELOAD``
  only takes effect at exec time, so for an already-running process
  the helper is advisory: :func:`apply` exports it for *child*
  processes and reports whether the current process got it.
* :func:`xla_flags` — compose an ``XLA_FLAGS`` preset: host-platform
  device count (the CPU "multi-device" trick used by the elastic
  tests) and the GPU latency-hiding/async-collective set, merged with
  (never clobbering) flags the user exported.
* :func:`enable_x64` — flip ``jax_enable_x64``; safe at any time.
* :func:`add_env_args` / :func:`apply_env_args` — argparse glue shared
  by ``launch/serve.py`` and ``launch/train.py``.
* :func:`enable_compile_cache` — JAX's persistent compilation cache,
  called by every entry point before its first compile (never at
  import).

Everything degrades to a no-op on missing files or an already-
initialised backend — launchers must behave identically on dev boxes,
CI, and accelerator images.
"""

from __future__ import annotations

import argparse
import glob
import os
import warnings

_TCMALLOC_GLOBS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc*.so*",
    "/usr/lib/aarch64-linux-gnu/libtcmalloc*.so*",
    "/usr/lib64/libtcmalloc*.so*",
    "/usr/lib/libtcmalloc*.so*",
)

# one flag-set per platform; merged under user-exported XLA_FLAGS
_GPU_PRESET = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
    "--xla_gpu_triton_gemm_any=true",
)


def find_tcmalloc() -> str | None:
    """Path to a ``libtcmalloc`` shared object, or None if absent."""
    for pattern in _TCMALLOC_GLOBS:
        hits = sorted(glob.glob(pattern))
        if hits:
            return hits[0]
    return None


def tcmalloc_env() -> dict[str, str]:
    """Env pairs that preload tcmalloc (empty dict when unavailable).

    Includes the large-alloc report threshold so numpy's multi-GB
    arenas don't spam warnings (SNIPPETS.md idiom)."""
    lib = find_tcmalloc()
    if lib is None:
        return {}
    preload = os.environ.get("LD_PRELOAD", "")
    if lib not in preload.split(":"):
        preload = f"{preload}:{lib}".strip(":")
    return {"LD_PRELOAD": preload,
            "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000"}


def xla_flags(*, host_device_count: int | None = None,
              platform: str | None = None,
              existing: str | None = None) -> str:
    """Compose an ``XLA_FLAGS`` string.

    ``host_device_count`` adds ``--xla_force_host_platform_device_
    count=N`` (fake N CPU devices — what the elastic remesh tests use);
    ``platform='gpu'`` appends the async/latency-hiding preset.  Flags
    already present in ``existing`` (default: the current environment)
    win — a user export is never overridden."""
    base = os.environ.get("XLA_FLAGS", "") if existing is None else existing
    flags = base.split()
    have = {f.split("=", 1)[0] for f in flags}

    def _add(flag: str) -> None:
        if flag.split("=", 1)[0] not in have:
            flags.append(flag)

    if host_device_count is not None:
        n = int(host_device_count)
        cores = os.cpu_count() or 1
        if n > cores:
            warnings.warn(
                f"host_device_count={n} > {cores} cores; capping",
                stacklevel=2)
            n = cores
        _add(f"--xla_force_host_platform_device_count={n}")
    if platform == "gpu":
        for f in _GPU_PRESET:
            _add(f)
    return " ".join(flags)


def enable_x64(flag: bool = True) -> None:
    """Toggle 64-bit mode (defers to ``JAX_ENABLE_X64`` when unset)."""
    import jax  # deferred: env helpers must be importable pre-jax

    if not flag:
        flag = bool(os.getenv("JAX_ENABLE_X64", False))
    jax.config.update("jax_enable_x64", bool(flag))


def apply(env: dict[str, str]) -> dict[str, str]:
    """Export ``env`` into ``os.environ``; returns what actually changed.

    ``XLA_FLAGS`` set after the XLA backend initialised, and
    ``LD_PRELOAD`` set after process start, do not affect *this*
    process — they still propagate to children, which is why this
    never raises, but a warning calls out the dead key."""
    import jax  # deferred import, see enable_x64

    changed: dict[str, str] = {}
    for k, v in env.items():
        if os.environ.get(k) == v:
            continue
        if k == "XLA_FLAGS":
            # jax.devices() memoises the backend; probe without init
            live = jax._src.xla_bridge._backends  # noqa: SLF001
            if live:
                warnings.warn(
                    "XLA_FLAGS set after backend init: affects child "
                    "processes only", stacklevel=2)
        os.environ[k] = v
        changed[k] = v
    return changed


CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and nothing is set here.  Otherwise the cache is the fixed
    ``<checkout>/.jax_cache`` — fixed because the path is part of what a
    later process must find again.  A 36-layer model compiles once per
    prefill bucket and chunk window, so a warm cache saves minutes."""
    import jax  # deferred, see enable_x64

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# .. argparse glue shared by the launchers ..

def add_env_args(ap: argparse.ArgumentParser) -> None:
    g = ap.add_argument_group("environment")
    g.add_argument("--x64", action="store_true",
                   help="enable 64-bit jax (jax_enable_x64)")
    g.add_argument("--host-devices", type=int, default=0,
                   help="fake N host-platform devices via XLA_FLAGS "
                        "(0 = leave alone); must precede first jax use")
    g.add_argument("--xla-preset", default="", choices=["", "cpu", "gpu"],
                   help="platform XLA_FLAGS preset (gpu: latency-hiding "
                        "scheduler + async stream)")
    g.add_argument("--tcmalloc", action="store_true",
                   help="preload libtcmalloc for child processes (and "
                        "report whether this process has it)")


def apply_env_args(args: argparse.Namespace) -> dict[str, str]:
    """Apply the ``add_env_args`` flags; returns the changed env pairs."""
    env: dict[str, str] = {}
    if args.tcmalloc:
        tc = tcmalloc_env()
        if not tc:
            warnings.warn("libtcmalloc not found; skipping preload",
                          stacklevel=2)
        env.update(tc)
    if args.host_devices or args.xla_preset:
        env["XLA_FLAGS"] = xla_flags(
            host_device_count=args.host_devices or None,
            platform=args.xla_preset or None)
    changed = apply(env)
    if args.x64:
        enable_x64(True)
    return changed
