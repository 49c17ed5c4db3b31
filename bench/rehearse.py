"""Compile-only memory rehearsal of each cell for a described TPU v5e.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]

No chip is needed: the TPU compiler, installed here, compiles each cell's
decode tick and last prefill chunk (the programs ``PipelinedScheduler``
serves with, rebuilt here from the same model calls) for one chip of a
described ``v5e:2x2``, from shapes alone.  It prints, per program, what
``memory_analysis()`` counts: arguments (weights plus the KV pool),
temporaries and outputs, against the chip's 16 GB, and which Pallas
kernels the program holds.  Run by hand before a cell's first chip run.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import spec  # noqa: E402
import system  # noqa: E402

KERNELS = ("ent_matmul_packed_fused", "paged_attention",
           "flash_attention_masked")


def programs(model):
    """The decode tick and the final prefill chunk, as the scheduler
    builds them (``runtime/scheduler.py``)."""

    def tick(params, cache, toks, pmask, ppos):
        cache = dict(cache)
        cache["pos"] = jnp.where(pmask, ppos, cache["pos"])
        logits, cache = model.decode_step(params, cache, tokens=toks)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    def chunk_last(params, toks, layers, slot, pos0, last_index):
        view = tuple(c.prefill_view(slot) if hasattr(c, "prefill_view")
                     else c for c in layers)
        c = {"layers": view, "pos": jnp.full((), pos0, jnp.int32)}
        out = model.apply(params, tokens=toks, cache=c, write_cache=True,
                          last_only=True, pos0=pos0, last_index=last_index)
        merged = tuple(f.admit(o, slot) if hasattr(f, "admit") else f
                       for f, o in zip(layers, out["cache"]["layers"]))
        return out["logits"][:, 0], merged

    return (jax.jit(tick, donate_argnums=(1,)),
            jax.jit(chunk_last, static_argnums=(4,), donate_argnums=(2,)))


def rehearse(name: str, dev) -> None:
    # the program picks a kernel or its oracle by jax.default_backend(),
    # which is the CPU here: answer "tpu" while these programs trace
    cpu_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        _rehearse(name, dev)
    finally:
        jax.default_backend = cpu_backend


def _rehearse(name: str, dev) -> None:
    cell = spec.load_cell(name)
    cfg, eng = cell.config, cell.workload["engine"]
    model = system.build_model(system.program(cell).model_config(cfg))
    on = jax.sharding.SingleDeviceSharding(dev)
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on), t)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init, key)
    if cfg["bench"]["weights"] == "ent":
        params = jax.eval_shape(lambda p: system.encode(p, "ent"), params)
    slots, max_len = eng["slots"], eng["max_len"]
    pages = -(-max_len // eng["page_size"]) * slots

    def cache_fn():
        c = model.init_cache(slots, max_len, kind="paged",
                             page_size=eng["page_size"], pages=pages,
                             mapped=False)
        c["pos"] = jnp.zeros((slots,), jnp.int32)
        c["start"] = jnp.zeros((slots,), jnp.int32)
        return c

    params, cache = place(params), place(jax.eval_shape(cache_fn))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=on)
    tick, chunk = programs(model)
    nbytes = lambda t: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(t))
    print(f"{name}: weights {nbytes(params) / 1e9:.2f} GB, KV pool "
          f"{nbytes(cache['layers']) / 1e9:.2f} GB ({pages} pages)", flush=True)
    for label, lowered in (
            ("decode", tick.lower(params, cache, i32(slots),
                                  jax.ShapeDtypeStruct((slots,), bool,
                                                       sharding=on),
                                  i32(slots))),
            ("chunk", chunk.lower(params, i32(1, eng["prefill_chunk"]),
                                  cache["layers"], i32(), 0, i32()))):
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        held = ([k for k in KERNELS if k in text]
                if "tpu_custom_call" in text else [])
        total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes)
        print(f"  {label}: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.2f} GB (aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f}), total {total / 1e9:.2f} "
              f"GB of 16; kernels {held}", flush=True)


def main(names: list[str]) -> int:
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names or [w["name"] for w in spec.benchmark()["workloads"]]:
        rehearse(name, topo.devices[0])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
