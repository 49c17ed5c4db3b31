"""Production training entry point.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --steps 1000 [--mesh 16x16 | 2x16x16] [--ckpt DIR] [--smoke]

On a real cluster every host runs this under `jax.distributed`; here the
mesh maps onto whatever devices exist (use --smoke for the reduced config
on CPU).  Wires together: config registry -> model -> sharded train step
(FSDP x TP x DP + seq-parallel activations) -> deterministic data stream
-> async checkpointing with resume -> straggler/health bookkeeping.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as shd
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import ARCH_IDS, get_config, get_optim, reduced_config
from repro.launch import env as envmod
from repro.launch.mesh import make_mesh
from repro.configs.base import TrainConfig
from repro.data.pipeline import Prefetcher, SyntheticSource, TokenStream
from repro.models.transformer import build_model
from repro.runtime.elastic import HealthMonitor, StragglerPolicy
from repro.runtime.train_loop import init_opt_state, make_train_step


def parse_mesh(spec: str):
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("pod", "data", "model")[-len(dims):] if len(dims) == 3 else ("data", "model")
    return dims, axes


def build_train(cfg, mesh, ocfg, tcfg, *, seed: int = 0):
    """Sharded training state and step on ``mesh``: params initialised
    from ``seed`` straight into their FSDP x TP shardings, AdamW state
    sharded like them, and the jitted step (params/opt donated).  A 1x1
    mesh gives the same step on one device.  Returns (params, opt,
    step_fn, batch_sharding)."""
    da = shd.data_axes(mesh)
    use_dist = mesh.size > 1
    model = build_model(
        cfg,
        act_sharding=P(da, "model", None) if use_dist else None,
        dist=(mesh, da) if use_dist else None)
    key = jax.random.PRNGKey(seed)
    psh = shd.params_shardings(jax.eval_shape(model.init, key), mesh)
    with mesh:
        params = jax.jit(model.init, out_shardings=psh)(key)
        opt_sh = jax.tree.map(
            lambda _: NamedSharding(mesh, P()),
            jax.eval_shape(lambda p: init_opt_state(tcfg, p), params))
        opt_sh["adam"]["m"] = opt_sh["adam"]["v"] = psh
        opt = jax.jit(lambda p: init_opt_state(tcfg, p),
                      out_shardings=opt_sh)(params)
        step_fn = jax.jit(
            make_train_step(
                model, ocfg, tcfg, data_axes=da if use_dist else None,
                grad_shardings=psh if use_dist else None),
            donate_argnums=(0, 1))
    return params, opt, step_fn, NamedSharding(mesh, P(da, None))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--remat", default="full", choices=("none", "full", "dots"))
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--log-every", type=int, default=10)
    envmod.add_env_args(ap)
    args = ap.parse_args()
    envmod.apply_env_args(args)
    envmod.enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced_config(cfg)
        args.seq = min(args.seq, 128)
        args.batch = min(args.batch, 8)
    ocfg = get_optim(args.arch)
    tcfg = TrainConfig(seq_len=args.seq, global_batch=args.batch,
                       microbatch=args.microbatch, remat=args.remat)

    dims, axes = parse_mesh(args.mesh)
    mesh = make_mesh(dims, axes)

    print(f"arch={cfg.name} params={cfg.param_count()/1e9:.2f}B "
          f"mesh={dims} remat={args.remat}")
    params, opt, step_fn, bspec = build_train(cfg, mesh, ocfg, tcfg)
    with mesh:
        start = 0
        ck = Checkpointer(args.ckpt) if args.ckpt else None
        if ck is not None:
            latest = ck.latest_step()
            if latest is not None:
                print(f"resuming from checkpoint step {latest}")
                state = ck.restore(latest, {"params": params, "opt": opt})
                params, opt, start = state["params"], state["opt"], latest

        stream = TokenStream(SyntheticSource(cfg.vocab_size, seed=1234),
                             global_batch=args.batch, seq_len=args.seq,
                             start_step=start)
        pf = Prefetcher(stream, depth=2)
        monitor = HealthMonitor()
        straggler = StragglerPolicy()
        times = {}
        try:
            for s in range(start, args.steps):
                t0 = time.perf_counter()
                batch = {k: jax.device_put(jnp.asarray(v), bspec)
                         for k, v in pf.next().items()}
                params, opt, m = step_fn(params, opt, batch)
                monitor.beat(0)
                times[0] = time.perf_counter() - t0
                if (s + 1) % args.log_every == 0:
                    tok_s = args.batch * args.seq / max(times[0], 1e-9)
                    print(f"step {s+1:5d} loss {float(m['loss']):.4f} "
                          f"lr {float(m['lr']):.2e} "
                          f"gnorm {float(m['grad_norm']):.2f} "
                          f"tok/s {tok_s:,.0f}")
                if ck is not None and (s + 1) % tcfg.checkpoint_every == 0:
                    ck.save(s + 1, {"params": params, "opt": opt})
        finally:
            pf.close()
            if ck is not None:
                ck.wait()
        del straggler  # policy exercised in tests; coordinator hooks go here


if __name__ == "__main__":
    main()
