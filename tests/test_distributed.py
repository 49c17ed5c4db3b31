"""Multi-device SPMD correctness, via subprocess with 8 host devices.

The shard_map EP MoE and the sharded train step must produce the SAME
numbers as the single-device reference — this is the correctness
guarantee behind every dry-run cell.  jax locks the device count at
first init, so these tests run in a fresh interpreter with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_shard_map_moe_matches_reference():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.models import moe

    assert len(jax.devices()) == 8
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=128,
                      moe=MoEConfig(num_experts=4, top_k=2,
                                    capacity_factor=2.0),
                      param_dtype="float32", compute_dtype="float32")
    p = moe.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

    y_ref, aux_ref = moe.apply(cfg, p, x)
    with mesh:
        y_sh, aux_sh = jax.jit(
            lambda p, x: moe.apply_sharded(cfg, p, x, mesh, "data"))(p, x)
    # same routing, same experts; capacity semantics differ only when
    # tokens drop — capacity_factor=2 makes both dropless here
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sh),
                               atol=2e-5, rtol=1e-4)
    # aux is per-shard-then-averaged in the distributed variant (see
    # moe.py) — same scale, not bit-identical
    np.testing.assert_allclose(float(aux_ref), float(aux_sh), rtol=0.2)
    print("moe parity OK")
    """)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import sharding as shd
    from repro.configs import get_config, reduced_config
    from repro.configs.base import OptimConfig, TrainConfig
    from repro.models.transformer import build_model
    from repro.runtime.train_loop import init_opt_state, make_train_step

    cfg = reduced_config(get_config("qwen2.5-3b"))
    mesh = make_mesh((2, 4), ("data", "model"))
    ocfg = OptimConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    tcfg = TrainConfig(seq_len=32, global_batch=4)
    batch = {"tokens": jnp.ones((4, 32), jnp.int32),
             "labels": jnp.ones((4, 32), jnp.int32)}

    m1 = build_model(cfg)
    p = m1.init(jax.random.PRNGKey(0))
    opt = init_opt_state(tcfg, p)
    p1, _, m1out = jax.jit(make_train_step(m1, ocfg, tcfg))(p, opt, batch)

    m2 = build_model(cfg, act_sharding=P("data", None, None),
                     dist=(mesh, "data"))
    with mesh:
        psh = shd.params_shardings(p, mesh)
        step = jax.jit(make_train_step(m2, ocfg, tcfg, data_axes="data",
                                       grad_shardings=psh),
                       in_shardings=(psh, None, None))
        p2, _, m2out = step(p, opt, batch)
    np.testing.assert_allclose(float(m1out["loss"]), float(m2out["loss"]),
                               rtol=2e-3)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=3e-4, rtol=3e-2)
    print("train-step parity OK")
    """)


@pytest.mark.slow
def test_decode_step_parity_on_mesh():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro import sharding as shd
    from repro.configs import get_config, reduced_config
    from repro.models.transformer import build_model

    cfg = reduced_config(get_config("mixtral-8x7b"))
    mesh = make_mesh((2, 4), ("data", "model"))
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    cache = m.init_cache(4, 8)
    toks = jnp.ones((4,), jnp.int32)
    l1, _ = m.decode_step(p, cache, tokens=toks)

    md = build_model(cfg, dist=(mesh, "data"))
    with mesh:
        psh = shd.params_shardings(p, mesh, profile="serve_tp")
        csh = shd.cache_shardings(cache, mesh)
        step = jax.jit(lambda p, c, t: md.decode_step(p, c, tokens=t),
                       in_shardings=(psh, csh, None))
        l2, _ = step(p, cache, toks)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=2e-4, rtol=2e-3)
    print("decode parity OK")
    """)
