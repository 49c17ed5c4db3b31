"""GPipe pipeline: parity vs sequential + schedule properties."""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.runtime.pipeline import bubble_fraction

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bubble_fraction():
    assert bubble_fraction(1, 4) == pytest.approx(3 / 4)
    assert bubble_fraction(16, 4) == pytest.approx(3 / 19)
    assert bubble_fraction(64, 2) == pytest.approx(1 / 65)


@pytest.mark.slow
def test_pipeline_matches_sequential():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    code = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.runtime.pipeline import pipeline_apply

    mesh = make_mesh((4,), ("stage",))
    S, D = 4, 16

    def stage_fn(params, x):          # one MLP stage
        return jnp.tanh(x @ params["w"] + params["b"])

    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (S, D, D)) * 0.3,
              "b": jnp.zeros((S, D))}
    x = jax.random.normal(jax.random.PRNGKey(1), (8, D))

    # sequential reference
    y_ref = x
    for s in range(S):
        y_ref = stage_fn({"w": params["w"][s], "b": params["b"][s]}, y_ref)

    with mesh:
        y_pipe = jax.jit(lambda p, x: pipeline_apply(
            stage_fn, p, x, mesh=mesh, num_micro=4))(params, x)
    np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)

    # microbatch count must not change the result
    with mesh:
        y2 = jax.jit(lambda p, x: pipeline_apply(
            stage_fn, p, x, mesh=mesh, num_micro=8))(params, x)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    print("pipeline parity OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "pipeline parity OK" in out.stdout
