"""Per-architecture smoke tests: reduced config, one fwd/train/decode step.

Exactly what the assignment mandates: every assigned arch instantiates at
toy scale and runs on CPU asserting output shapes + no NaNs.  The FULL
configs are exercised only via the dry-run (ShapeDtypeStructs).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.models.transformer import build_model, loss_fn

BATCH, SEQ = 2, 16


def _batch(cfg, key):
    toks = jax.random.randint(key, (BATCH, SEQ), 0, cfg.vocab_size)
    batch = {"labels": toks}
    if cfg.modality == "text":
        batch["tokens"] = toks
    else:  # audio/vlm: stub frontend supplies precomputed embeddings
        batch["embeds"] = jax.random.normal(key, (BATCH, SEQ, cfg.d_model))
    return batch


@pytest.fixture(scope="module")
def models():
    return {}


def _get(models, arch):
    if arch not in models:
        cfg = reduced_config(get_config(arch))
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(hash(arch) % 2**31))
        models[arch] = (cfg, m, params)
    return models[arch]


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_forward_shapes_and_finite(self, models, arch):
        cfg, m, params = _get(models, arch)
        batch = _batch(cfg, jax.random.PRNGKey(0))
        out = m.apply(params, tokens=batch.get("tokens"),
                      embeds=batch.get("embeds"), labels=batch["labels"])
        assert out["logits"].shape == (BATCH, SEQ, cfg.padded_vocab)
        assert np.isfinite(float(out["loss"])), arch
        assert np.all(np.isfinite(np.asarray(out["logits"]))), arch

    def test_one_train_step(self, models, arch):
        cfg, m, params = _get(models, arch)
        batch = _batch(cfg, jax.random.PRNGKey(1))
        (loss, _), grads = jax.value_and_grad(
            lambda p: loss_fn(m, p, batch), has_aux=True)(params)
        assert np.isfinite(float(loss)), arch
        flat = jax.tree.leaves(grads)
        assert flat, arch
        for g in flat:
            assert np.all(np.isfinite(np.asarray(g))), arch
        # grads must not be identically zero for the big matmuls
        total = sum(float(jnp.sum(jnp.abs(g))) for g in flat)
        assert total > 0, arch

    def test_one_decode_step(self, models, arch):
        cfg, m, params = _get(models, arch)
        cache = m.init_cache(BATCH, SEQ)
        if cfg.modality == "text":
            logits, cache = m.decode_step(
                params, cache, tokens=jnp.zeros((BATCH,), jnp.int32))
        else:
            logits, cache = m.decode_step(
                params, cache,
                embeds=jnp.ones((BATCH, 1, cfg.d_model), jnp.float32))
        assert logits.shape == (BATCH, cfg.padded_vocab)
        assert np.all(np.isfinite(np.asarray(logits))), arch
        assert int(cache["pos"]) == 1


class TestDecodePrefillConsistency:
    """Step-by-step decode must match the full forward (per family)."""

    @pytest.mark.parametrize("arch", ["qwen2.5-3b", "mamba2-370m",
                                      "mixtral-8x7b", "jamba-1.5-large",
                                      "granite-4.0-h-micro"])
    def test_consistency(self, models, arch):
        cfg, m, params = _get(models, arch)
        key = jax.random.PRNGKey(7)
        toks = jax.random.randint(key, (BATCH, 8), 0, cfg.vocab_size)
        if cfg.modality != "text":
            pytest.skip("embedding-input archs tested via families above")
        cache = m.init_cache(BATCH, 8)
        step_logits = []
        for t in range(8):
            lg, cache = m.decode_step(params, cache, tokens=toks[:, t])
            step_logits.append(lg)
        full = m.apply(params, tokens=toks)["logits"]
        np.testing.assert_allclose(
            np.asarray(jnp.stack(step_logits, 1)), np.asarray(full),
            atol=5e-3, rtol=2e-2)


class TestParamCounts:
    """Full configs: analytic parameter counts in the expected range."""

    @pytest.mark.parametrize(
        "arch,lo,hi",
        [
            ("mixtral-8x7b", 45e9, 49e9),      # 46.7B total
            ("qwen2-72b", 70e9, 76e9),
            ("minicpm-2b", 2.4e9, 3.0e9),
            ("starcoder2-15b", 14e9, 17e9),
            ("qwen2.5-3b", 2.8e9, 3.7e9),
            ("dbrx-132b", 125e9, 140e9),
            ("mamba2-370m", 0.3e9, 0.45e9),
            ("musicgen-medium", 1.2e9, 1.8e9),
            ("llava-next-34b", 32e9, 36e9),
            ("jamba-1.5-large", 360e9, 420e9),
            ("granite-4.0-h-micro", 3.1e9, 3.3e9),   # ~3.19B
        ],
    )
    def test_param_count_range(self, arch, lo, hi):
        cfg = get_config(arch)
        n = cfg.param_count()
        assert lo <= n <= hi, (arch, f"{n/1e9:.2f}B")

    def test_moe_active_less_than_total(self):
        for arch in ("mixtral-8x7b", "dbrx-132b", "jamba-1.5-large"):
            cfg = get_config(arch)
            assert cfg.active_param_count() < cfg.param_count()

    def test_mixtral_active_about_13b(self):
        cfg = get_config("mixtral-8x7b")
        assert 12e9 <= cfg.active_param_count() <= 14.5e9

    def test_long_context_applicability(self):
        """DESIGN.md §Arch-applicability: who runs long_500k."""
        runnable = {a for a in ARCH_IDS if get_config(a).is_sub_quadratic}
        assert runnable == {"mixtral-8x7b", "starcoder2-15b",
                            "jamba-1.5-large", "mamba2-370m",
                            "granite-4.0-h-micro"}
