"""EN-T w8a8 quantization stack: correctness + end-to-end serving."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.configs.base import QuantConfig
from repro.models import layers as L
from repro.models.transformer import build_model
from repro.quant.quantize import (dequantize_weight, qdense_apply,
                                  quantize_acts, quantize_params,
                                  quantize_weight)


class TestWeightQuant:
    def test_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
        rec = quantize_weight(w)
        err = jnp.abs(dequantize_weight(rec) - w)
        per_col_scale = jnp.max(jnp.abs(w), axis=0) / 127.0
        assert float(jnp.max(err - per_col_scale[None, :] / 2)) <= 1e-6

    def test_packed_planes_reconstruct_q(self):
        """The packed EN-T planes must decode to exactly the int8 weights
        (the packed record carries no int8 copy of its own)."""
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.normal(size=(32, 48)).astype(np.float32))
        rec = quantize_weight(w, ent_encode=True)
        assert set(rec) == {"planes_packed", "scale"}
        assert rec["planes_packed"].shape == (2, 32, 48)
        assert rec["planes_packed"].dtype == jnp.int8
        weights = jnp.asarray([1, 16], jnp.int32)
        recon = jnp.sum(rec["planes_packed"].astype(jnp.int32)
                        * weights[:, None, None], axis=0)
        q = quantize_weight(w, ent_encode=False)["q"]
        np.testing.assert_array_equal(np.asarray(recon),
                                      np.asarray(q, np.int32))
        np.testing.assert_array_equal(
            np.asarray(dequantize_weight(rec)),
            np.asarray(q.astype(jnp.float32) * rec["scale"]))

    def test_qdense_matches_float_within_quant_error(self):
        rng = np.random.default_rng(2)
        w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32)) * 0.1
        x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
        rec = quantize_weight(w)
        got = qdense_apply(rec, x, out_dtype=jnp.float32)
        want = x @ w
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert rel < 0.03, rel

    def test_act_quant_per_row(self):
        x = jnp.asarray([[1.0, -2.0, 0.5], [100.0, 50.0, -100.0]])
        q, s = quantize_acts(x)
        assert q.dtype == jnp.int8
        np.testing.assert_allclose(
            np.asarray(q * s), np.asarray(x), atol=np.asarray(s).max())


class TestQuantizeParams:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg = reduced_config(get_config("qwen2.5-3b"))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        qparams = quantize_params(params, QuantConfig(enabled=True))
        return cfg, model, params, qparams

    def test_skip_patterns_respected(self, setup):
        _, _, params, qparams = setup
        assert "kernel" in qparams["lm_head"]          # skipped: stays float
        assert "embedding" in qparams["embed"]
        g0 = qparams["groups"][0]
        assert "planes_packed" in g0["mixer"]["wq"]
        assert "q" not in g0["mixer"]["wq"]            # planes only
        assert "scale" in g0["ffn_norm"]               # norms untouched

    def test_stacked_kernels_quantized_per_group(self, setup):
        _, _, params, qparams = setup
        wq = qparams["groups"][0]["mixer"]["wq"]
        kern = params["groups"][0]["mixer"]["wq"]["kernel"]
        g = kern.shape[0]
        assert wq["planes_packed"].shape == (g, 2) + kern.shape[1:]
        assert wq["scale"].shape == (g, 1, kern.shape[-1])
        # slice-at-a-time encoding == encoding each [I, O] slice alone
        for i in range(g):
            one = jax.jit(quantize_weight)(kern[i])
            for leaf in ("planes_packed", "scale"):
                np.testing.assert_array_equal(np.asarray(wq[leaf][i]),
                                              np.asarray(one[leaf]))

    def test_quantized_model_serves_close_to_float(self, setup):
        cfg, model, params, qparams = setup
        toks = jnp.arange(2 * 16).reshape(2, 16) % cfg.vocab_size
        lf = model.apply(params, tokens=toks)["logits"]
        lq = model.apply(qparams, tokens=toks)["logits"]
        # compare next-token argmax agreement (robust metric)
        agree = float(jnp.mean(
            (jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).astype(jnp.float32)))
        assert agree > 0.9, agree

    def test_quantized_decode_runs(self, setup):
        cfg, model, params, qparams = setup
        cache = model.init_cache(2, 8)
        logits, cache = model.decode_step(
            qparams, cache, tokens=jnp.zeros((2,), jnp.int32))
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))


class TestEntServingEquivalence:
    def test_ent_planes_equal_plain_int8_path(self):
        """The EN-T encoded path must be numerically IDENTICAL to the
        plain int8 path (the encoding is exact) — the paper's claim that
        EN-T changes silicon cost, not results."""
        rng = np.random.default_rng(3)
        w = jnp.asarray(rng.normal(size=(96, 64)).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(4, 96)).astype(np.float32))
        rec_ent = quantize_weight(w, ent_encode=True)
        rec_plain = quantize_weight(w, ent_encode=False)
        y_ent = qdense_apply(rec_ent, x, out_dtype=jnp.float32)
        y_plain = qdense_apply(rec_plain, x, out_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(y_ent), np.asarray(y_plain))
