"""int8 KV cache: exactness of scale folding + decode quality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import attention, kv_cache
from repro.models.transformer import build_model


class TestKVQuantPrimitives:
    def test_quantize_roundtrip(self):
        t = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 4, 16))
        q, s = kv_cache.quantize_kv(t)
        assert q.dtype == jnp.int8 and s.dtype == jnp.bfloat16
        deq = q.astype(jnp.float32) * s.astype(jnp.float32)
        # error budget: 0.5*scale rounding + 127 * scale * 2^-8 from the
        # bf16 scale itself
        np.testing.assert_allclose(np.asarray(deq), np.asarray(t),
                                   atol=float(jnp.max(s)) * 1.1)

    def test_cache_shapes(self):
        cfg = reduced_config(get_config("qwen2.5-3b"))
        c = attention.init_cache(cfg, 2, 16, jnp.bfloat16, quantized=True)
        assert isinstance(c, kv_cache.DenseCache) and c.quantized
        assert c.k.dtype == jnp.int8
        assert c.k_s.shape == c.k.shape[:-1] + (1,)

    def test_paged_cache_scales_per_page(self):
        cfg = reduced_config(get_config("qwen2.5-3b"))
        c = attention.init_cache(cfg, 2, 16, jnp.bfloat16, quantized=True,
                                 kind="paged", page_size=8)
        assert isinstance(c, kv_cache.PagedCache) and c.quantized
        assert c.k.dtype == jnp.int8
        assert c.k_s.shape == c.k.shape[:-1] + (1,)   # [P, H, page, 1]
        assert c.block_table.shape == (2, 2)


class TestKVQuantDecode:
    @pytest.mark.parametrize("arch", ["qwen2.5-3b", "mixtral-8x7b",
                                      "starcoder2-15b"])
    def test_matches_float_decode(self, arch):
        """argmax-identical, logits within ~1% at toy scale; the scale
        folding itself is EXACT (per-slot scalars commute through the
        dots) so all error is int8 rounding of K/V."""
        cfg = reduced_config(get_config(arch))
        m = build_model(cfg)
        mq = build_model(cfg, kv_quant=True)
        params = m.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                  cfg.vocab_size)
        c1, c2 = m.init_cache(2, 8), mq.init_cache(2, 8)
        l1 = l2 = None
        for t in range(8):
            l1, c1 = m.decode_step(params, c1, tokens=toks[:, t])
            l2, c2 = mq.decode_step(params, c2, tokens=toks[:, t])
        a1 = np.argmax(np.asarray(l1), -1)
        a2 = np.argmax(np.asarray(l2), -1)
        assert (a1 == a2).all(), arch
        rel = (np.abs(np.asarray(l1) - np.asarray(l2)).max()
               / np.abs(np.asarray(l1)).max())
        assert rel < 0.05, (arch, rel)

    def test_cache_memory_half(self):
        cfg = reduced_config(get_config("qwen2.5-3b"))
        cf = attention.init_cache(cfg, 2, 64, jnp.bfloat16)
        cq = attention.init_cache(cfg, 2, 64, jnp.bfloat16, quantized=True)
        bytes_f = sum(np.asarray(x).nbytes for x in jax.tree.leaves(cf))
        bytes_q = sum(np.asarray(x).nbytes for x in jax.tree.leaves(cq))
        assert bytes_q < 0.6 * bytes_f  # int8 + small scale planes
