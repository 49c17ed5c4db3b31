"""Per-kernel interpret-mode validation against pure-jnp oracles.

Every Pallas kernel is swept over shapes/dtypes and asserted allclose
(bit-exact where integer) against its ref.py oracle, per the repo policy.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.multiplier import ent_digit_planes
from repro.kernels.ent_matmul.ent_matmul import ent_matmul
from repro.kernels.ent_matmul.ref import ent_matmul_ref
from repro.kernels.flash_attention.flash_attention import (
    flash_attention, flash_attention_masked)
from repro.kernels.flash_attention.ref import (attention_ref,
                                               masked_attention_ref)
from repro.kernels.int8_matmul.int8_matmul import int8_matmul
from repro.kernels.int8_matmul.ref import int8_matmul_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan.ref import ssd_decode_step_ref, ssd_scan_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan

RNG = np.random.default_rng(42)


def _qdata(m, k, n):
    x = jnp.asarray(RNG.integers(-128, 128, (m, k), dtype=np.int8))
    w = jnp.asarray(RNG.integers(-128, 128, (k, n), dtype=np.int8))
    sx = jnp.asarray(RNG.random((m, 1), dtype=np.float32) * 0.1 + 1e-3)
    sw = jnp.asarray(RNG.random((1, n), dtype=np.float32) * 0.1 + 1e-3)
    return x, w, sx, sw


class TestInt8Matmul:
    @pytest.mark.parametrize(
        "m,k,n,bm,bn,bk",
        [
            (128, 256, 128, 128, 128, 128),
            (256, 512, 384, 128, 128, 256),
            (64, 128, 64, 64, 64, 128),
            (128, 1024, 256, 128, 128, 512),
            (8, 128, 128, 8, 128, 128),     # decode-like skinny M
        ],
    )
    def test_shape_sweep(self, m, k, n, bm, bn, bk):
        x, w, sx, sw = _qdata(m, k, n)
        got = int8_matmul(x, w, sx, sw, block_m=bm, block_n=bn, block_k=bk,
                          out_dtype=jnp.float32, interpret=True)
        want = int8_matmul_ref(x, w, sx, sw, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    def test_out_dtypes(self, out_dtype):
        x, w, sx, sw = _qdata(128, 256, 128)
        got = int8_matmul(x, w, sx, sw, out_dtype=out_dtype, interpret=True)
        want = int8_matmul_ref(x, w, sx, sw, out_dtype=out_dtype)
        assert got.dtype == out_dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=1e-2)

    def test_int32_accumulation_no_overflow_path(self):
        """Extremes: all +/-128 activations x +/-128 weights at K=512."""
        x = jnp.full((128, 512), -128, jnp.int8)
        w = jnp.full((512, 128), -128, jnp.int8)
        sx = jnp.ones((128, 1), jnp.float32)
        sw = jnp.ones((1, 128), jnp.float32)
        got = int8_matmul(x, w, sx, sw, out_dtype=jnp.float32, interpret=True)
        assert np.all(np.asarray(got) == 128 * 128 * 512)


class TestEntMatmul:
    @pytest.mark.parametrize(
        "m,k,n,bk",
        [(128, 256, 128, 128), (128, 512, 256, 256), (64, 128, 64, 128),
         (8, 256, 128, 256)],
    )
    def test_bit_exact_vs_plain_int_matmul(self, m, k, n, bk):
        """The EN-T digit-plane kernel must be BIT-EXACT vs int32 matmul
        of the decoded weights — the encoding changes nothing numerically."""
        x, w, sx, sw = _qdata(m, k, n)
        planes = ent_digit_planes(w)
        got = ent_matmul(x, planes, sx, sw, block_m=min(128, m), block_n=min(128, n),
                         block_k=bk, interpret=True)
        plain = (np.asarray(x, np.int32) @ np.asarray(w, np.int32)).astype(np.float32)
        want = plain * np.asarray(sx) * np.asarray(sw)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)

    def test_matches_ref(self):
        x, w, sx, sw = _qdata(128, 256, 128)
        planes = ent_digit_planes(w)
        got = ent_matmul(x, planes, sx, sw, interpret=True, block_k=256)
        want = ent_matmul_ref(x, planes, sx, sw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_property_random_weights(self, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.integers(-128, 128, (64, 128), dtype=np.int8))
        w = jnp.asarray(rng.integers(-128, 128, (128, 64), dtype=np.int8))
        sx = jnp.ones((64, 1), jnp.float32)
        sw = jnp.ones((1, 64), jnp.float32)
        got = ent_matmul(x, ent_digit_planes(w), sx, sw, interpret=True,
                         block_m=64, block_n=64, block_k=128)
        want = np.asarray(x, np.int32) @ np.asarray(w, np.int32)
        np.testing.assert_array_equal(np.asarray(got, np.int64), want)


class TestFlashAttention:
    def _data(self, b, hq, hkv, sq, skv, d, dtype=np.float32):
        q = jnp.asarray(RNG.normal(size=(b, hq, sq, d)).astype(dtype))
        k = jnp.asarray(RNG.normal(size=(b, hkv, skv, d)).astype(dtype))
        v = jnp.asarray(RNG.normal(size=(b, hkv, skv, d)).astype(dtype))
        return q, k, v

    @pytest.mark.parametrize(
        "b,hq,hkv,s,d",
        [(1, 2, 2, 128, 64), (2, 4, 2, 256, 64), (1, 8, 1, 256, 128),
         (2, 2, 2, 512, 32)],
    )
    def test_causal_sweep(self, b, hq, hkv, s, d):
        q, k, v = self._data(b, hq, hkv, s, s, d)
        got = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=64, block_kv=64)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    def test_sliding_window_matches_ref(self):
        q, k, v = self._data(1, 4, 2, 256, 256, 64)
        for w in (32, 64, 128):
            got = flash_attention(q, k, v, causal=True, window=w,
                                  interpret=True, block_q=64, block_kv=64)
            want = attention_ref(q, k, v, causal=True, window=w)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-5, rtol=1e-4)

    def test_decode_suffix_query(self):
        """Sq=1 against a long KV stream (the serving decode path)."""
        q, k, v = self._data(2, 4, 4, 1, 384, 64)
        got = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=1, block_kv=128)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    def test_bf16(self):
        q, k, v = self._data(1, 2, 2, 128, 128, 64)
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
        got = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=64, block_kv=64)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=2e-2)

    def test_nonsquare_blocks(self):
        q, k, v = self._data(1, 2, 2, 256, 256, 64)
        got = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=128, block_kv=32)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("window", [None, 32])
    def test_kernel_path_differentiable(self, window):
        """Training differentiates through the kernel path (its backward
        is the oracle's): grads match the oracle's own."""
        import jax
        from repro.kernels.flash_attention import ops as attn_ops
        q, k, v = self._data(1, 4, 2, 128, 128, 32)

        def loss(use_kernel):
            return lambda q, k, v: jnp.sum(jnp.sin(attn_ops.attention(
                q, k, v, window=window, use_kernel=use_kernel)))

        got = jax.grad(loss("interpret"), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss("ref"), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4, rtol=1e-3)


class TestMaskedFlashAttention:
    """The ragged serving-prefill kernel vs the blocked jnp oracle."""

    def _data(self, b, hq, hkv, sq, skv, d):
        q = jnp.asarray(RNG.normal(size=(b, hq, sq, d)).astype(np.float32))
        k = jnp.asarray(RNG.normal(size=(b, hkv, skv, d)).astype(np.float32))
        v = jnp.asarray(RNG.normal(size=(b, hkv, skv, d)).astype(np.float32))
        return q, k, v

    def test_zero_start_matches_plain_flash(self):
        q, k, v = self._data(2, 4, 2, 128, 128, 64)
        start = jnp.zeros((2,), jnp.int32)
        got = flash_attention_masked(q, k, v, start, interpret=True,
                                     block_q=64, block_kv=64)
        want = flash_attention(q, k, v, interpret=True,
                               block_q=64, block_kv=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    def test_ragged_start_matches_oracle(self):
        q, k, v = self._data(3, 4, 4, 128, 128, 32)
        start = jnp.asarray([0, 17, 90], jnp.int32)
        got = flash_attention_masked(q, k, v, start, interpret=True,
                                     block_q=32, block_kv=64)
        want = masked_attention_ref(q, k, v, start=start)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    def test_pad_query_rows_are_exact_zeros(self):
        q, k, v = self._data(1, 2, 2, 64, 64, 32)
        start = jnp.asarray([40], jnp.int32)
        got = np.asarray(flash_attention_masked(q, k, v, start,
                                                interpret=True,
                                                block_q=32, block_kv=32))
        assert np.all(got[0, :, :40] == 0)
        assert np.any(got[0, :, 40:] != 0)

    def test_q_offset_suffix_chunk(self):
        """Chunked prefill: queries are the suffix of the kv stream."""
        q, k, v = self._data(2, 4, 2, 32, 128, 64)
        start = jnp.asarray([0, 9], jnp.int32)
        got = flash_attention_masked(q, k, v, start, q_offset=96,
                                     interpret=True, block_q=32, block_kv=64)
        want = masked_attention_ref(q, k, v, start=start, q_offset=96)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    def test_sliding_window(self):
        q, k, v = self._data(1, 4, 2, 128, 128, 64)
        start = jnp.asarray([13], jnp.int32)
        got = flash_attention_masked(q, k, v, start, window=32,
                                     interpret=True, block_q=64, block_kv=32)
        want = masked_attention_ref(q, k, v, start=start, window=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    def test_oracle_int8_kv_scale_folding(self):
        """k/v int8 with per-(slot, head) scales: folding after the dot
        equals dequantize-then-attend within f32 round-off."""
        q, _, _ = self._data(2, 4, 2, 32, 32, 16)
        kq = jnp.asarray(RNG.integers(-127, 128, (2, 2, 32, 16), np.int8))
        vq = jnp.asarray(RNG.integers(-127, 128, (2, 2, 32, 16), np.int8))
        ks = jnp.asarray(RNG.random((2, 2, 32), np.float32) * 0.02 + 1e-3)
        vs = jnp.asarray(RNG.random((2, 2, 32), np.float32) * 0.02 + 1e-3)
        got = masked_attention_ref(q, kq.astype(jnp.float32),
                                   vq.astype(jnp.float32),
                                   k_scale=ks, v_scale=vs)
        want = masked_attention_ref(q,
                                    kq.astype(jnp.float32) * ks[..., None],
                                    vq.astype(jnp.float32) * vs[..., None])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)


class TestSSDScan:
    def _data(self, b, l, h, p, g, n):
        x = jnp.asarray(RNG.normal(size=(b, l, h, p)).astype(np.float32))
        dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(b, l, h)).astype(np.float32))
        a = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(h,)).astype(np.float32))
        bm = jnp.asarray(RNG.normal(size=(b, l, g, n)).astype(np.float32))
        cm = jnp.asarray(RNG.normal(size=(b, l, g, n)).astype(np.float32))
        return x, dt, a, bm, cm

    @pytest.mark.parametrize(
        "b,l,h,p,g,n,chunk",
        [(1, 128, 2, 16, 1, 16, 64), (2, 256, 4, 32, 2, 16, 64),
         (1, 256, 4, 64, 1, 32, 128), (1, 512, 2, 32, 2, 64, 128)],
    )
    def test_shape_sweep(self, b, l, h, p, g, n, chunk):
        x, dt, a, bm, cm = self._data(b, l, h, p, g, n)
        got = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
        want = ssd_scan_ref(x, dt, a, bm, cm)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-3)

    def test_single_chunk_equals_multi_chunk(self):
        x, dt, a, bm, cm = self._data(1, 128, 2, 16, 1, 16)
        one = ssd_scan(x, dt, a, bm, cm, chunk=128, interpret=True)
        many = ssd_scan(x, dt, a, bm, cm, chunk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                                   atol=1e-4, rtol=1e-3)

    def test_state_decay_monotone(self):
        """With x=0 everywhere the output is exactly 0 (no state leaks)."""
        x, dt, a, bm, cm = self._data(1, 128, 2, 16, 1, 16)
        got = ssd_scan(jnp.zeros_like(x), dt, a, bm, cm, chunk=64, interpret=True)
        assert np.all(np.asarray(got) == 0)


class TestSSDDecodeStep:
    """The Mamba-2 decode state update (Pallas, interpret mode) against
    ``ssd_decode_step_ref``: stepped slots to f32 rounding (the C
    contraction sums in another order), masked slots and the other layer
    groups of the stack bit for bit."""

    def _data(self, b, h, p, n, g, layers=1):
        f = lambda *s: jnp.asarray(RNG.normal(size=s).astype(np.float32))
        state = f(layers, b, h, p, n)
        dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(b, h)).astype(np.float32))
        a = jnp.asarray(-RNG.uniform(1.0, 16.0, size=(h,)).astype(np.float32))
        return state, f(b, h, p), dt, a, f(b, g, n), f(b, g, n)

    @pytest.mark.parametrize("b,h,p,n,g", [
        (1, 8, 16, 16, 1), (3, 8, 16, 16, 1), (4, 4, 64, 128, 1),
        (2, 16, 64, 128, 2), (5, 64, 16, 32, 4)])
    def test_matches_ref(self, b, h, p, n, g):
        state, x, dt, a, bm, cm = self._data(b, h, p, n, g)
        want_h, want_y = ssd_decode_step_ref(state[0], x, dt, a, bm, cm)
        got_h, got_y = ssd_ops.ssd_decode_step(state, x, dt, a, bm, cm,
                                               jnp.int32(0),
                                               use_kernel="interpret")
        np.testing.assert_allclose(np.asarray(got_h)[0], np.asarray(want_h),
                                   atol=1e-5, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                                   atol=1e-4, rtol=1e-5)

    @pytest.mark.parametrize("b,h,p,n,g", [(4, 8, 16, 16, 1),
                                           (3, 16, 64, 128, 2)])
    def test_masked_slots_and_other_groups_unchanged(self, b, h, p, n, g):
        state, x, dt, a, bm, cm = self._data(b, h, p, n, g, layers=3)
        mask = jnp.arange(b) % 2 == 0
        keep = np.asarray(state)
        got, y = ssd_ops.ssd_decode_step(state, x, dt, a, bm, cm,
                                         jnp.int32(1), mask=mask,
                                         use_kernel="interpret")
        got, y, m = np.asarray(got), np.asarray(y), np.asarray(mask)
        np.testing.assert_array_equal(got[[0, 2]], keep[[0, 2]])
        np.testing.assert_array_equal(got[1][~m], keep[1][~m])
        assert not np.any(y[~m])
        want_h, want_y = ssd_decode_step_ref(keep[1], x, dt, a, bm, cm)
        np.testing.assert_allclose(got[1][m], np.asarray(want_h)[m],
                                   atol=1e-5, rtol=1e-6)
        np.testing.assert_allclose(y[m], np.asarray(want_y)[m],
                                   atol=1e-4, rtol=1e-5)
        # the oracle path keeps the same slots and groups
        ref_h, _ = ssd_ops.ssd_decode_step(state, x, dt, a, bm, cm,
                                           jnp.int32(1), mask=mask,
                                           use_kernel="ref")
        np.testing.assert_array_equal(np.asarray(ref_h)[[0, 2]], keep[[0, 2]])
        np.testing.assert_array_equal(np.asarray(ref_h)[1][~m], keep[1][~m])
