"""Packed 2-plane EN-T pipeline: pack/unpack, bit-exactness, overflow bound.

The packed form fuses adjacent digit planes (packed_j = p_2j + 4 p_{2j+1})
so a matmul costs 2 int8 matmuls instead of 4.  Everything here must be
BIT-exact: packing is a re-association of the same integer sum.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import multiplier as mult
from repro.kernels.ent_matmul.ent_matmul import (ent_matmul_packed,
                                                 ent_matmul_packed_fused)
from repro.kernels.ent_matmul import ops as ent_ops
from repro.kernels.ent_matmul.ref import (ent_matmul_int32_ref,
                                          ent_packed_fused_ref,
                                          ent_packed_matmul_int32_ref,
                                          ent_packed_matmul_ref)

RNG = np.random.default_rng(7)


def _ones_scales(m, n):
    return jnp.ones((m, 1), jnp.float32), jnp.ones((1, n), jnp.float32)


class TestPackUnpack:
    def test_exhaustive_int8_roundtrip(self):
        """All 256 int8 weight values: pack halves the planes, decodes
        exactly, and matches the independent numpy oracle."""
        w = jnp.asarray(np.arange(-128, 128, dtype=np.int8).reshape(16, 16))
        planes = mult.ent_digit_planes(w)
        packed = mult.pack_planes(planes)
        assert packed.shape == (2, 16, 16) and packed.dtype == jnp.int8
        np.testing.assert_array_equal(
            np.asarray(packed), mult.np_pack_planes(np.asarray(planes)))
        np.testing.assert_array_equal(
            np.asarray(mult.packed_to_weight(packed)),
            np.asarray(w, np.int32))

    def test_exhaustive_unpack_is_valid_decomposition(self):
        """unpack(pack(p)) digits stay in {-2..2} and re-pack identically."""
        w = jnp.asarray(np.arange(-128, 128, dtype=np.int8).reshape(16, 16))
        packed = mult.ent_packed_planes(w)
        up = mult.unpack_planes(packed)
        assert set(np.asarray(up).ravel().tolist()) <= {-2, -1, 0, 1, 2}
        np.testing.assert_array_equal(
            np.asarray(mult.planes_to_weight(up)), np.asarray(w, np.int32))
        np.testing.assert_array_equal(
            np.asarray(mult.pack_planes(up)), np.asarray(packed))

    def test_packed_value_range(self):
        """Packed plane values stay int8-safe: [-10, 10] in general,
        |packed_1| <= 8 for planes of real int8 weights."""
        w = jnp.asarray(np.arange(-128, 128, dtype=np.int8).reshape(16, 16))
        packed = np.asarray(mult.ent_packed_planes(w), np.int32)
        assert np.abs(packed).max() <= 10
        assert np.abs(packed[1]).max() <= 8


class TestPackedMatmulBitExact:
    def test_dense_matches_4plane_oracle(self):
        x = jnp.asarray(RNG.integers(-128, 128, (32, 64), dtype=np.int8))
        w = jnp.asarray(RNG.integers(-128, 128, (64, 48), dtype=np.int8))
        planes = mult.ent_digit_planes(w)
        got = mult.ent_packed_matmul(x, mult.pack_planes(planes))
        want = ent_matmul_int32_ref(x, planes)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_dense_matches_numpy_oracle(self):
        x = RNG.integers(-128, 128, (8, 16), dtype=np.int8)
        w = RNG.integers(-128, 128, (16, 24), dtype=np.int8)
        got = mult.ent_packed_matmul(
            jnp.asarray(x), mult.ent_packed_planes(jnp.asarray(w)))
        np.testing.assert_array_equal(
            np.asarray(got, np.int64), mult.np_ent_packed_matmul(x, w))

    @pytest.mark.parametrize(
        "m,k,n,bm,bn,bk",
        [(128, 256, 128, 128, 128, 128), (64, 128, 64, 64, 64, 128),
         (8, 256, 128, 8, 128, 256), (128, 512, 256, 128, 128, 512)],
    )
    def test_pallas_kernel_bit_exact(self, m, k, n, bm, bn, bk):
        """Packed Pallas kernel (interpret) == 4-plane int32 oracle."""
        x = jnp.asarray(RNG.integers(-128, 128, (m, k), dtype=np.int8))
        w = jnp.asarray(RNG.integers(-128, 128, (k, n), dtype=np.int8))
        planes = mult.ent_digit_planes(w)
        sx, sw = _ones_scales(m, n)
        got = ent_matmul_packed(x, mult.pack_planes(planes), sx, sw,
                                block_m=bm, block_n=bn, block_k=bk,
                                interpret=True)
        want = ent_matmul_int32_ref(x, planes)
        np.testing.assert_array_equal(
            np.asarray(got, np.int64), np.asarray(want, np.int64))

    def test_kernel_matches_packed_ref_with_scales(self):
        x = jnp.asarray(RNG.integers(-128, 128, (64, 256), dtype=np.int8))
        w = jnp.asarray(RNG.integers(-128, 128, (256, 128), dtype=np.int8))
        sx = jnp.asarray(RNG.random((64, 1), dtype=np.float32) * 0.1 + 1e-3)
        sw = jnp.asarray(RNG.random((1, 128), dtype=np.float32) * 0.1 + 1e-3)
        packed = mult.ent_packed_planes(w)
        got = ent_matmul_packed(x, packed, sx, sw, block_k=256, interpret=True)
        want = ent_packed_matmul_ref(x, packed, sx, sw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_property_random_int8(self, seed):
        rng = np.random.default_rng(seed)
        m, k, n = (int(rng.integers(1, 33)) for _ in range(3))
        x = rng.integers(-128, 128, (m, k), dtype=np.int8)
        w = rng.integers(-128, 128, (k, n), dtype=np.int8)
        got = mult.ent_packed_matmul(
            jnp.asarray(x), mult.ent_packed_planes(jnp.asarray(w)))
        np.testing.assert_array_equal(
            np.asarray(got), x.astype(np.int32) @ w.astype(np.int32))


class TestFusedQuantPath:
    def test_fused_kernel_matches_fused_ref(self):
        xf = jnp.asarray(RNG.normal(size=(64, 256)).astype(np.float32))
        w = jnp.asarray(RNG.integers(-128, 128, (256, 128), dtype=np.int8))
        sw = jnp.asarray(RNG.random((1, 128), dtype=np.float32) * 0.1 + 1e-3)
        packed = mult.ent_packed_planes(w)
        got = ent_ops.ent_quantized_matmul_fused(
            xf, packed, sw, use_kernel="interpret")
        want = ent_packed_fused_ref(xf, packed, sw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

    @pytest.mark.parametrize("m", [1, 3, 12, 20])
    def test_unaligned_rows_padded_match_oracle(self, m):
        """Row counts off the 8-row tile (decode slots, verify bursts)
        launch padded to it and slice back: exact vs the oracle."""
        from repro.kernels.int8_matmul.ops import quantized_matmul
        from repro.quant.quantize import quantize_acts
        xf = jnp.asarray(RNG.normal(size=(m, 256)).astype(np.float32))
        w = jnp.asarray(RNG.integers(-127, 128, (256, 128), dtype=np.int8))
        sw = jnp.asarray(RNG.random((1, 128), dtype=np.float32) * 0.1 + 1e-3)
        packed = mult.ent_packed_planes(w)
        got = ent_ops.ent_quantized_matmul_fused(
            xf, packed, sw, use_kernel="interpret")
        assert got.shape == (m, 128)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ent_packed_fused_ref(xf, packed, sw)))
        xq, sx = quantize_acts(xf)
        for op, wop in ((ent_ops.ent_quantized_matmul_packed, packed),
                        (quantized_matmul, w)):
            got = op(xq, wop, sx, sw, out_dtype=jnp.float32,
                     use_kernel="interpret")
            want = op(xq, wop, sx, sw, out_dtype=jnp.float32,
                      use_kernel="ref")
            assert got.shape == (m, 128)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_fused_equals_separate_quantize_then_matmul(self):
        """Fusing the act-quant into the kernel changes WHERE the int8 is
        made, not its value: identical to quantize_acts + packed matmul."""
        from repro.quant.quantize import quantize_acts
        xf = jnp.asarray(RNG.normal(size=(32, 128)).astype(np.float32))
        w = jnp.asarray(RNG.integers(-128, 128, (128, 64), dtype=np.int8))
        sw = jnp.ones((1, 64), jnp.float32)
        packed = mult.ent_packed_planes(w)
        fused = ent_ops.ent_quantized_matmul_fused(xf, packed, sw,
                                                   use_kernel="ref")
        xq, sx = quantize_acts(xf)
        separate = ent_packed_matmul_ref(xq, packed, sx, sw)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(separate))

    def test_fused_bf16_input(self):
        xf = jnp.asarray(RNG.normal(size=(16, 128)).astype(np.float32))
        w = jnp.asarray(RNG.integers(-128, 128, (128, 64), dtype=np.int8))
        sw = jnp.ones((1, 64), jnp.float32)
        packed = mult.ent_packed_planes(w)
        got = ent_ops.ent_quantized_matmul_fused(
            xf.astype(jnp.bfloat16), packed, sw, use_kernel="interpret")
        want = ent_packed_fused_ref(xf.astype(jnp.bfloat16), packed, sw)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=1e-6)


class TestOverflowBound:
    def test_worst_case_large_k_no_int32_overflow(self):
        """K = 2**16 with the adversarial all -128 x -128 operands: the
        shifted high-plane partial sum reaches 2**30 and must still be
        bit-exact vs an int64 oracle (no int32 wraparound)."""
        k = 1 << 16
        assert k <= mult.PACKED_MAX_K
        x = np.full((2, k), -128, np.int8)
        w = np.full((k, 8), -128, np.int8)
        packed = mult.ent_packed_planes(jnp.asarray(w))
        # worst case realized: the high packed plane of -128 is -8, so the
        # shifted term accumulates (-128 * -8) << 4 = 16384 per element
        assert int(np.asarray(packed[1]).min()) == -8
        got = mult.ent_packed_matmul(jnp.asarray(x), packed)
        want = x.astype(np.int64) @ w.astype(np.int64)
        assert int(want.max()) == (128 * 128) * k  # 2**30: near the edge
        np.testing.assert_array_equal(np.asarray(got, np.int64), want)

    def test_packed_max_k_constant(self):
        """The documented bound: K products of |x*packed_0| +
        |x*packed_1*16| <= 128*10*17 must fit int32."""
        assert mult.PACKED_MAX_K == (2**31 - 1) // (128 * 10 * 17)
        assert mult.PACKED_MAX_K >= 1 << 16

    def test_kernel_rejects_oversized_k(self):
        x = jnp.zeros((8, 8), jnp.int8)
        packed = jnp.zeros((2, 8, 8), jnp.int8)
        sx, sw = _ones_scales(8, 8)
        with pytest.raises(AssertionError):
            ent_matmul_packed(
                jnp.zeros((8, 1 << 20), jnp.int8),
                jnp.zeros((2, 1 << 20, 8), jnp.int8), sx, sw,
                block_m=8, block_n=8, block_k=128, interpret=True)
        # sanity: the in-bound shape passes
        ent_matmul_packed(x, packed, sx, sw, block_m=8, block_n=8,
                          block_k=8, interpret=True)


class TestQuantRecordIntegration:
    def test_quantize_weight_emits_packed_planes(self):
        from repro.quant.quantize import quantize_weight
        w = jnp.asarray(RNG.normal(size=(64, 32)).astype(np.float32))
        rec = quantize_weight(w)
        assert rec["planes_packed"].shape == (2, 64, 32)
        np.testing.assert_array_equal(
            np.asarray(mult.packed_to_weight(rec["planes_packed"])),
            np.asarray(quantize_weight(w, ent_encode=False)["q"], np.int32))

    def test_qdense_packed_equals_plain_int8(self):
        """Packed EN-T serving path == plain int8 path, bitwise (the
        encoding is exact; only the silicon cost changes)."""
        from repro.quant.quantize import qdense_apply, quantize_weight
        w = jnp.asarray(RNG.normal(size=(96, 64)).astype(np.float32))
        x = jnp.asarray(RNG.normal(size=(4, 96)).astype(np.float32))
        rec_ent = quantize_weight(w, ent_encode=True)
        rec_plain = quantize_weight(w, ent_encode=False)
        y_ent = qdense_apply(rec_ent, x, out_dtype=jnp.float32)
        y_plain = qdense_apply(rec_plain, x, out_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(y_ent), np.asarray(y_plain))
