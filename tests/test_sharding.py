"""Sharding rules: divisibility fallback, profiles, cache/batch specs.

Runs on a 1-device CPU by constructing an ABSTRACT 256-device mesh —
PartitionSpec derivation never touches devices.
"""

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import sharding as shd


@pytest.fixture(scope="module")
def mesh():
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def pod_mesh():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestParamRules:
    def test_column_parallel(self, mesh):
        spec = shd.param_pspec("groups/0/mixer/wq/kernel", (32, 4096, 4096), mesh)
        assert spec == P(None, "data", "model")

    def test_row_parallel(self, mesh):
        spec = shd.param_pspec("groups/0/mixer/wo/kernel", (32, 4096, 4096), mesh)
        assert spec == P(None, "model", "data")

    def test_embedding(self, mesh):
        spec = shd.param_pspec("embed/embedding", (152064, 8192), mesh)
        assert spec == P("model", "data")

    def test_divisibility_fallback(self, mesh):
        # 36 kv heads * 64 = 2304 divides 16; but a dim of 100 does not
        spec = shd.param_pspec("groups/0/mixer/wk/kernel", (40, 2304, 100), mesh)
        assert spec == P(None, "data", None)

    def test_norm_replicated(self, mesh):
        spec = shd.param_pspec("groups/0/ffn_norm/scale", (32, 4096), mesh)
        assert spec == P(None, None)

    def test_moe_experts_ep_when_divisible(self, mesh):
        spec = shd.param_pspec("groups/0/ffn/wi_gate", (40, 16, 6144, 10752), mesh)
        assert spec == P(None, "model", "data", None)

    def test_moe_experts_tp_when_not(self, mesh):
        spec = shd.param_pspec("groups/0/ffn/wi_gate", (32, 8, 4096, 14336), mesh)
        assert spec == P(None, None, "data", "model")

    def test_multipod_uses_compound_data(self, pod_mesh):
        spec = shd.param_pspec("groups/0/mixer/wq/kernel", (32, 4096, 4096),
                               pod_mesh)
        assert spec == P(None, ("pod", "data"), "model")


class TestProfiles:
    def test_serve_tp_stationary(self, mesh):
        spec = shd.param_pspec("groups/0/mixer/wq/kernel", (32, 4096, 4096),
                               mesh, profile="serve_tp")
        assert spec == P(None, None, "model")   # no data-axis FSDP

    def test_fsdp_rows_over_all(self, mesh):
        spec = shd.param_pspec("groups/0/mixer/wq/kernel", (48, 6144, 6144),
                               mesh, profile="fsdp")
        assert spec == P(None, ("data", "model"), None)

    def test_fsdp_small_dim_falls_back(self, mesh):
        # dim 128 does not divide 256 -> replicate rather than crash
        spec = shd.param_pspec("groups/0/mixer/wq/kernel", (2, 128, 64),
                               mesh, profile="fsdp")
        assert spec == P(None, None, None)


class TestQuantizedRecords:
    def test_q_like_kernel(self, mesh):
        spec = shd.param_pspec("groups/0/mixer/wq/q", (32, 4096, 4096), mesh,
                               profile="serve_tp")
        assert spec == P(None, None, "model")

    def test_planes_lead_axis(self, mesh):
        spec = shd.param_pspec("groups/0/mixer/wq/planes", (32, 4, 4096, 4096),
                               mesh, profile="serve_tp")
        assert spec == P(None, None, None, "model")

    def test_packed_planes_lead_axis(self, mesh):
        spec = shd.param_pspec("groups/0/mixer/wq/planes_packed",
                               (32, 2, 4096, 4096), mesh, profile="serve_tp")
        assert spec == P(None, None, None, "model")

    def test_scale_follows_out_channel(self, mesh):
        spec = shd.param_pspec("groups/0/mixer/wq/scale", (32, 1, 4096), mesh,
                               profile="serve_tp")
        assert spec == P(None, None, "model")


class TestBatchAndCache:
    def test_batch_sharded_on_data(self, mesh):
        assert shd.batch_pspec((256, 4096), mesh) == P("data", None)

    def test_batch1_replicates(self, mesh):
        assert shd.batch_pspec((1, 524288), mesh) == P(None, None)

    def test_kv_cache_seq_on_model(self, mesh):
        spec = shd.cache_pspec("layers/0/k", (80, 128, 32768, 8, 128), mesh)
        assert spec == P(None, "data", "model", None, None)

    def test_ssd_cache_heads_on_model(self, mesh):
        spec = shd.cache_pspec("layers/0/ssd", (9, 1, 256, 64, 128), mesh)
        assert spec == P(None, None, "model", None, None)

    def test_kv_cache_batch1(self, mesh):
        spec = shd.cache_pspec("layers/0/k", (9, 1, 524288, 8, 128), mesh)
        assert spec == P(None, None, "model", None, None)
