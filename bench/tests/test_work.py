"""Operation and byte counts against hand counts, the dense adapter's
counts and the readers built on them against the values the harness gave
before the counts moved into the adapter, and the rooflines' ceiling by
construction."""

import json
import os
import types

import pytest

import spec
import work
from trace_reduce import OpCall, Reduction
from peaks import PEAKS, peaks

BENCH = spec.BENCH
CONFIGS = {n: json.load(open(os.path.join(BENCH, "configs", f"{n}.json")))
           for n in ("qwen2.5-3b-ent", "minicpm-2b-bf16")}
QWEN = CONFIGS["qwen2.5-3b-ent"]
DENSE = spec.reader("programs", "dense")
V5E = PEAKS["TPU v5 lite"]


def test_ent_matmul_hand_count():
    # a decode tick's up-projection: 64 rows x [2048, 11008]
    ops, nbytes = work.ent_matmul(64, 2048, 11008)
    assert ops == 2 * 64 * 2048 * 11008 == 2_885_681_152
    # int8 weight + f32 column scale + bf16 rows in + bf16 rows out
    assert nbytes == 2048 * 11008 + 4 * 11008 + 2 * 64 * 2048 + 2 * 64 * 11008
    assert nbytes == 24_259_584
    # memory-bound at decode: bytes / bandwidth beats ops / int8 peak
    t = work.least_time(ops, nbytes, V5E["int8"], V5E["hbm_bytes_per_s"])
    assert t == pytest.approx(24_259_584 / 819e9)


def test_paged_attention_hand_count():
    # two slots at contexts 100 and 300, Qwen: 16 query heads, 2 KV heads of 128
    ops, nbytes = work.paged_attention([100, 300], 16, 2, 128, 2)
    assert ops == 4 * 400 * 16 * 128 == 3_276_800
    assert nbytes == 2 * 400 * 2 * 128 * 2 == 409_600


def test_decode_tick_hand_count():
    # Qwen2.5-3B: per layer q 2048x2048, k and v 2048x256, o 2048x2048,
    # gate/up 2048x11008, down 11008x2048
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008
    assert DENSE.layer_matmul_params(QWEN) == per_layer == 77_070_336
    assert DENSE.query_heads(QWEN) == 16
    mm, other = DENSE.token_ops(QWEN, 1000, head=True)
    assert mm == 2 * per_layer * 36
    assert other == 4 * 1000 * 16 * 128 * 36 + 2 * 2048 * 151936
    # a prefill chunk of 256 tokens at positions [256, 512)
    mm, other = DENSE.prefill_ops(QWEN, 256, 256, head=False)
    assert mm == 2 * per_layer * 36 * 256
    assert other == 4 * (256 * 256 + 256 * 257 / 2) * 16 * 128 * 36


# what the harness's work.py and readers gave before the counts moved into
# the adapter, for qwen2.5-3b-ent and minicpm-2b-bf16 as filed, on a fixed
# set of decode ticks (time, contexts of the active slots) and prefill
# chunks (time, start, real tokens, final)
with open(os.path.join(BENCH, "tests", "data", "work_before.json")) as f:
    BEFORE = json.load(f)
DECODES = [tuple(d) for d in BEFORE["decodes"]]
CHUNKS = [tuple(c) for c in BEFORE["chunks"]]


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_dense_adapter_counts_as_before(config):
    cfg, want = CONFIGS[config], BEFORE[config]
    for ctx, head, *v in want["token_ops"]:
        assert DENSE.token_ops(cfg, ctx, head) == tuple(v), (ctx, head)
    for lo, n, head, *v in want["prefill_ops"]:
        assert DENSE.prefill_ops(cfg, lo, n, head) == tuple(v), (lo, n, head)
    for lo, hi, *v in want["served_work"]:
        assert work.served_work(DENSE, cfg, DECODES, CHUNKS, lo,
                                hi) == tuple(v), (lo, hi)


@pytest.mark.parametrize("config", sorted(CONFIGS))
@pytest.mark.parametrize("metric", ["mfu", "step_mfu", "paged_attn_roofline"])
def test_readers_through_the_adapter_read_as_before(config, metric):
    # 72 paged attention calls of 0.1 ms on Qwen's pools, 2 s traced of 3
    calls = [OpCall("paged_attention", "paged_attention.1", 1e-4,
                    [("f32", (64, 2, 8, 128)), ("bf16", (64, 16, 1, 128)),
                     ("bf16", (8192, 2, 16, 128))])] * 72
    ctx = types.SimpleNamespace(
        cfg=CONFIGS[config], program=DENSE, peaks=peaks("TPU v5 lite"),
        trace=Reduction(2.0, 1.5, 1, 0.0, calls), trace_span=(0.4, 2.6),
        decodes=DECODES, chunks=CHUNKS, window=(0.0, 3.0), window_s=3.0)
    got = spec.reader("layer_metrics", metric).read(ctx)
    assert got == BEFORE[config]["readers"][metric]


def _roofline(calls):
    mod = spec.reader("layer_metrics", "ent_matmul_roofline")
    ctx = types.SimpleNamespace(trace=Reduction(1.0, 1.0, 1, 0.0, calls),
                                peaks=peaks("TPU v5 lite"))
    return mod.read(ctx)


def _call(m, k, n, seconds):
    return OpCall("ent_matmul_packed_fused", "ent_matmul_packed_fused.1",
                  seconds, [("f32", (m, n)), ("f32", (m, k)),
                            ("s8", (2, k, n))])


@pytest.mark.parametrize("m,k,n", [(64, 2048, 11008), (8, 11008, 2048),
                                   (256, 2048, 2048)])
def test_ent_roofline_cannot_pass_100(m, k, n):
    # a kernel that reads the 2-byte planes at the full HBM bandwidth
    ops, nbytes = work.ent_matmul(m, k, n)
    planes = 2 * k * n + (nbytes - k * n)
    t_planes = max(planes / V5E["hbm_bytes_per_s"], ops / V5E["int8"])
    assert _roofline([_call(m, k, n, t_planes)]) <= 100.0
    # a repacked kernel reading one byte a weight at the full bandwidth
    # meets the yardstick exactly: it reads 100%, no more
    t_packed = work.least_time(ops, nbytes, V5E["int8"],
                               V5E["hbm_bytes_per_s"])
    assert _roofline([_call(m, k, n, t_packed)]) == pytest.approx(100.0)


def test_decode_ceiling_is_half():
    # at decode the two planes cost twice the logical weight bytes
    m, k, n = 64, 2048, 11008
    ops, nbytes = work.ent_matmul(m, k, n)
    t = (nbytes + k * n) / V5E["hbm_bytes_per_s"]
    assert 50.0 < _roofline([_call(m, k, n, t)]) < 52.0


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
