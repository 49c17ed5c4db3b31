"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``: three calls each of the fused EN-T matmul, paged
decode attention and masked flash prefill, inside ``bench.*`` spans)."""

import os

import pytest

from trace_reduce import parse_shapes, reduce_trace

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return reduce_trace(TRACE)


def test_window_and_busy(red):
    assert red.chips == 1
    assert 0.004 < red.window_s < 0.005          # the bench.window span
    assert 0 < red.busy_s < red.window_s
    # the device clock of a TPU trace trails the host's by about a ms
    assert 0 < red.skew_s < 0.01


@pytest.mark.parametrize("kernel,shapes", [
    ("ent_matmul_packed_fused",
     [("f32", (8, 256)), ("f32", (8, 512)), ("s8", (2, 512, 256))]),
    ("paged_attention",
     [("f32", (4, 2, 4, 128)), ("s32", (4, 4)), ("s32", (4,))]),
    ("flash_attention_masked",
     [("bf16", (1, 8, 256, 128)), ("s32", (1,)), ("bf16", (1, 8, 256, 128))]),
])
def test_kernels_found_with_shapes(red, kernel, shapes):
    calls = red.calls(kernel)
    assert len(calls) == 3
    assert all(c.seconds > 0 for c in calls)
    assert calls[0].shapes[:3] == shapes


def test_breakdown(red):
    ops = red.device_ops(10)
    assert len(ops) <= 10
    assert ops[0][0] == "flash_attention_masked"
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = red.idle_gaps
    assert 0 < len(gaps) <= 10
    assert {label for label, _ in gaps} <= {"bench.tick", "bench.chunk",
                                            "no bench span open"}
    assert sum(s for _, s in gaps) <= red.window_s - red.busy_s + 1e-9


def test_parse_shapes_stops_before_attributes():
    text = ("%fusion.3 = bf16[8,128]{1,0} fusion(f32[8,128]{1,0} %a), "
            "kind=kLoop, calls=%c, metadata={op_name=\"x[1,2]\"}")
    assert parse_shapes(text) == [("bf16", (8, 128)), ("f32", (8, 128))]
