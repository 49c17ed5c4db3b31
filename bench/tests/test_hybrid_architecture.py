"""The Mamba-2 / attention hybrid architecture (``granite_hybrid``) as
files: its adapter's counts against hand counts at the published widths,
its reference's restart at each packed sequence, the kernel roofline's
reader, and a whole run of a toy Granite-4.0-H configuration on the CPU
through the server, chunked admission without a prefix cache, against
the reference, with the control failing the toy limit."""

import functools
import json
import os
import time
import types

import numpy as np
import pytest

import harness
import spec
import system
import work
from peaks import PEAKS, peaks
from tiny import ENGINE, MIX, tiny_config
from trace_reduce import OpCall, Reduction

GRANITE = json.load(open(os.path.join(spec.BENCH, "configs",
                                      "granite-4.0-h-micro-ent.json")))
PROG = spec.reader("programs", "granite_hybrid")
V5E = PEAKS["TPU v5 lite"]
SEED = 2**31 + 91
# the period kept whole (mixers at 0-4 and 6-9, attention at 5), at toy
# widths with the published ratios: 4 query / 2 KV heads of 16, 8 mixer
# heads of 16 (d_inner twice d_model), state 16
TOY = {"hidden_size": 64, "intermediate_size": 128,
       "shared_intermediate_size": 128, "num_hidden_layers": 10,
       "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 300,
       "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16}
# the check's limit at toy size, between the program's widest gap and the
# control's narrowest over three seeds on the CPU (EN-T: 0.0013-0.0017
# against 0.012-0.017; the logits are divided by 8)
TOY_LIMIT = 0.005


def toy_config() -> dict:
    cfg = dict(GRANITE, **TOY)
    cfg["bench"] = dict(cfg["bench"], kernels={"decode": [], "chunk": []})
    return cfg


def test_adapter_hand_counts():
    # per mixer: in_proj 2048 x 8512, out_proj 4096 x 2048; per attention
    # layer: q and o 2048 x 2048, k and v 2048 x 512; every MLP 3 x 2048 x 8192
    mixer = 2048 * 8512 + 4096 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    assert PROG.layer_matmul_params(GRANITE) == 36 * mixer + 4 * attn + 40 * mlp
    assert PROG.query_heads(GRANITE) == 32
    # a token at context 1000: conv 2 x 4 x 4352 and update 6 x 64 x 64 x 128
    # in each of 36 mixers, attention in 4 layers, the tied head
    mm, other = PROG.token_ops(GRANITE, 1000, head=True)
    assert mm == 2 * PROG.layer_matmul_params(GRANITE)
    recur = 36 * (2 * 4 * 4352 + 6 * 64 * 64 * 128)
    assert other == 4 * 1000 * 32 * 64 * 4 + recur + 2 * 2048 * 100352
    mm, other = PROG.prefill_ops(GRANITE, 256, 256, head=False)
    assert mm == 2 * PROG.layer_matmul_params(GRANITE) * 256
    assert other == 4 * (256 * 256 + 256 * 257 / 2) * 32 * 64 * 4 + recur * 256
    # one kernel call for 64 slots: 2 MiB of fp32 state a slot, read and
    # written, and 16 KiB each of x and y, 512 B each of B and C, 256 B of dt
    ops, nbytes = PROG.ssd_decode_work(GRANITE, 64)
    assert ops == 64 * 6 * 64 * 64 * 128
    assert nbytes == 64 * (2 * 2 * 2**20 + 2 * 16384 + 2 * 512 + 256)


def test_model_config_has_the_published_shape():
    cfg = PROG.model_config(GRANITE)
    assert cfg.num_groups == 4 and len(cfg.group) == 10
    assert cfg.attn_positions == (5,)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size) == (2048, 32, 8, 64, 8192, 100352)
    s = cfg.ssm
    assert (s.state_dim, s.head_dim, s.expand, s.ngroups,
            s.conv_width) == (128, 64, 2, 1, 4)
    assert (cfg.rope, cfg.attn_scale, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
        False, 0.015625, 12.0, 0.22, 8.0)
    assert 3.1e9 < cfg.param_count() < 3.3e9


def test_reference_restarts_at_each_sequence():
    """Two sequences packed in one row read as each alone: the recurrence
    and the conv window restart at the second's first token."""
    ref = PROG.ref.Reference(toy_config(), SEED)
    rng = np.random.default_rng(0)
    a, b = rng.integers(0, 300, 300), rng.integers(0, 300, 212)

    def hidden(seqs):
        toks = np.concatenate(seqs)
        seg = np.concatenate([np.full(len(s), i) for i, s in enumerate(seqs)])
        pad = 512 - len(toks)
        row = lambda x, fill: np.pad(x, (0, pad), constant_values=fill)[None]
        return np.asarray(ref.hidden(row(toks, 0).astype(np.int32),
                                     row(np.zeros_like(toks), 0),
                                     row(seg, -1).astype(np.int32), "f32"))

    both = hidden([a, b])[0]
    np.testing.assert_allclose(both[:300], hidden([a])[0, :300],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(both[300:512], hidden([b])[0, :212],
                               rtol=1e-5, atol=1e-5)
    # one sequence of both would carry a's state into b
    joined = hidden([np.concatenate([a, b])])[0]
    assert not np.allclose(joined[300:512], both[300:512], atol=1e-3)


def _ssd_ctx(calls, decodes):
    return types.SimpleNamespace(
        cfg=GRANITE, program=PROG, peaks=peaks("TPU v5 lite"),
        trace=Reduction(3.0, 2.9, 1, 0.0, calls), trace_span=(0.0, 3.0),
        decodes=decodes)


def _ssd_call(seconds):
    return OpCall("ssd_decode_step", "ssd_decode_step.3", seconds,
                  [("f32", (64, 1, 4096)), ("f32", (4, 64, 64, 64, 128))])


def test_ssd_roofline_reader():
    reader = spec.reader("layer_metrics", "ssd_decode_roofline")
    # 36 calls a tick, two ticks of 64 and 62 decoding slots
    decodes = [(1.0, [300] * 64), (1.2, [301] * 62), (5.0, [9] * 3)]
    t64 = work.least_time(*PROG.ssd_decode_work(GRANITE, 64), V5E["bf16"],
                          V5E["hbm_bytes_per_s"])
    # at the bandwidth for the mean of 63 slots: 100%, never more
    got = reader.read(_ssd_ctx([_ssd_call(t64 * 63 / 64)] * 72, decodes))
    assert got == pytest.approx(100.0)
    got = reader.read(_ssd_ctx([_ssd_call(t64 * 63 / 32)] * 72, decodes))
    assert got == pytest.approx(50.0)
    # bytes bound: the operations take a few thousandths of the byte time
    ops, nbytes = PROG.ssd_decode_work(GRANITE, 64)
    assert ops / V5E["bf16"] < 0.005 * nbytes / V5E["hbm_bytes_per_s"]
    # no kernel, no tick, or an adapter that counts none: no reading
    assert reader.read(_ssd_ctx([], decodes)) is None
    assert reader.read(_ssd_ctx([_ssd_call(1e-3)], [])) is None
    ctx = _ssd_ctx([_ssd_call(1e-3)], decodes)
    ctx.program = spec.reader("programs", "dense")
    assert reader.read(ctx) is None


@pytest.fixture
def interpret(monkeypatch):
    """Every Pallas kernel of the serving path in interpret mode."""
    from repro.kernels.ent_matmul import ops as ent_ops
    from repro.kernels.flash_attention import ops as attn_ops
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.kernels.ssd_scan import ops as ssd_ops
    for mod, name in ((ent_ops, "ent_quantized_matmul_fused"),
                      (attn_ops, "masked_attention"),
                      (paged_ops, "paged_attention"),
                      (ssd_ops, "ssd_decode_step")):
        orig = getattr(mod, name)

        @functools.wraps(orig)
        def forced(*a, _orig=orig, use_kernel="auto", **k):
            return _orig(*a, use_kernel="interpret", **k)

        monkeypatch.setattr(mod, name, forced)


def toy_cell() -> spec.Cell:
    wl = {"config": GRANITE["name"], "traffic": "tiny", "clients": 4,
          "engine": ENGINE, "correct": {"logit_gap": TOY_LIMIT}}
    return spec.Cell(name="tiny", workload=wl, config=toy_config(),
                     traffic=MIX, chips=1,
                     end_to_end=spec.benchmark()["end_to_end"], per_layer=[])


def test_toy_run_through_server_is_correct_and_control_is_not(
        short, interpret):
    res = harness.run_cell(toy_cell(), SEED, 2.0, False,
                           t_start=time.monotonic(), control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert res["sample"]["served_tokens"] > 0
    assert res["compiles_in_window"] == 0
    gap = res["checks"]["logit_gap"]["value"]
    assert res["control_gap"] >= 3 * gap
    assert not res["control_correct"]


def test_serve_takes_the_hybrid_chunked_without_prefix_cache():
    cfg = toy_config()
    model = system.build_model(PROG.model_config(cfg))
    params = PROG.make_params(model, cfg, SEED)
    eng, sched, _ = system.serve(model, params, ENGINE, record=False,
                                 prefix_cache=PROG.PREFIX_CACHE)
    assert eng._prefix is None and sched._chunked and sched._ssm
    texts = system.compiled_kernels(eng, sched)
    assert set(texts) == {"decode", "chunk"}


def test_serve_refuses_an_engine_that_admits_atomically(monkeypatch):
    """A speculative engine admits each request whole, outside the chunk
    stream, paged or not: ``serve`` refuses it before anything is
    measured."""
    cfg = tiny_config("qwen2.5-3b-ent")
    prog = spec.reader("programs", "dense")
    model = system.build_model(prog.model_config(cfg))
    params = prog.make_params(model, cfg, SEED)
    built = system.ServeEngine
    monkeypatch.setattr(system, "ServeEngine", lambda *a, **k: built(
        *a, **dict(k, draft_model=model, draft_params=params)))
    with pytest.raises(RuntimeError, match="admits each request atomically"):
        system.serve(model, params, ENGINE, record=False,
                     prefix_cache=prog.PREFIX_CACHE)
