"""A whole run on the CPU at toy size, minus the look for a chip: weights
from the seed, engine -> scheduler -> HTTP server on threads, the
JAX-free load generator in a child process, the end-to-end readers and
the check against the reference.  Then the same run with the timed path
broken underneath (``faults.py``), which must come out not correct, and
the control in the program's place, which must come out not correct at
the cell's limit while the program comes out correct."""

import functools
import time

import pytest

import faults
import harness
import spec
import system
from tiny import ENGINE, MIX, TINY, tiny_config

SEED = 2**31 + 77                      # more than 32 signed bits hold
# the check's limit at toy size, set between the program's widest gap and
# the control's narrowest over three seeds on the CPU (EN-T: 0.0065-0.0138
# against 0.28-0.40).  bf16 against its int8 control reads 0.0011-0.0088
# against 0.0128-0.0163 there, too close for a limit, so its toy limit
# only sits well above the program's readings, and the control is tested
# for the configurations that BENCHMARK.json runs.
TOY_LIMIT = {"qwen2.5-3b-ent": 0.05, "minicpm-2b-bf16": 0.03}


def tiny_cell(config: str) -> spec.Cell:
    wl = {"config": config, "traffic": "tiny", "clients": 4,
          "engine": ENGINE, "correct": {"logit_gap": TOY_LIMIT[config]}}
    return spec.Cell(name="tiny", workload=wl, config=tiny_config(config),
                     traffic=MIX, chips=1,
                     end_to_end=spec.benchmark()["end_to_end"], per_layer=[])


@pytest.fixture
def interpret(monkeypatch):
    """Every Pallas kernel of the serving path in interpret mode."""
    from repro.kernels.ent_matmul import ops as ent_ops
    from repro.kernels.flash_attention import ops as attn_ops
    from repro.kernels.paged_attention import ops as paged_ops
    for mod, name in ((ent_ops, "ent_quantized_matmul_fused"),
                      (attn_ops, "masked_attention"),
                      (paged_ops, "paged_attention")):
        orig = getattr(mod, name)

        @functools.wraps(orig)
        def forced(*a, _orig=orig, use_kernel="auto", **k):
            return _orig(*a, use_kernel="interpret", **k)

        monkeypatch.setattr(mod, name, forced)


def run(cell, control=False):
    return harness.run_cell(cell, SEED, 2.0, False, t_start=time.monotonic(),
                            control=control)


@pytest.mark.parametrize("config", ["qwen2.5-3b-ent", "minicpm-2b-bf16"])
def test_rehearsal_through_server(short, interpret, config):
    res = run(tiny_cell(config))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    # the window opened on a full batch, well before the ramp's cap
    assert res["metrics"]["setup_s"]["value"] < harness.RAMP_MAX_S
    assert res["sample"]["served_tokens"] > 0
    assert res["device"]["platform"] == "cpu"
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_decode_is_not_correct(short, monkeypatch, fault):
    monkeypatch.setattr(system, "serve", faults.broken(
        system.serve, fault, TINY["vocab_size"]))
    res = run(tiny_cell("qwen2.5-3b-ent"))
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > TOY_LIMIT["qwen2.5-3b-ent"]


@pytest.mark.parametrize("config", [c["name"] for c in
                                    spec.benchmark()["configs"]])
def test_control_reads_above_program(short, config):
    """The reference one precision step down (int4 weights for the EN-T
    configuration, int8 for bf16) in the program's place reads at least
    three times the program's gap and fails the limit the program
    passes."""
    res = run(tiny_cell(config), control=True)
    assert res["correct"], res["checks"]
    gap = res["checks"]["logit_gap"]["value"]
    assert res["control_gap"] >= 3 * gap
    assert res["control_gap"] > res["checks"]["logit_gap"]["limit"]
    assert not res["control_correct"]


@pytest.mark.parametrize("engine_prefix,adapter_prefix,cause", [
    (False, True, "without a prefix cache"),
    ("auto", False, "with a prefix cache"),
    (False, False, "admits each request atomically"),
])
def test_serve_checks_the_engine_against_the_adapter(
        monkeypatch, engine_prefix, adapter_prefix, cause):
    """An engine whose prefix cache disagrees with the program adapter, or
    whose scheduler admits atomically (a paged engine without a prefix
    cache does), is refused before anything is measured."""
    cfg = tiny_config("qwen2.5-3b-ent")
    prog = spec.reader("programs", "dense")
    model = system.build_model(prog.model_config(cfg))
    params = prog.make_params(model, cfg, SEED)
    built = system.ServeEngine
    monkeypatch.setattr(system, "ServeEngine", lambda *a, **k: built(
        *a, **dict(k, prefix_cache=engine_prefix)))
    with pytest.raises(RuntimeError, match=cause):
        system.serve(model, params, ENGINE, record=False,
                     prefix_cache=adapter_prefix)
