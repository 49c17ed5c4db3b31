"""Granite-4.0-H-Micro (Mamba-2 mixers and NoPE GQA layers in a 10-layer
period, muP scalars) at reduced widths, on seeded random weights, through
``ServeEngine`` -> ``PipelinedScheduler`` with chunked admission (no
prefix cache), against the plain reference of the benchmark
(``bench/references/granite_hybrid.py``, which imports nothing of the
program): prefill over several chunks and decode logits, a second
admission's chunks interleaved with the first request's decode ticks,
a reused slot, the tail pad of the last chunk, and NoPE and each muP
scalar one by one.

The program computes in float32 here (weights bf16, as drawn), so it
and the reference differ only by the order of float32 sums: ``TOL``,
relative to the largest logit, is a hundred times that rounding, and the
same model computing in bfloat16 (the mixers' activations rounded to 8
bits of mantissa) lies two hundred times outside it (asserted).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models.kv_cache import SSMCache
from repro.models.transformer import build_model
from repro.runtime.scheduler import PipelinedScheduler
from repro.runtime.serve_loop import ServeEngine

sys.path.append(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
import spec  # noqa: E402

PROG = spec.reader("programs", "granite_hybrid")
REF = PROG.ref
SEED = 2**31 + 5
# float32 program against the float32 reference: the largest logit gap
# over the prompt and answer positions, over the largest |logit|.  It
# reads 2.0e-6 to 2.5e-6 on three prompts; bfloat16 activations read
# 3.7e-2 to 4.9e-2, RoPE in place of NoPE 1.2e-2, and each muP scalar
# left out 0.22 to 7.
TOL = 2e-4
CHUNK, PAGE = 8, 4


def _reduced(**over):
    cfg = reduced_config(get_config("granite-4.0-h-micro"))
    return dataclasses.replace(cfg, param_dtype="bfloat16", **over)


def hf_config(cfg) -> dict:
    """The configuration file's keys for a program config: what the
    reference reads."""
    s = cfg.ssm
    return {
        "name": cfg.name, "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": ["attention" if m == "attn" else "mamba"
                        for m, _ in cfg.group] * cfg.num_groups,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "intermediate_size": cfg.d_ff, "shared_intermediate_size": cfg.d_ff,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": True, "position_embedding_type": "nope",
        "mamba_n_heads": s.expand * cfg.d_model // s.head_dim,
        "mamba_d_head": s.head_dim, "mamba_d_state": s.state_dim,
        "mamba_n_groups": s.ngroups, "mamba_d_conv": s.conv_width,
        "mamba_conv_bias": True, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
        "logits_scaling": 8}


@pytest.fixture(scope="module")
def granite():
    """(model config, reference, the reference's weights in the program's
    layout)."""
    cfg = _reduced()
    hf = hf_config(cfg)
    assert cfg.group == PROG.model_config(hf).group   # the 10-layer period
    params = PROG.make_params(build_model(cfg), hf, SEED)
    return cfg, REF.Reference(hf, SEED), params


def ref_logits(ref, tokens) -> np.ndarray:
    """The reference's logits [T, V] of one sequence."""
    t = -(-len(tokens) // 512) * 512
    row = np.zeros((1, t), np.int32)
    row[0, :len(tokens)] = tokens
    seg = np.full((1, t), -1, np.int32)
    seg[0, :len(tokens)] = 0
    h = ref.hidden(row, np.zeros_like(row), seg, "f32")[0, :len(tokens)]
    return np.asarray(h @ ref.head())


def rel_gap(got, want) -> float:
    got = np.asarray(got)[..., :want.shape[-1]]
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 256, n).tolist()


class Recorder:
    """Serve through the scheduler and record, per request, the logits of
    every token it was served (first token from the final chunk, the rest
    from decode ticks), and the state checks of the tests below."""

    def __init__(self, cfg, params, *, slots, depth=1):
        self.model = build_model(cfg)
        self.ticks = []          # logits [slots, V] of each decode tick
        orig = self.model.decode_step

        def decode_step(params, cache, **kw):
            logits, cache = orig(params, cache, **kw)
            jax.debug.callback(lambda l: self.ticks.append(np.asarray(l)),
                               logits, ordered=True)
            return logits, cache

        self.model.decode_step = decode_step
        self.eng = ServeEngine(self.model, params, slots=slots, max_len=64,
                               page_size=PAGE, prefix_cache="auto")
        self.sched = PipelinedScheduler(self.eng, pipeline_depth=depth,
                                        prefill_chunk=CHUNK)
        self.dispatched = []     # [(slot, uid)] active at each tick
        self.first = {}          # uid -> first-token logits [V]
        self.admitted_state = {}  # uid -> the slot's SSM state at admission
        self.parked_ticks = 0
        self._wrap()

    def ssm_state(self, slot):
        return [np.asarray(a[:, slot]) for c in self.eng.cache["layers"]
                if isinstance(c, SSMCache) for a in (c.conv, c.ssd)]

    def _wrap(self):
        sched = self.sched
        dispatch, complete = sched._dispatch_decode, sched._complete_admission

        def _dispatch_decode():
            pf = sched._prefill
            before = None if pf is None else self.ssm_state(pf.slot)
            entry = dispatch()
            self.dispatched.append(list(entry.active))
            if before is not None:       # the parked slot's state stays
                after = self.ssm_state(pf.slot)
                assert all(np.array_equal(a, b)
                           for a, b in zip(before, after))
                self.parked_ticks += 1
            return entry

        def _complete_admission(slot, req, logits):
            self.first[req.uid] = np.asarray(logits)[0]
            self.admitted_state[req.uid] = self.ssm_state(slot)
            return complete(slot, req, logits)

        sched._dispatch_decode = _dispatch_decode
        sched._complete_admission = _complete_admission

    def logits(self, uid, served: int) -> np.ndarray:
        jax.effects_barrier()
        rows = [self.first[uid]]
        for tick, active in zip(self.ticks, self.dispatched):
            rows += [tick[s] for s, u in active if u == uid]
        return np.stack(rows[:served])


def check_served(ref, rec, uid, prompt, served):
    want = ref_logits(ref, prompt + served[:-1])[len(prompt) - 1:]
    gap = rel_gap(rec.logits(uid, len(served)), want)
    assert gap < TOL, gap
    return gap


def test_forward_matches_reference_and_bf16_does_not(granite):
    cfg, ref, params = granite
    toks = _prompt(40, 1)
    want = ref_logits(ref, toks)
    f32 = build_model(cfg).apply(params, tokens=jnp.asarray([toks]))
    assert rel_gap(f32["logits"][0], want) < TOL
    bf16 = build_model(dataclasses.replace(cfg, compute_dtype="bfloat16"))
    out = bf16.apply(params, tokens=jnp.asarray([toks]))
    assert rel_gap(out["logits"][0].astype(jnp.float32), want) > 10 * TOL


def test_chunked_prefill_and_decode_match_reference(granite):
    """A prompt of three chunks (the last tail-padded) then decode; a
    second request admitted over three chunks while the first decodes,
    its slot parked between them: each tick leaves the parked slot's
    recurrent state as it was."""
    cfg, ref, params = granite
    rec = Recorder(cfg, params, slots=2)
    a, b = _prompt(21, 2), _prompt(19, 3)
    ua = rec.sched.submit(a, max_new_tokens=12)
    while rec.sched.status(ua) != "active":
        rec.sched.tick()
    ub = rec.sched.submit(b, max_new_tokens=6)
    res = rec.sched.run()
    assert rec.parked_ticks >= 2            # b's chunks interleaved
    check_served(ref, rec, ua, a, res[ua])
    check_served(ref, rec, ub, b, res[ub])


def test_reused_slot_starts_from_zero_state(granite):
    """One slot serves two requests in turn: the second prefills from a
    zero state, whatever the first left there."""
    cfg, ref, params = granite
    rec = Recorder(cfg, params, slots=1)
    a, b = _prompt(17, 4), _prompt(13, 5)
    ua = rec.sched.submit(a, max_new_tokens=10)
    ub = rec.sched.submit(b, max_new_tokens=8)
    res = rec.sched.run()
    check_served(ref, rec, ua, a, res[ua])
    check_served(ref, rec, ub, b, res[ub])
    alone = Recorder(cfg, params, slots=1)
    u = alone.sched.submit(b, max_new_tokens=8)
    assert alone.sched.run()[u] == res[ub]
    for x, y in zip(alone.admitted_state[u], rec.admitted_state[ub]):
        np.testing.assert_array_equal(x, y)


def test_tail_pad_does_not_advance_the_state(granite):
    """The last chunk of a 21-token prompt carries 3 pad tokens: the
    state at admission is the unpadded prompt's (a one-shot prefill),
    and the pads, had they been fed, would have moved it."""
    cfg, ref, params = granite
    rec = Recorder(cfg, params, slots=1)
    p = _prompt(21, 6)
    u = rec.sched.submit(p, max_new_tokens=2)
    rec.sched.run()
    model = build_model(cfg)

    def prefill(tokens):
        cache = model.init_cache(1, 64)
        _, cache = model.prefill(params, cache, tokens=jnp.asarray([tokens]))
        return [np.asarray(a[:, 0]) for c in cache["layers"]
                if isinstance(c, SSMCache) for a in (c.conv, c.ssd)]

    exact, padded = prefill(p), prefill(p + [0] * 3)
    for got, want in zip(rec.admitted_state[u], exact):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert not all(np.allclose(x, y, rtol=1e-3)
                   for x, y in zip(padded, exact))


# each muP scalar and NoPE: the program at the published value matches
# the reference (test above); with the field at its default it does not
DEFAULTS = {"rope": True, "embedding_multiplier": 1.0,
            "residual_multiplier": 1.0, "attn_scale": None,
            "logits_scaling": 1.0}


@pytest.mark.parametrize("field", sorted(DEFAULTS))
def test_each_scalar_and_nope_is_applied(granite, field):
    cfg, ref, params = granite
    toks = _prompt(32, 7)
    want = ref_logits(ref, toks)
    model = build_model(dataclasses.replace(cfg, **{field: DEFAULTS[field]}))
    got = model.apply(params, tokens=jnp.asarray([toks]))["logits"][0]
    assert rel_gap(got, want) > 10 * TOL


def test_served_state_bytes_in_metrics(granite):
    cfg, _, params = granite
    rec = Recorder(cfg, params, slots=2)
    st = rec.sched.stats()
    n = cfg.ssm.expand * cfg.d_model
    heads = n // cfg.ssm.head_dim
    conv = n + 2 * cfg.ssm.ngroups * cfg.ssm.state_dim
    per_slot = (heads * cfg.ssm.head_dim * cfg.ssm.state_dim * 4
                + (cfg.ssm.conv_width - 1) * conv * 4)
    mixers = len(cfg.ssm_positions) * cfg.num_groups
    assert st["ssm_state_bytes"] == 2 * mixers * per_slot
