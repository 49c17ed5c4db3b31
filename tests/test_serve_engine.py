"""Batched-prefill serving engine: bit-equivalence with the sequential
decode prefill, ragged left-padded batches, EOS early-stop, sampling-path
bugfixes, and the continuous-batching ServeEngine (CI fast-tier smoke)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models.transformer import build_model
from repro.runtime.serve_loop import ServeEngine, generate


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced_config(get_config("qwen2.5-3b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _sequential_prefill(model, params, toks, max_len, mask=None, start=None):
    cache = model.init_cache(toks.shape[0], max_len)
    if start is not None:
        cache["start"] = start
    logits = None
    for t in range(toks.shape[1]):
        logits, cache = model.decode_step(
            params, cache, tokens=toks[:, t],
            token_mask=None if mask is None else mask[:, t])
    return logits, cache


def _assert_trees_equal(ca, cb):
    assert jax.tree.structure(ca) == jax.tree.structure(cb)
    for a, b in zip(jax.tree.leaves(ca), jax.tree.leaves(cb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestBatchedPrefillParity:
    """model.apply(write_cache=True) must be BIT-identical to stepping the
    prompt through decode_step token by token — logits and cache state."""

    def test_uniform_batch_bit_identical(self, tiny):
        cfg, model, params = tiny
        toks = jax.random.randint(jax.random.PRNGKey(7), (2, 10), 1,
                                  cfg.vocab_size)
        la, ca = model.prefill(params, model.init_cache(2, 16), tokens=toks)
        lb, cb = _sequential_prefill(model, params, toks, 16)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        _assert_trees_equal(ca, cb)
        assert int(np.asarray(ca["pos"]).reshape(-1)[0]) == 10

    def test_ragged_padded_batch_bit_identical(self, tiny):
        cfg, model, params = tiny
        b, s0 = 3, 10
        lens = jnp.asarray([10, 6, 3])
        mask = jnp.arange(s0)[None, :] >= (s0 - lens[:, None])
        toks = jax.random.randint(jax.random.PRNGKey(3), (b, s0), 1,
                                  cfg.vocab_size)
        toks = jnp.where(mask, toks, 0)
        la, ca = model.prefill(params, model.init_cache(b, 16), tokens=toks,
                               pad_mask=mask)
        lb, cb = _sequential_prefill(model, params, toks, 16, mask=mask,
                                     start=(s0 - lens).astype(jnp.int32))
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        _assert_trees_equal(ca, cb)

    def test_ragged_rows_match_unpadded_prefill(self, tiny):
        """Left padding must be invisible: every ragged row's last-token
        logits equal a dedicated unpadded prefill of that row."""
        cfg, model, params = tiny
        s0 = 10
        lens = [10, 6, 3]
        mask = jnp.arange(s0)[None, :] >= (s0 - jnp.asarray(lens)[:, None])
        toks = jax.random.randint(jax.random.PRNGKey(5), (3, s0), 1,
                                  cfg.vocab_size)
        toks = jnp.where(mask, toks, 0)
        la, _ = model.prefill(params, model.init_cache(3, 16), tokens=toks,
                              pad_mask=mask)
        for i, n in enumerate(lens):
            li, _ = model.prefill(params, model.init_cache(1, 16),
                                  tokens=toks[i:i + 1, s0 - n:])
            np.testing.assert_array_equal(np.asarray(li[0]), np.asarray(la[i]))

    @pytest.mark.parametrize("arch", ["mamba2-370m", "jamba-1.5-large"])
    def test_ssm_and_hybrid_bit_identical(self, arch):
        cfg = reduced_config(get_config(arch))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 1,
                                  cfg.vocab_size)
        la, ca = model.prefill(params, model.init_cache(2, 12), tokens=toks)
        lb, cb = _sequential_prefill(model, params, toks, 12)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        _assert_trees_equal(ca, cb)

    def test_sliding_window_bit_identical_and_wrap_chunks(self):
        cfg = reduced_config(get_config("mixtral-8x7b"))   # window = 8
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 1,
                                  cfg.vocab_size)
        la, ca = model.prefill(params, model.init_cache(2, 32), tokens=toks)
        lb, cb = _sequential_prefill(model, params, toks, 32)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        _assert_trees_equal(ca, cb)
        # a prompt longer than the ring no longer raises: Model.prefill
        # auto-chunks at the ring width (parity asserted at atol in
        # tests/test_chunked_prefill.py — the ring reorders the f32
        # reduction, so wrap parity is exact-math, not bit-exact)
        long = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 1,
                                  cfg.vocab_size)
        lw, _ = model.prefill(params, model.init_cache(2, 32), tokens=long)
        ls, _ = _sequential_prefill(model, params, long, 32)
        np.testing.assert_allclose(np.asarray(lw), np.asarray(ls),
                                   atol=1e-5, rtol=1e-4)

    def test_prefill_requires_fresh_cache(self, tiny):
        cfg, model, params = tiny
        toks = jnp.ones((2, 4), jnp.int32)
        _, cache = model.prefill(params, model.init_cache(2, 8), tokens=toks)
        with pytest.raises(ValueError, match="pos0=0 requires"):
            model.prefill(params, cache, tokens=toks)

    def test_quantized_kv_cache_bit_identical(self, tiny):
        cfg, _, _ = tiny
        model = build_model(cfg, kv_quant=True)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(9), (2, 8), 1,
                                  cfg.vocab_size)
        la, ca = model.prefill(params, model.init_cache(2, 12), tokens=toks)
        lb, cb = _sequential_prefill(model, params, toks, 12)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        _assert_trees_equal(ca, cb)


class TestGenerateServing:
    def test_batched_equals_sequential_end_to_end(self, tiny):
        cfg, model, params = tiny
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 1,
                                    cfg.vocab_size)
        o1 = generate(model, params, prompt, steps=6)
        o2 = generate(model, params, prompt, steps=6, prefill="sequential")
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    def test_ragged_generate_matches_unpadded(self, tiny):
        cfg, model, params = tiny
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 1,
                                    cfg.vocab_size)
        out = generate(model, params, prompt, steps=5, prompt_lens=[8, 3])
        assert out.shape == (2, 5)
        solo = generate(model, params, prompt[1:, 5:], steps=5)
        np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(solo[0]))

    def test_eos_early_stop_and_per_sequence_masking(self, tiny):
        cfg, model, params = tiny
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 1,
                                    cfg.vocab_size)
        free = generate(model, params, prompt, steps=6)
        eos = int(free[0, 0])   # row 0 emits this greedily at step 0
        out = np.asarray(generate(model, params, prompt, steps=6,
                                  eos_id=eos, pad_id=-1))
        assert out[0, 0] == eos
        assert (out[0, 1:] == -1).all()          # stopped row: pad after EOS
        row1 = np.asarray(free[1])
        if eos not in row1:                       # unstopped row: unaffected
            np.testing.assert_array_equal(out[1], row1)

    def test_eos_everywhere_stops_early_with_full_width(self, tiny):
        cfg, model, params = tiny
        prompt = jnp.ones((2, 4), jnp.int32)
        free = np.asarray(generate(model, params, prompt, steps=1))
        out = np.asarray(generate(model, params, prompt, steps=8,
                                  eos_id=int(free[0, 0]), pad_id=-1))
        assert out.shape == (2, 8)                # early stop keeps the shape

    def test_temperature_without_key_defaults(self, tiny):
        cfg, model, params = tiny
        prompt = jnp.ones((1, 4), jnp.int32)
        o1 = generate(model, params, prompt, steps=5, temperature=1.0)
        o2 = generate(model, params, prompt, steps=5, temperature=1.0,
                      key=jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    def test_greedy_branch_still_deterministic(self, tiny):
        cfg, model, params = tiny
        prompt = jnp.ones((1, 4), jnp.int32)
        o1 = generate(model, params, prompt, steps=5)
        o2 = generate(model, params, prompt, steps=5)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    def test_empty_prompt_raises(self, tiny):
        cfg, model, params = tiny
        with pytest.raises(ValueError, match="S0 >= 1"):
            generate(model, params, jnp.zeros((2, 0), jnp.int32), steps=2)
        with pytest.raises(ValueError, match="steps"):
            generate(model, params, jnp.ones((1, 4), jnp.int32), steps=0)

    def test_bad_prompt_lens_raise(self, tiny):
        cfg, model, params = tiny
        prompt = jnp.ones((2, 4), jnp.int32)
        with pytest.raises(ValueError, match="prompt_lens"):
            generate(model, params, prompt, steps=2, prompt_lens=[0, 4])
        with pytest.raises(ValueError, match="prompt_lens"):
            generate(model, params, prompt, steps=2, prompt_lens=[5, 4])


class TestServeEngine:
    """Fast serving smoke (CI fast tier): tiny config, few tokens."""

    def test_continuous_batching_serves_all(self, tiny):
        cfg, model, params = tiny
        events = []
        eng = ServeEngine(model, params, slots=2, max_len=64,
                          on_token=lambda u, t, d: events.append((u, t, d)))
        prompts = [[1, 2, 3, 4, 5], [7, 8, 9], list(range(1, 12)), [3, 4], [5]]
        uids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        res = eng.run()
        assert set(res) == set(uids)              # 5 requests through 2 slots
        assert all(len(res[u]) == 4 for u in uids)
        assert len(events) == 20
        assert sum(d for _, _, d in events) == 5  # one done-flag per request

    def test_engine_matches_generate(self, tiny):
        cfg, model, params = tiny
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        eng = ServeEngine(model, params, slots=3, max_len=64)
        uid = eng.submit(prompt, max_new_tokens=6)
        res = eng.run()
        ref = generate(model, params, jnp.asarray([prompt], jnp.int32), steps=6)
        assert res[uid] == np.asarray(ref)[0].tolist()

    def test_slot_refill_after_finish(self, tiny):
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=64)
        uids = [eng.submit([1, 2, 3], max_new_tokens=3) for _ in range(3)]
        res = eng.run()
        assert all(len(res[u]) == 3 for u in uids)

    @pytest.mark.parametrize("kind", ["paged", "dense"])
    def test_request_done_at_its_first_token_frees_its_slot(self, tiny,
                                                            kind):
        """A request that ends at its first token (``max_new_tokens=1``,
        emitted by its admission) hands its slot back: one slot serves a
        queue of them."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=32,
                          cache_kind=kind)
        uids = [eng.submit(p, max_new_tokens=1)
                for p in ([1, 2, 3], [4, 5], [6])]
        for _ in range(8):                        # bounded: no spin
            if not eng.step():
                break
        assert sorted(eng._results) == uids
        assert all(len(eng._results[u]) == 1 for u in uids)
        assert eng._free == [0]
        eng.check_leaks()

    def test_engine_eos_stops_request(self, tiny):
        cfg, model, params = tiny
        prompt = [3, 1, 4, 1, 5]
        ref = np.asarray(generate(model, params,
                                  jnp.asarray([prompt], jnp.int32), steps=4))[0]
        eng = ServeEngine(model, params, slots=2, max_len=64,
                          eos_id=int(ref[1]))
        uid = eng.submit(prompt, max_new_tokens=10)
        res = eng.run()
        assert res[uid] == ref[:2].tolist()       # stops right at EOS

    def test_engine_rejects_bad_requests(self, tiny):
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=16)
        with pytest.raises(ValueError, match="empty"):
            eng.submit([])
        with pytest.raises(ValueError, match="max_len"):
            eng.submit([1, 2, 3], max_new_tokens=100)
        with pytest.raises(ValueError, match="at least one slot"):
            ServeEngine(model, params, slots=0, max_len=16)
