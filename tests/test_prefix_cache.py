"""Prefix sharing & copy-on-write on the serving engine.

The contract under test: with ``prefix_cache=True`` the ServeEngine maps
cached system-prompt pages into newcomers' block tables and prefills
only the unshared suffix, and the emitted streams stay BIT-identical to
the same prompts served unshared — bf16 and int8-KV, spec decoding on
and off, copy-on-write divergence included.  Accounting is exact: warm
admission allocates only ceil(unshared_tokens / page) fresh pages, the
radix cache LRU-evicts under pool pressure without touching pinned
pages, and the allocator leak check (refcounts == block-table occupancy
+ cache pins) holds at every tick boundary.

Host-side allocator/radix unit tests live in tests/test_page_allocator.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_config, reduced_config
from repro.models.transformer import build_model
from repro.runtime.serve_loop import ServeEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced_config(get_config("qwen2.5-3b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def tiny_int8():
    cfg = reduced_config(get_config("qwen2.5-3b"))
    model = build_model(cfg, kv_quant=True)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


SYS = list(range(1, 9))          # 8 tokens = 2 full pages at page_size 4


def _serve(model, params, prompts, *, prefix, page_size=4, max_new=4,
           temp=0.5, spec=False, slots=2, max_len=32, pages=None,
           interleave=None):
    kw = (dict(draft_model=model, draft_params=params, spec_k=3)
          if spec else {})
    eng = ServeEngine(model, params, slots=slots, max_len=max_len,
                      page_size=page_size, pages=pages,
                      prefix_cache=prefix, **kw)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=max_new, temperature=temp)
        for _ in range(interleave[i] if interleave else 0):
            eng.step()
            eng.check_leaks()
    out = eng.run()
    return [out[uid] for uid in sorted(out)], eng


class TestSharedPrefixBitIdentity:
    """Shared-prefix serving == unshared serving, bit for bit."""

    @pytest.mark.parametrize("fixture", ["tiny", "tiny_int8"])
    def test_shared_equals_unshared(self, fixture, request):
        cfg, model, params = request.getfixturevalue(fixture)
        prompts = [SYS + [20 + i, 30 + i] for i in range(3)] + [SYS]
        on, eng = _serve(model, params, prompts, prefix=True)
        off, _ = _serve(model, params, prompts, prefix=False)
        assert on == off
        assert eng.prefix_stats["hits"] >= 3       # every follower matched
        assert eng.prefix_stats["hit_tokens"] >= 3 * len(SYS)

    @pytest.mark.parametrize("temp", [0.0, 0.7])
    def test_spec_decode_shared_equals_unshared(self, tiny, temp):
        """A verify burst near a shared page must CoW, not scribble:
        spec-decode emissions stay bit-identical to unshared serving at
        greedy and hot temperatures."""
        cfg, model, params = tiny
        prompts = [SYS + [20 + i] for i in range(3)]
        on, eng = _serve(model, params, prompts, prefix=True, spec=True,
                         temp=temp, max_new=6)
        off, _ = _serve(model, params, prompts, prefix=False, spec=True,
                        temp=temp, max_new=6)
        assert on == off
        assert eng.prefix_stats["hits"] >= 2

    def test_full_match_peek_bit_identical(self, tiny):
        """A fully cached prompt admits with a READ-ONLY peek of its
        last token's logits: no write lands anywhere, so concurrent
        duplicates share every page live with zero CoW copies and the
        streams stay bit-identical to unshared serving."""
        cfg, model, params = tiny
        prompts = [SYS, SYS, SYS]               # exact full-page duplicates
        on, eng = _serve(model, params, prompts, prefix=True)
        off, _ = _serve(model, params, prompts, prefix=False)
        assert on == off
        assert eng.prefix_stats["cow_copies"] == 0   # nothing ever copied
        assert eng.prefix_stats["hits"] == 2


class TestPrefixAccounting:
    def test_warm_admission_allocates_only_suffix_pages(self, tiny):
        """The acceptance bound: a warm shared-prefix admission takes
        exactly ceil(unshared_tokens / page) fresh pages."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=2, max_len=32, page_size=4,
                          prefix_cache=True)
        eng.submit(SYS + [40], max_new_tokens=2)
        eng.run()                               # warm: SYS's 2 pages cached
        eng.submit(SYS + [50, 51, 52], max_new_tokens=2)   # 3-token suffix
        eng._admit()
        (slot,) = eng._active
        assert len(eng._slot_shared[slot]) == 2            # SYS reused
        assert len(eng._slot_pages[slot]) == 1             # ceil(3/4)
        eng.check_leaks()
        eng.run()

    def test_full_match_admits_with_zero_fresh_pages(self, tiny):
        """The thundering-herd bound: a fully cached prompt takes NO
        pages at admission — the peek writes nothing, so the pool is
        untouched until the slot's first decode write."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=2, max_len=32, page_size=4,
                          prefix_cache=True)
        eng.submit(SYS, max_new_tokens=2)
        eng.run()                               # warm: SYS's 2 pages cached
        free_before = eng.page_stats["free"]
        eng.submit(SYS, max_new_tokens=2)
        eng._admit()
        (slot,) = eng._active
        assert eng._slot_pages[slot] == []                 # zero fresh pages
        assert len(eng._slot_shared[slot]) == 2            # SYS reused
        assert eng.page_stats["free"] == free_before
        eng.check_leaks()
        eng.run()

    def test_admission_stalls_when_matched_pages_become_pinned(self, tiny):
        """Pages an admission is about to pin must not be counted as
        evictable by its own availability check: under pool pressure a
        cached prompt's admission STALLS until a slot frees, instead of
        over-admitting and crashing a later in-flight page grab with
        'page reservation accounting is broken'."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=2, max_len=16, page_size=4,
                          pages=5, prefix_cache=True)
        warm = list(range(1, 13))               # 3 full pages
        eng.submit(warm, max_new_tokens=2)
        eng.run()                               # 3 cached pages, 2 free
        eng.submit(list(range(90, 99)), max_new_tokens=2)  # unrelated
        eng.submit(warm, max_new_tokens=2)      # cached: must wait its turn
        out = eng.run()                         # drains — never RuntimeError
        assert len(out) == 3
        eng.check_leaks()

    def test_ragged_suffixes_share_compiles(self, tiny):
        """Admission compiles are keyed on the chunk grid (window shape,
        window start), not raw suffix length: ragged warm suffixes
        reuse ONE compile of the final chunk program, at the window
        their match depth starts."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=2, max_len=32, page_size=4,
                          prefix_cache=True, prefill_chunk=8)
        eng.submit(SYS, max_new_tokens=2)
        eng.run()                               # cold compile (pos0=0)
        sizes = []
        for i, sfx in enumerate([1, 2, 3, 5, 7]):   # ragged, one window
            eng.submit(SYS + [40 + 10 * i + j for j in range(sfx)],
                       max_new_tokens=2)
            eng.run()
            sizes.append(eng._chunk_last._cache_size())
        # one entry for the cold prompt's window [0, 8), one shared by
        # every warm ragged suffix's window [8, 16), from the first on
        assert sizes == [2] * 5
        assert eng._chunk_mid._cache_size() == 0
        assert eng.prefix_stats["hits"] == 5

    def test_leak_check_at_every_tick(self, tiny):
        cfg, model, params = tiny
        prompts = [SYS + [20 + i] for i in range(4)]
        _serve(model, params, prompts, prefix=True,
               interleave=[1, 2, 0, 1])
        _serve(model, params, prompts, prefix=True, spec=True,
               interleave=[0, 2, 1, 0])

    def test_eviction_under_pool_pressure(self, tiny):
        """Distinct prompts fill the cache; later admissions must evict
        idle (unpinned) entries instead of stalling forever."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=16, page_size=4,
                          pages=6, prefix_cache=True)
        for i in range(4):                      # 4 distinct 8-token prompts
            eng.submit([100 * i + j for j in range(1, 9)], max_new_tokens=2)
        out = eng.run()
        assert len(out) == 4
        assert eng.prefix_stats["evicted"] > 0
        stats = eng.page_stats
        assert stats["free"] + stats["resident"] == stats["total"]

    def test_prefix_requires_paged_backend(self, tiny):
        cfg, model, params = tiny
        with pytest.raises(ValueError, match="paged backend"):
            ServeEngine(model, params, slots=2, max_len=32,
                        cache_kind="dense", prefix_cache=True)

    def test_ssm_models_cannot_share(self):
        cfg = reduced_config(get_config("mamba2-370m"))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="SSM"):
            ServeEngine(model, params, slots=2, max_len=32,
                        prefix_cache=True)
        # "auto" resolves to off and the engine still serves
        eng = ServeEngine(model, params, slots=2, max_len=32)
        assert eng.prefix_stats is None
        eng.submit(list(range(1, 7)), max_new_tokens=2)
        assert len(eng.run()) == 1


class TestSuffixPrefill:
    """Model-level pos0 resume: the primitive shared admission rests on."""

    def test_resume_matches_oneshot(self, tiny):
        cfg, model, params = tiny
        toks = jax.random.randint(jax.random.PRNGKey(11), (2, 10), 1,
                                  cfg.vocab_size)
        la, ca = model.prefill(params, model.init_cache(2, 16, kind="paged"),
                               tokens=toks)
        _, cb = model.prefill(params, model.init_cache(2, 16, kind="paged"),
                              tokens=toks[:, :6])
        lb, cb = model.prefill(params, cb, tokens=toks[:, 6:], pos0=6)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        for a, b in zip(jax.tree.leaves(ca["layers"]),
                        jax.tree.leaves(cb["layers"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resume_rejects_pad_mask(self, tiny):
        cfg, model, params = tiny
        toks = jnp.ones((1, 4), jnp.int32)
        _, cache = model.prefill(params, model.init_cache(1, 16),
                                 tokens=toks)
        with pytest.raises(ValueError, match="unpadded"):
            model.prefill(params, cache, tokens=toks,
                          pad_mask=jnp.ones((1, 4), bool), pos0=4)


class TestInterleavingProperty:
    @given(data=st.data())
    @settings(max_examples=3, deadline=None, derandomize=True)
    def test_random_interleavings_stay_identical_and_leak_free(self, data,
                                                               tiny):
        """Random admit/decode/EOS/spec interleavings over prompts with
        random shared-prefix depth: the allocator invariants (refcount
        == table occurrences + cache pins, no page both free and
        mapped) hold at every tick, and the emitted streams match the
        unshared engine bit for bit."""
        cfg, model, params = tiny
        spec = data.draw(st.booleans())
        nreq = data.draw(st.integers(2, 4))
        prompts, interleave = [], []
        for i in range(nreq):
            depth = data.draw(st.sampled_from([0, 4, 8]))
            sfx = data.draw(st.integers(1, 3))
            prompts.append(SYS[:depth]
                           + [50 + 10 * i + j for j in range(sfx)])
            interleave.append(data.draw(st.integers(0, 2)))
        temp = data.draw(st.sampled_from([0.0, 0.6]))
        on, eng = _serve(model, params, prompts, prefix=True, spec=spec,
                         temp=temp, max_new=3, interleave=interleave)
        off, _ = _serve(model, params, prompts, prefix=False, spec=spec,
                        temp=temp, max_new=3, interleave=interleave)
        assert on == off
        eng.check_leaks()
