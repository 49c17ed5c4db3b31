"""First-class KV-cache backends: dense / ring / paged behind one protocol.

Pins the PR's acceptance contract:

* PagedCache decode and chunked prefill are BIT-identical to DenseCache
  (bf16 and int8-KV) — the page gather reconstructs the dense view
  exactly, so the masked-attention core sees the same operands;
* the multi-wrap ``prompt_update`` regression (a chunk lapping the ring
  used to scatter duplicate slot indices with unspecified order);
* hypothesis property sweeps of ring-wrap placement and the paged
  gather against a dense oracle across B/S/W/page-size combos;
* ServeEngine on the paged backend: zero-row-copy admission, page-pool
  accounting (allocation, admission stall, release on EOS), and
  bit-equality with ``generate``; ServeEngine on the ring backend:
  a sliding-window config served end to end with a prompt longer than
  the window.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_config, reduced_config
from repro.models import attention, kv_cache
from repro.models.transformer import build_model
from repro.runtime.serve_loop import ServeEngine, generate


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced_config(get_config("qwen2.5-3b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def windowed():
    cfg = reduced_config(get_config("mixtral-8x7b"))   # sliding window = 8
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _rows(key, b, s, h=2, hd=4):
    return jax.random.normal(key, (b, s, h, hd), jnp.float32)


# --- prompt_update multi-wrap regression -------------------------------------

class TestPromptUpdateWrap:
    def _oracle(self, c, new, pos0, w):
        out = np.array(c)
        for t in range(new.shape[1]):
            out[:, (pos0 + t) % w] = new[:, t]
        return out

    def test_single_wrap_matches_oracle(self):
        c = jnp.zeros((2, 8, 2, 4))
        new = _rows(jax.random.PRNGKey(0), 2, 6)
        got = kv_cache.prompt_update(c, new, pos0=5, ring=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      self._oracle(c, np.asarray(new), 5, 8))

    def test_multi_wrap_keeps_last_window(self):
        """S > W: the chunk laps the ring; only the last W rows survive.
        The old scatter wrote duplicate indices (unspecified order)."""
        w = 4
        c = jnp.zeros((2, w, 2, 4))
        new = _rows(jax.random.PRNGKey(1), 2, 11)   # laps the ring twice
        got = kv_cache.prompt_update(c, new, pos0=3, ring=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      self._oracle(c, np.asarray(new), 3, w))

    def test_exact_lap_boundary(self):
        w = 4
        c = jnp.zeros((1, w, 2, 4))
        for s in (w, w + 1, 2 * w, 2 * w + 3):
            new = _rows(jax.random.PRNGKey(s), 1, s)
            got = kv_cache.prompt_update(c, new, pos0=2, ring=True)
            np.testing.assert_array_equal(
                np.asarray(got), self._oracle(c, np.asarray(new), 2, w))


class TestRingPlacementProperty:
    @settings(max_examples=40, deadline=None)
    @given(w=st.integers(2, 9), s=st.integers(1, 20), pos0=st.integers(0, 25))
    def test_ring_write_matches_sequential_oracle(self, w, s, pos0):
        c = jnp.zeros((2, w, 1, 3))
        new = _rows(jax.random.PRNGKey(s * 31 + pos0), 2, s, h=1, hd=3)
        got = np.asarray(kv_cache.prompt_update(c, new, pos0=pos0, ring=True))
        want = np.zeros_like(got)
        for t in range(s):
            want[:, (pos0 + t) % w] = np.asarray(new)[:, t]
        np.testing.assert_array_equal(got, want)


# --- paged primitives vs the dense oracle ------------------------------------

def _fill_pair(key, b, w, h, hd, page, quantized, chunks):
    """Drive a DenseCache and a PagedCache through the same chunked
    prompt writes + one token write; returns the dense token_view and
    the paged gather_view (the position-ordered baseline the in-place
    kernel read replaced — still the oracle the pool layout is pinned
    against)."""
    dtype = jnp.bfloat16
    dense = kv_cache.DenseCache(k=jnp.zeros((b, w, h, hd), dtype),
                                v=jnp.zeros((b, w, h, hd), dtype))
    if quantized:
        dense = kv_cache.DenseCache(
            k=jnp.zeros((b, w, h, hd), jnp.int8),
            v=jnp.zeros((b, w, h, hd), jnp.int8),
            k_s=jnp.zeros((b, w, h, 1), jnp.bfloat16),
            v_s=jnp.zeros((b, w, h, 1), jnp.bfloat16))
    paged = kv_cache.paged_init(b, w, h, hd, dtype, quantized=quantized,
                                page_size=page)
    pos0 = 0
    for s in chunks:
        key, k1, k2 = jax.random.split(key, 3)
        kr, vr = _rows(k1, b, s, h, hd), _rows(k2, b, s, h, hd)
        dense = dense.write_prompt(kr, vr, pos0)[0]
        paged = paged.write_prompt(kr, vr, pos0)[0]
        pos0 += s
    key, k1, k2 = jax.random.split(key, 3)
    kr, vr = _rows(k1, b, 1, h, hd), _rows(k2, b, 1, h, hd)
    pos = jnp.full((b,), pos0, jnp.int32)
    dense = dense.write_token(kr, vr, pos, per_seq=True)
    paged = paged.write_token(kr, vr, pos, per_seq=True)
    start = jnp.zeros((b,), jnp.int32)
    return dense.token_view(pos, start), paged.gather_view(pos, start), pos0


class TestPagedGatherOracle:
    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("page,chunks", [
        (4, [5, 3, 2]), (8, [8, 4]), (16, [7]), (2, [1, 1, 4, 6]),
    ])
    def test_view_matches_dense(self, page, chunks, quantized):
        w = 16
        dv, pv, pos0 = _fill_pair(jax.random.PRNGKey(0), 2, w, 2, 4, page,
                                  quantized, chunks)
        dk, dvv, dks, dvs, dvalid = dv
        pk, pvv, pks, pvs, pvalid = pv
        # the paged view may be padded past W by page rounding; written
        # slots must be identical and the pad tail masked invalid
        np.testing.assert_array_equal(np.asarray(pk[:, :w]), np.asarray(dk))
        np.testing.assert_array_equal(np.asarray(pvv[:, :w]), np.asarray(dvv))
        np.testing.assert_array_equal(np.asarray(pvalid[:, :w]),
                                      np.asarray(dvalid))
        assert not np.asarray(pvalid[:, w:]).any()
        if quantized:
            np.testing.assert_array_equal(np.asarray(pks[:, :w]),
                                          np.asarray(dks))
            np.testing.assert_array_equal(np.asarray(pvs[:, :w]),
                                          np.asarray(dvs))

    @settings(max_examples=25, deadline=None)
    @given(b=st.integers(1, 3), page=st.sampled_from([2, 3, 4, 8]),
           s1=st.integers(1, 8), s2=st.integers(0, 8),
           quantized=st.booleans())
    def test_property_sweep(self, b, page, s1, s2, quantized):
        w = 24   # > max(s1) + max(s2) + 1: the token write stays in range
        chunks = [s1] + ([s2] if s2 else [])
        dv, pv, _ = _fill_pair(jax.random.PRNGKey(b * 7 + s1 + s2), b, w, 1,
                               4, page, quantized, chunks)
        np.testing.assert_array_equal(np.asarray(pv[0][:, :w]),
                                      np.asarray(dv[0]))
        np.testing.assert_array_equal(np.asarray(pv[4][:, :w]),
                                      np.asarray(dv[4]))


# --- speculative burst primitives: write_tokens / fork / rollback ------------

def _make_cache(kind, b, w, h, hd, quantized, page=4):
    if kind == "paged":
        return kv_cache.paged_init(b, w, h, hd, jnp.bfloat16,
                                   quantized=quantized, page_size=page)
    kw = {}
    dtype = jnp.bfloat16
    if quantized:
        kw = {"k_s": jnp.zeros((b, w, h, 1), jnp.bfloat16),
              "v_s": jnp.zeros((b, w, h, 1), jnp.bfloat16)}
        dtype = jnp.int8
    cls = kv_cache.DenseCache if kind == "dense" else kv_cache.RingCache
    extra = {} if kind == "dense" else {"window": w}
    return cls(k=jnp.zeros((b, w, h, hd), dtype),
               v=jnp.zeros((b, w, h, hd), dtype), **kw, **extra)


class TestWriteTokensParity:
    """The speculative burst write: ``write_tokens`` of S rows must be
    BIT-identical to S sequential ``write_token`` calls on every backend
    (bf16 and int8-KV) — including a ring wrap and a paged write that
    crosses page boundaries."""

    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("kind", ["dense", "ring", "paged"])
    def test_burst_equals_sequential(self, kind, quantized):
        b, h, hd, s = 2, 2, 4, 5
        w = 8 if kind == "ring" else 16
        # slot 1 starts at 6: the ring burst wraps (positions 6..10 over
        # an 8-ring), the paged burst crosses two page-4 boundaries
        pos = jnp.asarray([3, 6], jnp.int32)
        cache = _make_cache(kind, b, w, h, hd, quantized)
        key = jax.random.PRNGKey(11)
        kr, vr = _rows(key, b, s, h, hd), _rows(jax.random.fold_in(key, 1),
                                                b, s, h, hd)
        burst = cache.write_tokens(kr, vr, pos)
        seq = cache
        for t in range(s):
            seq = seq.write_token(kr[:, t:t + 1], vr[:, t:t + 1], pos + t,
                                  per_seq=True)
        assert jax.tree.structure(burst) == jax.tree.structure(seq)
        for a, e in zip(jax.tree.leaves(burst), jax.tree.leaves(seq)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(e))

    def test_single_token_burst_is_write_token(self):
        cache = _make_cache("dense", 1, 8, 1, 4, False)
        kr, vr = _rows(jax.random.PRNGKey(0), 1, 1, 1, 4), \
            _rows(jax.random.PRNGKey(1), 1, 1, 1, 4)
        pos = jnp.asarray([2], jnp.int32)
        a = cache.write_tokens(kr, vr, pos)
        e = cache.write_token(kr, vr, pos, per_seq=True)
        np.testing.assert_array_equal(np.asarray(a.k), np.asarray(e.k))


class TestForkRollback:
    """Block-table fork/rollback: a rejected verify burst leaves the
    paged cache's OBSERVABLE state (mappings + every valid row) exactly
    where a never-speculated cache sits."""

    def test_rollback_restores_table_and_valid_rows(self):
        b, w, h, hd, page = 1, 16, 2, 4, 4
        base = kv_cache.paged_init(b, w, h, hd, jnp.bfloat16,
                                   page_size=page, mapped=False)
        table = np.zeros((b, 4), np.int32)
        table[0, :2] = [1, 2]              # pages covering the 6-row prompt
        c = base.with_table(jnp.asarray(table))
        key = jax.random.PRNGKey(3)
        c = c.write_prompt(_rows(key, b, 6, h, hd),
                           _rows(jax.random.fold_in(key, 1), b, 6, h, hd),
                           0)[0]
        pos = jnp.asarray([6], jnp.int32)
        start = jnp.zeros((b,), jnp.int32)
        control = c                        # the never-speculated twin

        snap = c.fork()
        # the burst maps one page beyond the prompt's (engine pre-map)
        # and writes rows 6..9 — crossing into the fresh page
        t2 = table.copy()
        t2[0, 2] = 3
        spec = c.with_table(jnp.asarray(t2)).write_tokens(
            _rows(jax.random.fold_in(key, 2), b, 4, h, hd),
            _rows(jax.random.fold_in(key, 3), b, 4, h, hd), pos)
        rolled = spec.rollback(snap)

        # mappings restored: the speculative page is unmapped again
        np.testing.assert_array_equal(np.asarray(rolled.block_table),
                                      np.asarray(control.block_table))
        # the next REAL decode write overwrites the burst's position-6
        # row; after it, every valid column reads back bit-identical
        kr = _rows(jax.random.fold_in(key, 4), b, 1, h, hd)
        vr = _rows(jax.random.fold_in(key, 5), b, 1, h, hd)
        got = rolled.write_token(kr, vr, pos, per_seq=True)
        want = control.write_token(kr, vr, pos, per_seq=True)
        gv, wv = got.gather_view(pos, start), want.gather_view(pos, start)
        valid = np.asarray(wv[4])
        np.testing.assert_array_equal(np.asarray(gv[4]), valid)
        for a, e in zip(gv[:2], wv[:2]):
            np.testing.assert_array_equal(np.asarray(a)[valid],
                                          np.asarray(e)[valid])

    def test_row_backends_fork_is_free(self):
        c = _make_cache("dense", 1, 8, 1, 4, False)
        assert c.fork() is None
        assert c.rollback(None) is c


class TestRingBurstRejected:
    def test_ring_verify_view_raises(self):
        c = _make_cache("ring", 1, 8, 1, 4, False)
        with pytest.raises(ValueError, match="speculative"):
            c.verify_view(jnp.asarray([5], jnp.int32),
                          jnp.zeros((1,), jnp.int32), 3)


# --- model-level paged == dense (the acceptance bit-identity) ----------------

class TestPagedDenseModelParity:
    @pytest.mark.parametrize("kv_quant", [False, True])
    def test_decode_and_chunked_prefill_bit_identical(self, tiny, kv_quant):
        cfg, _, _ = tiny
        model = build_model(cfg, kv_quant=kv_quant)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(7), (2, 10), 1,
                                  cfg.vocab_size)
        ld, cd = model.prefill(params, model.init_cache(2, 16), tokens=toks,
                               chunk=4)
        lp, cp = model.prefill(
            params, model.init_cache(2, 16, kind="paged", page_size=4),
            tokens=toks, chunk=4)
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
        for t in range(4):
            ld, cd = model.decode_step(params, cd, tokens=toks[:, t])
            lp, cp = model.decode_step(params, cp, tokens=toks[:, t])
            np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))

    def test_generate_paged_equals_dense(self, tiny):
        cfg, model, params = tiny
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 1,
                                    cfg.vocab_size)
        o1 = generate(model, params, prompt, steps=6)
        o2 = generate(model, params, prompt, steps=6, cache_kind="paged")
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    def test_ragged_paged_prefill_bit_identical(self, tiny):
        cfg, model, params = tiny
        b, s0 = 3, 10
        lens = jnp.asarray([10, 6, 3])
        mask = jnp.arange(s0)[None, :] >= (s0 - lens[:, None])
        toks = jax.random.randint(jax.random.PRNGKey(5), (b, s0), 1,
                                  cfg.vocab_size)
        toks = jnp.where(mask, toks, 0)
        ld, _ = model.prefill(params, model.init_cache(b, 16), tokens=toks,
                              pad_mask=mask)
        lp, _ = model.prefill(
            params, model.init_cache(b, 16, kind="paged", page_size=8),
            tokens=toks, pad_mask=mask)
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))

    def test_paged_rejects_sliding_window(self, windowed):
        cfg, model, _ = windowed
        with pytest.raises(ValueError, match="window"):
            model.init_cache(2, 16, kind="paged")
        with pytest.raises(ValueError, match="ring"):
            model.init_cache(2, 16, kind="dense")


# --- ServeEngine on the new backends -----------------------------------------

class TestServeEnginePaged:
    def test_paged_is_the_default_and_matches_dense_engine(self, tiny):
        cfg, model, params = tiny
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        outs = {}
        for kind in ("paged", "dense", None):
            eng = ServeEngine(model, params, slots=2, max_len=64,
                              cache_kind=kind)
            if kind is None:
                assert eng.cache_kind == "paged"
            uid = eng.submit(prompt, max_new_tokens=6)
            outs[kind] = eng.run()[uid]
        assert outs["paged"] == outs["dense"] == outs[None]

    def test_page_accounting_alloc_stall_release(self, tiny):
        """An undersized pool admission-stalls (requests wait for an
        EOS) and every page returns to the free list at drain."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=2, max_len=64, page_size=16,
                          pages=3)   # 2 slots want 8 pages fully provisioned
        uids = [eng.submit(list(range(1, 18)), max_new_tokens=4)
                for _ in range(3)]   # 17 tokens -> 2 pages
        res = eng.run()
        assert set(res) == set(uids)
        assert all(len(res[u]) == 4 for u in uids)
        stats = eng.page_stats
        assert stats["total"] == 3 and stats["reserved"] == 0
        # drained slots hold nothing; only prefix-cache pins may remain
        assert stats["free"] + stats["resident"] == stats["total"]
        assert stats["resident"] == stats["cached"]
        assert not eng._slot_pages and not eng._slot_shared
        assert (eng._table == 0).all()
        eng.check_leaks()

    def test_reservation_covers_decode_worst_case(self, tiny):
        """A pool that can only hold one request's worst case at a time
        serves a burst sequentially — in-flight requests never exhaust
        the pool mid-decode (admission reserves prompt + max_new)."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=2, max_len=64, page_size=8,
                          pages=5)
        uids = [eng.submit(list(range(1, 13)), max_new_tokens=20)
                for _ in range(2)]   # 12 + 20 new -> 4 pages each
        res = eng.run()
        assert all(len(res[u]) == 20 for u in uids)
        stats = eng.page_stats
        assert stats["total"] == 5 and stats["reserved"] == 0
        assert stats["free"] + stats["resident"] == stats["total"]
        assert stats["resident"] == stats["cached"]
        eng.check_leaks()

    def test_unservable_max_new_rejected_up_front(self, tiny):
        """max_new_tokens counts toward the worst-case page need: a
        request the pool can never satisfy fails at submit, not with a
        mid-decode engine crash."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=64, page_size=16,
                          pages=1)
        with pytest.raises(ValueError, match="pages"):
            eng.submit(list(range(1, 11)), max_new_tokens=40)

    def test_decode_grabs_pages_across_boundaries(self, tiny):
        """max_new_tokens pushes the slot across a page boundary: decode
        must map fresh pages on the fly."""
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=64, page_size=4)
        uid = eng.submit([5, 3, 1], max_new_tokens=10)   # 1 page
        res = eng.run()
        assert len(res[uid]) == 10
        ref = generate(model, params, jnp.asarray([[5, 3, 1]], jnp.int32),
                       steps=10)
        assert res[uid] == np.asarray(ref)[0].tolist()

    def test_oversized_prompt_rejected_up_front(self, tiny):
        cfg, model, params = tiny
        eng = ServeEngine(model, params, slots=1, max_len=64, page_size=16,
                          pages=1)
        with pytest.raises(ValueError, match="pages"):
            eng.submit(list(range(1, 30)), max_new_tokens=2)


class TestServeEngineRing:
    def test_sliding_window_engine_end_to_end(self, windowed):
        """The engine guard is gone: a sliding-window config serves
        prompts LONGER than the window, matching generate() on the same
        left-padded bucket grid bit for bit."""
        cfg, model, params = windowed
        assert cfg.sliding_window == 8
        eng = ServeEngine(model, params, slots=2, max_len=64)
        assert eng.cache_kind == "ring"
        prompt = [int(t) for t in
                  np.random.default_rng(3).integers(1, cfg.vocab_size, 12)]
        uid = eng.submit(prompt, max_new_tokens=5)         # buckets to 16
        res = eng.run()
        padded = jnp.asarray([[0] * 4 + prompt], jnp.int32)
        ref = generate(model, params, padded, steps=5, prompt_lens=[12])
        assert res[uid] == np.asarray(ref)[0].tolist()

    def test_ring_engine_continuous_batching(self, windowed):
        cfg, model, params = windowed
        eng = ServeEngine(model, params, slots=2, max_len=64)
        prompts = [[1, 2, 3], list(range(1, 14)), [9, 9], [4, 5, 6, 7]]
        uids = [eng.submit(p, max_new_tokens=3) for p in prompts]
        res = eng.run()
        assert set(res) == set(uids)
        assert all(len(res[u]) == 3 for u in uids)


class TestSSMProtocol:
    def test_ssm_cache_is_a_dataclass_pytree(self):
        cfg = reduced_config(get_config("mamba2-370m"))
        model = build_model(cfg)
        cache = model.init_cache(2, 8)
        from repro.models.kv_cache import SSMCache
        found = [c for group in [cache["layers"]] for c in group
                 if isinstance(c, SSMCache)]
        assert found, "SSM layers should carry SSMCache state"

    def test_hybrid_engine_admission_no_special_cases(self):
        """Jamba (SSM + attn + MoE) through the engine: SSM state rides
        the same prefill_view/admit protocol as the attention caches."""
        cfg = reduced_config(get_config("jamba-1.5-large"))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        eng = ServeEngine(model, params, slots=2, max_len=32)
        prompt = [3, 1, 4, 1, 5]
        uid = eng.submit(prompt, max_new_tokens=4)
        res = eng.run()
        ref = generate(model, params, jnp.asarray([prompt], jnp.int32),
                       steps=4)
        assert res[uid] == np.asarray(ref)[0].tolist()
