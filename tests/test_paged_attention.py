"""In-place paged decode attention: kernel + oracle vs the gather path.

Pins this PR's acceptance contract:

* the jnp oracle (the CPU serving path) is BIT-identical to the
  gather-then-``masked_attention`` read it replaced — for bf16 and
  int8-KV with per-page scales, any ``block_pages`` streaming
  granularity, partial last pages, page-0 null table entries, and
  ragged per-slot lengths (hypothesis sweep over (B, page_size, ctx));
* the Pallas kernel (interpret mode) matches the oracle at float
  tolerance over the same grid, for any ``pages_per_block`` and both
  ways it brings pages in (by hand at lane-aligned head dims, by the
  grid's pipeline otherwise), and emits exact zeros for fully-masked
  (all-null) slots; its trip count follows each slot's live pages, not
  the table's width;
* the model decode path no longer touches ``PagedCache._gather``:
  a ServeEngine decode tick and ``Model.decode_step`` run end to end
  with the gather forcibly disabled, and stay bit-identical to the
  dense backend.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_config, reduced_config
from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.paged_attention import (
    live_extent, paged_attention_kernel)
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.models import kv_cache
from repro.models.transformer import build_model
from repro.runtime.serve_loop import ServeEngine, generate

RNG = np.random.default_rng(7)


def _filled_cache(b, max_len, lens, h=2, hd=16, page=4, quantized=False,
                  dtype=jnp.float32):
    """A PagedCache written token by token to ragged depths ``lens``
    (slot i stops writing at lens[i]); returns (cache, pos [B])."""
    pc = kv_cache.paged_init(b, max_len, h, hd, dtype, page_size=page,
                             quantized=quantized)
    for t in range(max(lens)):
        k = jnp.asarray(RNG.normal(size=(b, 1, h, hd)).astype(np.float32))
        v = jnp.asarray(RNG.normal(size=(b, 1, h, hd)).astype(np.float32))
        slot = jnp.asarray([min(t, n - 1) for n in lens], jnp.int32)
        pc = pc.write_token(k, v, slot, per_seq=True)
    return pc, jnp.asarray([n - 1 for n in lens], jnp.int32)


def _gather_read(q, pc, pos, start):
    """The PR4 decode read: gather_view + masked_attention (the oracle
    the in-place op must reproduce bit for bit)."""
    kop, vop, ks, vs, valid = pc.gather_view(pos, start)
    kw = {}
    if ks is not None:
        sc = lambda s: s[..., 0].transpose(0, 2, 1).astype(jnp.float32)
        kw = dict(k_scale=sc(ks), v_scale=sc(vs))
    dt = q.dtype if kop.dtype == jnp.int8 else kop.dtype
    return attn_ops.masked_attention(
        q, kop.astype(dt).transpose(0, 2, 1, 3),
        vop.astype(dt).transpose(0, 2, 1, 3), valid=valid[:, None, :], **kw)


def _scales(pc):
    return (dict(k_scales=pc.k_s, v_scales=pc.v_s) if pc.quantized else {})


class TestOracleBitIdentity:
    """The jnp oracle == gather-then-masked_attention, bit for bit."""

    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("page,lens", [
        (4, [13, 7]),        # partial last page + ragged depths
        (8, [16, 16]),       # exact page boundary
        (2, [5, 11]),        # tiny pages
    ])
    def test_matches_gather_read(self, page, lens, quantized):
        b, h, hd, max_len = 2, 2, 16, 16
        pc, pos = _filled_cache(b, max_len, lens, h, hd, page, quantized)
        start = jnp.asarray([0, 2], jnp.int32)
        q = jnp.asarray(RNG.normal(size=(b, 4, 1, hd)).astype(np.float32))
        want = _gather_read(q, pc, pos, start)
        for bp in (1, 2, None):   # any streaming granularity is bit-exact
            got = paged_attention_ref(
                q, pc.k, pc.v, pc.block_table, pos, start, page_size=page,
                block_pages=bp, **_scales(pc))
            np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        # pool-wide scores (one GEMM vs the whole pool + column select)
        # are the same dots, hence also bit-exact
        got = paged_attention_ref(
            q, pc.k, pc.v, pc.block_table, pos, start, page_size=page,
            score_mode="pool", **_scales(pc))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_bf16_pool_bit_identical(self):
        pc, pos = _filled_cache(2, 16, [9, 12], page=4, dtype=jnp.bfloat16)
        start = jnp.zeros((2,), jnp.int32)
        q = jnp.asarray(RNG.normal(size=(2, 4, 1, 16)).astype(np.float32))
        want = _gather_read(q, pc, pos, start)
        got = paged_attention_ref(q, pc.k, pc.v, pc.block_table, pos, start,
                                  page_size=4, block_pages=2)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_null_pages_beyond_pos_change_nothing(self):
        """Unmapping the tail pages past a slot's depth (what the engine
        allocator leaves unmapped) must not change the output."""
        pc, pos = _filled_cache(2, 24, [13, 7], page=4)
        start = jnp.zeros((2,), jnp.int32)
        q = jnp.asarray(RNG.normal(size=(2, 4, 1, 16)).astype(np.float32))
        base = paged_attention_ref(q, pc.k, pc.v, pc.block_table, pos, start,
                                   page_size=4)
        bt = np.asarray(pc.block_table).copy()
        bt[0, 4:] = 0   # slot 0 holds positions 0..12 -> pages 0..3
        bt[1, 2:] = 0   # slot 1 holds positions 0..6  -> pages 0..1
        got = paged_attention_ref(q, pc.k, pc.v, jnp.asarray(bt), pos, start,
                                  page_size=4)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(got))

    @settings(max_examples=25, deadline=None)
    @given(b=st.integers(1, 3), page=st.sampled_from([2, 3, 4, 8]),
           ctx=st.integers(1, 20), quantized=st.booleans())
    def test_property_sweep(self, b, page, ctx, quantized):
        """(B, page_size, ctx) sweep: ragged depths derived from ctx,
        partial pages included; oracle == gather read bit for bit."""
        max_len = 24
        lens = [max(1, ctx - 3 * i) for i in range(b)]
        pc, pos = _filled_cache(b, max_len, lens, h=1, hd=8, page=page,
                                quantized=quantized)
        start = jnp.zeros((b,), jnp.int32)
        q = jnp.asarray(RNG.normal(size=(b, 2, 1, 8)).astype(np.float32))
        want = _gather_read(q, pc, pos, start)
        got = paged_attention_ref(q, pc.k, pc.v, pc.block_table, pos, start,
                                  page_size=page, block_pages=2,
                                  **_scales(pc))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


class TestKernelParity:
    """Pallas kernel (interpret) vs the oracle, float tolerance."""

    @pytest.mark.parametrize("hd", [16, 128])
    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("page,ppb", [(4, None), (8, 1), (8, 2)])
    def test_matches_oracle(self, page, ppb, quantized, hd):
        b, h, max_len = 2, 2, 16
        pc, pos = _filled_cache(b, max_len, [13, 7], h, hd, page, quantized)
        start = jnp.asarray([0, 2], jnp.int32)
        q = jnp.asarray(RNG.normal(size=(b, 4, 1, hd)).astype(np.float32))
        want = paged_attention_ref(q, pc.k, pc.v, pc.block_table, pos, start,
                                   page_size=page, **_scales(pc))
        kw = {} if ppb is None else {"pages_per_block": ppb}
        got = paged_attention_kernel(
            q, pc.k, pc.v, pc.block_table, pos, start, pc.k_s, pc.v_s,
            page_size=page, interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=1e-5)

    def test_fully_masked_slot_emits_zeros(self):
        """An idle serving slot (all-null table row) must emit exact
        zeros — the compute-skipped blocks leave the accumulator at 0."""
        pc, pos = _filled_cache(2, 16, [9, 5], page=4)
        bt = np.asarray(pc.block_table).copy()
        bt[1, :] = 0
        q = jnp.asarray(RNG.normal(size=(2, 4, 1, 16)).astype(np.float32))
        start = jnp.zeros((2,), jnp.int32)
        for impl in ("ref", "interpret"):
            got = paged_ops.paged_attention(
                q, pc.k, pc.v, jnp.asarray(bt), pos, start, page_size=4,
                use_kernel=impl)
            assert np.all(np.asarray(got)[1] == 0), impl
            assert np.any(np.asarray(got)[0] != 0), impl

    def test_ops_dispatch_interpret_end_to_end(self):
        """The ops entry point (the decode_step call) in interpret mode:
        kernel result == the CPU ref dispatch at tolerance."""
        pc, pos = _filled_cache(2, 16, [10, 16], page=8, quantized=True)
        q = jnp.asarray(RNG.normal(size=(2, 4, 1, 16)).astype(np.float32))
        start = jnp.zeros((2,), jnp.int32)
        a = paged_ops.paged_attention(q, pc.k, pc.v, pc.block_table, pos,
                                      start, page_size=8, use_kernel="ref",
                                      **_scales(pc))
        k = paged_ops.paged_attention(q, pc.k, pc.v, pc.block_table, pos,
                                      start, page_size=8,
                                      use_kernel="interpret", **_scales(pc))
        np.testing.assert_allclose(np.asarray(k), np.asarray(a), atol=2e-6,
                                   rtol=1e-5)


def _pool_case(tables, pos, start, *, page, hd, h=2, group=2,
               quantized=False, npool=32, seed=0):
    """Random pools of ``npool`` pages and q, with the given tables."""
    rng = np.random.default_rng(seed)
    shape = (npool, h, page, hd)
    if quantized:
        kv = [jnp.asarray(rng.integers(-127, 128, shape).astype(np.int8))
              for _ in range(2)]
        sc = [jnp.asarray(rng.uniform(5e-3, 2e-2, shape[:-1] + (1,))
                          .astype(np.float32)) for _ in range(2)]
    else:
        kv = [jnp.asarray(rng.normal(size=shape).astype(np.float32))
              for _ in range(2)]
        sc = [None, None]
    b = len(tables)
    q = jnp.asarray(rng.normal(size=(b, h * group, 1, hd)).astype(np.float32))
    return (q, kv[0], kv[1], jnp.asarray(np.asarray(tables, np.int32)),
            jnp.asarray(pos, jnp.int32), jnp.asarray(start, jnp.int32),
            sc[0], sc[1])


# (tables, pos, start, page, pages_per_block, extra): one slot per row
LIVE_CASES = {
    # 7 live pages over 4 blocks of 2, the last ending mid-block and
    # mid-page; the second slot's pos on a page's first position
    "multi_block_mid_page": ([[3, 9, 4, 11, 6, 20, 7, 0, 0, 0],
                              [5, 8, 12, 13, 0, 0, 0, 0, 0, 0]],
                             [25, 12], [0, 0], 4, 2, {}),
    # pos on a page's last position, in both slots
    "pos_on_page_end": ([[2, 5, 7, 0, 0, 0], [9, 0, 0, 0, 0, 0]],
                        [11, 3], [0, 0], 4, 2, {}),
    # left-pad start > 0: leading null pages, start mid-page
    "start_after_null_pages": ([[0, 0, 6, 14, 15, 0], [0, 3, 4, 0, 0, 0]],
                               [18, 10], [9, 5], 4, 2, {}),
    # shuffled, descending ids; page 17 shared (read only) by both slots
    "shuffled_shared_page": ([[30, 2, 17, 9, 1, 0], [17, 25, 4, 0, 0, 0]],
                             [19, 9], [0, 0], 4, 3, {}),
    "one_page_per_block": ([[4, 1, 7, 0], [2, 6, 0, 0]], [13, 6], [0, 0],
                           4, 1, {}),
    "block_wider_than_table": ([[4, 1, 7, 3], [2, 6, 0, 0]], [15, 5],
                               [0, 1], 4, 16, {}),
    "int8_kv_scales": ([[3, 9, 4, 11, 0, 0], [5, 8, 0, 0, 0, 0]], [13, 6],
                       [0, 2], 4, 2, {"quantized": True}),
    # an idle slot: all-null row and a stale pos past a used page
    "all_null_slot": ([[3, 9, 4, 0], [0, 0, 0, 0]], [10, 14], [0, 0], 4, 2,
                      {}),
}


class TestLiveBlocks:
    """Kernel (interpret) vs oracle over the slot shapes the live-block
    loop must get right, by hand (D 128) and by the pipeline (D 16)."""

    @pytest.mark.parametrize("hd", [16, 128])
    @pytest.mark.parametrize("case", sorted(LIVE_CASES))
    def test_matches_oracle(self, case, hd):
        tables, pos, start, page, ppb, extra = LIVE_CASES[case]
        args = _pool_case(tables, pos, start, page=page, hd=hd, **extra)
        q, k, v, table, pos, start, ks, vs = args
        want = paged_attention_ref(q, k, v, table, pos, start,
                                   page_size=page, k_scales=ks, v_scales=vs)
        got = paged_attention_kernel(q, k, v, table, pos, start, ks, vs,
                                     page_size=page, pages_per_block=ppb,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=1e-5)
        idle = ~np.asarray(table).any(-1)
        assert np.all(np.asarray(got)[idle] == 0)

    @pytest.mark.parametrize("quantized", [False, True])
    def test_mha_head_dim_64(self, quantized):
        """MiniCPM's shape: G = 1 (MHA), head dim 64."""
        tables, pos, start, page, _, _ = LIVE_CASES["multi_block_mid_page"]
        args = _pool_case(tables, pos, start, page=page, hd=64, h=4,
                          group=1, quantized=quantized)
        q, k, v, table, pos, start, ks, vs = args
        want = paged_attention_ref(q, k, v, table, pos, start,
                                   page_size=page, k_scales=ks, v_scales=vs)
        got = paged_attention_kernel(q, k, v, table, pos, start, ks, vs,
                                     page_size=page, pages_per_block=2,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=1e-5)

    def test_trip_count_follows_live_pages(self):
        """The blocks a slot runs come from its live pages (``pos``,
        ``start``, mapped entries), whatever the table's width."""
        page, ppb = 4, 2
        pos = jnp.asarray([25, 12, 14, 18], jnp.int32)
        start = jnp.asarray([0, 0, 0, 9], jnp.int32)
        for width in (10, 128):
            table = np.zeros((4, width), np.int32)
            table[0, :7] = np.arange(1, 8)        # pages 0-6: blocks 0-3
            table[1, :4] = np.arange(8, 12)       # pages 0-3: blocks 0-1
            table[2, 5] = 12                      # a page past pos: dead
            table[3, 2:5] = np.arange(13, 16)     # start 9: pages 2-4
            ext = np.asarray(live_extent(jnp.asarray(table), pos, start,
                                         page_size=page,
                                         pages_per_block=ppb))
            np.testing.assert_array_equal(ext[0], [0, 0, 0, 2])   # first
            np.testing.assert_array_equal(ext[1], [6, 3, -1, 4])  # last
            np.testing.assert_array_equal(ext[2], [0, 0, 0, 1])   # 1st block
            np.testing.assert_array_equal(ext[3], [4, 2, 0, 2])   # trips

    @pytest.mark.parametrize("hd", [16, 128])
    def test_output_independent_of_table_width(self, hd):
        """Widening the table with null pages (a longer ``max_len``)
        changes no bit of the result: no extra block runs."""
        tables, pos, start, page, ppb, _ = LIVE_CASES["multi_block_mid_page"]
        args = list(_pool_case(tables, pos, start, page=page, hd=hd))
        narrow = paged_attention_kernel(*args, page_size=page,
                                        pages_per_block=ppb, interpret=True)
        args[3] = jnp.pad(args[3], ((0, 0), (0, 54)))
        wide = paged_attention_kernel(*args, page_size=page,
                                      pages_per_block=ppb, interpret=True)
        np.testing.assert_array_equal(np.asarray(narrow), np.asarray(wide))


class TestDecodePathInPlace:
    """The engine decode path runs WITHOUT the page gather."""

    @pytest.fixture(scope="class")
    def tiny(self):
        cfg = reduced_config(get_config("qwen2.5-3b"))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        return cfg, model, params

    def test_decode_step_never_gathers(self, tiny, monkeypatch):
        """decode_step through PagedCache must not call _gather (the
        read is pool + table); the result stays bit-identical to the
        dense backend."""
        cfg, model, params = tiny
        toks = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 1,
                                  cfg.vocab_size)
        ld, cd = model.prefill(params, model.init_cache(2, 16), tokens=toks)
        lp, cp = model.prefill(
            params, model.init_cache(2, 16, kind="paged", page_size=4),
            tokens=toks)

        def boom(self, c):
            raise AssertionError("decode path gathered the paged view")

        monkeypatch.setattr(kv_cache.PagedCache, "_gather", boom)
        for t in range(3):
            ld, cd = model.decode_step(params, cd, tokens=toks[:, t])
            lp, cp = model.decode_step(params, cp, tokens=toks[:, t])
            np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))

    def test_engine_ticks_without_gather(self, tiny, monkeypatch):
        """A full ServeEngine run on the paged default — admission
        prefill, decode ticks, EOS release — with ``_gather`` disabled
        (unchunked admission attends over fresh K/V only, so nothing on
        the serving path needs the gathered view); output matches
        generate() bit for bit."""
        cfg, model, params = tiny

        def boom(self, c):
            raise AssertionError("engine serving path gathered pages")

        monkeypatch.setattr(kv_cache.PagedCache, "_gather", boom)
        eng = ServeEngine(model, params, slots=2, max_len=32, page_size=4)
        assert eng.cache_kind == "paged"
        prompt = [3, 1, 4, 1, 5]
        uid = eng.submit(prompt, max_new_tokens=6)
        res = eng.run()
        ref = generate(model, params, jnp.asarray([prompt], jnp.int32),
                       steps=6, cache_kind="dense")
        assert res[uid] == np.asarray(ref)[0].tolist()

    def test_kv_quant_decode_bit_identical(self, tiny):
        """int8-KV paged decode through the in-place op == dense int8
        decode, bit for bit (per-page scales folded in-op)."""
        cfg, _, _ = tiny
        model = build_model(cfg, kv_quant=True)
        params = model.init(jax.random.PRNGKey(1))
        toks = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 1,
                                  cfg.vocab_size)
        ld, cd = model.prefill(params, model.init_cache(2, 16), tokens=toks)
        lp, cp = model.prefill(
            params, model.init_cache(2, 16, kind="paged", page_size=8),
            tokens=toks)
        for t in range(4):
            ld, cd = model.decode_step(params, cd, tokens=toks[:, t])
            lp, cp = model.decode_step(params, cp, tokens=toks[:, t])
            np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))


# (head dim, int8 KV, layer): the DMA path, the pipelined path, both with
# int8 scale pools, and the first layer of the stack
STACKED_CASES = {"hd128_layer1": (128, False, 1), "hd64_layer2": (64, False, 2),
                 "int8_hd128_layer2": (128, True, 2),
                 "int8_hd64_layer1": (64, True, 1),
                 "hd128_layer0": (128, False, 0)}


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("case", sorted(STACKED_CASES))
def test_stacked_pool_write_and_read_match_the_layer_path(case, mode):
    """A decode tick's write and read of layer g in the stacked
    [G, P, H, page, D] pools == the same write and read on layer g's
    pool sliced out of the stack, bit for bit: the oracle
    (``PagedCache.write_token`` with ``layer`` set) and the Pallas
    kernels (interpret).  Slot 0 sits on the null page; every other
    layer of the stack is left as it was."""
    hd, quantized, layer = STACKED_CASES[case]
    g, b, h, hq, page, pps = 3, 4, 2, 4, 8, 4
    rng = np.random.default_rng(11)
    npages = 1 + b * pps
    shape = (g, npages, h, page, hd)
    if quantized:
        pools = [jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                 for _ in range(2)]
        pools += [jnp.asarray(rng.uniform(0.01, 0.1, shape[:-1] + (1,)),
                              jnp.bfloat16) for _ in range(2)]
    else:
        pools = [jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                 for _ in range(2)]
    table = np.zeros((b, pps), np.int32)
    table[1:] = 1 + rng.permutation(b * pps)[:(b - 1) * pps].reshape(b - 1,
                                                                      pps)
    table = jnp.asarray(table)
    pos = jnp.asarray([5, 3, 17, 30], jnp.int32)
    start = jnp.asarray([0, 0, 2, 0], jnp.int32)
    k, v = (jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.bfloat16)
            for _ in range(2))
    q = jnp.asarray(rng.normal(size=(b, hq, 1, hd)), jnp.bfloat16)
    names = ("k", "v", "k_s", "v_s")
    kw = dict(zip(names, pools), block_table=table, page_size=page)

    one = kv_cache.PagedCache(**{**kw, **{n: c[layer] for n, c in
                                          zip(names, pools)}})
    want = one.write_token(k, v, pos, per_seq=True)
    stacked = kv_cache.PagedCache(**kw, layer=jnp.int32(layer))
    if mode == "ref":
        got = stacked.write_token(k, v, pos, per_seq=True)
    else:
        pid = jnp.take_along_axis(table, (pos // page)[:, None], 1)[:, 0]
        rows = [r[:, 0] for r in one._stored(k, v) if r is not None]
        new = paged_ops.paged_write(pools, rows, pid, pos % page,
                                    jnp.int32(layer), use_kernel="interpret")
        got = dataclasses.replace(stacked, **dict(zip(names, new)))
    for n, c in zip(names, pools):
        new = np.asarray(getattr(got, n))
        np.testing.assert_array_equal(new[layer], np.asarray(getattr(want, n)))
        rest = [i for i in range(g) if i != layer]
        np.testing.assert_array_equal(new[rest], np.asarray(c)[rest])

    read = lambda c, **kw: paged_ops.paged_attention(
        q, c.k, c.v, table, pos, start, page_size=page, k_scales=c.k_s,
        v_scales=c.v_s, use_kernel=mode, **kw)
    np.testing.assert_array_equal(
        np.asarray(read(got, layer=jnp.int32(layer))), np.asarray(read(want)))
