"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: these
tests compile each serving kernel at Qwen2.5-3B's real widths for a
described ``v5e:2x2`` topology and assert the compiled program holds the
Mosaic kernel (``tpu_custom_call``).  They catch what interpret-mode
parity never sees — block shapes that break the TPU tiling rule, VMEM
overflows — at no chip cost.  Nothing runs, so they say nothing about
results or speed.

The topology is described inside a module fixture (never at import): a
process that describes it holds the TPU library until it exits, so only
the worker that runs this file may touch it.
"""

import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.ent_matmul import ops as ent_ops
from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.paged_attention import ops as paged_ops

# Qwen2.5-3B: d_model 2048, 16 q / 2 kv heads of 128, d_ff 11008
D, HQ, HKV, HD, FF = 2048, 16, 2, 128, 11008
PROJ = [(D, D), (D, HKV * HD), (D, FF), (FF, D)]   # q/o, k/v, up/gate, down
SLOTS, PAGE, MAX_LEN = 8, 16, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, kernel, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    assert kernel in text, f"kernel {kernel!r} not in the program"
    return text


@pytest.mark.parametrize("m", [3, 8, 512])
@pytest.mark.parametrize("k,n", PROJ)
def test_ent_fused_matmul(one_chip, m, k, n):
    """Fused packed-plane EN-T matmul: decode (M = slots), prefill
    (M = 512) and an M off the 8-row tile."""
    _compile(lambda x, p, sw: ent_ops.ent_quantized_matmul_fused(
        x, p, sw, use_kernel="pallas"), one_chip, "ent_matmul_packed_fused",
        ((m, k), jnp.bfloat16), ((2, k, n), jnp.int8), ((1, n), jnp.float32))


# (slots, max_len, q heads, kv heads, head dim): the compile width above,
# the serving cell's 64 slots at 2048 tokens, and MiniCPM-2B's MHA
PAGED = {"qwen_8x1024": (SLOTS, MAX_LEN, HQ, HKV, HD),
         "qwen_64x2048": (64, 2048, HQ, HKV, HD),
         "minicpm_mha_d64": (SLOTS, MAX_LEN, 36, 36, 64)}


@pytest.mark.parametrize("shape", sorted(PAGED))
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_attention(one_chip, kv_dtype, shape):
    """In-place paged decode over the [P, Hkv, page, D] pool, bf16 KV and
    int8 KV with per-row scale pools: pages by hand (lane-aligned bf16)
    and by the grid's pipeline (head dim 64, int8 scales)."""
    slots, max_len, hq, hkv, hd = PAGED[shape]
    pps = max_len // PAGE
    pool = (slots * pps + 1, hkv, PAGE, hd)
    shapes = [((slots, hq, 1, hd), jnp.bfloat16), (pool, kv_dtype),
              (pool, kv_dtype), ((slots, pps), jnp.int32),
              ((slots,), jnp.int32), ((slots,), jnp.int32)]
    if kv_dtype == jnp.int8:
        shapes += [(pool[:-1] + (1,), jnp.bfloat16)] * 2

    def fn(q, k, v, table, pos, start, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_ops.paged_attention(
            q, k, v, table, pos, start, page_size=PAGE, k_scales=ks,
            v_scales=vs, use_kernel="pallas")

    _compile(fn, one_chip, "paged_attention", *shapes)


@pytest.mark.parametrize("shape", sorted(PAGED))
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_write(one_chip, kv_dtype, shape):
    """A decode tick's K/V row write into layer 1 of a two-layer stack of
    pools, bf16 KV and int8 KV with its scale pools, each new pool the
    buffer of the pool it updates."""
    slots, max_len, _, hkv, hd = PAGED[shape]
    pool = (2, slots * (max_len // PAGE) + 1, hkv, PAGE, hd)
    pools = [(pool, kv_dtype)] * 2
    rows = [((slots, hkv, hd), kv_dtype)] * 2
    if kv_dtype == jnp.int8:
        pools += [(pool[:-1] + (1,), jnp.bfloat16)] * 2
        rows += [((slots, hkv, 1), jnp.bfloat16)] * 2
    n = len(pools)

    def fn(*args):
        return paged_ops.paged_write(args[:n], args[n:2 * n], *args[2 * n:],
                                     use_kernel="pallas")

    text = _compile(fn, one_chip, "paged_write", *pools, *rows,
                    ((slots,), jnp.int32), ((slots,), jnp.int32),
                    ((), jnp.int32))
    assert "output_to_operand_aliasing" in text


@pytest.mark.parametrize("sq,skv", [(512, 512), (256, 512)])
def test_flash_masked(one_chip, sq, skv):
    """Ragged masked flash prefill: a whole 512-token prompt, and a
    256-token suffix over a 256-token cached prefix."""
    def fn(q, k, v, start):
        return attn_ops.masked_attention(q, k, v, start=start,
                                         q_offset=skv - sq,
                                         use_kernel="pallas")

    _compile(fn, one_chip, "flash_attention_masked",
             ((1, HQ, sq, HD), jnp.bfloat16),
             ((1, HKV, skv, HD), jnp.bfloat16),
             ((1, HKV, skv, HD), jnp.bfloat16), ((1,), jnp.int32))


@pytest.mark.parametrize("m", [8, 64])
def test_ent_fused_matmul_lane_padded_columns(one_chip, m):
    """A projection whose width is no multiple of the 128-lane tile
    (Granite-4.0-H's Mamba-2 in_proj, 2048 x 8512) runs the fused kernel
    on planes padded to 8576 columns at encode, and the result keeps
    the true width."""
    from repro.quant.quantize import qdense_apply, quantize_weight
    rec = jax.eval_shape(quantize_weight,
                         jax.ShapeDtypeStruct((D, 8512), jnp.bfloat16))
    assert rec["planes_packed"].shape == (2, D, 8576)
    assert rec["scale"].shape == (1, 8512)

    def fn(x, planes, scale):
        y = qdense_apply({"planes_packed": planes, "scale": scale}, x,
                         use_kernel="pallas")
        assert y.shape == (m, 8512)
        return y

    _compile(fn, one_chip, "ent_matmul_packed_fused",
             ((m, D), jnp.bfloat16), ((2, D, 8576), jnp.int8),
             ((1, 8512), jnp.float32))


# (slots, heads, head dim, state, groups): Granite-4.0-H-Micro's mixers
# at the serving cell's 64 slots, and Jamba's 8 groups of 32 heads
SSD = {"granite_64": (64, 64, 64, 128, 1), "jamba_8": (8, 256, 64, 128, 8)}


@pytest.mark.parametrize("shape", sorted(SSD))
def test_ssd_decode_step(one_chip, shape):
    """Mamba-2 decode state update: one group of the stacked
    [L, B, H, P, N] fp32 state updated in place, the group index and a
    slot mask in scalar memory."""
    from repro.kernels.ssd_scan import ops as ssd_ops
    b, h, p, n, g = SSD[shape]

    def fn(state, x, dt, a, bm, cm, mask, group):
        return ssd_ops.ssd_decode_step(state, x, dt, a, bm, cm, group,
                                       mask=mask, use_kernel="pallas")

    text = _compile(fn, one_chip, "ssd_decode_step",
                    ((4, b, h, p, n), jnp.float32), ((b, h, p), jnp.float32),
                    ((b, h), jnp.float32), ((h,), jnp.float32),
                    ((b, g, n), jnp.float32), ((b, g, n), jnp.float32),
                    ((b,), jnp.bool_), ((), jnp.int32))
    # the new state is the state operand's buffer (output 1 <- operand 7)
    assert "output_to_operand_aliasing={{1}: (7, {})}" in text


def _tick_hlo(one_chip, monkeypatch, config, layers, slots, max_len, pages):
    """The compiled decode tick of ``config`` cut to ``layers`` layers, at
    its published widths with EN-T planes, ``slots`` slots of ``max_len``
    tokens on a paged pool of ``pages`` pages and a donated cache: the
    engine's greedy tick (``make_serve_programs``), the program served."""
    import dataclasses

    from repro.configs.base import QuantConfig
    from repro.models.transformer import build_model
    from repro.quant.quantize import quantize_params
    from repro.runtime.sampling import make_sampler
    from repro.runtime.serve_loop import make_serve_programs

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = build_model(dataclasses.replace(config, num_layers=layers))
    params = jax.eval_shape(lambda k: quantize_params(
        model.init(k), QuantConfig(enabled=True)), jax.random.PRNGKey(0))

    def cache_fn():
        c = model.init_cache(slots, max_len, kind="paged", page_size=PAGE,
                             pages=pages, mapped=False)
        c["pos"] = jnp.zeros((slots,), jnp.int32)
        c["start"] = jnp.zeros((slots,), jnp.int32)
        return c

    tick = make_serve_programs(model, make_sampler())[0]
    place = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), t)
    args = place((params, jax.eval_shape(cache_fn),
                  jax.ShapeDtypeStruct((slots,), jnp.int32),
                  jax.ShapeDtypeStruct((slots,), jnp.bool_),
                  jax.ShapeDtypeStruct((slots,), jnp.int32)))
    text = tick.lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') > 0
    return text


# ops that make a new array of their result's shape
MOVERS = r"(copy|copy-start|fusion|dynamic-slice|dynamic-update-slice|scatter)"


def _moved(text, dtype, shape):
    """[(computation, line)] of each op of ``text`` whose result (or a
    tuple element of it) is a ``dtype`` array of ``shape``, a trailing
    shape, given with a leading group axis or without one."""
    pat = re.compile(r"= \(?" + dtype + r"\[(\d+,)?" + re.escape(shape)
                     + r"\S* " + MOVERS + r"\(")
    out, comp = [], None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
        if pat.search(line):
            out.append((comp, line.strip()[:160]))
    return out


def _kv_write_aliases_its_pools(text, layers_with_attn):
    """Each paged layer's K/V rows are written by ``paged_write``, whose
    outputs are its pool operands' buffers."""
    writes = [line for line in text.splitlines()
              if re.match(r"\s*%paged_write\S* = .* custom-call\(", line)]
    assert len(writes) == layers_with_attn, writes
    for line in writes:
        assert "output_to_operand_aliasing={{0}: (4, {}), {1}: (5, {})}" \
            in line, line[:400]


def test_qwen_decode_tick_holds_no_copy_of_the_kv_pool(one_chip, monkeypatch):
    """Qwen2.5-3B's decode tick at the published widths (four layers, the
    serving cell's 64 slots on 8,192 pages, EN-T planes, donated cache):
    each layer writes its token's K/V rows where they lie in the stacked
    [G, P, Hkv, page, D] pools (``paged_write``, aliased) and
    ``paged_attention`` reads them there; no op makes a copy, slice,
    write-back or scatter of a layer's pool or of the stack."""
    from repro.configs.qwen2_5_3b import CONFIG
    text = _tick_hlo(one_chip, monkeypatch, CONFIG, 4, 64, 2048, 8192)
    assert "paged_attention" in text
    moved = _moved(text, "bf16", f"{8192 + 1},{HKV},{PAGE},{HD}]")
    moved += _moved(text, "bf16", f"{4 * (8192 + 1)},{HKV},{PAGE},{HD}]")
    assert not moved, moved
    _kv_write_aliases_its_pools(text, 1)


def test_granite_decode_tick_holds_no_copy_of_the_ssd_state(one_chip,
                                                             monkeypatch):
    """Granite-4.0-H's decode tick at the published widths (two layer
    groups, 8 slots, EN-T planes, donated cache): each mixer's state is
    stepped where it lies in the stacked [G, B, H, P, N] fp32 state by
    ``ssd_decode_step``; no copy, slice or write-back of a group's
    state, or of the stack, is left in the compiled program.  The
    attention layers' K/V pools ([G, P, 8, 16, 64]) are written and read
    where they lie too: the loop over the groups holds no op that makes
    a pool.  What is left is the program's edge: the device's default
    layout for a bf16 array with a 64-wide minor dim puts the page axis
    on the lanes, where the kernels take pages row-major, so each stacked
    pool is laid out once on the way in and once on the way out."""
    from repro.configs.granite_4_0_h_micro import CONFIG
    slots, pages = SLOTS, SLOTS * MAX_LEN // PAGE
    text = _tick_hlo(one_chip, monkeypatch, CONFIG, 20, slots, MAX_LEN,
                     pages)
    assert "ssd_decode_step" in text
    state = f"{slots},64,64,128]"          # [.., B, H, P, N] f32
    moved = _moved(text, "f32", state)
    assert not moved, moved
    kv = _moved(text, "bf16", f"{pages + 1},8,16,64]")
    kv += _moved(text, "bf16", f"{2 * (pages + 1)},8,16,64]")
    entry = [comp for comp, _ in kv if comp.startswith("%main")]
    assert len(entry) == len(kv) == 4, kv      # K and V, in and out
    assert all(" copy(" in line for _, line in kv), kv
    _kv_write_aliases_its_pools(text, 1)
