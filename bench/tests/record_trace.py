"""Record the small device trace that ``test_trace_reduce.py`` reads.

    python bench/tests/record_trace.py OUT_DIR

Runs on a TPU: a few calls of the three Pallas kernels of the serving
path (fused EN-T matmul, paged decode attention, masked flash prefill)
at small shapes, inside host spans named like the harness's, under the
JAX profiler.  Writes ``OUT_DIR/small.xplane.pb`` and prints every plane,
line and the first events with their stats, so the reduction can be
checked against what the profiler really writes.
"""

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.core.multiplier import ent_packed_planes
    from repro.kernels.ent_matmul import ops as ent_ops
    from repro.kernels.flash_attention import ops as attn_ops
    from repro.kernels.paged_attention import ops as paged_ops

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU found", file=sys.stderr)
        return 1
    key = jax.random.PRNGKey(0)
    w = jax.random.randint(key, (512, 256), -127, 128).astype(jnp.int8)
    packed = ent_packed_planes(w)
    sw = jnp.full((1, 256), 0.01, jnp.float32)
    x = jax.random.normal(key, (8, 512), jnp.bfloat16)
    ent = jax.jit(lambda x: ent_ops.ent_quantized_matmul_fused(
        x, packed, sw, use_kernel="pallas"))
    pages, page, hkv, hd, slots = 17, 16, 2, 128, 4
    q = jax.random.normal(key, (slots, 8, 1, hd), jnp.bfloat16)
    kp = jax.random.normal(key, (pages, hkv, page, hd), jnp.bfloat16)
    table = jnp.arange(1, 17, dtype=jnp.int32).reshape(slots, 4)
    pos = jnp.asarray([5, 20, 40, 63], jnp.int32)
    paged = jax.jit(lambda q: paged_ops.paged_attention(
        q, kp, kp, table, pos, page_size=page, use_kernel="pallas"))
    qf = jax.random.normal(key, (1, 8, 256, hd), jnp.bfloat16)
    kf = jax.random.normal(key, (1, hkv, 256, hd), jnp.bfloat16)
    flash = jax.jit(lambda q: attn_ops.masked_attention(
        q, kf, kf, use_kernel="pallas"))
    for f, a in ((ent, x), (paged, q), (flash, qf)):
        jax.block_until_ready(f(a))
    print("compiled text holds kernel:",
          "ent_matmul_packed_fused" in ent.lower(x).compile().as_text())

    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.tick"):
                jax.block_until_ready((ent(x), paged(q)))
            with jax.profiler.TraceAnnotation("bench.chunk"):
                jax.block_until_ready(flash(qf))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb"))[-1]
    os.makedirs(out_dir, exist_ok=True)
    dest = os.path.join(out_dir, "small.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(tmp)
    print("wrote", dest, os.path.getsize(dest), "bytes")
    for plane in ProfileData.from_file(dest).planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events), "events")
            for e in events[:6]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns,
                      dict(e.stats))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
