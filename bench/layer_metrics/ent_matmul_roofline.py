"""Share of its roofline that the fused EN-T matmul kernel reaches.

Device time: the ``ent_matmul_packed_fused`` events of the trace.  Least
time: per event, from its own shapes, the logical int8-weight product
(``work.ent_matmul``) at the int8 peak or the HBM bandwidth, whichever
bounds.  The two digit planes the kernel reads are not counted as work,
so the share at decode tops out near 50% today, and a repacked weight
is held to the same yardstick."""

LAYER = "kernels"
MOVES = "itl_p95_ms"
KERNEL = "ent_matmul_packed_fused"


def read(ctx):
    from work import ent_matmul, ent_matmul_event, least_time
    calls = ctx.trace.calls(KERNEL) if ctx.trace else []
    if not calls:
        return None
    p = ctx.peaks
    least = sum(least_time(*ent_matmul(*ent_matmul_event(c.shapes)),
                           p["int8"], p["hbm_bytes_per_s"]) for c in calls)
    return 100.0 * least / sum(c.seconds for c in calls)
