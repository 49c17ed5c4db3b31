"""Faults planted in the timed path, to show that the check catches them.

    system.serve = faults.broken(system.serve, "token" | "state", vocab)

``broken`` wraps ``system.serve`` so that the scheduler's decode tick,
the program the window drives, is broken as it is built:

* ``token``: the tick's sampled token is altered where it is produced
  (the next id, modulo the vocabulary);
* ``state``: the tick hands its KV state back unchanged (positions still
  advance), so every token after the prompt attends to a stale cache.

The kernel check still compiles the real tick.  Used by the check's own
tests at toy size and by ``calibrate.py --fault`` at a cell's size on the
chip; a benchmark run never plants one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("token", "state")


def broken(serve, fault: str, vocab: int):
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")

    def serve_broken(model, *a, **k):
        eng, sched, srv = serve(model, *a, **k)
        tick = sched._greedy_tick

        def _stale(params, cache, toks, pmask, ppos):
            c = dict(cache)
            c["pos"] = jnp.where(pmask, ppos, c["pos"])
            logits, new = model.decode_step(params, c, tokens=toks)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    dict(new, layers=cache["layers"]))

        stale = jax.jit(_stale, donate_argnums=(1,))

        def faulty(params, cache, toks, pmask, ppos):
            if fault == "state":
                return stale(params, cache, toks, pmask, ppos)
            tok, new = tick(params, cache, toks, pmask, ppos)
            return (tok + 1) % vocab, new

        faulty.lower = tick.lower       # the kernel check compiles the real one
        sched._greedy_tick = faulty
        return eng, sched, srv

    return serve_broken
