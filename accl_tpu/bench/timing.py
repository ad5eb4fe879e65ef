"""Chained-timing harness — shared by bench.py (the metric of record)
and scripts/kernel_tune.py.

Methodology (why this shape):
- iterations are CHAINED INSIDE ONE COMPILED PROGRAM (lax.fori_loop;
  the carry feeds forward so no elision is possible) — one dispatch per
  trial regardless of iteration count, so per-call host dispatch does
  not pose as kernel time;
- fixed operands ride as traced ARGUMENTS via `consts` (a closure
  would bake them into the program as constants);
- completion is forced by a scalar device->host readback (cannot
  resolve before the producing loop finishes); the MINIMUM observed
  round-trip cost is subtracted — a running min refreshed with one
  probe per timed_chain call, never a median: the min can only
  under-subtract, so a slow probe deflates a sample instead of
  inflating it past the chip's physical peak;
- minimum over trials; quantities that will be RATIOED share windows
  (interleave via `timed_chain_ab`).
"""
from __future__ import annotations

import time


def make_harness(jax, jnp):
    """Returns (probe, timed_chain, timed_chain_ab, sync_s)."""
    from jax import lax

    probe = jax.jit(lambda x: x.reshape(-1)[-1])

    warm = jnp.zeros((1024,), jnp.float32)
    float(probe(warm))  # compile the probe

    # running MINIMUM of the completion-barrier round trip (see module
    # docstring: a banked median from a congested window over-subtracts
    # and reports rates above the chip's physical peak)
    sync_state = {"min": float("inf")}

    def _sync_sample() -> float:
        t0 = time.perf_counter()
        float(probe(warm))
        dt = time.perf_counter() - t0
        if dt < sync_state["min"]:
            sync_state["min"] = dt
        return dt

    for _ in range(3):
        _sync_sample()
    sync_s = sync_state["min"]

    chain_cache: dict = {}

    def timed_chain(fn, x0, iters, trials=5, consts=()):
        """BEST (minimum) per-iteration seconds of the in-jit chained
        loop `fori_loop(0, iters, lambda _, v: fn(v, *consts), x0)`.
        fn must be shape/dtype-preserving in its first argument."""
        # key includes operand shapes/dtypes: the same fn re-timed on a
        # different shape must pay its compile+warm OUTSIDE the timed
        # trials (jax.jit would otherwise retrace inside the first one)
        sig = tuple((v.shape, str(v.dtype)) for v in (x0, *consts))
        # key on the fn OBJECT (functions/partials are hashable): keying
        # on id(fn) would only be correct while the cached closure keeps
        # fn alive, a lifetime coupling one refactor away from returning
        # a stale compiled chain for a recycled id
        key = (fn, iters, sig)
        chained = chain_cache.get(key)
        if chained is None:
            chained = jax.jit(lambda x, *cs: lax.fori_loop(
                0, iters, lambda _, v: fn(v, *cs), x))
            float(probe(chained(x0, *consts)))  # compile + warm
            chain_cache[key] = chained
        _sync_sample()  # refresh the running-min RTT in this window
        sync_min = sync_state["min"]
        vals = []
        for _ in range(trials):
            t0 = time.perf_counter()
            out = chained(x0, *consts)
            float(probe(out))  # true completion barrier
            elapsed = time.perf_counter() - t0
            # RTT jitter can push elapsed below the observed sync min;
            # fall back to the unsubtracted time, never negative
            net = elapsed - sync_min if elapsed > sync_min else elapsed
            vals.append(net / iters)
        return min(vals)

    def timed_chain_ab(fns: dict, x0, iters, trials=5, consts=()) -> dict:
        """Interleaved A/B timing: one trial of each fn per round, best
        window per fn — ratioed quantities must share windows."""
        best = {k: None for k in fns}
        for _ in range(trials):
            for k, fn in fns.items():
                dt = timed_chain(fn, x0, iters, trials=1, consts=consts)
                if best[k] is None or dt < best[k]:
                    best[k] = dt
        return best

    return probe, timed_chain, timed_chain_ab, sync_s
