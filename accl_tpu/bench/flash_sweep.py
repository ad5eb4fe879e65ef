"""Shared flash-attention schedule sweep harness.

Used by the live-chip tuner (scripts/flash_tune.py) and bench.py's
variant stage, so methodology fixes (round structure, flops accounting,
matmul-peak context) happen in exactly one place.  The matmul peak is
measured interleaved with the candidates so every ratio shares a
window.
"""
from __future__ import annotations

import sys
import time

#: the bench shape of record (BENCH_r{N} flash_d128 detail keys):
#: head-packed [B*H, T, D] causal attention, f32 inputs, bf16 MXU.
#: D=64 sweeps use H=8, D=64 — same total flops (H*D preserved).
B, T, H, D = 4, 2048, 4, 128
MM_N = 4096


def causal_flops():
    """Matmul flops of the sweep shape (causal halves the score work).
    Invariant under the D=64 variant (H doubles as D halves)."""
    return 4 * B * H * T * T * D / 2


def make_inputs(jax, jnp, d=D):
    """(q, k, v) head-packed operands of the sweep shape; `d` picks the
    head dim (64 or 128) with H scaled to keep total flops fixed."""
    h = (H * D) // d
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    def mk(kk):
        return jax.random.normal(kk, (B * h, T, d), jnp.float32)

    return mk(k1), mk(k2), mk(k3)


def matmul_context(jax, jnp):
    """(fn, a, b) for the bf16 matmul that anchors the MXU peak."""
    ka, kb = jax.random.split(jax.random.PRNGKey(7))
    ma = jax.random.normal(ka, (MM_N, MM_N), jnp.bfloat16)
    mb = jax.random.normal(kb, (MM_N, MM_N), jnp.bfloat16)
    def mm(x, y):
        return (x @ y).astype(jnp.bfloat16)

    return mm, ma, mb


def make_variant(bq, bk, ck=None, qt=1, fd=False, cast=False,
                 kernel="resident", sm=None):
    """A schedule candidate closure over flash_attention_packed.
    ``sm``: static_max pin (the r5 VPU-minimal schedule — drops the
    max/alpha/clamp passes; exact within f32 range of the pin)."""
    from ..ops.flash import flash_attention_packed as fap

    def fn(x, kk, vv):
        return fap(x, kk, vv, causal=True, kernel=kernel, block_q=bq,
                   block_k=bk, chunk_k=ck, q_tiles=qt, fuse_denom=fd,
                   kv_cast_scratch=cast, static_max=sm)
    return fn


def run_sweep(jax, jnp, timed_chain, cands, rounds=3, log=None, d=D):
    """Interleaved best-of-rounds sweep.

    Returns (best, best_mm): best maps candidate name -> best seconds
    (or an error string for candidates that failed to compile/run);
    best_mm is the matmul's best seconds in the same windows.
    """
    if log is None:
        def log(msg):
            print(msg, file=sys.stderr, flush=True)
    q, k, v = make_inputs(jax, jnp, d=d)
    mm, ma, mb = matmul_context(jax, jnp)

    best = {n: None for n in cands}
    best_mm = None
    dead: set = set()
    for r in range(rounds):
        dmm = timed_chain(mm, ma, iters=48, trials=1, consts=(mb,))
        best_mm = dmm if best_mm is None else min(best_mm, dmm)
        for name, fn in cands.items():
            if name in dead:
                continue
            t0 = time.perf_counter()
            try:
                dv = timed_chain(fn, q, iters=64, trials=1, consts=(k, v))
            except Exception as e:  # noqa: BLE001 — one candidate dying
                dead.add(name)      # must not take down the sweep
                best[name] = f"{type(e).__name__}: {e}"
                log(f"  {name}: DEAD {e}")
                continue
            log(f"  [r{r}] {name}: {dv * 1e3:.2f} ms "
                f"(wall {time.perf_counter() - t0:.0f}s)")
            prev = best[name]
            best[name] = dv if prev is None else min(prev, dv)
    return best, best_mm


def report(best, best_mm):
    """{matmul_bf16_tflops, schedules: {name: {tflops, mxu_frac}}}."""
    flops = causal_flops()
    mm_tf = 2 * MM_N**3 / best_mm / 1e12
    res = {"matmul_bf16_tflops": round(mm_tf, 2), "schedules": {}}
    for name, dt in best.items():
        if isinstance(dt, float):
            tf = flops / dt / 1e12
            res["schedules"][name] = {
                "tflops": round(tf, 2), "mxu_frac": round(tf / mm_tf, 3)}
        else:
            res["schedules"][name] = {"error": dt}
    return res
