"""Single-chip kernel benchmark: the on-path reduction lane and the
Pallas kernels the collectives and models dispatch, compiled on the TPU.

Headline: sustained throughput of the reduction arithmetic lane
(accl_tpu.ops.reduce_ops, the reference reduce_ops plugin's role) on
large fp32 buffers, as effective bandwidth = 3 x bytes / time (read a,
read b, write out).  The reference CCLO's internal datapath moves
64 B/cycle @ 250 MHz = 16 GB/s through its reduction unit, so
vs_baseline = throughput / 16 GB/s (BASELINE.md "CCLO internal
datapath").  Detail stages: flash attention, wire compression and the
compiled virtual self-ring collectives.

It runs in ONE process and only on the TPU: with no chip it exits
non-zero and prints no result, and a stage that raises fails the run.

Methodology (accl_tpu/bench/timing.py): iterations are chained inside
one compiled program (lax.fori_loop, the carry feeds forward), a scalar
readback forces completion, the measured readback round trip is
subtracted, and quantities that are ratioed are timed interleaved.

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": "GB/s", "vs_baseline": N,
   "device": {...}, "detail": {...}}
"""
from __future__ import annotations

import json
import sys
import time

BASELINE_GBPS = 16.0  # reference CCLO datapath (BASELINE.md)


def _headline_stage(jax, jnp, timed_chain_ab, timed_chain) -> dict:
    """pallas_add on 64 Mi fp32 elements against the same 3-stream add
    through plain XLA (the practical HBM ceiling), interleaved."""
    from accl_tpu.ops.reduce_ops import pallas_add

    # Operands are laid out 2D (rows, 128) — the kernels' native tile
    # shape — because a 1D loop carry has a different physical layout
    # (T(1024) vs T(8,128)) and XLA then inserts a full-array relayout
    # copy per chained iteration in front of the pallas call.
    n = 64 << 20
    a = jax.random.normal(jax.random.PRNGKey(0), (n // 128, 128),
                          jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (n // 128, 128),
                          jnp.float32)
    # VMEM tile depth: dispatch-bound at small blocks, pipeline-starved
    # at huge ones; best of a short ladder
    best_dt, best_rows = None, 0
    for rows in (512, 2048):
        def fn(x, bb, r=rows):
            return pallas_add(x, bb, interpret=False, block_rows=r,
                              donate=True)
        dt_r = timed_chain(fn, a, 8, trials=2, consts=(b,))
        if best_dt is None or dt_r < best_dt:
            best_dt, best_rows = dt_r, rows

    def run(x, bb):
        return pallas_add(x, bb, interpret=False, block_rows=best_rows,
                          donate=True)

    def xla_add(x, bb):
        return x + bb

    dts = timed_chain_ab({"pallas": run, "xla": xla_add}, a, 30,
                         consts=(b,))
    nbytes = 3 * n * 4
    return {
        "gbps": nbytes / dts["pallas"] / 1e9,
        "xla_add_gbps": nbytes / dts["xla"] / 1e9,
        "roofline_frac": dts["xla"] / dts["pallas"],
        "pallas_block_rows": best_rows,
    }


def _flash_operands(jax, jnp):
    """Shared operand/context pack for the two flash stages (each stage
    re-measures the matmul peak interleaved with its own kernels)."""
    B, T, H, D = 4, 2048, 8, 64
    H2, D2 = 4, 128
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (B, T, H, D), jnp.float32)
    k = jax.random.normal(k2, (B, T, H, D), jnp.float32)
    v = jax.random.normal(k3, (B, T, H, D), jnp.float32)
    q2 = jax.random.normal(k1, (B, T, H2, D2), jnp.float32)
    k2_ = jax.random.normal(k2, (B, T, H2, D2), jnp.float32)
    v2 = jax.random.normal(k3, (B, T, H2, D2), jnp.float32)
    # head-packed operands (the zero-transpose entries; transposes
    # measured ~free on this chip, so numbers stay comparable)
    def pk(x, h, d):
        return x.transpose(0, 2, 1, 3).reshape(B * h, T, d)
    ops = {
        "B": B, "T": T, "H": H, "D": D, "H2": H2, "D2": D2,
        "q": q, "k": k, "v": v, "q2": q2, "k2": k2_, "v2": v2,
        "q2p": pk(q2, H2, D2), "k2p": pk(k2_, H2, D2),
        "v2p": pk(v2, H2, D2),
        "q1p": pk(q, H, D), "k1p": pk(k, H, D), "v1p": pk(v, H, D),
        # causal: ~half of the 4*B*H*T^2*D matmul flops
        "flops": 4 * B * H * T * T * D / 2,
        "mm_n": 4096,
    }
    ka, kb = jax.random.split(jax.random.PRNGKey(7))
    ops["ma"] = jax.random.normal(ka, (4096, 4096), jnp.bfloat16)
    ops["mb"] = jax.random.normal(kb, (4096, 4096), jnp.bfloat16)
    ops["mm"] = lambda x, y: (x @ y).astype(jnp.bfloat16)
    return ops


def _flash_stage(jax, jnp, timed_chain) -> dict:
    """CORE flash record: the BTHD entries (d64 + d128), the interleaved
    matmul peak, the verified fwd+bwd composite, and the
    splash-attention external anchor, with the same chained-iteration
    methodology as the headline metric."""
    from jax.experimental.pallas.ops.tpu import splash_attention as _sp

    from accl_tpu.bench.flash_sweep import make_variant
    from accl_tpu.ops.flash import flash_attention
    from accl_tpu.ops.flash import flash_attention_packed as _fap

    detail: dict = {}
    o = _flash_operands(jax, jnp)
    T = o["T"]
    flops, mm_n = o["flops"], o["mm_n"]

    def fa(x, kk, vv):  # chained: output feeds the next queries
        return flash_attention(x, kk, vv, causal=True, interpret=False)

    # EXTERNAL ANCHOR: JAX's own splash-attention kernel on the same
    # packed operands, same windows — the practical same-shape ceiling
    # this chip generation offers.  [B*H2, T, D2] is exactly splash's
    # single-device MHA layout (heads, seq, hd) with a per-head causal
    # mask.
    _mask = _sp.splash_attention_mask.MultiHeadMask(
        [_sp.splash_attention_mask.CausalMask((T, T))] * (o["B"] * o["H2"]))
    _splash = _sp.make_splash_mha_single_device(_mask)

    def splash_fwd(x, kk, vv):
        return _splash(x, kk, vv)

    def splash_bwd(x, kk, vv):
        g = jax.grad(lambda a, b, c: jnp.sum(
            _splash(a, b, c)), argnums=(0, 1, 2))(x, kk, vv)
        return g[0] + g[1] + g[2]

    # backward pass (the custom-VJP Pallas kernels): grad over ALL THREE
    # operands, with dq+dk+dv summed into the chain carry so every
    # output is live (r4 timed argnums=(0,) and DCE deleted the dkv
    # call).  The program lowered for the TPU must contain all three
    # pallas calls (fwd rerun + dq + dkv) before the number is reported.
    def fa_bwd(x, kk, vv):
        g = jax.grad(lambda a, b, c: jnp.sum(
            _fap(a, b, c, causal=True, kernel="resident")
            .astype(jnp.float32)), argnums=(0, 1, 2))(x, kk, vv)
        return g[0] + g[1] + g[2]

    n_pallas = jax.jit(fa_bwd).trace(
        o["q2p"], o["k2p"], o["v2p"]).lower(
            lowering_platforms=("tpu",)).as_text().count("tpu_custom_call")
    detail["flash_fwdbwd_pallas_calls"] = n_pallas

    # forward reference for the fwd+bwd consistency gate: the SAME
    # packed resident entry fa_bwd re-runs
    fa_res = make_variant(256, 512)

    # interleaved best-of-rounds; iteration counts put >= ~10 ms of
    # device work per dispatch so readback jitter amortizes away
    best = {}

    def keep(name, dt):
        best[name] = dt if name not in best else min(best[name], dt)

    for _ in range(12):
        keep("fa", timed_chain(fa, o["q"], iters=64, trials=1,
                               consts=(o["k"], o["v"])))
        keep("mm", timed_chain(o["mm"], o["ma"], iters=48, trials=1,
                               consts=(o["mb"],)))
        keep("f2", timed_chain(fa, o["q2"], iters=64, trials=1,
                               consts=(o["k2"], o["v2"])))
        keep("res", timed_chain(fa_res, o["q2p"], iters=64, trials=1,
                                consts=(o["k2p"], o["v2p"])))
        keep("bwd", timed_chain(fa_bwd, o["q2p"], iters=24, trials=1,
                                consts=(o["k2p"], o["v2p"])))
        keep("sp", timed_chain(splash_fwd, o["q2p"], iters=64, trials=1,
                               consts=(o["k2p"], o["v2p"])))
        keep("sp_bwd", timed_chain(splash_bwd, o["q2p"], iters=24,
                                   trials=1, consts=(o["k2p"], o["v2p"])))

    detail["flash_attention_tflops"] = flops / best["fa"] / 1e12
    mm_peak = 2 * mm_n**3 / best["mm"]
    detail["matmul_bf16_tflops"] = mm_peak / 1e12
    detail["flash_mxu_frac"] = (flops / best["fa"]) / mm_peak
    detail["flash_d128_tflops"] = flops / best["f2"] / 1e12
    detail["flash_d128_mxu_frac"] = (flops / best["f2"]) / mm_peak
    detail["flash_d128_fwdref_tflops"] = flops / best["res"] / 1e12
    # the timed chain runs forward + backward per iteration (jax.grad
    # re-runs the custom-VJP forward): 2 fwd matmuls + 7 bwd matmuls per
    # causal cell = 4.5x the fwd flops.  Reported only when consistent
    # with the same-window forward: the implied backward-only rate must
    # not exceed the matmul peak (r4's DCE'd number failed exactly this).
    bwd_flops = 4.5 * flops
    composite_frac = (bwd_flops / best["bwd"]) / mm_peak
    implied_bwd_frac = (((3.5 * flops) / (best["bwd"] - best["res"])
                         / mm_peak) if best["bwd"] > best["res"] else None)
    if (n_pallas >= 3 and composite_frac <= 1.0
            and (implied_bwd_frac is None or implied_bwd_frac <= 1.05)):
        detail["flash_d128_fwdbwd_tflops"] = bwd_flops / best["bwd"] / 1e12
        detail["flash_d128_fwdbwd_mxu_frac"] = composite_frac
        if implied_bwd_frac is not None:
            detail["flash_d128_bwdonly_mxu_frac"] = implied_bwd_frac
    else:
        detail["flash_d128_fwdbwd_inconsistent"] = {
            "pallas_calls": n_pallas,
            "composite_frac": composite_frac,
            "implied_bwd_frac": implied_bwd_frac,
        }
    detail["splash_anchor_tflops"] = flops / best["sp"] / 1e12
    detail["splash_anchor_mxu_frac"] = (flops / best["sp"]) / mm_peak
    detail["splash_anchor_fwdbwd_tflops"] = bwd_flops / best["sp_bwd"] / 1e12
    detail["splash_anchor_fwdbwd_mxu_frac"] = (
        (bwd_flops / best["sp_bwd"]) / mm_peak)
    return detail


def _flash_variants_stage(jax, jnp, timed_chain) -> dict:
    """Schedule-candidate sweep: the packed d128/d64 families (incl. the
    r5 static-max pin) and the bf16-input lane, with their OWN
    interleaved matmul peak.  Candidate construction is shared with the
    tuner scripts (flash_sweep docstring)."""
    from accl_tpu.bench.flash_sweep import make_variant

    detail: dict = {}
    o = _flash_operands(jax, jnp)
    flops, mm_n = o["flops"], o["mm_n"]
    d128_variants = {
        "resident": make_variant(256, 512),
        "resident_bq512": make_variant(512, 512),
        "resident_bq512_qt2": make_variant(512, 512, qt=2),
        "resident_bq512_bk1024": make_variant(512, 1024),
        # r5 static-max pin: drops the max/alpha/clamp VPU passes
        "resident_sm40": make_variant(256, 512, sm=40.0),
        "resident_bq512_sm40": make_variant(512, 512, sm=40.0),
    }
    d64_variants = {
        "resident": make_variant(256, 512),
        "resident_fd": make_variant(256, 512, fd=True),
        "resident_qt2_fd": make_variant(256, 512, qt=2, fd=True),
        "resident_fd_sm40": make_variant(256, 512, fd=True, sm=40.0),
    }
    # bf16-input lane: the kernel as a bf16-activation model calls it
    # (cast once, outside the timing)
    q2b, k2b, v2b = (x.astype(jnp.bfloat16)
                     for x in (o["q2p"], o["k2p"], o["v2p"]))
    fa_bf16 = make_variant(256, 512)

    best_mm = best_bf = None
    best_pk: dict = {}
    best_pk64: dict = {}
    for _ in range(10):
        d2 = timed_chain(o["mm"], o["ma"], iters=48, trials=1,
                         consts=(o["mb"],))
        best_mm = d2 if best_mm is None else min(best_mm, d2)
        db = timed_chain(fa_bf16, q2b, iters=64, trials=1,
                         consts=(k2b, v2b))
        best_bf = db if best_bf is None else min(best_bf, db)
        for name, vfn in d128_variants.items():
            dv = timed_chain(vfn, o["q2p"], iters=64, trials=1,
                             consts=(o["k2p"], o["v2p"]))
            best_pk[name] = min(best_pk.get(name, dv), dv)
        for name, vfn in d64_variants.items():
            dv = timed_chain(vfn, o["q1p"], iters=64, trials=1,
                             consts=(o["k1p"], o["v1p"]))
            best_pk64[name] = min(best_pk64.get(name, dv), dv)

    mm_peak = 2 * mm_n**3 / best_mm
    detail["variants_matmul_bf16_tflops"] = mm_peak / 1e12
    detail["flash_d128_bf16_tflops"] = flops / best_bf / 1e12
    detail["flash_d128_bf16_mxu_frac"] = (flops / best_bf) / mm_peak
    for tag, table in (("d128", best_pk), ("d64", best_pk64)):
        win = min(table, key=lambda n: table[n])
        detail[f"flash_{tag}_packed_tflops"] = flops / table[win] / 1e12
        detail[f"flash_{tag}_packed_mxu_frac"] = (
            (flops / table[win]) / mm_peak)
        detail[f"flash_{tag}_packed_schedule"] = win
        detail[f"flash_{tag}_packed_all"] = {
            n: flops / dt / 1e12 for n, dt in table.items()}
    return detail


def _compression_stage(jax, jnp, timed_chain_ab) -> dict:
    """Wire-compression roundtrip lane vs the same-window XLA cast pair
    (the practical ceiling for this access pattern)."""
    import jax.lax as _lax

    from accl_tpu.ops.compression import compress_cast, decompress_cast

    # 256 MB fp32: larger than any on-chip scratch (at 64 MB XLA pins
    # the whole chained cast loop on chip and measures on-chip
    # bandwidth, not the HBM-streaming ceiling a wire-compression lane
    # faces).  2D layout for the same copy-free-carry reason as the
    # headline operands.
    x = jax.random.normal(jax.random.PRNGKey(3), ((64 << 20) // 512, 512),
                          jnp.float32)

    def roundtrip(v):  # chained compress -> decompress
        return decompress_cast(compress_cast(v, jnp.bfloat16,
                                             interpret=False),
                               jnp.float32, interpret=False)

    # BOTH halves sit behind optimization_barriers: with one, the
    # simplifier folds convert(convert(x)) to x across chained
    # iterations and elides every roundtrip but the first
    def xla_rt(v):
        h = _lax.optimization_barrier(v.astype(jnp.bfloat16))
        return _lax.optimization_barrier(h.astype(jnp.float32))

    dts = timed_chain_ab({"pallas": roundtrip, "xla": xla_rt}, x,
                         iters=24, trials=8)
    # bytes per roundtrip: read 4B + write 2B + read 2B + write 4B
    nbytes = x.size * 12
    return {"compression_gbps": nbytes / dts["pallas"] / 1e9,
            "compression_xla_gbps": nbytes / dts["xla"] / 1e9}


def _selfring_stage(jax, jnp, timed_chain) -> dict:
    """Execute the Mosaic-COMPILED ring collectives on the chip as a
    virtual 8-rank self-ring: every hop is a real remote DMA
    (device_id = self) with the real semaphore handshakes and
    ACK-window flow control (the reference's execute-the-synthesized-
    artifact rung, test/model/simulator/cclo_sim.cpp:57-559).
    Correctness is asserted against the self-ring closed forms (ag →
    x tiled V times; rs → fold of our own V chunks) before anything is
    timed."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from accl_tpu.ops.ring import (
        ring_all_gather_pallas,
        ring_all_reduce_pallas,
        ring_reduce_scatter_pallas,
    )

    V = 8
    rows = 4096                      # 4096 x 128 f32 = 2 MB chunk
    mesh = Mesh(np.array(jax.devices()[:1]), ("r",))
    spec = P()                       # 1-member axis: full array local

    def smap(f):
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=spec,
                                     out_specs=spec, check_vma=False))

    x = jax.random.normal(jax.random.PRNGKey(11), (rows, 128), jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(12), (V, rows, 128),
                           jnp.float32)

    ag = smap(lambda v: ring_all_gather_pallas(v, "r", ring_size=V))
    got = np.asarray(ag(x))
    want = np.broadcast_to(np.asarray(x), (V, rows, 128))
    if not np.array_equal(got, want):
        raise AssertionError("self-ring allgather mismatch")
    rs = smap(lambda v: ring_reduce_scatter_pallas(v, "r", ring_size=V))
    got = np.asarray(rs(xs))
    want = np.asarray(xs).astype(np.float64).sum(axis=0)
    err = np.max(np.abs(got - want) / (np.abs(want) + 1e-6))
    if not err < 1e-3:
        raise AssertionError(f"self-ring reduce-scatter mismatch {err}")

    # bandwidth of the remote-DMA path: (V-1) hops x chunk bytes per
    # kernel; chained via the [0] row (== x for the self-ring)
    ag_chain = smap(lambda v: ring_all_gather_pallas(v, "r", ring_size=V)[0])
    dt = timed_chain(ag_chain, x, iters=48, trials=3)
    detail = {"ring_selfring_ag_gbps": (V - 1) * rows * 128 * 4 / dt / 1e9}
    # allreduce self-ring: rs + ag, renormalized by V so the chain
    # carry stays bounded
    arx = jax.random.normal(jax.random.PRNGKey(13), (V * rows, 128),
                            jnp.float32)
    ar_chain = smap(lambda v: ring_all_reduce_pallas(v, "r", ring_size=V) / V)
    dt = timed_chain(ar_chain, arx, iters=32, trials=3)
    detail["ring_selfring_ar_gbps"] = 2 * (V - 1) * rows * 128 * 4 / dt / 1e9
    return detail


def main() -> None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"bench.py measures the TPU; JAX found platform "
                 f"{backend!r}")
    import jax.numpy as jnp

    from accl_tpu.bench.timing import make_harness
    from accl_tpu.utils.compile_cache import enable as _enable_cache

    cache_dir = _enable_cache()
    dev = jax.devices()[0]
    print(f"[bench] device={dev.device_kind} x{len(jax.devices())} "
          f"compile_cache={cache_dir}", file=sys.stderr)
    _probe, timed_chain, timed_chain_ab, _sync_s = make_harness(jax, jnp)

    t0 = time.perf_counter()
    head = _headline_stage(jax, jnp, timed_chain_ab, timed_chain)
    detail = {k: v for k, v in head.items() if k != "gbps"}
    detail.update(_flash_stage(jax, jnp, timed_chain))
    detail.update(_flash_variants_stage(jax, jnp, timed_chain))
    detail.update(_compression_stage(jax, jnp, timed_chain_ab))
    detail.update(_selfring_stage(jax, jnp, timed_chain))
    print(f"[bench] stages took {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    print(json.dumps({
        "metric": "on-path reduction lane sustained throughput "
                  "(fp32 sum, TPU)",
        "value": head["gbps"],
        "unit": "GB/s",
        "vs_baseline": head["gbps"] / BASELINE_GBPS,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "detail": detail,
    }))


if __name__ == "__main__":
    main()
