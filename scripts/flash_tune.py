"""Flash-attention schedule tuner — runs on the live TPU chip.

Sweeps resident-schedule block shapes / chunking / q-tile interleave /
fused-denominator on the bench's D=128 shape and prints a TFLOPs table
(matmul peak measured interleaved, so fractions share a window).  The
sweep loop itself lives in accl_tpu.bench.flash_sweep.

Usage: python scripts/flash_tune.py [rounds]
Env:   FLASH_TUNE_ONLY=substr1,substr2   filter candidates
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from accl_tpu.bench.flash_sweep import make_variant, report, run_sweep
from accl_tpu.utils.compile_cache import enable as _enable_cache

_enable_cache()

ROUNDS = int(sys.argv[1]) if len(sys.argv) > 1 else 6


def main():
    print(f"backend={jax.default_backend()}", file=sys.stderr)
    from accl_tpu.bench.timing import make_harness

    _probe, timed_chain, _ab, _sync = make_harness(jax, jnp)

    cands = {}
    for bq, bk in ((256, 512), (512, 512), (256, 256), (512, 256),
                   (1024, 512), (512, 1024), (256, 1024)):
        cands[f"res_bq{bq}_bk{bk}"] = make_variant(bq, bk)
    for bq, bk, ck in ((256, 512, 256), (512, 512, 256), (512, 512, 128),
                       (256, 512, 128)):
        cands[f"res_bq{bq}_bk{bk}_ck{ck}"] = make_variant(bq, bk, ck=ck)
    for bq, bk in ((256, 512), (512, 512)):
        cands[f"res_bq{bq}_bk{bk}_cast"] = make_variant(bq, bk, cast=True)
    for bq, bk, ck, qt in ((256, 512, None, 2), (512, 512, None, 2),
                           (512, 512, None, 4), (256, 512, None, 4),
                           (512, 512, 256, 2), (256, 512, 256, 2),
                           (512, 1024, None, 2)):
        ckn = f"_ck{ck}" if ck else ""
        cands[f"res_bq{bq}_bk{bk}{ckn}_qt{qt}"] = make_variant(
            bq, bk, ck=ck, qt=qt)
    for bq, bk, qt in ((256, 512, 1), (512, 512, 2), (256, 512, 2)):
        cands[f"res_bq{bq}_bk{bk}_qt{qt}_fd"] = make_variant(
            bq, bk, qt=qt, fd=True)

    only = os.environ.get("FLASH_TUNE_ONLY")
    if only:
        keep = [s.strip() for s in only.split(",")]
        cands = {n: f for n, f in cands.items()
                 if any(s in n for s in keep)}

    best, best_mm = run_sweep(jax, jnp, timed_chain, cands, rounds=ROUNDS)
    res = report(best, best_mm)
    print(f"matmul_bf16: {res['matmul_bf16_tflops']:.1f} TFLOPs")
    rows = sorted(res["schedules"].items(),
                  key=lambda kv: -kv[1].get("tflops", 0.0))
    for name, r in rows:
        if "tflops" in r:
            print(f"  {name:32s} {r['tflops']:7.2f} TF  "
                  f"frac={r['mxu_frac']:.3f}")
        else:
            print(f"  {name:32s} [{r['error']}]")


if __name__ == "__main__":
    main()
