"""Ring-kernel busbw sanity sweep (VERDICT r1 item 3).

Runs the segmented Pallas ring allreduce against the XLA psum path on
the same mesh across message sizes and prints a CSV of seconds and
effective busbw (nccl convention: 2*(P-1)/P * bytes / time).  On the
CPU rung the kernels execute under the Pallas TPU interpreter, so the
absolute numbers are meaningless — the sweep is a *sanity* check that
the segmented driver scales linearly and a harness that produces real
numbers the moment it runs on a TPU slice.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 python scripts/ring_sweep.py [--ranks 8]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--sizes", type=str, default="")  # elements per member
    ap.add_argument("--tpu", action="store_true",
                    help="run on the TPU (default: the virtual-CPU rung)")
    args = ap.parse_args()

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    if not args.sizes:
        # the interpreter is ~10^4 x slower than hardware: keep the CPU
        # rung's sweep tiny; the TPU sweep covers the BASELINE.md range
        args.sizes = ("4096,65536,1048576,16777216" if args.tpu
                      else "1024,4096,16384")
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accl_tpu.ops.ring import ring_all_reduce_segmented
    from accl_tpu.utils.platform import pallas_interpret
    from accl_tpu.parallel.mesh import make_mesh

    Pn = args.ranks
    interp = pallas_interpret()
    mesh = make_mesh(dp=Pn)

    print("impl,elements,bytes,seconds,busbw_GBps")
    for n in (int(s) for s in args.sizes.split(",")):
        x = jax.device_put(
            np.random.default_rng(0).standard_normal((Pn, n)).astype(np.float32),
            NamedSharding(mesh, P("dp", None)))

        ring = jax.jit(jax.shard_map(
            lambda xb: ring_all_reduce_segmented(
                xb[0], "dp", interpret=interp)[None],
            mesh=mesh, in_specs=P("dp", None), out_specs=P("dp", None),
            check_vma=False))
        xla = jax.jit(jax.shard_map(
            lambda xb: jax.lax.psum(xb, "dp"),
            mesh=mesh, in_specs=P("dp", None), out_specs=P("dp", None)))

        for name, fn in (("ring", ring), ("xla_psum", xla)):
            try:
                jax.block_until_ready(fn(x))  # compile
                t0 = time.perf_counter()
                iters = 3 if not interp else 1
                for _ in range(iters):
                    jax.block_until_ready(fn(x))
                dt = (time.perf_counter() - t0) / iters
            except Exception as e:  # pragma: no cover
                print(f"{name},{n},{n * 4},ERROR,{type(e).__name__}: {e}",
                      file=sys.stderr)
                continue
            busbw = 2 * (Pn - 1) / Pn * n * 4 / dt / 1e9
            print(f"{name},{n},{n * 4},{dt:.6f},{busbw:.3f}")


if __name__ == "__main__":
    main()
